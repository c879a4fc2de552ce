"""The program's own host spans, copy-back counters and result latency.

The service and the fleet driver record ``jax.profiler.TraceAnnotation``
spans at their layer boundaries, so a profiled run puts the host's work
on the device trace's clock. A client loop that wraps its calls the way
the chip benchmark does (``pump`` around ``DetectionService.pump``,
``result`` around reading a round's results) sees each loop span split
into sibling program spans: host work apart from waits on the device.
"""
import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.core.pipeline import PipelineConfig
from repro.core.pipeline.fleet import FleetPipeline, WireStats
from repro.serve import AdmissionConfig, DetectionService
from repro.serve.sessions import MAX_LATENCY_SAMPLES, SessionStats

# Program span -> the loop spans it may lie inside.
PARENTS = {
    "service.take": {"pump"},
    "service.retire": {"pump", "drain"},
    "fleet.window": {"pump"},
    "fleet.staging_wait": {"pump"},
    "fleet.pack": {"pump"},
    "fleet.stage": {"pump"},
    "fleet.dispatch": {"pump"},
    "fleet.copy_wait": {"result"},
    "fleet.copy_back": {"result"},
}
LOOP = ("pump", "result", "drain")


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self) -> float:
        return self.now


def _spaced_stream(seed: int, n: int, dt_us: int = 100):
    rng = np.random.default_rng(seed)
    return (
        rng.integers(40, 560, n).astype(np.int64),
        rng.integers(40, 400, n).astype(np.int64),
        (np.arange(n, dtype=np.int64) + 1) * dt_us,
        rng.integers(0, 2, n).astype(np.int64),
    )


def _host_spans(directory) -> list[tuple[str, float, float, dict]]:
    """Loop and program spans of the capture under ``directory``:
    (name, start s, end s, stats), sorted by start."""
    files = sorted(directory.glob("**/*.xplane.pb"))
    assert files, "the profiler wrote no trace"
    out = []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in PARENTS or ev.name in LOOP:
                    t0 = ev.start_ns * 1e-9
                    out.append((ev.name, t0, t0 + ev.duration_ns * 1e-9,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: s[1])


def _traced_service_run(trace_root) -> list:
    """Three stations at pipeline depth 2: warm-up rounds, then four traced
    rounds and a drain, each call wrapped in the loop span the chip
    benchmark puts around it."""
    svc = DetectionService(
        PipelineConfig(), tiers=(4,),
        admission=AdmissionConfig(max_delay_s=1e9, max_items=1 << 30),
        max_inflight_rounds=2,
    )
    sids = [svc.attach() for _ in range(3)]
    data = {sid: _spaced_stream(10 + sid, 12 * 400) for sid in sids}
    pending: list = []

    def one_round(r: int) -> None:
        for sid in sids:
            sl = slice(r * 400, (r + 1) * 400)
            svc.feed(sid, *(a[sl] for a in data[sid]))
        with TraceAnnotation("pump", round=r):
            pending.append(svc.pump(force=True))
        while len(pending) > svc.max_inflight_rounds:
            feeds = pending.pop(0)
            with TraceAnnotation("result"):
                [fd.result for fd in feeds]

    for r in range(6):  # every (slots, windows) shape compiles here
        one_round(r)
    jax.profiler.start_trace(str(trace_root))
    try:
        for r in range(6, 10):
            one_round(r)
        with TraceAnnotation("drain"):
            svc.drain()
        while pending:
            with TraceAnnotation("result"):
                [fd.result for fd in pending.pop(0)]
    finally:
        jax.profiler.stop_trace()
    return _host_spans(trace_root)


@pytest.fixture(scope="module")
def spans(tmp_path_factory):
    return _traced_service_run(tmp_path_factory.mktemp("trace"))


def test_every_program_span_is_recorded(spans):
    assert set(PARENTS) <= {s[0] for s in spans}


def test_program_spans_nest_in_their_loop_spans(spans):
    loop = [s for s in spans if s[0] in LOOP]
    for name, t0, t1, _ in spans:
        if name in LOOP:
            continue
        holders = {p for p, a, b, _ in loop if a <= t0 and t1 <= b}
        assert holders & PARENTS[name], (name, t0, t1, holders)


def test_program_spans_are_disjoint_siblings(spans):
    prog = [s for s in spans if s[0] in PARENTS]
    for (a, _, a_end, _), (b, b_start, _, _) in zip(prog, prog[1:]):
        assert a_end <= b_start, (a, b)


def test_spans_per_round_do_not_grow_with_stations(spans):
    """Each ``pump`` and ``result`` span holds each program span at most
    once, and a drain one wait per round in flight: spans are per round,
    never per station, window or event."""
    for name, a, b, _ in spans:
        if name not in LOOP:
            continue
        inner = [s[0] for s in spans if s[0] in PARENTS and a <= s[1] and s[2] <= b]
        if name == "drain":
            assert set(inner) == {"service.retire"} and len(inner) <= 2, inner
        else:
            assert len(inner) == len(set(inner)), (name, inner)


def test_fleet_spans_carry_the_round_of_their_dispatch(spans):
    """Every ``fleet.*`` span of one pump names the round it dispatches,
    and that round's copy-back names the same round."""
    dispatched = []
    for name, a, b, _ in spans:
        if name != "pump":
            continue
        rounds = {
            s[3]["round"] for s in spans
            if s[0].startswith("fleet.") and a <= s[1] and s[2] <= b
        }
        assert len(rounds) == 1, rounds
        dispatched.append(rounds.pop())
    copied = [s[3]["round"] for s in spans if s[0] == "fleet.copy_back"]
    assert dispatched == sorted(dispatched)
    assert set(dispatched) <= set(copied)


# --- copy-back counters --------------------------------------------------------

@pytest.mark.parametrize("hot", [4, 1], ids=["full", "hot_rows"])
def test_copy_back_counters_match_the_leaves(hot):
    fp = FleetPipeline(PipelineConfig(), n_sensors=4)
    chunks = [None] * 4
    for s in range(hot):
        chunks[s] = _spaced_stream(50 + s, 800)
    res = fp.feed(chunks)
    assert fp.wire_stats.d2h_transfers == 0  # nothing read yet
    res.sensor(0)
    res.sensor(hot - 1)  # a second read copies nothing more
    stacked = (res.clusters, res.metrics, res.tracks)
    leaves = jax.tree.leaves(stacked) + jax.tree.leaves(res.final_tracks)
    rows = jax.tree.leaves(stacked)
    want = sum(a.nbytes for a in leaves)
    if hot < 2:  # the hot-row path copies only the rows that closed windows
        assert res._hot_rows is not None
        want -= sum(a.nbytes // 4 * (4 - hot) for a in rows)
    else:
        assert res._hot_rows is None
    assert fp.wire_stats.d2h_transfers == len(leaves)
    assert fp.wire_stats.d2h_bytes == want


def test_wire_stats_add_sums_the_copy_back():
    a = WireStats(rounds=1, d2h_transfers=22, d2h_bytes=1000)
    a.add(WireStats(rounds=2, d2h_transfers=3, d2h_bytes=24))
    assert (a.rounds, a.d2h_transfers, a.d2h_bytes) == (3, 25, 1024)


# --- latency at the result ------------------------------------------------------

def test_result_latency_is_stamped_when_the_result_reaches_the_host():
    clock = FakeClock()
    svc = DetectionService(
        PipelineConfig(), tiers=(2,),
        admission=AdmissionConfig(max_delay_s=1e9, max_items=1 << 30),
        clock=clock, max_inflight_rounds=2,
    )
    sid = svc.attach()
    x, y, t, p = _spaced_stream(7, 800)
    svc.feed(sid, x[:400], y[:400], t[:400], p[:400])
    clock.now += 0.010
    svc.feed(sid, x[400:], y[400:], t[400:], p[400:])
    clock.now += 0.005
    (fd,) = svc.pump(force=True)
    assert fd.latency_ms == pytest.approx(15.0)  # queue wait, at dispatch
    stats = svc.session(sid).stats
    assert stats.latency_ms == [pytest.approx(15.0)]
    assert stats.result_latency_ms == []  # nothing on the host yet
    clock.now += 0.020
    fd.result
    assert stats.result_latency_ms == [pytest.approx(35.0)]
    clock.now += 0.020
    fd.result  # a second read is not a second sample
    assert stats.result_latency_ms == [pytest.approx(35.0)]


def test_result_latency_samples_are_bounded():
    stats = SessionStats()
    for i in range(MAX_LATENCY_SAMPLES + 5):
        stats.record_result_latency(float(i))
    assert len(stats.result_latency_ms) == MAX_LATENCY_SAMPLES
    assert stats.result_latency_ms[0] == 5.0
    assert stats.result_latency_ms[-1] == float(MAX_LATENCY_SAMPLES + 4)
