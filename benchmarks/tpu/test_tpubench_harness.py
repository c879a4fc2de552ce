"""Tests of the chip benchmark's harness that need no chip: the traffic
generator, the lookup of cells, metrics and costs by name, the reduction of
a recorded chip trace, the result line, the refusal to run off a TPU, and
the comparison that decides ``correct`` with its control.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from tpubench import bench, check, context, host, reference, traffic
from tpubench import trace as T

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
FIXTURES = HERE / "fixtures"
SEED = 2**31 + 12345  # wider than 32 signed bits, as the driver's are


def small(name: str, stations: int = 3, tile_s: float = 0.4) -> traffic.Traffic:
    return dataclasses.replace(
        traffic.load(name), stations=stations, tile_s=tile_s,
        check_stations=min(2, stations),
    )


# --- traffic ---------------------------------------------------------------

def _rounds(tr: traffic.Traffic, seed: int, n_rounds: int, cfg: dict):
    """Events per chunk and windows closed per round, per station."""
    streams = traffic.make_streams(tr, seed, 640, 480)
    bat = cfg["batcher"]
    events, closed = [], []
    for s in streams:
        events.append([len(s.chunk(b)[2]) for b in range(n_rounds)])
        counts = []
        for b in range(n_rounds):
            n = s.events_before(b + 1)
            t = s.prefix(n)[2]
            counts.append(len(reference.closed_bounds(
                t, bat["time_threshold_us"], bat["size_threshold"])))
        closed.append(np.diff([0] + counts))
    return np.asarray(events), np.asarray(closed)


@pytest.mark.parametrize("name", ["live", "backlog"])
def test_traffic_is_seeded_and_deterministic(name):
    cfg = json.loads((HERE / "configs/paper_vga_float.json").read_text())
    tr = small(name, tile_s=0.4 if name == "live" else 4.0)
    n = 2 * tr.chunks_per_tile + 2
    ev1, w1 = _rounds(tr, SEED, n, cfg)
    ev2, w2 = _rounds(tr, SEED, n, cfg)
    ev3, _ = _rounds(tr, SEED + 1, n, cfg)
    assert np.array_equal(ev1, ev2) and np.array_equal(w1, w2)
    assert ev1.sum() > 0 and w1.sum() > 0
    # Another seed: the same arrivals in another layout (the events an RSO
    # scatters off the sensor's edge aside).
    assert np.abs(ev1 - ev3).sum() <= 1e-3 * ev1.sum()
    x1 = traffic.make_streams(tr, SEED, 640, 480)[0].x
    x3 = traffic.make_streams(tr, SEED + 1, 640, 480)[0].x
    assert not np.array_equal(x1[:100], x3[:100])
    # Every round after the first tile repeats a round of the first tile
    # (the warm-up visits every (slots, windows) shape the window uses).
    shapes = w1.max(axis=0)
    p = tr.chunks_per_tile
    assert np.array_equal(shapes[p + 1:2 * p], shapes[1:p])
    assert set(shapes[tr.warmup_rounds:]) <= set(shapes[:tr.warmup_rounds])
    # Chunks lie on the tile grid and the stream is time-sorted.
    s = traffic.make_streams(tr, SEED, 640, 480)[0]
    t = s.prefix(s.events_before(n))[2]
    assert np.all(np.diff(t) >= 0)
    lo, hi = 3 * tr.chunk_us, 4 * tr.chunk_us
    c = s.chunk(3)[2]
    assert len(c) == 0 or (c.min() >= lo and c.max() < hi)


def test_check_sample_is_seeded():
    tr = small("live", stations=12)
    a = traffic.check_sample(tr, SEED)
    assert a == traffic.check_sample(tr, SEED)
    assert len(a) == tr.check_stations and all(0 <= i < 12 for i in a)


# --- lookup by name ----------------------------------------------------------

def test_every_cell_resolves_by_name():
    spec = bench.load_spec()
    for cell in spec["workloads"]:
        plan = bench.cell_plan(spec, cell["name"])
        assert plan["cfg"]["name"] == cell["config"]
        assert plan["traffic"].name == cell["traffic"]
        names = {m["name"] for m in plan["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert plan["per_layer"], cell["name"]
        for m in plan["per_layer"]:
            assert callable(context.reader(m["name"]))
            assert m["moves"] in names


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A cell, a traffic mix and a metric added as files and entries, with
    no edit to any existing file."""
    spec = bench.load_spec()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    mix = json.loads((HERE / "traffic/live.json").read_text())
    mix["stations"] = 5
    (tmp_path / "traffic" / "throwaway_mix.json").write_text(json.dumps(mix))
    (tmp_path / "metrics" / "throwaway_ms.live.py").write_text(
        "def read(ctx):\n    return ctx.per_round_ms(ctx.span_s('feed'))\n"
    )
    shutil.copytree(HERE / "configs", tmp_path / "benchmarks/tpu/configs")
    spec["workloads"].append({
        "name": "paper_vga_fixed_untracked.throwaway_mix", "config": "paper_vga_fixed_untracked",
        "traffic": "throwaway_mix", "chips": 1, "why": "throwaway",
    })
    spec["per_layer"].append({
        "name": "throwaway_ms.live", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "session service",
        "moves": "events_per_s", "workloads": ["paper_vga_fixed_untracked.throwaway_mix"],
    })
    plan = bench.cell_plan(
        spec, "paper_vga_fixed_untracked.throwaway_mix", root=tmp_path,
        traffic_dir=tmp_path / "traffic",
    )
    assert plan["traffic"].stations == 5
    assert [m["name"] for m in plan["per_layer"]] == ["throwaway_ms.live"]
    read = context.reader("throwaway_ms.live", tmp_path / "metrics")
    digest = T.Digest((0.0, 1.0), [["feed", 0.0, 0.5, -1], ["pump", 0.5, 0.6, 0]],
                      [], [], np.zeros((0, 3)))
    ctx = context.Context(digest, plan["cfg"], plan["traffic"],
                          {"step": "jit_step", "decode": "jit_decode"}, "TPU v5 lite")
    assert read(ctx) == pytest.approx(500.0)


def test_peaks_are_keyed_by_device_kind():
    assert context.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        context.peaks("cpu")


def test_window_pipeline_cost_matches_the_program_model():
    sys.path.insert(0, str(ROOT))
    from benchmarks.roofline_report import _megakernel_cost_model
    from repro.core.pipeline import PipelineConfig

    cfg = json.loads((HERE / "configs/paper_vga_fixed.json").read_text())
    pc = PipelineConfig(numerics="fixed", metrics_impl="megakernel")
    model = _megakernel_cost_model(pc, n_windows=6, capacity=256)
    flops, hbm = context.kernel_cost("window_pipeline")((2, 3), cfg)
    assert flops == model["flops"] and hbm == model["bytes"]


# --- the trace reduction, on a recorded chip trace ---------------------------

@pytest.mark.parametrize("fixture", [
    "paper_vga_fixed.live", "paper_vga_fixed.backlog", "paper_vga_fixed_untracked.backlog"])
def test_trace_reduction_on_a_recorded_chip_trace(fixture):
    """Every metric reader of the fixture's traffic on a trace recorded on a
    TPU v5 lite (a few rounds of the fixed route). The fixtures recorded
    before the program had spans of its own leave their readers silent."""
    digest = T.Digest.load(FIXTURES / f"{fixture}.json.gz")
    config, traffic_name = fixture.split(".")
    plan = bench.plan_for(config, traffic_name)
    ctx = context.Context(digest, plan["cfg"], plan["traffic"],
                          {"step": "jit_step", "decode": "jit_decode"}, "TPU v5 lite")
    assert 0 < ctx.busy_s < ctx.window_s
    names = sorted(p.name[:-3] for p in (HERE / "metrics").glob(f"*.{traffic_name}.py"))
    assert names
    values = {n: context.reader(n)(ctx) for n in names}
    if not ctx.has_spans(*T.PROGRAM_SPANS):
        silent = {n for n in names if n in PROGRAM_SPAN_READERS}
        assert all(values.pop(n) is None for n in silent), values
    assert all(v is not None for v in values.values()), values
    for name, v in values.items():
        if name.endswith("_roofline") or "idle_share" in name:
            assert 0 < v <= 100, (name, v)
        else:
            assert v > 0, (name, v)
    out = bench._breakdown(ctx)
    assert 0 < len(out["device_ops"]) <= 10 and 0 < len(out["idle_gaps"]) <= 10
    assert any(k.startswith("jit_step/") for k, _ in out["device_ops"])
    labels = {k for k, _ in out["idle_gaps"]}
    assert labels <= set(T.SPANS) | set(T.PROGRAM_SPANS) | {"none"}


PROGRAM_SPAN_READERS = {
    "pump_host_ms.backlog", "dispatch_wait_ms.backlog", "copy_back_ms.backlog",
    "copy_back_wait_ms.backlog",
}


def test_interval_arithmetic():
    a = T.merge([(0, 2), (1, 3), (5, 6)])
    assert a.tolist() == [[0, 3], [5, 6]]
    b = T.merge([(2, 5.5)])
    assert T.intersect(a, b).tolist() == [[2, 3], [5, 5.5]]
    assert T.complement(a, 0, 7).tolist() == [[3, 5], [6, 7]]
    assert T.length(a) == 4
    assert T.op_shape("%vmap.1 = (s32[16,50,16,128]{3,2}, s32[1]) custom-call()") == (16, 50, 16, 128)


# --- the host line ------------------------------------------------------------

def test_host_line_of_a_cpu_run_parses():
    """A run prints one ``# host:`` line: the loop's steps per round, the
    window's events by 5 s slice, which sum to the window's events, and
    what the loop's thread and the process used."""
    lines = []
    _cell_run(log=lambda *a: lines.append(" ".join(map(str, a))))
    host_lines = [ln for ln in lines if ln.startswith("# host: ")]
    assert len(host_lines) == 1
    h = json.loads(host_lines[0][len("# host: "):])
    events = int(next(ln for ln in lines if ln.startswith("# window ")).rsplit(" ", 1)[1])
    assert sum(h["slice_events"]) == events > 0
    assert len(h["slice_events"]) == len(h["slice_events_per_s"]) == 1
    assert set(h["steps_ms"]) == {"generator", "feed", "pump", "result"}
    assert all(0 <= p50 <= p90 for p50, p90 in h["steps_ms"].values())
    t = h["thread"]
    assert set(t) == {"utime", "stime", "cpu_share"}
    assert t["utime"] >= 0 and t["stime"] >= 0 and 0 < t["cpu_share"]
    assert h["process_cpu_s"] >= t["utime"] + t["stime"] - 1e-3
    assert h["gc2"]["count"] >= 0 and h["gc2"]["ms"] >= 0


@dataclasses.dataclass
class _Round:
    host_time: float
    events: int


@pytest.mark.parametrize("times,t_end,events,secs", [
    ([0.5, 4.9, 5.0, 12.0], 12.0, [2, 1, 1], [5, 5, 2]),  # the last slice 2 s long
    ([1.0, 14.9, 15.2], 15.0, [1, 0, 2], [5, 5, 5.2]),  # the drain's round in the last
    ([0.1], 1.0, [1], [1.0]),  # a window shorter than a slice
])
def test_window_slices_sum_to_the_window(times, t_end, events, secs):
    s = host.slices([_Round(t, 10) for t in times], 0.0, t_end)
    assert s["slice_events"] == [10 * n for n in events]
    assert np.allclose(s["slice_events_per_s"], 10 * np.asarray(events) / np.asarray(secs))


def test_watch_counts_generation_2_collections():
    import gc

    hooks = len(gc.callbacks)
    with host.Watch() as w:
        gc.collect(2)
        gc.collect(0)
    assert w.fields()["gc2"]["count"] == 1 and len(gc.callbacks) == hooks


# --- the command -----------------------------------------------------------

def _run(cwd: Path, env_extra: dict):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/tpu/run.py", "--workload", "paper_vga_fixed_untracked.backlog",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_refuses_to_run_off_the_tpu():
    proc = _run(ROOT, {})
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks/tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


# --- correctness: the reference, its control ------------------------------

def _stations(cfg_name: str, n_events: int = 4000):
    cfg = json.loads((HERE / f"configs/{cfg_name}.json").read_text())
    tr = small("live", stations=2)
    out = {}
    for i, s in enumerate(traffic.make_streams(tr, SEED, 640, 480)):
        out[i] = (s.prefix(n_events), n_events, None)
    return cfg, out


@pytest.mark.parametrize(
    "cfg_name", ["paper_vga_float", "paper_vga_fixed", "paper_vga_fixed_untracked"])
def test_control_in_bfloat16_is_not_correct(cfg_name):
    """The reference computed one precision below the configuration's,
    put in the program's place, fails the limits; the reference itself
    passes them."""
    import ml_dtypes

    cfg, stations = _stations(cfg_name)
    limits = cfg["check"]["limits"]
    same = check.control(cfg, stations, np.float32)
    assert check.verdict(same, limits)[0], same
    low = check.control(cfg, stations, ml_dtypes.bfloat16)
    ok, rows = check.verdict(low, limits)
    assert not ok, rows


# --- a run end to end on the CPU, with the timed path broken underneath ----

class _Broken:
    """The fleet step with one fault planted in it."""

    __name__ = "step"

    def __init__(self, step, fault: str):
        self.step, self.fault = step, fault

    def _cache_size(self):
        return self.step._cache_size()

    def __call__(self, packed, valid, state, atlas, meta, uniform):
        import jax.numpy as jnp

        if self.fault == "half_batch":  # even slots left out of the step
            keep = (jnp.arange(valid.shape[0]) % 2 == 1)[:, None, None]
            valid = valid & keep
        final, clusters, mets, states, atlas = self.step(
            packed, valid, state, atlas, meta, uniform
        )
        if self.fault == "state_unchanged":  # the tracker carry never moves
            final = state
        if self.fault == "altered":  # one count off in every slot's first window
            clusters = clusters._replace(count=clusters.count.at[:, 0, 0].add(1))
        return final, clusters, mets, states, atlas


def _cpu_run(config: str, traffic_name: str, traced: bool = False, seconds: float = 0.6,
             **traffic_changes):
    import time

    plan = bench.plan_for(config, traffic_name)
    plan["traffic"] = dataclasses.replace(
        small(traffic_name, tile_s=0.4 if traffic_name == "live" else 2.0), **traffic_changes
    )
    return bench.run_cell(plan, SEED, seconds, traced, time.perf_counter(),
                          require_tpu=False, log=lambda *a: None)


def _cell_run(metrics_impl_cpu: str = "staged", log=lambda *a: None):
    """The benchmark's cell on this CPU: 16 stations (the cell's slot tier),
    0.1 s chunks, and the fixed datapath staged in jnp, whose integers the
    Pallas kernel reproduces bit for bit, in place of the interpreted
    kernel."""
    import time

    plan = bench.plan_for("paper_vga_fixed_untracked", "backlog")
    plan["cfg"] = dict(plan["cfg"], metrics_impl=metrics_impl_cpu)
    plan["traffic"] = dataclasses.replace(
        small("backlog", stations=16, tile_s=0.4), chunk_ms=100.0
    )
    return bench.run_cell(plan, SEED, 0.3, False, time.perf_counter(),
                          require_tpu=False, log=log)


@pytest.mark.parametrize("traced", [False, True])
def test_result_line_keys(traced):
    out = _cpu_run("paper_vga_float", "live", traced)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out) == keys + (["breakdown"] if traced else []) + ["check"]
    limits = json.loads((HERE / "configs/paper_vga_float.json").read_text())["check"]["limits"]
    assert set(out["check"]) == set(limits)
    for v in out["metrics"].values():
        assert set(v) == {"value", "unit"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(out["device"])
    if traced:
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        plan = bench.plan_for("paper_vga_float", "live")
        assert set(out["metrics"]) == {m["name"] for m in plan["end_to_end"]}
    json.dumps(out)


def _fault_rule(monkeypatch) -> None:
    """The reference tracker follows the program's known fault."""
    from calibrate import slot0_overwrite

    monkeypatch.setattr(reference, "detections_used", slot0_overwrite)


def test_the_tracker_fault_shows(monkeypatch):
    """The program's tracker spawns a second track on a matched detection
    in slot 0 (its ``det_used`` scatter lets unmatched track slots
    overwrite the match): against the reference's rule the run is not
    correct, and against the fault's rule it is, so nothing else differs."""
    out = _cpu_run("paper_vga_float", "live")
    assert out["correct"] is False and out["check"]["exact"]["value"] > 0
    _fault_rule(monkeypatch)
    same = _cpu_run("paper_vga_float", "live")
    assert same["correct"] is True, same["check"]


def _plant(monkeypatch, fault: str) -> None:
    from repro.core.pipeline import fleet

    real = fleet.make_fleet_fn
    monkeypatch.setattr(
        fleet, "make_fleet_fn",
        lambda config, with_tracking=True: _Broken(real(config, with_tracking), fault),
    )


def test_the_cell_is_correct_on_this_cpu():
    out = _cell_run()
    assert out["correct"] is True, out["check"]
    assert out["attempted"] > 0


@pytest.mark.parametrize("fault", ["half_batch", "altered"])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    """Each fault the benchmark's cell can have, planted in its fleet step,
    turns ``correct`` false. (The untracked fixed step carries no state of
    its own to leave unchanged, and one chip exchanges nothing.)"""
    _plant(monkeypatch, fault)
    out = _cell_run()
    assert out["correct"] is False, out["check"]


def test_a_tracker_state_left_unchanged_is_not_correct(monkeypatch):
    """The configurations with tracking carry the tracker state: a step that
    returns it unchanged is caught. The reference follows the tracker's
    known fault here, so that only the planted fault differs."""
    _plant(monkeypatch, "state_unchanged")
    _fault_rule(monkeypatch)
    out = _cpu_run("paper_vga_float", "live")
    assert out["correct"] is False, out["check"]
