"""Fleet feed latency + aggregate throughput vs sequential pipelines.

The fleet engine's pitch is serving-shaped: N live sensors behind ONE
vmapped/jitted step, so a constellation pays one dispatch per feed round
instead of one per sensor. This benchmark builds a scenario-diverse
N-sensor sky (cycling the rate-balanced family presets, each sensor with
independent pointing jitter), chunks every sensor's stream into fixed
event-time slices (default 20 ms, the live cadence), and replays the
same round sequence two ways:

* **fleet** — one :class:`FleetPipeline` fed all sensors per round; the
  wall time of each ``feed`` call is the whole fleet's per-round latency
  (host windowing for every sensor + one donated-carry vmapped step +
  consuming the round's detections), which is also each sensor's feed
  latency since all sensors' windows close inside that one call.
* **sequential** — N independent :class:`StreamingPipeline` objects fed
  one after another in the same round order: the N-dispatches-per-round
  baseline a naive multi-sensor deployment runs on the same host.

Methodology notes:

* Both replays consume their results the way the quickstarts do — the
  per-feed detection count is read back to host — so the comparison
  covers end-to-end serving cost, not just device residency.
* Both replays run once cold (warming every jit shape: one compile per
  distinct fleet window count), then three steady-state passes with GC
  disabled. Per-round wall times are recorded for BOTH sides and the
  passes are combined by per-round minimum before summing — the classic
  least-noise wall-clock estimator (the same rule the scan bench gates
  on), applied symmetrically. This matters on shared hosts: the
  reference runner exhibits a ~10 Hz external scheduler stall (~20 ms,
  visible as a drifting periodic spike in *both* replays) that a single
  pass sum absorbs ~15-25% of; the stall indices drift between passes,
  so the per-round min converges to the quiet-host sustained rate. The
  raw best-pass sums are reported alongside for transparency.
* The sensor mix cycles the *rate-balanced* scenario families so every
  sensor closes about one window per 20 ms round. A sensor with 10x the
  event rate of its neighbours (e.g. the full ``hot_columns`` stressor)
  pads every other sensor to its window count each feed and the fleet
  loses its dispatch-amortization edge by design; that ragged regime is
  pinned by the bit-identity tests, while this bench measures the
  steady co-observing regime the throughput claim is about.

Gates (exit code 1 on failure, BENCH_NO_FAIL=1 to disable):

* steady-state fleet per-feed p99 <= BUDGET_MS (62 ms paper budget)
* aggregate event throughput >= 3x the sequential baseline
  (BENCH_GATE_SPEEDUP=0 to skip on noisy shared runners)

Results land in BENCH_fleet.json at the repo root with the uniform
``bench`` block (name / p50_ms / p99_ms / gates) the ``benchmarks.run``
aggregator consumes.

  PYTHONPATH=src python benchmarks/fleet_throughput.py
  N_SENSORS=8 DURATION_S=2 CHUNK_US=20000 BUDGET_MS=62 ...  (CI knobs)
"""
import gc
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax
import numpy as np
from _common import git_commit

from repro.core.pipeline import FleetPipeline, PipelineConfig, StreamingPipeline
from repro.data.evas import iter_chunks
from repro.data.synthetic import SCENARIO_FAMILIES, make_fleet_recordings
from repro.launch.compile_cache import enable_compile_cache

N_SENSORS = int(os.environ.get("N_SENSORS", "8"))
DURATION_S = float(os.environ.get("DURATION_S", "3.0"))
CHUNK_US = int(os.environ.get("CHUNK_US", "20000"))
BUDGET_MS = float(os.environ.get("BUDGET_MS", "62"))
N_PASSES = int(os.environ.get("N_PASSES", "5"))
REPO_ROOT = Path(__file__).resolve().parent.parent

# Rate-balanced family subset: comparable events/s per sensor (see
# module docstring for why the 10x-rate stressors sit this one out).
BALANCED_FAMILIES = ("crossing", "geo_slow", "tumbling", "ballistic", "jitter")


def _recordings():
    recs = []
    for s in range(N_SENSORS):
        fam = BALANCED_FAMILIES[s % len(BALANCED_FAMILIES)]
        recs.extend(
            make_fleet_recordings(
                1, scenario=SCENARIO_FAMILIES[fam],
                seed0=101 * s, duration_s=DURATION_S,
            )
        )
    return recs


def _rounds(recs):
    """Per-round chunk tuples: ``rounds[i][s]`` is sensor s's i-th slice
    (or None once that sensor's stream is exhausted)."""
    per_sensor = [list(iter_chunks(r, CHUNK_US)) for r in recs]
    n_rounds = max(len(c) for c in per_sensor)
    return [
        [c[i] if i < len(c) else None for c in per_sensor]
        for i in range(n_rounds)
    ]


def _replay_fleet(rounds, config):
    """One fleet feed per round; (per-feed ms, windows, detections)."""
    fp = FleetPipeline(config, n_sensors=N_SENSORS)
    times, windows, dets = [], 0, 0
    for chunks in rounds:
        t0 = time.perf_counter()
        out = fp.feed(chunks)
        if out.clusters is not None:  # consume: this round's detections
            dets += int(np.asarray(out.clusters.valid).sum())
        jax.block_until_ready((out.metrics, out.tracks))
        times.append((time.perf_counter() - t0) * 1e3)
        windows += out.total_windows
    tail = fp.flush()
    if tail.clusters is not None:
        dets += int(np.asarray(tail.clusters.valid).sum())
    jax.block_until_ready((tail.metrics, tail.tracks))
    windows += tail.total_windows
    return times, windows, dets


def _replay_sequential(rounds, config):
    """N independent single-sensor pipelines, fed back to back in the
    same round order; (per-round ms, windows, detections)."""
    pipes = [StreamingPipeline(config) for _ in range(N_SENSORS)]
    times, windows, dets = [], 0, 0
    for chunks in rounds:
        t0 = time.perf_counter()
        for sp, chunk in zip(pipes, chunks):
            if chunk is None:
                continue
            res = sp.feed(*chunk)
            dets += int(np.asarray(res.clusters.valid).sum())
            jax.block_until_ready((res.metrics, res.tracks))
            windows += res.num_windows
        times.append((time.perf_counter() - t0) * 1e3)
    for sp in pipes:
        res = sp.flush()
        dets += int(np.asarray(res.clusters.valid).sum())
        jax.block_until_ready((res.metrics, res.tracks))
        windows += res.num_windows
    return times, windows, dets


def main() -> None:
    enable_compile_cache()
    config = PipelineConfig()  # paper defaults: 16px cells, 20 ms / 250 ev
    recs = _recordings()
    rounds = _rounds(recs)
    n_events = sum(len(r) for r in recs)
    print(
        f"backend={jax.default_backend()}  sensors={N_SENSORS}  "
        f"events={n_events:,}  rounds={len(rounds)} x {CHUNK_US / 1e3:.0f} ms  "
        f"budget={BUDGET_MS} ms"
    )
    for r in recs:
        print(f"  {r.name:<24} {len(r):>8,} events")

    # Cold pass: compiles one fleet step per distinct window count.
    t0 = time.perf_counter()
    _, n_windows, n_dets = _replay_fleet(rounds, config)
    cold_s = time.perf_counter() - t0
    _replay_sequential(rounds, config)  # warm the single-sensor shapes

    # Steady-state passes over the identical round sequence, GC off.
    gc.collect()
    gc.disable()
    try:
        fleet_passes = [_replay_fleet(rounds, config)[0] for _ in range(N_PASSES)]
        seq_results = [_replay_sequential(rounds, config) for _ in range(N_PASSES)]
    finally:
        gc.enable()
    # Per-round minimum across passes (symmetric least-noise combiner —
    # see module docstring), plus the raw best single pass.
    arr = np.minimum.reduce([np.asarray(p) for p in fleet_passes])
    seq_arr = np.minimum.reduce([np.asarray(r[0]) for r in seq_results])
    fleet_s = float(arr.sum()) / 1e3
    seq_s = float(seq_arr.sum()) / 1e3
    fleet_best_pass_s = min(sum(p) for p in fleet_passes) / 1e3
    seq_best_pass_s = min(sum(r[0]) for r in seq_results) / 1e3
    _, seq_windows, seq_dets = seq_results[0]

    p50, p95, p99 = (float(np.percentile(arr, q)) for q in (50, 95, 99))
    peak = float(arr.max())
    fleet_evs = n_events / fleet_s
    seq_evs = n_events / seq_s
    speedup = seq_s / fleet_s

    assert seq_windows == n_windows and seq_dets == n_dets, "drivers diverged"
    print(f"windows processed: {n_windows}  detections: {n_dets}")
    print(f"cold pass (incl. compiles): {cold_s:.2f} s")
    print(
        f"steady-state fleet per-feed latency ({N_SENSORS} sensors/feed): "
        f"p50={p50:.2f} ms  p95={p95:.2f} ms  p99={p99:.2f} ms  max={peak:.2f} ms"
    )
    print(
        f"aggregate throughput (per-round min over {N_PASSES} passes): "
        f"fleet {fleet_evs:,.0f} ev/s in {fleet_s:.2f} s vs "
        f"sequential {seq_evs:,.0f} ev/s in {seq_s:.2f} s"
    )
    print(
        f"  (raw best single pass: fleet {fleet_best_pass_s:.2f} s, "
        f"sequential {seq_best_pass_s:.2f} s)"
    )
    gate_p99 = p99 <= BUDGET_MS
    gate_speedup = speedup >= 3.0
    print(
        f"p99 vs paper budget: {p99:.2f} ms <= {BUDGET_MS} ms "
        f"({'PASS' if gate_p99 else 'FAIL'})"
    )
    print(
        f"fleet over sequential: {speedup:.2f}x "
        f"({'PASS' if gate_speedup else 'FAIL'} >= 3x acceptance)"
    )

    payload = {
        "backend": jax.default_backend(),
        "commit": git_commit(),
        "n_sensors": N_SENSORS,
        "duration_s": DURATION_S,
        "chunk_us": CHUNK_US,
        "n_events": n_events,
        "n_rounds": len(rounds),
        "n_windows": n_windows,
        "n_detections": n_dets,
        "budget_ms": BUDGET_MS,
        "cold_pass_s": round(cold_s, 3),
        "latency_ms": {
            "p50": round(p50, 3),
            "p95": round(p95, 3),
            "p99": round(p99, 3),
            "max": round(peak, 3),
        },
        "throughput": {
            "fleet_events_per_sec": round(fleet_evs, 1),
            "sequential_events_per_sec": round(seq_evs, 1),
            "fleet_wall_s": round(fleet_s, 3),
            "sequential_wall_s": round(seq_s, 3),
            "fleet_best_pass_s": round(fleet_best_pass_s, 3),
            "sequential_best_pass_s": round(seq_best_pass_s, 3),
            "n_passes": N_PASSES,
            "speedup": round(speedup, 2),
        },
        "bench": {
            "name": "fleet_throughput",
            "p50_ms": round(p50, 3),
            "p99_ms": round(p99, 3),
            "gates": [
                {
                    "name": "feed_p99_within_budget",
                    "value": round(p99, 3),
                    "threshold": BUDGET_MS,
                    "op": "<=",
                    "pass": gate_p99,
                },
                {
                    "name": "fleet_speedup_over_sequential",
                    "value": round(speedup, 2),
                    "threshold": 3.0,
                    "op": ">=",
                    "pass": gate_speedup,
                },
            ],
        },
    }
    out_path = REPO_ROOT / "BENCH_fleet.json"
    out_path.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {out_path}")

    if os.environ.get("BENCH_NO_FAIL"):
        return
    gates = [gate_p99]
    if os.environ.get("BENCH_GATE_SPEEDUP", "1") != "0":
        gates.append(gate_speedup)
    if not all(gates):
        sys.exit(1)


if __name__ == "__main__":
    main()
