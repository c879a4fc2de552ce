"""Ingest saturation: offered-load sweep, pipelined vs synchronous.

PR 7 made the service fault-tolerant; this bench measures what the
pipelined ingest path (DESIGN.md Sec. 14) buys at saturation. A fixed
fleet of N_SESSIONS sensors offers rising per-round event loads (one
"round" = one 20 ms live-cadence beat: every session feeds one chunk,
then one forced pump dispatches the fleet step). Each load level runs
twice over identical streams:

* **sync** — ``max_inflight_rounds=1``: every round is awaited before
  the next feed (the pre-pipelining behaviour, bit-identical outputs);
* **pipelined** — ``max_inflight_rounds=DEPTH``: host packing of round
  N+1 overlaps device compute of rounds N.. (double-buffered staging),
  results consumed lazily, ``drain()`` inside the timed region so the
  tail is never hidden.

Per level and mode the bench reports offered vs **sustained** events/s
(total events / wall time) and per-round p50/p99. The **knee** is the
highest level a mode still sustains >= KNEE_FRACTION x offered — the
service's live-cadence capacity.

Gates (exit code 1 on failure, BENCH_NO_FAIL=1 to disable):

* pipelined knee per-round p99 <= BUDGET_MS (62 ms paper budget);
* pipelined peak sustained >= RATIO x sync peak sustained. Pipelining
  moves host packing off the critical path but conserves total work, so
  the 1.3x target needs a second core for the XLA worker thread to run
  on; on a single-core host the gate degrades to a documented
  no-regression floor (0.95x), same convention as the relaxed CI gates
  in ci.yml ("tracked from dedicated hardware"). BENCH_GATE_RATIO
  overrides either. The json records both the applied and the
  multi-core target so dashboards can track the real number;
* knee wire compression >= WIRE_TARGET (2.0x): host->device bytes on
  the default ragged wire (DESIGN.md Sec. 16) vs the dense-equivalent
  cost of the same rounds, measured at the knee's occupancy. The floor
  is intentionally below the ~2.8x the 250-events-per-256-slot steady
  state delivers: degenerate rounds (all-full windows plus quantum
  padding, or near-empty rounds dominated by the WIRE_QUANTUM floor)
  compress less, and the gate must hold at whatever occupancy the knee
  lands on. BENCH_GATE_WIRE overrides.

Results land in BENCH_ingest.json at the repo root with the uniform
``bench`` block the ``benchmarks.run`` aggregator consumes.

  PYTHONPATH=src python benchmarks/serve_saturation.py
  N_SESSIONS=8 LEVELS=250,500,1000 DEPTH=3 BUDGET_MS=62 ...  (CI knobs)
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

from repro.core.pipeline import PipelineConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import AdmissionConfig, DetectionService

N_SESSIONS = int(os.environ.get("N_SESSIONS", "8"))
N_ROUNDS = int(os.environ.get("N_ROUNDS", "40"))
N_WARMUP = int(os.environ.get("N_WARMUP", "4"))
CHUNK_US = int(os.environ.get("CHUNK_US", "20000"))  # live-cadence round
BUDGET_MS = float(os.environ.get("BUDGET_MS", "62"))
DEPTH = int(os.environ.get("DEPTH", "3"))  # pipelined max_inflight_rounds
KNEE_FRACTION = float(os.environ.get("KNEE_FRACTION", "0.95"))
# Events per sensor per round. 250 is the paper's size cut (one window
# per sensor per round); higher levels close 2/4/8 windows per round.
LEVELS = tuple(
    int(v) for v in os.environ.get("LEVELS", "125,250,500,1000").split(",")
)
RATIO_TARGET_MULTICORE = 1.3
RATIO_FLOOR_1CORE = 0.95
WIRE_TARGET = float(os.environ.get("BENCH_GATE_WIRE", "2.0"))
REPO_ROOT = Path(__file__).resolve().parent.parent

TIERS = (N_SESSIONS,)


def _stream(seed: int, n: int, dt_us: int):
    rng = np.random.default_rng(seed)
    return (
        rng.integers(40, 560, n).astype(np.int64),
        rng.integers(40, 400, n).astype(np.int64),
        (np.arange(n, dtype=np.int64) + 1) * dt_us,
        rng.integers(0, 2, n).astype(np.int64),
    )


def _replay(level: int, depth: int):
    """One offered-load level at one pipeline depth.

    Returns (per-round ms, sustained events/s, windows). Event
    timestamps are spaced so each round's chunk spans exactly CHUNK_US
    of sensor time — the offered load is level * N_SESSIONS events per
    20 ms beat, fed as fast as the service absorbs them (no pacing:
    sustained >= offered means the service keeps up with live cadence).
    """
    svc = DetectionService(
        PipelineConfig(), tiers=TIERS,
        admission=AdmissionConfig(max_delay_s=1e9, max_items=1 << 30),
        max_inflight_rounds=depth,
    )
    total = (N_WARMUP + N_ROUNDS) * level
    dt_us = max(1, CHUNK_US // level)
    streams = [_stream(7 * s + 1, total, dt_us) for s in range(N_SESSIONS)]
    sids = [svc.attach(f"sat{s}") for s in range(N_SESSIONS)]
    served = []

    def beat(rnd):
        lo, hi = rnd * level, (rnd + 1) * level
        for s, sid in enumerate(sids):
            x, y, t, p = streams[s]
            served.extend(svc.feed(sid, x[lo:hi], y[lo:hi], t[lo:hi], p[lo:hi]))
        served.extend(svc.pump(force=True))

    for rnd in range(N_WARMUP):  # compiles this level's (S, W) step shape
        beat(rnd)
    svc.drain()
    served.clear()

    ws = svc.wire_stats
    w0 = (ws.rounds, ws.wire_bytes, ws.dense_bytes)
    times = []
    t_all = time.perf_counter()
    for rnd in range(N_WARMUP, N_WARMUP + N_ROUNDS):
        t0 = time.perf_counter()
        beat(rnd)
        times.append((time.perf_counter() - t0) * 1e3)
    # The drain is part of the measured window: pipelining may not defer
    # the tail's cost outside the sustained-throughput accounting.
    svc.drain()
    wall_s = time.perf_counter() - t_all
    # Timed-region wire accounting (warmup rounds excluded).
    d_rounds = max(1, ws.rounds - w0[0])
    wire = {
        "wire_bytes_per_round": round((ws.wire_bytes - w0[1]) / d_rounds, 1),
        "dense_bytes_per_round": round((ws.dense_bytes - w0[2]) / d_rounds, 1),
        "wire_ratio": round(
            (ws.dense_bytes - w0[2]) / max(1, ws.wire_bytes - w0[1]), 3
        ),
    }
    windows = sum(fd.num_windows for fd in served)
    sustained = N_ROUNDS * level * N_SESSIONS / wall_s
    for sid in sids:
        svc.detach(sid)
    return times, sustained, windows, wire


def _sweep(depth: int):
    rows = []
    gc.collect()
    gc.disable()
    try:
        for level in LEVELS:
            times, sustained, windows, wire = _replay(level, depth)
            offered = level * N_SESSIONS / (CHUNK_US / 1e6)
            arr = np.asarray(times)
            rows.append({
                "level_events_per_sensor": level,
                "offered_events_s": round(offered, 1),
                "sustained_events_s": round(sustained, 1),
                "utilization": round(sustained / offered, 3),
                "p50_ms": round(float(np.percentile(arr, 50)), 3),
                "p99_ms": round(float(np.percentile(arr, 99)), 3),
                "windows": windows,
                **wire,
            })
    finally:
        gc.enable()
    return rows


def _knee(rows):
    """Highest level still sustaining >= KNEE_FRACTION x offered; falls
    back to the first level (everything saturated) so the p99 gate always
    has a defined operating point."""
    passing = [r for r in rows if r["utilization"] >= KNEE_FRACTION]
    return passing[-1] if passing else rows[0]


def main() -> None:
    enable_compile_cache()
    host_cores = os.cpu_count() or 1
    ratio_target = RATIO_TARGET_MULTICORE if host_cores >= 2 else RATIO_FLOOR_1CORE
    ratio_target = float(os.environ.get("BENCH_GATE_RATIO", ratio_target))
    print(
        f"backend={jax.default_backend()}  host_cores={host_cores}  "
        f"sessions={N_SESSIONS}  levels={LEVELS} ev/sensor/round  "
        f"rounds={N_ROUNDS}  depth={DEPTH}"
    )

    sync_rows = _sweep(depth=1)
    pipe_rows = _sweep(depth=DEPTH)

    print(f"\n{'level':>6} {'offered/s':>11} {'sync/s':>11} {'pipe/s':>11} "
          f"{'ratio':>6} {'sync p99':>9} {'pipe p99':>9}")
    for sr, pr in zip(sync_rows, pipe_rows):
        print(
            f"{sr['level_events_per_sensor']:>6} "
            f"{sr['offered_events_s']:>11,.0f} "
            f"{sr['sustained_events_s']:>11,.0f} "
            f"{pr['sustained_events_s']:>11,.0f} "
            f"{pr['sustained_events_s'] / sr['sustained_events_s']:>6.2f} "
            f"{sr['p99_ms']:>9.2f} {pr['p99_ms']:>9.2f}"
        )

    knee = _knee(pipe_rows)
    sync_peak = max(r["sustained_events_s"] for r in sync_rows)
    pipe_peak = max(r["sustained_events_s"] for r in pipe_rows)
    ratio = pipe_peak / sync_peak

    gate_p99 = knee["p99_ms"] <= BUDGET_MS
    gate_ratio = ratio >= ratio_target
    gate_wire = knee["wire_ratio"] >= WIRE_TARGET
    print(
        f"\nknee (pipelined): {knee['level_events_per_sensor']} ev/sensor/"
        f"round = {knee['offered_events_s']:,.0f} ev/s offered, sustained "
        f"{knee['sustained_events_s']:,.0f} ev/s, p99 {knee['p99_ms']:.2f} ms"
    )
    print(
        f"knee p99 vs paper budget: {knee['p99_ms']:.2f} ms <= {BUDGET_MS} ms "
        f"({'PASS' if gate_p99 else 'FAIL'})"
    )
    print(
        f"pipelined/sync peak sustained: {pipe_peak:,.0f} / {sync_peak:,.0f} "
        f"= {ratio:.2f}x >= {ratio_target}x "
        f"({'PASS' if gate_ratio else 'FAIL'}; multi-core target "
        f"{RATIO_TARGET_MULTICORE}x, {host_cores} core(s) here)"
    )
    print(
        f"knee wire compression: {knee['wire_ratio']:.2f}x >= {WIRE_TARGET}x "
        f"({'PASS' if gate_wire else 'FAIL'}; "
        f"{knee['wire_bytes_per_round']:,.0f} B/round ragged vs "
        f"{knee['dense_bytes_per_round']:,.0f} B/round dense-equivalent)"
    )

    payload = {
        "backend": jax.default_backend(),
        "commit": git_commit(),
        "host_cores": host_cores,
        "n_sessions": N_SESSIONS,
        "n_rounds": N_ROUNDS,
        "chunk_us": CHUNK_US,
        "depth": DEPTH,
        "levels": list(LEVELS),
        "knee_fraction": KNEE_FRACTION,
        "sync": sync_rows,
        "pipelined": pipe_rows,
        "knee": knee,
        "sustained_ratio": round(ratio, 3),
        "ratio_target_applied": ratio_target,
        "ratio_target_multicore": RATIO_TARGET_MULTICORE,
        "wire_target": WIRE_TARGET,
        "bench": {
            "name": "serve_saturation",
            "p50_ms": knee["p50_ms"],
            "p99_ms": knee["p99_ms"],
            "bytes_per_round": knee["wire_bytes_per_round"],
            "gates": [
                {
                    "name": "knee_p99_within_budget",
                    "value": knee["p99_ms"],
                    "threshold": BUDGET_MS,
                    "op": "<=",
                    "pass": gate_p99,
                },
                {
                    "name": "pipelined_sustained_vs_sync",
                    "value": round(ratio, 3),
                    "threshold": ratio_target,
                    "op": ">=",
                    "pass": gate_ratio,
                },
                {
                    "name": "wire_compression",
                    "value": knee["wire_ratio"],
                    "threshold": WIRE_TARGET,
                    "op": ">=",
                    "pass": gate_wire,
                },
            ],
        },
    }
    out_path = REPO_ROOT / "BENCH_ingest.json"
    out_path.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {out_path}")

    if os.environ.get("BENCH_NO_FAIL"):
        return
    if not (gate_p99 and gate_ratio and gate_wire):
        sys.exit(1)


if __name__ == "__main__":
    main()
