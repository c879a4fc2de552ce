"""Constellation scaling curve: sensors vs aggregate events/s and p99.

The scale-out bench for the sharded serving layer (DESIGN.md Sec. 15).
For each sensor count in SENSORS a :class:`ConstellationService` with
SHARDS shards runs N_ROUNDS live-cadence beats (every sensor feeds one
LEVEL-event chunk spanning CHUNK_US of sensor time, then one forced
pump dispatches every shard's round; compressed cross-shard exchange
stays on), reporting aggregate sustained events/s and per-round
p50/p99. A second single-shard run at RATIO_SENSORS sensors measures
what sharding itself buys at equal sensor count.

Gates (exit code 1 on failure, BENCH_NO_FAIL=1 to disable):

* **monotone scaling** — aggregate events/s strictly non-decreasing
  from 8 up through MONOTONE_MIN_SENSORS (>= 128): batching more
  sensors through the vmapped shard steps must amortize, not thrash.
  Host-bounded like the p99 gate: only points up to GATE_MAX_SENSORS
  are gated (a 1-core host is oversubscribed past ~32 live sensors and
  its aggregate legitimately dips); the reference multi-core host gates
  the full 8 -> 128 curve. The json records the applied bound.
* **p99 budget** — per-round p99 <= BUDGET_MS (the paper's 62 ms) at
  every point that fits the host: sensor counts up to GATE_MAX_SENSORS,
  which defaults to 32 x host_cores (one core drives ~32 live sensors
  inside the budget on the CPU backend; larger points are still
  measured and recorded, tracked from dedicated hardware).
* **shard speedup** — SHARDS-shard aggregate >= target x the 1-shard
  aggregate at RATIO_SENSORS sensors. The 2x target requires shards to
  actually run concurrently: a multi-device mesh (one device slice per
  shard) plus enough host cores to drive them. On a single-device or
  single-core host the shards time-slice one device, so the gate
  degrades to a documented no-regression floor (0.85x — the shard
  layer may not cost more than 15% overhead even where it cannot win),
  same convention as the ingest bench. BENCH_GATE_SHARDS overrides
  either; the json records applied and multi-device targets.
* **multi-shard chaos** — the shard chaos harness
  (:mod:`repro.serve.chaos_shards`, whole-shard stall included) must
  leave healthy outputs bit-identical with no session lost (CHAOS=0
  skips, e.g. when the suite already ran it).

Results land in BENCH_constellation.json at the repo root with the
uniform ``bench`` block the ``benchmarks.run`` aggregator consumes.

  PYTHONPATH=src python benchmarks/constellation_scaling.py
  SENSORS=8,32,128,512 SHARDS=2 LEVEL=250 N_ROUNDS=12 ...  (CI knobs)
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
from repro.core.pipeline.fleet import tier_capacity
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.batcher import AdmissionConfig
from repro.serve.constellation import ConstellationService

SENSORS = tuple(
    int(v) for v in os.environ.get("SENSORS", "8,32,128,512").split(",")
)
SHARDS = int(os.environ.get("SHARDS", "2"))
LEVEL = int(os.environ.get("LEVEL", "250"))  # events/sensor/round (1 window)
N_ROUNDS = int(os.environ.get("N_ROUNDS", "12"))
N_WARMUP = int(os.environ.get("N_WARMUP", "3"))
CHUNK_US = int(os.environ.get("CHUNK_US", "20000"))  # live-cadence beat
BUDGET_MS = float(os.environ.get("BUDGET_MS", "62"))
RATIO_SENSORS = int(os.environ.get("RATIO_SENSORS", "32"))
MONOTONE_MIN_SENSORS = int(os.environ.get("MONOTONE_MIN_SENSORS", "128"))
EXCHANGE = os.environ.get("EXCHANGE", "int8_ef")
SHARD_TARGET_MULTIDEVICE = 2.0
SHARD_FLOOR_SHARED_DEVICE = 0.85
REPO_ROOT = Path(__file__).resolve().parent.parent


def _stream(seed: int, n: int, dt_us: int):
    rng = np.random.default_rng(seed)
    return (
        rng.integers(40, 560, n).astype(np.int64),
        rng.integers(40, 400, n).astype(np.int64),
        (np.arange(n, dtype=np.int64) + 1) * dt_us,
        rng.integers(0, 2, n).astype(np.int64),
    )


def _replay(n_sensors: int, n_shards: int):
    """One (sensor count, shard count) point: aggregate sustained
    events/s over N_ROUNDS forced-pump beats, per-round times, and the
    constellation's exchange stats. Each shard's slot pool is sized to
    its share up front (one tier, one compile per shard shape)."""
    per_shard = tier_capacity(max(1, -(-n_sensors // n_shards)))
    cs = ConstellationService(
        PipelineConfig(),
        n_shards=n_shards,
        tiers=(per_shard,),
        admission=AdmissionConfig(max_delay_s=1e9, max_items=1 << 30),
        exchange=EXCHANGE,
    )
    total = (N_WARMUP + N_ROUNDS) * LEVEL
    dt_us = max(1, CHUNK_US // LEVEL)
    streams = [_stream(7 * s + 1, total, dt_us) for s in range(n_sensors)]
    gids = [cs.attach(f"c{s}") for s in range(n_sensors)]
    served = []

    def beat(rnd):
        lo, hi = rnd * LEVEL, (rnd + 1) * LEVEL
        for s, gid in enumerate(gids):
            x, y, t, p = streams[s]
            served.extend(cs.feed(gid, x[lo:hi], y[lo:hi], t[lo:hi], p[lo:hi]))
        served.extend(cs.pump(force=True))

    for rnd in range(N_WARMUP):  # compiles each shard's (S, W) step shape
        beat(rnd)
    cs.drain()
    served.clear()

    times = []
    t_all = time.perf_counter()
    for rnd in range(N_WARMUP, N_WARMUP + N_ROUNDS):
        t0 = time.perf_counter()
        beat(rnd)
        times.append((time.perf_counter() - t0) * 1e3)
    # The drain is in the measured window: in-flight rounds may not hide
    # their cost outside the sustained-throughput accounting.
    cs.drain()
    wall_s = time.perf_counter() - t_all
    windows = sum(fd.num_windows for fd in served)
    aggregate = N_ROUNDS * LEVEL * n_sensors / wall_s
    exchange = cs.exchange.stats
    for gid in gids:
        cs.detach(gid)
    del cs
    gc.collect()
    return times, aggregate, windows, exchange


def _point(n_sensors: int, n_shards: int) -> dict:
    times, aggregate, windows, exchange = _replay(n_sensors, n_shards)
    arr = np.asarray(times)
    return {
        "sensors": n_sensors,
        "shards": n_shards,
        "offered_events_s": round(n_sensors * LEVEL / (CHUNK_US / 1e6), 1),
        "aggregate_events_s": round(aggregate, 1),
        "p50_ms": round(float(np.percentile(arr, 50)), 3),
        "p99_ms": round(float(np.percentile(arr, 99)), 3),
        "windows": windows,
        "exchange_wire_bytes": exchange["wire_bytes"],
        "exchange_ratio": round(exchange["compression_ratio"], 3),
    }


def _run_chaos() -> dict:
    from repro.serve.chaos_shards import ShardChaosConfig, ShardChaosHarness

    rep = ShardChaosHarness(ShardChaosConfig(seed=7)).run()
    return {
        "bit_identical": rep.bit_identical,
        "lost_sessions": rep.lost_sessions,
        "escaped_errors": len(rep.escaped_errors),
        "rescues": rep.rescues,
        "migrations": rep.migrations,
        "fired": rep.fired,
    }


def main() -> None:
    enable_compile_cache()
    host_cores = os.cpu_count() or 1
    n_devices = len(jax.devices())
    gate_max_sensors = int(
        os.environ.get("GATE_MAX_SENSORS", str(32 * host_cores))
    )
    multi = n_devices >= SHARDS and host_cores >= 2 * SHARDS
    shard_target = (
        SHARD_TARGET_MULTIDEVICE if multi else SHARD_FLOOR_SHARED_DEVICE
    )
    shard_target = float(os.environ.get("BENCH_GATE_SHARDS", shard_target))
    print(
        f"backend={jax.default_backend()}  devices={n_devices}  "
        f"host_cores={host_cores}  shards={SHARDS}  sensors={SENSORS}  "
        f"level={LEVEL} ev/sensor/round  rounds={N_ROUNDS}"
    )

    gc.collect()
    points = [_point(n, SHARDS) for n in SENSORS]
    single = _point(RATIO_SENSORS, 1)
    paired = next(p for p in points if p["sensors"] == RATIO_SENSORS)
    shard_ratio = paired["aggregate_events_s"] / single["aggregate_events_s"]

    print(f"\n{'sensors':>8} {'offered/s':>12} {'aggregate/s':>12} "
          f"{'p50 ms':>8} {'p99 ms':>8} {'xchg':>6}")
    for p in points:
        print(
            f"{p['sensors']:>8} {p['offered_events_s']:>12,.0f} "
            f"{p['aggregate_events_s']:>12,.0f} {p['p50_ms']:>8.2f} "
            f"{p['p99_ms']:>8.2f} {p['exchange_ratio']:>6.2f}"
        )
    print(
        f"1-shard @ {RATIO_SENSORS}: {single['aggregate_events_s']:,.0f} ev/s"
        f"  ->  {SHARDS}-shard ratio {shard_ratio:.2f}x"
    )

    # Gate 1: monotone aggregate throughput from 8 up through
    # MONOTONE_MIN_SENSORS — bounded, like the p99 gate, to the points
    # that fit the host. On a 1-core CPU host the 128-sensor point is
    # oversubscribed by construction and its aggregate legitimately
    # dips; the reference multi-core host gates the full 8 -> 128 curve.
    monotone_bound = min(MONOTONE_MIN_SENSORS, gate_max_sensors)
    curve = [p for p in points if p["sensors"] <= monotone_bound]
    steps = [
        b["aggregate_events_s"] / a["aggregate_events_s"]
        for a, b in zip(curve, curve[1:])
    ]
    monotone_min = min(steps) if steps else 1.0
    gate_monotone = monotone_min >= 1.0

    # Gate 2: p99 within the paper budget at every point that fits.
    gated_points = [p for p in points if p["sensors"] <= gate_max_sensors]
    worst_p99 = max((p["p99_ms"] for p in gated_points), default=0.0)
    gate_p99 = worst_p99 <= BUDGET_MS

    # Gate 3: sharding speedup at equal sensor count.
    gate_shards = shard_ratio >= shard_target

    # Gate 4: multi-shard chaos (whole-shard stall included).
    chaos = None
    gate_chaos = True
    if os.environ.get("CHAOS", "1") != "0":
        chaos = _run_chaos()
        gate_chaos = (
            chaos["bit_identical"]
            and chaos["lost_sessions"] == 0
            and chaos["escaped_errors"] == 0
            and chaos["rescues"] >= 1
        )

    print(
        f"\nmonotone 8->{monotone_bound} (target {MONOTONE_MIN_SENSORS}, "
        f"host-bounded): min step ratio {monotone_min:.3f} >= 1.0 "
        f"({'PASS' if gate_monotone else 'FAIL'})"
    )
    print(
        f"p99 <= {BUDGET_MS} ms at sensors <= {gate_max_sensors}: worst "
        f"{worst_p99:.2f} ms ({'PASS' if gate_p99 else 'FAIL'})"
    )
    print(
        f"{SHARDS}-shard vs 1-shard @ {RATIO_SENSORS}: {shard_ratio:.2f}x >= "
        f"{shard_target}x ({'PASS' if gate_shards else 'FAIL'}; "
        f"multi-device target {SHARD_TARGET_MULTIDEVICE}x, "
        f"{n_devices} device(s) / {host_cores} core(s) here)"
    )
    if chaos is not None:
        print(
            f"shard chaos: bit_identical={chaos['bit_identical']} "
            f"lost={chaos['lost_sessions']} rescues={chaos['rescues']} "
            f"({'PASS' if gate_chaos else 'FAIL'})"
        )

    ref = gated_points[-1] if gated_points else points[0]
    payload = {
        "backend": jax.default_backend(),
        "commit": git_commit(),
        "host_cores": host_cores,
        "n_devices": n_devices,
        "shards": SHARDS,
        "level_events_per_sensor": LEVEL,
        "n_rounds": N_ROUNDS,
        "chunk_us": CHUNK_US,
        "exchange": EXCHANGE,
        "points": points,
        "single_shard": single,
        "shard_ratio": round(shard_ratio, 3),
        "shard_target_applied": shard_target,
        "shard_target_multidevice": SHARD_TARGET_MULTIDEVICE,
        "gate_max_sensors": gate_max_sensors,
        "monotone_bound_applied": monotone_bound,
        "chaos": chaos,
        "bench": {
            "name": "constellation_scaling",
            "p50_ms": ref["p50_ms"],
            "p99_ms": ref["p99_ms"],
            "gates": [
                {
                    "name": "aggregate_monotone_to_128",
                    "value": round(monotone_min, 3),
                    "threshold": 1.0,
                    "op": ">=",
                    "pass": gate_monotone,
                },
                {
                    "name": "p99_within_budget_fitting_points",
                    "value": round(worst_p99, 3),
                    "threshold": BUDGET_MS,
                    "op": "<=",
                    "pass": gate_p99,
                },
                {
                    "name": "shard_speedup_equal_sensors",
                    "value": round(shard_ratio, 3),
                    "threshold": shard_target,
                    "op": ">=",
                    "pass": gate_shards,
                },
                {
                    "name": "shard_chaos_bit_identical",
                    "value": 1.0 if gate_chaos else 0.0,
                    "threshold": 1.0,
                    "op": ">=",
                    "pass": gate_chaos,
                },
            ],
        },
    }
    out_path = REPO_ROOT / "BENCH_constellation.json"
    out_path.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {out_path}")

    if os.environ.get("BENCH_NO_FAIL"):
        return
    if not (gate_monotone and gate_p99 and gate_shards and gate_chaos):
        sys.exit(1)


if __name__ == "__main__":
    main()
