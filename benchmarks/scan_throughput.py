"""Loop vs scan vs vmapped-scan throughput, frame vs event metrics paths.

The loop driver is now memoized per config (ISSUE 3), so its steady-state
row measures pure per-window dispatch/host-sync/batching overhead — the
"loop (cold, re-jit)" row clears the caches first to keep the historical
as-shipped baseline (trace+compile included, the ISSUE 1 acceptance
line). The scanned driver pays one dispatch per recording. On top of
that dispatch story, the per-window core itself has two implementations
(ISSUE 2): the frame-based oracle that scatters a sensor-sized
accumulation image per window, and the frame-free event-space path
(O(events + K*patch^2) per window) that is bit-identical and must clear
>= 3x on the pre-windowed scan row. A per-stage breakdown (conditioning
/ histogram / metrics / tracking) attributes the win.

Results also land in BENCH_scan.json at the repo root so the perf
trajectory is tracked across PRs. Acceptance gates (exit code 1 on
failure, set BENCH_NO_FAIL=1 to disable):

* scan end-to-end >= 3x over the cold (re-jit) loop (ISSUE 1 line)
* event-space pre-windowed scan >= 3x over the frame path (ISSUE 2 line)
* fused fixed-point megakernel (ONE Pallas launch per window batch) vs
  the staged per-stage-kernel float path (two launches per window) on
  the same pre-windowed batch (ISSUE 6 line): >= 1x where launches are
  real (compiled TPU), a 0.5x regression floor under the CPU Pallas
  interpreter, plus a backend-independent HBM-traffic gate (<= 0.01x of
  the staged path) from the benchmarks/roofline_report.py window report,
  embedded alongside the measured ratio

  PYTHONPATH=src python benchmarks/scan_throughput.py
  N_WINDOWS=16 MEGA_WINDOWS=8 BENCH_GATE_EVENT=0 BENCH_GATE_MEGA=0
  ... (CI smoke knobs)
"""
import dataclasses
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax
import numpy as np
from _common import git_commit, time_fn

from repro.core import metrics as M
from repro.core.events import pad_windows
from repro.core.pipeline import (
    PipelineConfig,
    _cluster,
    _condition,
    _histogram_fn,
    _tracker_fn,
    init_tracks,
    make_process_window,
    make_scan_fn,
    run_many_scan,
    run_recording,
    run_recording_scan,
    tracker_step,
)
from repro.data.synthetic import Recording, make_recording
from repro.launch.compile_cache import enable_compile_cache

N_WINDOWS = int(os.environ.get("N_WINDOWS", "64"))
# The megakernel rows use a smaller window count: interpret-mode Pallas
# (CPU) unrolls the (W,) grid at trace time, so compile cost scales with W.
MEGA_WINDOWS = int(os.environ.get("MEGA_WINDOWS", "8"))
N_SENSORS = int(os.environ.get("N_SENSORS", "4"))
REPO_ROOT = Path(__file__).resolve().parent.parent


def _recording_with_windows(n_windows: int, seed: int = 0) -> Recording:
    """A synthetic recording truncated to exactly n_windows dual-threshold
    windows."""
    rec = make_recording(seed=seed, duration_s=3.0, n_rsos=2)
    config = PipelineConfig()
    windowed = pad_windows(rec.x, rec.y, rec.t, rec.p, config.batcher)
    if windowed.num_windows < n_windows:
        raise SystemExit(
            f"recording too short: {windowed.num_windows} < {n_windows} windows"
        )
    cut = int(windowed.stops[n_windows - 1])
    return Recording(
        x=rec.x[:cut], y=rec.y[:cut], t=rec.t[:cut], p=rec.p[:cut],
        kind=rec.kind[:cut], obj=rec.obj[:cut], rso_tracks=rec.rso_tracks,
        duration_us=int(rec.t[cut - 1]), name=f"{rec.name}-{n_windows}w",
    )


def _stage_breakdown(
    config: PipelineConfig, us_event: float, stacked
) -> dict[str, float]:
    """Per-stage wall times (ms) over the stacked windows: cumulative scans
    over prefixes of the frame-path window core, reported as deltas, plus
    the event-space metrics stage for the head-to-head."""
    hist_fn = _histogram_fn(config)
    grid = config.grid

    def scan_upto(stage):
        @jax.jit
        def run(b):
            def step(carry, batch):
                batch = _condition(config, batch)
                if stage == "conditioning":
                    return carry, batch.valid.sum()
                clusters = _cluster(config, hist_fn, batch)
                if stage == "histogram":
                    return carry, clusters.count.sum()
                mets = M.cluster_metrics_frame(batch, clusters, grid.width, grid.height)
                if stage == "metrics":
                    return carry, mets["shannon_entropy"].sum()
                carry, _ = tracker_step(
                    carry, clusters, mets["shannon_entropy"], config.tracker
                )
                return carry, mets["shannon_entropy"].sum()

            return jax.lax.scan(step, init_tracks(config.tracker), b)

        return run

    out: dict[str, float] = {}
    prev = 0.0
    for stage in ("conditioning", "histogram", "metrics", "tracking"):
        fn = scan_upto(stage)
        us = time_fn(lambda: fn(stacked), iters=5)
        out[stage] = max((us - prev) / 1e3, 0.0)  # deltas; clamp timer noise
        prev = us

    # Event-space metrics stage: the measured event scan row minus the
    # shared conditioning+histogram+tracking prefix cost.
    shared = out["conditioning"] + out["histogram"] + out["tracking"]
    out["metrics (event)"] = max(us_event / 1e3 - shared, 0.0)
    return out


def main() -> None:
    enable_compile_cache()
    config = PipelineConfig()  # metrics_impl="event" default
    config_frame = dataclasses.replace(config, metrics_impl="frame")
    rec = _recording_with_windows(N_WINDOWS)
    n_events = len(rec)
    print(
        f"backend={jax.default_backend()}  windows={N_WINDOWS}  "
        f"events={n_events:,}  sensors(vmap)={N_SENSORS}"
    )

    # Cold loop: clear the per-config caches so every call re-traces and
    # re-compiles — the historical "as shipped" baseline the ISSUE 1
    # acceptance line is defined against.
    def cold_loop():
        make_process_window.cache_clear()
        _tracker_fn.cache_clear()
        return run_recording(rec, config, with_tracking=True)

    us_loop = time_fn(cold_loop, warmup=1, iters=3)

    # Steady-state loop: make_process_window / _tracker_fn are memoized
    # per config, so a warm run_recording measures pure per-window
    # dispatch / host-sync / batching overhead.
    us_steady = time_fn(
        lambda: run_recording(rec, config, with_tracking=True), iters=5
    )

    # Scanned driver, end to end: host windowing + one compiled scan.
    us_scan = time_fn(
        lambda: run_recording_scan(rec, config, with_tracking=True).clusters.count,
        iters=5,
    )

    # Device-only scan: windows prebuilt, pure compiled time — the
    # frame-path oracle vs the frame-free event path head to head.
    # Samples are interleaved (alternating order) and the speedup is the
    # median of per-pair ratios, so slowly-varying host load hits both
    # rows of a pair equally and the ratio stays meaningful on shared
    # machines.
    import time as _time

    windowed = pad_windows(rec.x, rec.y, rec.t, rec.p, config.batcher)
    init = init_tracks(config.tracker)
    scan_event = make_scan_fn(config, True)
    scan_frame = make_scan_fn(config_frame, True)

    def _once(fn) -> float:
        t0 = _time.perf_counter()
        jax.block_until_ready(fn(windowed.batch, init))
        return (_time.perf_counter() - t0) * 1e6

    for fn in (scan_event, scan_frame):
        jax.block_until_ready(fn(windowed.batch, init))  # compile warmup
    samples_e: list[float] = []
    samples_f: list[float] = []
    for i in range(16):
        if i % 2:
            samples_e.append(_once(scan_event))
            samples_f.append(_once(scan_frame))
        else:
            samples_f.append(_once(scan_frame))
            samples_e.append(_once(scan_event))
    us_device_event = sorted(samples_e)[len(samples_e) // 2]
    us_device_frame = sorted(samples_f)[len(samples_f) // 2]
    pair_ratios = sorted(f / e for f, e in zip(samples_f, samples_e))
    ratio_event_over_frame = pair_ratios[len(pair_ratios) // 2]
    # Gate on the min/min ratio: the minimum is the classic least-noise
    # wall-time estimator (timeit-style), and scheduler/GC jitter on small
    # shared boxes lands almost entirely in the right tail.
    ratio_event_over_frame_best = min(samples_f) / min(samples_e)

    # Fused fixed-point megakernel (ONE Pallas launch per window batch)
    # vs the staged per-stage-kernel float path (two interpret-mode
    # launches per window), same pre-windowed batch, same interleaved
    # paired sampling as above.
    config_mega = dataclasses.replace(
        config, numerics="fixed", metrics_impl="megakernel"
    )
    config_kpath = dataclasses.replace(
        config, use_kernels=True, metrics_impl="kernel"
    )
    batch_mega = jax.tree_util.tree_map(
        lambda a: a[:MEGA_WINDOWS], windowed.batch
    )
    scan_mega = make_scan_fn(config_mega, True)
    scan_kpath = make_scan_fn(config_kpath, True)

    def _once_b(fn) -> float:
        t0 = _time.perf_counter()
        jax.block_until_ready(fn(batch_mega, init))
        return (_time.perf_counter() - t0) * 1e6

    for fn in (scan_mega, scan_kpath):
        jax.block_until_ready(fn(batch_mega, init))  # compile warmup
    samples_m: list[float] = []
    samples_k: list[float] = []
    for i in range(8):
        if i % 2:
            samples_m.append(_once_b(scan_mega))
            samples_k.append(_once_b(scan_kpath))
        else:
            samples_k.append(_once_b(scan_kpath))
            samples_m.append(_once_b(scan_mega))
    us_mega = sorted(samples_m)[len(samples_m) // 2]
    us_kpath = sorted(samples_k)[len(samples_k) // 2]
    mega_pair_ratios = sorted(k / m for k, m in zip(samples_k, samples_m))
    ratio_mega = mega_pair_ratios[len(mega_pair_ratios) // 2]
    ratio_mega_best = min(samples_k) / min(samples_m)

    # Vmapped scan across N_SENSORS recordings (one dispatch total).
    recs = [_recording_with_windows(N_WINDOWS, seed=s) for s in range(N_SENSORS)]
    us_vmap = time_fn(
        lambda: run_many_scan(recs, config)[-1].clusters.count, iters=5
    )

    stages = _stage_breakdown(config_frame, us_device_event, windowed.batch)

    rows: dict[str, dict[str, float]] = {}

    def report(name: str, us: float, windows: int, events: int) -> None:
        rows[name] = {
            "ms": round(us / 1e3, 3),
            "windows_per_sec": round(windows / (us * 1e-6), 1),
            "events_per_sec": round(events / (us * 1e-6), 1),
        }
        print(
            f"{name:<28} {us / 1e3:9.2f} ms   "
            f"{windows / (us * 1e-6):12,.0f} win/s   "
            f"{events / (us * 1e-6):14,.0f} ev/s"
        )

    print(f"{'driver':<28} {'wall':>12}   {'windows/sec':>12}   {'events/sec':>14}")
    report("loop (cold, re-jit)", us_loop, N_WINDOWS, n_events)
    report("loop (steady-state)", us_steady, N_WINDOWS, n_events)
    report("scan (end-to-end)", us_scan, N_WINDOWS, n_events)
    report("scan (pre-windowed, frame)", us_device_frame, N_WINDOWS, n_events)
    report("scan (pre-windowed, event)", us_device_event, N_WINDOWS, n_events)
    n_events_mega = int(np.asarray(batch_mega.valid).sum())
    report("staged kernels (float)", us_kpath, MEGA_WINDOWS, n_events_mega)
    report("megakernel (fixed)", us_mega, MEGA_WINDOWS, n_events_mega)
    report(
        f"vmap scan x{N_SENSORS}",
        us_vmap,
        N_SENSORS * N_WINDOWS,
        sum(len(r) for r in recs),
    )

    print("\nper-stage breakdown (frame-path scan body, ms over all windows):")
    for stage, ms in stages.items():
        print(f"  {stage:<18} {ms:8.2f} ms")

    speedup_scan = us_loop / us_scan
    speedup_event = ratio_event_over_frame
    gate_scan = speedup_scan >= 3.0
    gate_event = ratio_event_over_frame_best >= 3.0
    # Off TPU both contenders run under the Pallas interpreter, which
    # charges per grid point per op — the fused kernel's larger body pays
    # more interpretation than its one-launch saving returns, so the CPU
    # floor is a 0.5x regression guard; the >= 1x claim is gated where
    # launches are real (compiled TPU). The deterministic fusion evidence
    # (HBM traffic gate below) holds on every backend.
    mega_threshold = 1.0 if jax.default_backend() == "tpu" else 0.5
    gate_mega = ratio_mega_best >= mega_threshold
    print(
        f"\nscan end-to-end speedup over loop: {speedup_scan:.1f}x "
        f"({'PASS' if gate_scan else 'FAIL'} >= 3x acceptance)"
    )
    print(
        f"event-space speedup over frame path (pre-windowed): "
        f"{ratio_event_over_frame_best:.1f}x best, "
        f"{speedup_event:.1f}x paired-median "
        f"({'PASS' if gate_event else 'FAIL'} >= 3x best acceptance)"
    )
    print(
        f"megakernel speedup over staged kernel path "
        f"({MEGA_WINDOWS} windows): {ratio_mega_best:.1f}x best, "
        f"{ratio_mega:.1f}x paired-median "
        f"({'PASS' if gate_mega else 'FAIL'} >= {mega_threshold}x best "
        f"acceptance on this backend)"
    )

    # Roofline bytes/flops delta for the fused launch (ISSUE 6 evidence;
    # the measured ratio above pairs with this analytic/HLO comparison).
    import roofline_report

    wr = roofline_report.window_report(n_windows=4, capacity=256)
    gate_traffic = wr["mega_over_fixed_bytes"] <= 0.01
    print()
    print(roofline_report.window_markdown_table(wr))
    print(
        f"megakernel HBM traffic vs staged fixed: "
        f"{wr['mega_over_fixed_bytes']:.4f}x "
        f"({'PASS' if gate_traffic else 'FAIL'} <= 0.01x acceptance)"
    )

    payload = {
        "backend": jax.default_backend(),
        "commit": git_commit(),
        "n_windows": N_WINDOWS,
        "n_events": n_events,
        "rows": rows,
        "stages_ms": {k: round(v, 3) for k, v in stages.items()},
        "speedups": {
            "scan_end_to_end_over_loop": round(speedup_scan, 2),
            "event_over_frame_prewindowed": round(speedup_event, 2),
            "event_over_frame_prewindowed_best": round(
                ratio_event_over_frame_best, 2
            ),
            "megakernel_over_staged_kernels": round(ratio_mega, 2),
            "megakernel_over_staged_kernels_best": round(ratio_mega_best, 2),
        },
        "mega_windows": MEGA_WINDOWS,
        "roofline_window": wr,
        # Uniform block consumed by the benchmarks.run aggregator; the
        # percentiles are over the pre-windowed event-scan samples (the
        # steady-state compiled dispatch this bench is really about).
        "bench": {
            "name": "scan_throughput",
            "p50_ms": round(us_device_event / 1e3, 3),
            "p99_ms": round(
                float(np.percentile(np.asarray(samples_e), 99)) / 1e3, 3
            ),
            "gates": [
                {
                    "name": "scan_end_to_end_over_loop",
                    "value": round(speedup_scan, 2),
                    "threshold": 3.0,
                    "op": ">=",
                    "pass": gate_scan,
                },
                {
                    "name": "event_over_frame_prewindowed_best",
                    "value": round(ratio_event_over_frame_best, 2),
                    "threshold": 3.0,
                    "op": ">=",
                    "pass": gate_event,
                },
                {
                    "name": "megakernel_over_staged_kernels_best",
                    "value": round(ratio_mega_best, 2),
                    "threshold": mega_threshold,
                    "op": ">=",
                    "pass": gate_mega,
                },
                {
                    "name": "megakernel_hbm_traffic_over_staged",
                    "value": round(wr["mega_over_fixed_bytes"], 4),
                    "threshold": 0.01,
                    "op": "<=",
                    "pass": gate_traffic,
                },
            ],
        },
    }
    out_path = REPO_ROOT / "BENCH_scan.json"
    out_path.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"\nwrote {out_path}")

    if os.environ.get("BENCH_NO_FAIL"):
        return
    gates = [gate_scan]
    if os.environ.get("BENCH_GATE_EVENT", "1") != "0":
        gates.append(gate_event)
    if os.environ.get("BENCH_GATE_MEGA", "1") != "0":
        gates.append(gate_mega)
        gates.append(gate_traffic)
    if not all(gates):
        sys.exit(1)


if __name__ == "__main__":
    main()
