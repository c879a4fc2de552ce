"""Streaming quickstart: detect orbiting objects from a live-style feed.

Replays a synthetic EVAS-like recording through the streaming engine in
20 ms chunks — the cadence of a live event camera — instead of handing
the whole file to the offline driver. Each ``feed`` call windows the
incoming events with the paper's dual-threshold policy, runs ONE jit'd
step over the windows that closed, and returns their clusters, quality
metrics, and tracker state; the dual-threshold remainder, persistent
event atlas, and tracker carry ride along in ``StreamingPipeline.state``
between calls, so the results are bit-identical to
``run_recording_scan`` over the same events no matter how the stream is
chunked.

  PYTHONPATH=src python examples/stream_quickstart.py
"""
import time

import numpy as np

from repro.core.events import stride_bounds
from repro.core.pipeline import PipelineConfig, StreamingPipeline
from repro.core.tracking import confirmed
from repro.data.synthetic import make_recording
from repro.launch.compile_cache import enable_compile_cache

CHUNK_US = 20_000  # feed 20 ms of events at a time


def main() -> None:
    enable_compile_cache()
    print("Generating a 2 s synthetic EVAS-like recording (2 RSOs)...")
    rec = make_recording(seed=7, duration_s=2.0, n_rsos=2, lens="standard")
    print(f"  {len(rec):,} events")

    cfg = PipelineConfig()  # paper defaults: 16px cells, min_events=5
    sp = StreamingPipeline(cfg, with_tracking=True)

    n_windows = 0
    n_detections = 0
    latencies = []
    for lo, hi, _ in stride_bounds(rec.t, CHUNK_US):
        t0 = time.perf_counter()
        res = sp.feed(rec.x[lo:hi], rec.y[lo:hi], rec.t[lo:hi], rec.p[lo:hi])
        n_det = int(np.asarray(res.clusters.valid).sum())  # syncs the step
        latencies.append((time.perf_counter() - t0) * 1e3)
        n_windows += res.num_windows
        n_detections += n_det
    tail = sp.flush()  # close the trailing partial window
    n_windows += tail.num_windows
    n_detections += int(np.asarray(tail.clusters.valid).sum())

    print(f"Processed {n_windows} windows from {len(latencies)} chunked feeds.")
    print(f"Clusters passing min_events=5: {n_detections}")
    lat = np.asarray(latencies[3:])  # skip jit warmup feeds
    print(
        f"Steady-state per-chunk latency: p50={np.percentile(lat, 50):.1f} ms "
        f"p99={np.percentile(lat, 99):.1f} ms (paper budget: 62 ms)"
    )

    final = sp.state.tracks
    conf = np.asarray(confirmed(final, cfg.tracker))
    print(f"Confirmed tracks: {int(conf.sum())}")
    for i in np.flatnonzero(conf):
        print(
            f"  track {i}: pos=({float(final.x[i]):6.1f},{float(final.y[i]):6.1f}) "
            f"vel=({float(final.vx[i]):+5.2f},{float(final.vy[i]):+5.2f}) px/win "
            f"hits={int(final.hits[i])} entropy={float(final.entropy[i]):.2f}"
        )


if __name__ == "__main__":
    main()
