"""Quickstart: detect orbiting objects in a synthetic night-sky recording.

Runs the paper's full pipeline — dual-threshold event batching, grid
quantization (the FPGA IP core as a Pallas kernel / jnp), cluster
formation with min_events=5, entropy metrics, and tracking — and prints
the detections with their quality metrics.

Uses the device-resident scan driver (``run_recording_scan``): the whole
recording is windowed on host once, then conditioning -> clustering ->
metrics -> tracking run as a single compiled ``lax.scan`` with one
device dispatch. When events arrive as a live stream instead of a
recorded file, feed them incrementally to ``StreamingPipeline`` — see
``examples/stream_quickstart.py`` — for bit-identical results per
closed window.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core.pipeline import PipelineConfig, run_recording_scan, evaluate_detection
from repro.core.tracking import confirmed
from repro.data.synthetic import make_recording
from repro.launch.compile_cache import enable_compile_cache

def main() -> None:
    enable_compile_cache()
    print("Generating a 2 s synthetic EVAS-like recording (2 RSOs)...")
    rec = make_recording(seed=7, duration_s=2.0, n_rsos=2, lens="standard")
    print(f"  {len(rec):,} events "
          f"({np.sum(rec.kind == 2):,} RSO / {np.sum(rec.kind == 1):,} star "
          f"/ {np.sum(rec.kind == 0):,} noise)")

    cfg = PipelineConfig()  # paper defaults: 16px cells, min_events=5
    result = run_recording_scan(rec, cfg, with_tracking=True)
    print(f"Processed {result.num_windows} windows "
          f"(20 ms / 250-event batches, one compiled scan).")

    n_det = int(np.asarray(result.clusters.valid).sum())
    print(f"Clusters passing min_events=5: {n_det}")

    final = result.final_tracks
    conf = np.asarray(confirmed(final, cfg.tracker))
    print(f"Confirmed tracks: {int(conf.sum())}")
    for i in np.flatnonzero(conf):
        print(
            f"  track {i}: pos=({float(final.x[i]):6.1f},{float(final.y[i]):6.1f}) "
            f"vel=({float(final.vx[i]):+5.2f},{float(final.vy[i]):+5.2f}) px/win "
            f"hits={int(final.hits[i])} entropy={float(final.entropy[i]):.2f}"
        )

    score = evaluate_detection(rec, cfg)
    print(
        f"Detection accuracy vs ground truth: {100 * score.accuracy:.1f}% "
        f"(tp={score.tp} fp={score.fp} fn={score.fn} tn={score.tn})"
    )


if __name__ == "__main__":
    main()
