"""Paper Table IV: system specs — detection accuracy (sampled detections
vs ground truth, the paper's 97% protocol), end-to-end throughput, and
the TPU roofline for the quantization kernel (the II=1 / 200 MEv/s
analogue)."""
from __future__ import annotations

import time

import numpy as np

from repro.core.pipeline import PipelineConfig, merge_candidates, collect_candidates, score_threshold
from repro.core.pipeline import run_recording
from repro.data.synthetic import make_recording
from repro.launch.mesh import TARGET_DEVICE_KIND, chip_peaks


def bench() -> list[tuple[str, float, str]]:
    rows = []
    recs = [
        make_recording(seed=s, duration_s=1.0, n_rsos=1 + s % 3) for s in (1, 2)
    ] + [make_recording(seed=11, duration_s=1.0, n_rsos=1, lens="telephoto"),
         make_recording(seed=21, duration_s=1.0, n_rsos=2, lens="wide")]
    cfg = PipelineConfig()

    # Accuracy at the paper's operating point, >= 1000 sampled detections.
    cand = merge_candidates([collect_candidates(r, cfg) for r in recs])
    score = score_threshold(cand, 5)
    n_samples = score.tp + score.fp + score.fn + score.tn
    rows.append(
        ("table4/detection_accuracy", 0.0,
         f"{100 * score.accuracy:.1f}pct_n{n_samples}_paper97")
    )
    rows.append(
        ("table4/precision_recall", 0.0,
         f"p{100 * score.precision:.1f}_r{100 * score.recall:.1f}")
    )

    # End-to-end throughput (events/s through the full pipeline).
    rec = recs[0]
    t0 = time.perf_counter()
    run_recording(rec, cfg, with_tracking=True)
    dt = time.perf_counter() - t0
    rows.append(
        ("table4/pipeline_throughput", dt / max(len(rec), 1) * 1e6,
         f"{len(rec) / dt / 1e3:.0f}kEv_s_cpu")
    )

    # Quantize-kernel roofline on the TPU target: 4B in + 4B out per event
    # at HBM bandwidth (the stream is too light to be compute-bound).
    ev_per_s = chip_peaks(TARGET_DEVICE_KIND).hbm_bw / 8.0
    rows.append(
        ("table4/quantize_kernel_roofline", 0.0,
         f"{ev_per_s / 1e9:.0f}GEv_s_vs_paper_0.2GEv_s")
    )
    # Config constants carried from the paper.
    rows.append(("table4/grid_size", 0.0, "16x16_cells"))
    rows.append(("table4/min_events", 0.0, "5"))
    rows.append(("table4/batch", 0.0, "250ev_20ms"))
    return rows
