"""End-to-end RSO detection pipeline (paper Fig. 2), as a layered package.

Stages, matching the paper's data flow:

  event capture -> conditioning (ROI + persistent-event removal)
    -> spatial quantization        [FPGA IP core -> Pallas kernel / jnp]
    -> cluster formation           [client software -> scatter + top-k]
    -> min_events threshold + metrics
    -> tracking (spatial-coherence validation)

Layers (each also importable directly):

* ``config``      — :class:`PipelineConfig` + per-stage impl selectors.
* ``window_core`` — the per-window stage shared by every driver, and the
  legacy host-loop driver :func:`run_recording`.
* ``scan``        — the device-resident step core and the offline
  drivers :func:`run_recording_scan` / :func:`run_many_scan`.
* ``event_core``  — the phased event-space step core with the
  persistent tagged atlas (DESIGN.md Sec. 5).
* ``stream``      — :class:`StreamingPipeline`: resumable chunked feeds,
  bit-identical to the scan driver for any chunking.
* ``fleet``       — :class:`FleetPipeline`: N live sensors through one
  vmapped/jitted step with sensor-sharded stacked carries,
  bit-identical per sensor to N independent streaming pipelines.
* ``evaluate``    — device-resident candidate truth-matching, scoring,
  and the O(1)-dispatch :func:`threshold_sweep`.
* ``oracles``     — host-side (numpy / Python-loop) matching oracles.

This module re-exports the full public API, so
``from repro.core.pipeline import run_recording_scan`` keeps working as
it did when the pipeline was a single module.
"""
from repro.core.pipeline.config import (  # noqa: F401
    PipelineConfig,
    _histogram_fn,
    _metrics_fn,
)
from repro.core.pipeline.window_core import (  # noqa: F401
    WindowResult,
    _cluster,
    _condition,
    _tracker_fn,
    _window_core,
    make_process_window,
    run_recording,
)
from repro.core.pipeline.scan import (  # noqa: F401
    ScanResult,
    make_atlas,
    make_scan_fn,
    make_stream_fn,
    run_many_scan,
    run_recording_scan,
)
from repro.core.pipeline.stream import (  # noqa: F401
    StreamState,
    StreamingPipeline,
    empty_scan_result,
    tag_limit,
)
from repro.core.pipeline.fleet import (  # noqa: F401
    DEFAULT_TIERS,
    FleetPipeline,
    FleetResult,
    FleetState,
    PendingRound,
    SensorCursor,
    make_fleet_fn,
    tier_capacity,
)
from repro.core.pipeline.evaluate import (  # noqa: F401
    Candidates,
    DetectionScore,
    collect_candidates,
    collect_candidates_fleet,
    collect_candidates_many,
    evaluate_detection,
    match_candidates,
    merge_candidates,
    score_threshold,
    threshold_sweep,
    track_positions,
    track_table,
)
from repro.core.pipeline.oracles import (  # noqa: F401
    collect_candidates_loop,
    collect_candidates_numpy,
)
# Tracker entry points have always been reachable via this module; keep
# that surface for drivers and benchmarks.
from repro.core.tracking import init_tracks, tracker_step  # noqa: F401
