"""Pipeline configuration + per-stage implementation selectors.

This is the one place the pipeline's tuning knobs are documented
(README and DESIGN.md point here):

* ``metrics_impl`` — which implementation computes the six per-cluster
  quality metrics (paper Sec. III-E). All three produce bit-identical
  values (pinned by ``tests/test_event_metrics.py``):

  - ``"event"`` (default): frame-free event-space path, O(E + K *
    patch^2) per window. Inside the scan/stream drivers it additionally
    uses the persistent window-tagged event atlas (DESIGN.md Sec. 5).
  - ``"frame"``: the paper's original data flow — sensor-sized
    accumulation image, global-max normalizer, patch slicing. O(sensor
    area) per window; kept as the bit-exactness oracle.
  - ``"kernel"``: the event path with its count patches built by the
    Pallas ``patch_metrics`` kernel (interpret-mode on CPU, compiled on
    TPU).

* ``scan_chunk`` — window-block size for the event-space driver's
  batched conditioning/clustering/stats phases (DESIGN.md Sec. 5). A
  cache-locality / vector-width scheduling knob only: results are
  invariant to it, including across the streaming engine's feed
  boundaries.

* ``use_kernels`` — route spatial quantization + cluster accumulation
  through the Pallas ``cluster_accum`` kernel instead of the jnp
  scatter (bit-identical; exercised by ``tests/test_pipeline_scan.py``).

* ``numerics`` — arithmetic datapath for the per-window stage chain:

  - ``"float"`` (default): the float32 golden model described above.
  - ``"fixed"``: the hardware-faithful integer datapath
    (``repro.core.fixed_point``) — int32 accumulators everywhere, float
    only in the per-cluster scalar epilogue, mirroring the paper's
    fixed-point fabric. Detection scores are bit-identical to the float
    path where DESIGN.md Sec. 12 claims so, and within documented
    bounds elsewhere. Under ``numerics="fixed"``, ``metrics_impl``
    selects ``"event"``/``"staged"`` (staged integer jnp stages, the
    golden reference) or ``"megakernel"`` (the fused Pallas
    ``window_pipeline`` kernel: one launch per window batch,
    bit-identical to the staged fixed path); ``"frame"``/``"kernel"``,
    ``use_kernels`` and ``merge_neighbors`` are float-path-only and
    raise ``ValueError``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax

from repro.core import metrics as M
from repro.core.events import DEFAULT_ROI, BatcherConfig, EventBatch
from repro.core.grid_clustering import Clusters, GridConfig, cell_histogram
from repro.core.tracking import TrackerConfig


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    grid: GridConfig = GridConfig()
    batcher: BatcherConfig = BatcherConfig()
    tracker: TrackerConfig = TrackerConfig()
    roi: tuple[int, int, int, int] = DEFAULT_ROI
    hot_pixel_max: int = 12
    merge_neighbors: bool = False
    use_kernels: bool = False  # route quantize+accumulate through Pallas
    metrics_impl: str = "event"  # "event" | "frame" | "kernel" (see module doc)
    scan_chunk: int = 8  # event-scan phase block size (scheduling only)
    numerics: str = "float"  # "float" | "fixed" (see module doc)


def _histogram_fn(config: PipelineConfig) -> Callable[[EventBatch], tuple]:
    if config.use_kernels:
        # Imported lazily: kernels are optional at pipeline import time.
        from repro.kernels import ops as kops

        def fn(batch: EventBatch):
            # Trace-time call (no nested jit): shapes are static inside
            # both the per-window jit and the scan body.
            return kops.cluster_accum_call(
                batch.x, batch.y, batch.t, batch.valid,
                cell_size=config.grid.cell_size,
                grid_w=config.grid.grid_w,
                grid_h=config.grid.grid_h,
                width=config.grid.width,
                height=config.grid.height,
            )

        return fn
    return lambda batch: cell_histogram(batch, config.grid)


def _metrics_fn(
    config: PipelineConfig,
) -> Callable[[EventBatch, Clusters], dict[str, jax.Array]]:
    """Per-window metrics stage for the configured implementation."""
    impl = config.metrics_impl
    w, h = config.grid.width, config.grid.height
    if impl == "frame":
        return lambda batch, clusters: M.cluster_metrics_frame(batch, clusters, w, h)
    if impl == "event":
        return lambda batch, clusters: M.cluster_metrics_events(batch, clusters, w, h)
    if impl == "kernel":
        from repro.kernels import ops as kops

        return lambda batch, clusters: kops.patch_metrics_call(
            batch, clusters, width=w, height=h
        )
    raise ValueError(f"unknown metrics_impl: {impl!r}")
