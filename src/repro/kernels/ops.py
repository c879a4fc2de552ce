"""Public jit'd wrappers around the Pallas kernels.

Handles stream padding/layout so callers pass natural 1-D event arrays,
and selects interpret mode automatically: compiled on TPU, interpreted
(kernel body executed in Python by the Pallas interpreter) on CPU so the
same code path is testable everywhere.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import cluster_accum as _ca
from repro.kernels import event_unpack as _eu
from repro.kernels import grid_quantize as _gq
from repro.kernels import patch_metrics as _pm
from repro.kernels import window_entropy as _we
from repro.kernels import window_pipeline as _wp


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(a: jax.Array, n: int, fill=0) -> jax.Array:
    pad = n - a.shape[0]
    if pad == 0:
        return a
    return jnp.concatenate([a, jnp.full((pad,), fill, a.dtype)])


@partial(jax.jit, static_argnames=("cell_size", "interpret"))
def grid_quantize_packed(
    words: jax.Array, cell_size: int = 16, interpret: bool | None = None
) -> jax.Array:
    """Quantize a 1-D stream of packed 32-bit event words (paper IP core).

    Pads to the kernel's (8, 128) tile, runs the Pallas kernel, and returns
    the first N packed cell words.
    """
    interpret = _default_interpret() if interpret is None else interpret
    n = words.shape[0]
    tile = _gq.BLOCK_ROWS * _gq.BLOCK_COLS
    n_pad = -(-n // tile) * tile
    padded = _pad_to(words.astype(jnp.uint32), n_pad)
    out = _gq.grid_quantize_packed(
        padded.reshape(-1, _gq.BLOCK_COLS), cell_size, interpret=interpret
    )
    return out.reshape(-1)[:n]


def event_unpack_call(
    words: jax.Array, interpret: bool | None = None
) -> tuple[jax.Array, jax.Array]:
    """Trace-time event-word unpack for the ragged ingest decoder.

    Takes a 1-D uint32 wire stream of any length, pads to the kernel's
    (8, 128) tile, and returns the first N (x, y) int32 coordinates —
    the same values :func:`repro.core.events.unpack_words` yields. No
    jit wrapper: every shape is static at trace time, so this is safe
    inside the enclosing wire-decoder jit without nesting a dispatch
    boundary.
    """
    interpret = _default_interpret() if interpret is None else interpret
    n = words.shape[0]
    tile = _eu.BLOCK_ROWS * _eu.BLOCK_COLS
    n_pad = -(-n // tile) * tile
    padded = _pad_to(words.astype(jnp.uint32), n_pad)
    x, y = _eu.event_unpack(
        padded.reshape(-1, _eu.BLOCK_COLS), interpret=interpret
    )
    return x.reshape(-1)[:n], y.reshape(-1)[:n]


def cluster_accum_call(
    x: jax.Array,
    y: jax.Array,
    t: jax.Array,
    valid: jax.Array,
    *,
    cell_size: int,
    grid_w: int,
    grid_h: int,
    width: int | None = None,
    height: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Trace-time fused quantize + per-cell count/centroid accumulation.

    No jit wrapper: all shapes (event count, pad amount, grid) are static
    at trace time, so this is safe to call inside an enclosing ``jax.jit``
    or a ``lax.scan`` body (the scanned pipeline path) without nesting a
    dispatch boundary per window.
    """
    interpret = _default_interpret() if interpret is None else interpret
    n = x.shape[0]
    n_pad = -(-n // _ca.EVENT_TILE) * _ca.EVENT_TILE
    return _ca.cluster_accum(
        _pad_to(x.astype(jnp.int32), n_pad),
        _pad_to(y.astype(jnp.int32), n_pad),
        _pad_to(t.astype(jnp.float32), n_pad),
        _pad_to(valid.astype(jnp.float32), n_pad),
        cell_size=cell_size,
        grid_w=grid_w,
        grid_h=grid_h,
        width=width,
        height=height,
        interpret=interpret,
    )


cluster_accum = jax.jit(
    cluster_accum_call,
    static_argnames=("cell_size", "grid_w", "grid_h", "width", "height", "interpret"),
)
cluster_accum.__doc__ = (
    "Jit'd entry point for host callers; see :func:`cluster_accum_call`."
)


def patch_metrics_call(
    batch,
    clusters,
    *,
    width: int = 640,
    height: int = 480,
    interpret: bool | None = None,
) -> dict:
    """Trace-time six cluster metrics with the Pallas patch scatter.

    The event-space metrics path
    (:func:`repro.core.metrics.cluster_metrics_events`) with its
    per-cluster count patches built by the ``patch_metrics`` kernel
    instead of a scatter-add: the patches are the same exact
    integers, and everything downstream is the shared jnp code, so the
    metrics are bit-identical to the event path. Like
    :func:`cluster_accum_call` this is safe inside an enclosing jit or
    scan body. Returns the metric dict keyed by
    ``repro.core.metrics.METRIC_NAMES``.
    """
    from repro.core import metrics as M

    interpret = _default_interpret() if interpret is None else interpret

    def count_patches(batch, clusters, width, height):
        inb = (
            (batch.x >= 0) & (batch.x < width)
            & (batch.y >= 0) & (batch.y < height)
        )
        x0, y0 = M.window_origin(
            clusters.centroid_x, clusters.centroid_y, width, height
        )
        n_pad = -(-batch.x.shape[0] // _pm.LANE) * _pm.LANE
        return _pm.patch_counts(
            _pad_to(batch.x.astype(jnp.int32), n_pad),
            _pad_to(batch.y.astype(jnp.int32), n_pad),
            _pad_to((batch.valid & inb).astype(jnp.float32), n_pad),
            x0,
            y0,
            interpret=interpret,
        )

    return M.cluster_metrics_events(
        batch, clusters, width, height, count_patches=count_patches
    )


def window_pipeline_call(
    stacked,
    config,
    *,
    window: int | None = None,
    bins: int | None = None,
    interpret: bool | None = None,
):
    """Trace-time fused per-window fixed-point pipeline (the megakernel).

    ``stacked`` is an EventBatch with (W, E) leaves (a window batch, as
    produced by ``pad_windows``); ``config`` a PipelineConfig. ONE kernel
    launch covers conditioning, clustering, and metrics for every window
    in the batch — versus two interpret-mode launches *per window* on the
    staged kernel path (``use_kernels`` + ``metrics_impl="kernel"``).
    The kernel covers the integer datapath; the float metric epilogue is
    the SAME vmapped ``fixed_point.fixed_metric_epilogue`` the staged
    path runs, applied here to the kernel's integer surfaces — that
    shared final stage is what makes fused-vs-staged bit-identity
    structural. Like the other ``*_call`` entry points this is safe
    inside an enclosing jit. Returns ``(FixedClusters, metrics)`` with
    (W, K) leaves; metrics keyed by ``repro.core.metrics.METRIC_NAMES``.
    """
    from functools import partial as _partial

    from repro.core import metrics as M
    from repro.core.fixed_point import FixedClusters, fixed_metric_epilogue

    interpret = _default_interpret() if interpret is None else interpret
    window = M.WINDOW if window is None else window
    bins = M.HIST_BINS if bins is None else bins
    e = stacked.x.shape[-1]
    e_pad = -(-e // _wp.LANE) * _wp.LANE

    def pad_ev(a, fill=0):
        if e_pad == e:
            return a
        pad_width = [(0, 0)] * (a.ndim - 1) + [(0, e_pad - e)]
        return jnp.pad(a, pad_width, constant_values=fill)

    grid = config.grid
    k = grid.max_clusters
    cl, surf = _wp.window_pipeline(
        pad_ev(stacked.x.astype(jnp.int32)),
        pad_ev(stacked.y.astype(jnp.int32)),
        pad_ev(stacked.t.astype(jnp.int32)),
        pad_ev(stacked.valid.astype(jnp.int32)),
        roi=tuple(config.roi),
        hot_pixel_max=config.hot_pixel_max,
        cell_size=grid.cell_size,
        grid_w=grid.grid_w,
        grid_h=grid.grid_h,
        min_events=grid.min_events,
        k=k,
        width=grid.width,
        height=grid.height,
        window=window,
        bins=bins,
        interpret=interpret,
    )
    rows = {f: cl[..., r, :k] for r, f in enumerate(_wp.CL_FIELDS)}
    fc = FixedClusters(
        cq_x=rows["cq_x"], cq_y=rows["cq_y"], cq_t=rows["cq_t"],
        count=rows["count"], cell_x=rows["cell_x"], cell_y=rows["cell_y"],
        x0=rows["x0"], y0=rows["y0"], valid=rows["valid"] != 0,
    )
    norm = rows["norm"][..., :1]  # (W, 1); every lane carries the value
    hist = surf[..., :bins]
    s1, s2, s_g, s_e2, edges = (
        surf[..., bins + i] for i in range(len(_wp.SURF_FIELDS))
    )
    epi = jax.vmap(_partial(fixed_metric_epilogue, n=window * window))
    for _ in range(stacked.x.ndim - 1):
        epi = jax.vmap(epi)
    mets = epi(
        hist, s1, s2, s_g, s_e2, edges, fc.count, fc.valid,
        jnp.broadcast_to(norm, fc.count.shape),
    )
    return fc, mets


@partial(jax.jit, static_argnames=("window", "bins", "interpret"))
def window_entropy(
    frame: jax.Array,
    cx: jax.Array,
    cy: jax.Array,
    *,
    window: int = 48,
    bins: int = 32,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-cluster (3, K) [shannon, renyi, contrast] window metrics."""
    interpret = _default_interpret() if interpret is None else interpret
    return _we.window_entropy(
        frame, cx, cy, window=window, bins=bins, interpret=interpret
    )
