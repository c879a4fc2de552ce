"""Hardware-faithful fixed-point window datapath (``numerics="fixed"``).

The paper's 62 ms / 8.5 W numbers come from fixed-point programmable
logic; the float pipeline reproduces the *algorithm* but not the
*datapath*. This module is the integer datapath: every accumulation in
the per-window stage chain — grid quantization, cell histogram,
coincidence/persistence filtering, patch scatter, intensity histogram,
Sobel, moment sums, edge counting — runs in integer arithmetic (int8/
int16-ranged inputs, int32 accumulators, the FPGA's DSP48/BRAM regime),
and only a small per-cluster scalar epilogue (log2/sqrt of exact
integers — a LUT/CORDIC stage in fabric) touches float32.

Number formats (DESIGN.md Sec. 12):

* coordinates: 10-bit sensor range carried as int16 (int8 once
  patch-relative), cells int16;
* all accumulators int32: per-cell ``count <= capacity`` (9 bits),
  ``sum_x < capacity * width`` (18 bits), ``sum_t < capacity *
  time_threshold_us`` (23 bits);
* centroids: UQ10.8 (int32, ``CENTROID_FRAC`` fractional bits), rounded
  half-to-even to match ``jnp.round``;
* patch origins: exact integer round-half-even division of the raw
  sums — NOT a re-rounding of the Q10.8 centroid, which would double-
  round — so origins are bit-identical to the float golden model;
* Sobel gradients: ``|g| <= 4 * capacity`` (int32), squared magnitude
  ``g2 <= 32 * capacity^2`` and its patch sum ``<= 64 * capacity^2``
  (int32-safe for capacity <= 4096).

Float-golden-model relationship (pinned by ``tests/test_fixed_point.py``):

* bit-identical: conditioning masks, cluster counts/cells/validity,
  window origins, count patches, histogram counts, and the
  shannon/renyi/local-contrast/event-count metrics (identical integers
  feed the identical float epilogue expressions);
* bounded: centroids within ``2**-8`` px (Q10.8 quantization),
  ``differential_entropy`` and ``edge_density`` within the analytic
  bounds documented in DESIGN.md Sec. 12 (the fixed path defines the
  gradient mean through an exact integer sqrt and the edge threshold
  through the exact integer compare ``16 * g2 > max(g2)``).

The fused Pallas megakernel (``repro.kernels.window_pipeline``) executes
this same datapath in one kernel launch per window batch and shares
:func:`fixed_metric_epilogue`, so staged-vs-fused bit-identity is
structural.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import metrics as M
from repro.core.events import EventBatch, coincidence_counts
from repro.core.grid_clustering import Clusters, GridConfig, _top_k_cells, quantize
from repro.core.pipeline.config import PipelineConfig
from repro.core.tracking import TrackState, tracker_step

CENTROID_FRAC = 8  # UQ10.8 centroid format (1/256 px resolution)
CENTROID_ONE = 1 << CENTROID_FRAC


class FixedClusters(NamedTuple):
    """Integer cluster set for one window (K slots), Q10.8 centroids.

    ``x0``/``y0`` are the 48x48 metric-patch origins, computed by exact
    integer division of the raw coordinate sums (bit-identical to the
    float path's ``round(centroid)`` origin — see module doc).
    """

    cq_x: jax.Array  # (K,) int32, UQ10.8 centroid column
    cq_y: jax.Array  # (K,) int32, UQ10.8 centroid row
    cq_t: jax.Array  # (K,) int32, UQ23.8 mean event time (us, window-rel)
    count: jax.Array  # (K,) int32
    cell_x: jax.Array  # (K,) int32
    cell_y: jax.Array  # (K,) int32
    x0: jax.Array  # (K,) int32 patch origin column
    y0: jax.Array  # (K,) int32 patch origin row
    valid: jax.Array  # (K,) bool

    def to_clusters(self) -> Clusters:
        """Dequantize to the standard float cluster struct (|error| <=
        2**-(CENTROID_FRAC+1) px vs the float path; invalid slots keep
        the float path's -1 sentinels)."""
        scale = jnp.float32(1.0 / CENTROID_ONE)

        def dq(cq):
            return jnp.where(self.valid, cq.astype(jnp.float32) * scale, -1.0)

        return Clusters(
            centroid_x=dq(self.cq_x),
            centroid_y=dq(self.cq_y),
            centroid_t=dq(self.cq_t),
            count=self.count,
            cell_x=self.cell_x,
            cell_y=self.cell_y,
            valid=self.valid,
        )


def round_div_half_even(num: jax.Array, den: jax.Array) -> jax.Array:
    """Exact round-half-to-even integer division (non-negative operands).

    Matches ``jnp.round(num / den)`` for every ratio the pipeline
    produces (num < 2**26, den <= capacity): the f32 quotient is within
    ulp of the rational, the rational is either exactly on a .5 boundary
    (then the f32 division is exact — the quotient fits 24 bits) or at
    least ``1/(2*den)`` away, and ``1/(2*den)`` dwarfs the division
    rounding error. This is the fabric-side divider the megakernel and
    the staged path share for patch origins.
    """
    q = num // den
    r = num - q * den
    two_r = 2 * r
    round_up = (two_r > den) | ((two_r == den) & ((q & 1) == 1))
    return q + round_up.astype(num.dtype)


def isqrt(v: jax.Array) -> jax.Array:
    """Exact integer floor-sqrt for int32 values (the LUT/CORDIC stage).

    f32 sqrt of an int <= 2**26 has error well below 1/2, so one
    correction step in each direction pins the exact floor.
    """
    r = jnp.floor(jnp.sqrt(v.astype(jnp.float32))).astype(jnp.int32)
    r = r - (r * r > v).astype(jnp.int32)
    r = r + ((r + 1) * (r + 1) <= v).astype(jnp.int32)
    return r


# ---------------------------------------------------------------------------
# Stage 1-2: grid quantization + integer cell histogram.
# ---------------------------------------------------------------------------

def cell_stats_fixed(
    batch: EventBatch, grid: GridConfig
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Integer scatter of (count, sum_x, sum_y, sum_t) per grid cell.

    Same masking/clipping as :func:`repro.core.grid_clustering.cell_histogram`
    but with int32 accumulators — the sums are exact integers below 2**24
    either way, so count/sum surfaces are bit-identical across numerics.
    """
    cx, cy = quantize(batch.x, batch.y, grid.cell_size)
    inb = (
        (batch.x >= 0)
        & (batch.x < grid.width)
        & (batch.y >= 0)
        & (batch.y < grid.height)
    )
    w = (batch.valid & inb).astype(jnp.int32)
    flat = jnp.clip(cy * grid.grid_w + cx, 0, grid.n_cells - 1)
    stats = jnp.stack([w, w * batch.x, w * batch.y, w * batch.t], axis=-1)
    acc = jnp.zeros((grid.n_cells, 4), jnp.int32).at[flat].add(stats)
    return acc[:, 0], acc[:, 1], acc[:, 2], acc[:, 3]


def clusters_fixed_from_stats(
    count: jax.Array,
    sum_x: jax.Array,
    sum_y: jax.Array,
    sum_t: jax.Array,
    grid: GridConfig,
    width: int | None = None,
    height: int | None = None,
    window: int = M.WINDOW,
) -> FixedClusters:
    """Top-K threshold + UQ10.8 centroids + exact integer patch origins.

    Cell selection reuses ``_top_k_cells`` on the identical int32 counts,
    so slot order / counts / cells / validity are bit-identical to the
    float path; only the centroid representation is quantized.
    """
    width = grid.width if width is None else width
    height = grid.height if height is None else height
    k = grid.max_clusters
    top_count, top_idx = _top_k_cells(count, k)
    valid = top_count >= grid.min_events
    den = jnp.maximum(top_count, 1)
    sx, sy, st = sum_x[top_idx], sum_y[top_idx], sum_t[top_idx]

    def q8(s):
        # Split form q*2^f + rdiv(r*2^f, den): never overflows int32 for
        # any sum below 2^31 (s * CENTROID_ONE would, for large time
        # sums), and rounds identically — the integer part q*2^f is
        # even, so the half-even parity check only needs the low word.
        q = s // den
        r = s - q * den
        return q * CENTROID_ONE + round_div_half_even(r * CENTROID_ONE, den)

    # Patch origin: round(centroid) from the RAW sums (single rounding),
    # then the same -window//2 + clip geometry as metrics.window_origin.
    # Invalid slots mirror the float path's -1.0 sentinel centroid.
    ox = jnp.where(valid, round_div_half_even(sx, den), -1)
    oy = jnp.where(valid, round_div_half_even(sy, den), -1)
    x0 = jnp.clip(ox - window // 2, 0, width - window)
    y0 = jnp.clip(oy - window // 2, 0, height - window)
    neg = jnp.int32(-CENTROID_ONE)  # dequantizes to the -1.0 sentinel
    return FixedClusters(
        cq_x=jnp.where(valid, q8(sx), neg),
        cq_y=jnp.where(valid, q8(sy), neg),
        cq_t=jnp.where(valid, q8(st), neg),
        count=jnp.where(valid, top_count, 0),
        cell_x=jnp.where(valid, (top_idx % grid.grid_w).astype(jnp.int32), -1),
        cell_y=jnp.where(valid, (top_idx // grid.grid_w).astype(jnp.int32), -1),
        x0=x0,
        y0=y0,
        valid=valid,
    )


# ---------------------------------------------------------------------------
# Stage 3-4: integer metric surfaces + shared float epilogue.
# ---------------------------------------------------------------------------

def sobel_int(patch: jax.Array) -> tuple[jax.Array, jax.Array]:
    """3x3 Sobel on an integer count patch — pure int32 shift-and-add over
    static slices, so the megakernel runs this same code inside Mosaic."""
    h, w = patch.shape
    padded = jnp.pad(patch, 1)

    def shift(dy: int, dx: int) -> jax.Array:
        return padded[dy:dy + h, dx:dx + w]

    left, right = shift(1, 0), shift(1, 2)
    up, down = shift(0, 1), shift(2, 1)
    ul, ur = shift(0, 0), shift(0, 2)
    dl, dr = shift(2, 0), shift(2, 2)
    gx = (ur - ul) + 2 * (right - left) + (dr - dl)
    gy = (dl - ul) + 2 * (down - up) + (dr - ur)
    return gx, gy


def fixed_metric_epilogue(
    hist_i: jax.Array,  # (bins,) int32 histogram counts
    s1: jax.Array,  # scalar int32: sum of patch counts
    s2: jax.Array,  # scalar int32: sum of squared patch counts
    s_g: jax.Array,  # scalar int32: sum of floor-sqrt gradient magnitudes
    s_e2: jax.Array,  # scalar int32: sum of squared gradient magnitudes
    edges: jax.Array,  # scalar int32: exact integer edge count
    count: jax.Array,  # scalar int32 cluster event count
    valid: jax.Array,  # scalar bool
    norm_i: jax.Array,  # scalar int32 frame normalizer (max coincidence)
    n: int,  # patch pixel count (window**2)
) -> dict[str, jax.Array]:
    """The one float stage of the fixed datapath: per-cluster scalar
    transcendentals over exact integers (a LUT stage in fabric).

    Shared verbatim by the staged jnp path and the Pallas megakernel, so
    their bit-identity is structural; shannon/renyi/contrast evaluate the
    same expressions as ``metrics._exact_cluster_metrics`` over the same
    integers and stay bit-identical to the float golden model too.
    """
    norm = norm_i.astype(jnp.float32)

    mean = s1.astype(jnp.float32) / n
    var_c = jnp.maximum(s2.astype(jnp.float32) / n - mean * mean, 0.0)
    contrast = jnp.sqrt(var_c) / norm

    # Fixed-point differential entropy: the gradient first moment uses
    # the exact integer floor-sqrt (|Δ| < 1/norm per pixel vs the float
    # path's sqrt); the second moment is exact. DESIGN.md Sec. 12 bounds
    # the resulting shift.
    m1 = (s_g.astype(jnp.float32) / n) / norm
    m2 = (s_e2.astype(jnp.float32) / n) / (norm * norm)
    var_g = jnp.maximum(m2 - m1 * m1, 1e-12)
    diff_entropy = 0.5 * jnp.log2(2.0 * jnp.pi * jnp.e * var_g)

    m = {
        "shannon_entropy": M._shannon_from_counts(hist_i),
        "renyi_entropy": M._renyi_from_counts(hist_i),
        "differential_entropy": diff_entropy,
        "local_contrast": contrast,
        "edge_density": edges.astype(jnp.float32) / n,
        "event_count": count.astype(jnp.float32),
    }
    return {k: jnp.where(valid, v, 0.0) for k, v in m.items()}


def fixed_metric_surfaces(
    batch: EventBatch,
    x0: jax.Array,
    y0: jax.Array,
    width: int,
    height: int,
    window: int = M.WINDOW,
    bins: int = M.HIST_BINS,
) -> dict[str, jax.Array]:
    """Every integer surface the metric epilogue consumes, for K clusters.

    Pure int32 arithmetic: coincidence counts, histogram bin indices via
    integer division (``(c * bins) // norm`` — provably equal to the
    float path's truncation, DESIGN.md Sec. 12), patch scatter, Sobel,
    exact edge compare ``16 * g2 > max(g2)``, integer floor-sqrt sums.
    """
    inb = (
        (batch.x >= 0) & (batch.x < width) & (batch.y >= 0) & (batch.y < height)
    )
    w = batch.valid & inb
    c, leader = coincidence_counts(batch.x, batch.y, w)
    c = c.astype(jnp.int32)
    norm_i = jnp.maximum(jnp.max(jnp.where(w, c, 0)), 1)

    bin_idx = jnp.clip((c * bins) // norm_i, 0, bins - 1)
    bins_onehot = (
        (bin_idx[:, None] == jnp.arange(bins, dtype=jnp.int32)[None, :])
        & leader[:, None]
    ).astype(jnp.int32)  # (E, bins)

    rx = batch.x[None, :] - x0[:, None]  # (K, E)
    ry = batch.y[None, :] - y0[:, None]
    inp = (rx >= 0) & (rx < window) & (ry >= 0) & (ry < window) & w[None, :]
    inp_i = inp.astype(jnp.int32)
    lead_inp = (inp & leader[None, :]).astype(jnp.int32)

    hist = lead_inp @ bins_onehot  # (K, bins) int32
    occ = lead_inp.sum(axis=-1)
    npix = window * window
    hist = hist.at[:, 0].add(npix - occ)
    s1 = inp_i.sum(axis=-1)
    s2 = (lead_inp * (c * c)[None, :]).sum(axis=-1)

    def per_patch(x0k, y0k):
        rxk = batch.x - x0k
        ryk = batch.y - y0k
        ink = (rxk >= 0) & (rxk < window) & (ryk >= 0) & (ryk < window) & w
        return (
            jnp.zeros((window, window), jnp.int32)
            .at[jnp.clip(ryk, 0, window - 1), jnp.clip(rxk, 0, window - 1)]
            .add(ink.astype(jnp.int32))
        )

    patches = jax.vmap(per_patch)(x0, y0)  # (K, window, window) int32
    gx, gy = jax.vmap(sobel_int)(patches)
    g2 = gx * gx + gy * gy
    g2max = jnp.max(g2, axis=(1, 2))
    edges = jnp.sum(
        16 * g2 > g2max[:, None, None], axis=(1, 2), dtype=jnp.int32
    )
    s_g = jnp.sum(isqrt(g2), axis=(1, 2), dtype=jnp.int32)
    s_e2 = jnp.sum(g2, axis=(1, 2), dtype=jnp.int32)
    return {
        "hist": hist, "s1": s1, "s2": s2, "s_g": s_g, "s_e2": s_e2,
        "edges": edges, "norm_i": norm_i, "patches": patches,
    }


def fixed_cluster_metrics(
    batch: EventBatch,
    fc: FixedClusters,
    width: int,
    height: int,
    window: int = M.WINDOW,
    bins: int = M.HIST_BINS,
) -> dict[str, jax.Array]:
    """Six metrics for K cluster slots, integer datapath end to end."""
    s = fixed_metric_surfaces(batch, fc.x0, fc.y0, width, height, window, bins)
    k = fc.x0.shape[0]
    return jax.vmap(
        functools.partial(fixed_metric_epilogue, n=window * window)
    )(
        s["hist"], s["s1"], s["s2"], s["s_g"], s["s_e2"], s["edges"],
        fc.count, fc.valid, jnp.broadcast_to(s["norm_i"], (k,)),
    )


# ---------------------------------------------------------------------------
# Per-window stage + scan-driver cores (the numerics="fixed" routing).
# ---------------------------------------------------------------------------

def _check_fixed_config(config: PipelineConfig) -> None:
    if config.merge_neighbors:
        raise ValueError(
            "numerics='fixed' does not support merge_neighbors (the merge "
            "weight-averages float centroids); run the float path instead"
        )
    if config.use_kernels:
        raise ValueError(
            "numerics='fixed' ignores use_kernels: the staged fixed path is "
            "integer jnp, and metrics_impl='megakernel' is the fused Pallas "
            "route — set use_kernels=False"
        )
    if config.metrics_impl not in ("event", "staged", "megakernel"):
        raise ValueError(
            "numerics='fixed' supports metrics_impl 'event'/'staged' (the "
            "staged integer path) or 'megakernel' (fused Pallas); got "
            f"{config.metrics_impl!r}"
        )


def fixed_window_stage(
    config: PipelineConfig, batch: EventBatch
) -> tuple[FixedClusters, dict[str, jax.Array]]:
    """Conditioning -> integer clustering -> integer metrics, one window.

    The staged golden reference for the megakernel: identical math, one
    jnp stage at a time.
    """
    from repro.core.pipeline.window_core import _condition

    batch = _condition(config, batch)
    fc = clusters_fixed_from_stats(
        *cell_stats_fixed(batch, config.grid), config.grid
    )
    mets = fixed_cluster_metrics(
        batch, fc, config.grid.width, config.grid.height
    )
    return fc, mets


def make_fixed_process_window(config: PipelineConfig):
    """Jit'd per-window fixed stage returning the standard float cluster
    struct (drop-in for ``make_process_window``)."""
    _check_fixed_config(config)
    if config.metrics_impl == "megakernel":
        from repro.kernels import ops as kops

        @jax.jit
        def process_window(batch: EventBatch):
            stacked = jax.tree.map(lambda a: a[None], batch)
            fc, mets = kops.window_pipeline_call(stacked, config)
            one = jax.tree.map(lambda a: a[0], fc)
            return one.to_clusters(), {k: v[0] for k, v in mets.items()}

        return process_window

    @jax.jit
    def process_window(batch: EventBatch):
        fc, mets = fixed_window_stage(config, batch)
        return fc.to_clusters(), mets

    return process_window


def _make_fixed_core(config: PipelineConfig, with_tracking: bool):
    """Step core for ``numerics="fixed"`` with the standard carry
    signature (atlas threaded through untouched).

    ``metrics_impl='event'/'staged'`` scans the staged integer stage one
    window at a time; ``'megakernel'`` runs the whole window batch
    through ONE Pallas launch and only the tracker scans.
    """
    _check_fixed_config(config)
    fused = config.metrics_impl == "megakernel"
    if fused:
        from repro.kernels import ops as kops

    def tracker_scan(state: TrackState, clusters, shannon):
        def step(carry, inp):
            cl, sh = inp
            carry, _ = tracker_step(carry, cl, sh, config.tracker)
            return carry, carry

        return jax.lax.scan(step, state, (clusters, shannon))

    def core(stacked: EventBatch, state: TrackState, atlas: jax.Array, tag0):
        del tag0  # only the event-space atlas needs window tags
        if fused:
            fc, mets = kops.window_pipeline_call(stacked, config)
            clusters = fc.to_clusters()
        else:
            def step(carry, batch):
                fc, m = fixed_window_stage(config, batch)
                return carry, (fc.to_clusters(), m)

            _, (clusters, mets) = jax.lax.scan(step, 0, stacked)
        if with_tracking:
            final, states = tracker_scan(
                state, clusters, mets["shannon_entropy"]
            )
        else:
            final, states = state, state
        return final, clusters, mets, states, atlas

    return core
