"""Cluster quality metrics (paper Sec. III-E).

For every detected cluster the paper extracts a 48x48 pixel window around
the centroid from a *reconstructed frame* (event accumulation image) and
computes six statistics used to pick the ``min_events`` operating point:

* Shannon entropy of the intensity histogram,
* Renyi entropy of order 2,
* differential entropy from the gradient-magnitude standard deviation,
* local contrast (intensity std),
* edge density (paper: Canny; here: Sobel magnitude + non-maximum-style
  threshold — Canny's hysteresis is a host-side heuristic that does not
  change the ranking the paper uses, noted in DESIGN.md Sec. 3),
* event count (carried through from clustering).

Two equivalent paths produce the six metrics (DESIGN.md Sec. 4):

* the **frame-based oracle** (:func:`cluster_metrics_frame`) scatters the
  window into a sensor-sized accumulation image and slices patches out of
  it — O(sensor area) per window, kept as the bit-exactness reference;
* the **event-space path** (:func:`cluster_metrics_events`) accumulates
  each cluster's 48x48 count patch directly from events via
  centroid-relative coordinates and recovers the frame's global-max
  normalizer from per-pixel coincidence counts — O(E + K * patch^2) per
  window, bit-identical to the oracle.

Bit-identity holds because every cross-path quantity is an exact small
integer (pixel counts, histogram counts, edge counts, integer moment
sums): float sums of exact integers below 2^24 are order-independent,
and both paths share :func:`_exact_cluster_metrics` for everything
downstream of those integers.

All functions are fixed-shape, jit- and vmap-friendly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.events import EventBatch, coincidence_counts
from repro.core.grid_clustering import Clusters

WINDOW = 48  # paper: 48x48 pixel window
HIST_BINS = 32
EDGE_THRESHOLD = 0.25


def accumulate_image(
    batch: EventBatch, width: int = 640, height: int = 480
) -> jax.Array:
    """Dense per-pixel event-count image (the un-normalized accumulation
    frame). Events outside the sensor are masked out of the weights, not
    clipped into a neighbouring pixel."""
    inb = (
        (batch.x >= 0) & (batch.x < width) & (batch.y >= 0) & (batch.y < height)
    )
    w = (batch.valid & inb).astype(jnp.float32)
    flat = jnp.clip(batch.y * width + batch.x, 0, width * height - 1)
    img = jnp.zeros((height * width,), jnp.float32).at[flat].add(w)
    return img.reshape(height, width)


def reconstruct_frame(
    batch: EventBatch, width: int = 640, height: int = 480
) -> jax.Array:
    """Accumulate events into an intensity frame, normalized to [0, 1]."""
    img = accumulate_image(batch, width, height)
    return img / jnp.maximum(img.max(), 1.0)


def window_origin(
    cx: jax.Array, cy: jax.Array, width: int, height: int, window: int = WINDOW
) -> tuple[jax.Array, jax.Array]:
    """Top-left corner of the edge-clamped (window, window) patch around a
    centroid — the one geometry shared by every metrics path."""
    x0 = jnp.clip(jnp.round(cx).astype(jnp.int32) - window // 2, 0, width - window)
    y0 = jnp.clip(jnp.round(cy).astype(jnp.int32) - window // 2, 0, height - window)
    return x0, y0


def extract_window(
    frame: jax.Array, cx: jax.Array, cy: jax.Array, window: int = WINDOW
) -> jax.Array:
    """Extract a (window, window) patch centered at (cx, cy), edge-clamped."""
    h, w = frame.shape
    x0, y0 = window_origin(cx, cy, w, h, window)
    return jax.lax.dynamic_slice(frame, (y0, x0), (window, window))


def _histogram_counts(patch: jax.Array, bins: int = HIST_BINS) -> jax.Array:
    """Integer intensity-histogram counts of a [0, 1] patch, as float32.

    Implemented as a one-hot compare-and-sum rather than a scatter-add:
    counts are exact small integers either way (bit-identical result), but
    the dense reduction vectorizes where vmapped scatters serialize —
    ~5x faster on CPU and the layout the scanned pipeline wants.
    """
    flat = patch.reshape(-1)
    idx = jnp.clip((flat * bins).astype(jnp.int32), 0, bins - 1)
    # int8 compares vectorize best on CPU; only valid while every bin
    # index fits in int8.
    cmp_dtype = jnp.int8 if bins <= 127 else jnp.int32
    onehot = idx.astype(cmp_dtype)[None, :] == jnp.arange(bins, dtype=cmp_dtype)[:, None]
    return onehot.sum(axis=1, dtype=jnp.int32).astype(jnp.float32)


def _ordered_sum(x: jax.Array) -> jax.Array:
    """Sum over the last axis in one fixed pairwise order.

    ``jnp.sum`` leaves a float reduction's order to the compiler, and
    XLA:TPU picks it per layout: the same values summed inside a batch of
    256 patches and inside a batch of 16 x 32 can differ in the last bit,
    which breaks the served-vs-scan bit-identity on the chip. Adding the
    halves of explicit slices pins the order (XLA does not reassociate
    float adds); odd lengths take a zero, which adds exactly.
    """
    while x.shape[-1] > 1:
        if x.shape[-1] % 2:
            x = jnp.concatenate([x, jnp.zeros_like(x[..., :1])], axis=-1)
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x[..., 0]


def _shannon_from_counts(counts: jax.Array) -> jax.Array:
    """H = -sum p_i log2 p_i from integer histogram counts (any dtype)."""
    c = counts.astype(jnp.float32)
    p = c / jnp.maximum(jnp.sum(c), 1.0)  # exact integer sum
    return -_ordered_sum(
        jnp.where(p > 0, p * jnp.log2(jnp.maximum(p, 1e-12)), 0.0)
    )


def _renyi_from_counts(counts: jax.Array) -> jax.Array:
    """H2 = -log2 sum p_i^2 from integer histogram counts (any dtype).

    sum p_i^2 = sum c_i^2 / (sum c_i)^2 with both sums in int32, so no
    float reduction (and no reduction order) enters the result.
    """
    c = counts.astype(jnp.int32)
    total = jnp.maximum(jnp.sum(c), 1).astype(jnp.float32)
    sq = jnp.sum(c * c).astype(jnp.float32)
    return -jnp.log2(jnp.maximum(sq / (total * total), 1e-12))


def shannon_entropy(patch: jax.Array, bins: int = HIST_BINS) -> jax.Array:
    """H = -sum p_i log2 p_i over the intensity histogram."""
    return _shannon_from_counts(_histogram_counts(patch, bins))


def renyi_entropy(patch: jax.Array, bins: int = HIST_BINS) -> jax.Array:
    """H2 = -log2 sum p_i^2 (collision entropy)."""
    return _renyi_from_counts(_histogram_counts(patch, bins))


def _sobel(patch: jax.Array) -> tuple[jax.Array, jax.Array]:
    """3x3 Sobel cross-correlation via shift-and-add.

    Zero-padded shifts match conv_general_dilated's SAME behaviour but
    lower to six adds per axis — far cheaper than a general convolution on
    CPU/VPU for a fixed 3x3 stencil, and fully fusable inside scan bodies.
    The shifts are static slices, which Mosaic lowers inside a Pallas TPU
    kernel too (a ``dynamic_slice`` it does not).
    """
    h, w = patch.shape
    padded = jnp.pad(patch, 1)

    def shift(dy: int, dx: int) -> jax.Array:
        return padded[dy:dy + h, dx:dx + w]

    left = shift(1, 0)
    right = shift(1, 2)
    up = shift(0, 1)
    down = shift(2, 1)
    ul, ur = shift(0, 0), shift(0, 2)
    dl, dr = shift(2, 0), shift(2, 2)
    gx = (ur - ul) + 2.0 * (right - left) + (dr - dl)
    gy = (dl - ul) + 2.0 * (down - up) + (dr - ur)
    return gx, gy


def gradient_magnitude(patch: jax.Array) -> jax.Array:
    gx, gy = _sobel(patch)
    return jnp.sqrt(gx * gx + gy * gy + 1e-12)


def _diff_entropy_from_g(g: jax.Array) -> jax.Array:
    var = jnp.maximum(jnp.var(g), 1e-12)
    return 0.5 * jnp.log2(2.0 * jnp.pi * jnp.e * var)


def _edge_density_from_g(g: jax.Array, threshold: float = 0.25) -> jax.Array:
    g = g / jnp.maximum(g.max(), 1e-3)
    return jnp.mean((g > threshold).astype(jnp.float32))


def differential_entropy(patch: jax.Array) -> jax.Array:
    """Gaussian-model differential entropy of gradient magnitudes:
    h = 0.5 * log2(2 pi e sigma^2)."""
    return _diff_entropy_from_g(gradient_magnitude(patch))


def local_contrast(patch: jax.Array) -> jax.Array:
    """Standard deviation of pixel intensities within the window."""
    return jnp.std(patch)


def edge_density(patch: jax.Array, threshold: float = 0.25) -> jax.Array:
    """Ratio of edge pixels to total pixels (Sobel-magnitude detector).

    The 1e-3 normalization floor keeps flat patches edge-free (frames are
    normalized to [0, 1], so real edges have O(1) gradients).
    """
    return _edge_density_from_g(gradient_magnitude(patch), threshold)


@jax.jit
def cluster_metrics(frame: jax.Array, clusters: Clusters) -> dict[str, jax.Array]:
    """Vectorized metric computation for every cluster slot. Invalid slots
    get zeros. Returns a dict of (K,) arrays keyed by metric name.

    Jitted: op-by-op dispatch of this many small ops costs seconds.
    Legacy reference operating on a pre-normalized frame; the pipeline
    routes through :func:`cluster_metrics_frame` /
    :func:`cluster_metrics_events` instead, which share the
    exactly-replayable metric core (values agree with this function to
    float tolerance, not bit-for-bit — see DESIGN.md Sec. 4).
    """

    def per_cluster(cx, cy, count, valid):
        patch = extract_window(frame, cx, cy)
        counts = _histogram_counts(patch)
        g = gradient_magnitude(patch)
        m = {
            "shannon_entropy": _shannon_from_counts(counts),
            "renyi_entropy": _renyi_from_counts(counts),
            "differential_entropy": _diff_entropy_from_g(g),
            "local_contrast": local_contrast(patch),
            "edge_density": _edge_density_from_g(g),
            "event_count": count.astype(jnp.float32),
        }
        return {k: jnp.where(valid, v, 0.0) for k, v in m.items()}

    return jax.vmap(per_cluster)(
        clusters.centroid_x, clusters.centroid_y, clusters.count, clusters.valid
    )


# ---------------------------------------------------------------------------
# Exactly-replayable metric core, shared by the frame-based oracle and the
# frame-free event-space path (DESIGN.md Sec. 4). Every quantity entering a
# reduction is either an exact small integer (order-independent sum) or
# summed in one fixed order (:func:`_ordered_sum`), so the result does not
# depend on how the compiler batches or lays out the patches.
# ---------------------------------------------------------------------------

def _exact_cluster_metrics(
    cnt_patch: jax.Array,  # (window, window) integer event counts, as f32
    hist_counts: jax.Array,  # (bins,) integer histogram counts, as f32
    norm: jax.Array,  # scalar frame normalizer: max(global max count, 1)
    count: jax.Array,  # scalar cluster event count
    valid: jax.Array,  # scalar cluster validity
    moments: tuple[jax.Array, jax.Array] | None = None,  # (sum c, sum c^2)
) -> dict[str, jax.Array]:
    """Six metrics for one cluster from its integer count patch.

    ``local_contrast`` uses integer moment sums (sum c, sum c^2 <= 2^24,
    exact in f32) and ``edge_density`` compares squared gradient
    magnitudes against a squared threshold, so both are computable from
    sparse events without replaying a dense reduction order — callers
    with event-side moments pass them via ``moments`` and skip two dense
    passes; the sums are exact integers either way, so the result is
    bit-identical. The gradient-magnitude statistics run densely on the
    count patch, which both paths materialize bit-identically: the
    squared magnitudes sum as exact integers, the magnitudes in one fixed
    order.
    """
    n = cnt_patch.size

    # Local contrast: std of normalized intensities via integer moments.
    if moments is None:
        s1 = jnp.sum(cnt_patch)
        s2 = jnp.sum(cnt_patch * cnt_patch)
    else:
        s1, s2 = moments
    mean = s1 / n
    var_c = jnp.maximum(s2 / n - mean * mean, 0.0)
    contrast = jnp.sqrt(var_c) / norm

    # Gradient field of the integer counts (Sobel outputs stay integer).
    gx, gy = _sobel(cnt_patch)
    g2 = gx * gx + gy * gy  # exact integers, summed in int32 below
    e2 = g2 / (norm * norm) + 1e-12  # squared magnitude
    g = jnp.sqrt(e2)
    s_g = _ordered_sum(g.reshape(-1))
    s_e2 = jnp.sum(g2.astype(jnp.int32)).astype(jnp.float32) / (
        norm * norm
    ) + n * 1e-12
    m1 = s_g / n
    var_g = jnp.maximum(s_e2 / n - m1 * m1, 1e-12)
    diff_entropy = 0.5 * jnp.log2(2.0 * jnp.pi * jnp.e * var_g)

    # Edge density: g / max(g.max(), 1e-3) > t, evaluated in squared
    # magnitude space (sqrt is monotone, so max commutes; the count of
    # edge pixels is an exact integer sum).
    den = jnp.maximum(jnp.sqrt(jnp.max(e2)), 1e-3)
    thr = (EDGE_THRESHOLD * den) * (EDGE_THRESHOLD * den)
    edges = jnp.sum((e2 > thr).astype(jnp.float32))
    edge_density_v = edges / n

    m = {
        "shannon_entropy": _shannon_from_counts(hist_counts),
        "renyi_entropy": _renyi_from_counts(hist_counts),
        "differential_entropy": diff_entropy,
        "local_contrast": contrast,
        "edge_density": edge_density_v,
        "event_count": count.astype(jnp.float32),
    }
    return {k: jnp.where(valid, v, 0.0) for k, v in m.items()}


def cluster_metrics_frame(
    batch: EventBatch,
    clusters: Clusters,
    width: int = 640,
    height: int = 480,
) -> dict[str, jax.Array]:
    """Frame-based oracle: metrics via a dense sensor-sized count image.

    Scatters the window into an O(sensor-area) accumulation image, takes
    the global max as the normalizer, and slices each cluster's count
    patch out with :func:`extract_window` — the paper's original data
    flow. Kept as the bit-exactness reference for
    :func:`cluster_metrics_events` (identical integer count patches and
    histogram counts feed the shared core).
    """
    img = accumulate_image(batch, width, height)
    norm = jnp.maximum(jnp.max(img), 1.0)

    def per_cluster(cx, cy, count, valid):
        cnt = extract_window(img, cx, cy)
        hist = _histogram_counts(cnt / norm)
        return _exact_cluster_metrics(cnt, hist, norm, count, valid)

    return jax.vmap(per_cluster)(
        clusters.centroid_x, clusters.centroid_y, clusters.count, clusters.valid
    )


def event_normalizer(batch: EventBatch, width: int, height: int):
    """Per-event coincidence counts, leaders, and the frame normalizer —
    everything :func:`reconstruct_frame` provides, recovered in event
    space. Returns (counts, leader, weight, norm)."""
    inb = (
        (batch.x >= 0) & (batch.x < width) & (batch.y >= 0) & (batch.y < height)
    )
    w = batch.valid & inb
    c, leader = coincidence_counts(batch.x, batch.y, w)
    norm = jnp.maximum(jnp.max(jnp.where(w, c, 0)).astype(jnp.float32), 1.0)
    return c, leader, w, norm


def event_histogram_counts(
    batch: EventBatch,
    c: jax.Array,
    leader: jax.Array,
    w: jax.Array,
    norm: jax.Array,
    x0: jax.Array,  # (K,) patch origins
    y0: jax.Array,
    window: int = WINDOW,
    bins: int = HIST_BINS,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Patch intensity-histogram counts straight from events: (K, bins).

    Every occupied pixel contributes through its leader event (whose bin
    index is the same expression the dense path evaluates per pixel);
    unoccupied pixels land in bin 0. Also returns the per-cluster
    integer moment sums ``(sum c, sum c^2)`` over the patch (exact in
    f32) for the contrast metric.
    """
    val = c.astype(jnp.float32) / norm
    bin_idx = jnp.clip((val * bins).astype(jnp.int32), 0, bins - 1)
    bins_onehot = (
        (bin_idx[:, None] == jnp.arange(bins)[None, :]) & leader[:, None]
    ).astype(jnp.float32)  # (E, bins)

    rx = batch.x[None, :] - x0[:, None]  # (K, E)
    ry = batch.y[None, :] - y0[:, None]
    inp = (
        (rx >= 0) & (rx < window) & (ry >= 0) & (ry < window) & w[None, :]
    ).astype(jnp.float32)

    lead_inp = inp * leader.astype(jnp.float32)[None, :]
    hist = jnp.matmul(  # (K, bins) exact integer counts: full f32 passes
        lead_inp, bins_onehot, precision=jax.lax.Precision.HIGHEST
    )
    occ = jnp.sum(lead_inp, axis=-1)
    hist = hist.at[:, 0].add(window * window - occ)
    # Moments: sum of pixel counts == events in patch; sum of squared
    # pixel counts through leaders. Exact integers below 2^24.
    s1 = jnp.sum(inp, axis=-1)
    c2 = (c * c).astype(jnp.float32)
    s2 = jnp.sum(lead_inp * c2[None, :], axis=-1)
    return hist, (s1, s2)


def cluster_count_patches(
    batch: EventBatch,
    clusters: Clusters,
    width: int = 640,
    height: int = 480,
    window: int = WINDOW,
) -> jax.Array:
    """(K, window, window) integer count patches accumulated directly from
    events via centroid-relative coordinates — no sensor-sized buffer."""
    inb = (
        (batch.x >= 0) & (batch.x < width) & (batch.y >= 0) & (batch.y < height)
    )
    w = batch.valid & inb
    x0, y0 = window_origin(
        clusters.centroid_x, clusters.centroid_y, width, height, window
    )

    def per_cluster(x0k, y0k):
        rx = batch.x - x0k
        ry = batch.y - y0k
        inp = (rx >= 0) & (rx < window) & (ry >= 0) & (ry < window) & w
        return (
            jnp.zeros((window, window), jnp.float32)
            .at[jnp.clip(ry, 0, window - 1), jnp.clip(rx, 0, window - 1)]
            .add(inp.astype(jnp.float32))
        )

    return jax.vmap(per_cluster)(x0, y0)


def cluster_metrics_events(
    batch: EventBatch,
    clusters: Clusters,
    width: int = 640,
    height: int = 480,
    count_patches=cluster_count_patches,
) -> dict[str, jax.Array]:
    """Frame-free metrics: O(E + K * patch^2) per window, bit-identical to
    :func:`cluster_metrics_frame`.

    The normalizer comes from per-pixel coincidence counts, histogram
    counts from leader events, and each cluster's count patch is
    accumulated directly from events — ``reconstruct_frame`` and the
    sensor-sized scatter never run. ``count_patches`` builds the patches
    (same signature as :func:`cluster_count_patches`); the Pallas
    ``patch_metrics`` kernel route passes its own.
    """
    c, leader, w, norm = event_normalizer(batch, width, height)
    x0, y0 = window_origin(
        clusters.centroid_x, clusters.centroid_y, width, height
    )
    hist, moments = event_histogram_counts(batch, c, leader, w, norm, x0, y0)
    patches = count_patches(batch, clusters, width, height)
    return jax.vmap(_exact_cluster_metrics)(
        patches, hist, jnp.broadcast_to(norm, x0.shape), clusters.count,
        clusters.valid, moments,
    )


METRIC_NAMES = (
    "shannon_entropy",
    "renyi_entropy",
    "differential_entropy",
    "local_contrast",
    "edge_density",
    "event_count",
)


def metric_matrix(metrics: dict[str, jax.Array]) -> jax.Array:
    """Stack the metric dict into a (K, 6) matrix in METRIC_NAMES order."""
    return jnp.stack([metrics[name] for name in METRIC_NAMES], axis=-1)


def correlation_matrix(samples: jax.Array) -> jax.Array:
    """Pearson correlation matrix across metric columns (paper Fig. 7).

    ``samples``: (N, M) matrix of N cluster observations x M metrics.
    """
    x = samples - samples.mean(axis=0, keepdims=True)
    cov = (x.T @ x) / jnp.maximum(samples.shape[0] - 1, 1)
    std = jnp.sqrt(jnp.clip(jnp.diag(cov), 1e-12))
    return cov / (std[:, None] * std[None, :])
