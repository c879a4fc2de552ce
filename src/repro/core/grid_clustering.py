"""Grid clustering (Schikuta 1996) adapted for streaming event data.

This is the paper's core algorithm, split exactly as the paper splits it:

* :func:`quantize` — the *stateless* spatial quantization stage (the FPGA IP
  core): ``cell = coord // cell_size``. The production path runs this (and
  the fused variant) as a Pallas TPU kernel (``repro.kernels``); this module
  is the composable pure-JAX implementation used as reference and on hosts.
* :func:`form_clusters` — the *stateful* cluster-formation stage (the
  paper's software client): aggregate events by cell, apply the
  ``min_events`` threshold (paper optimum: 5), emit centroids.

Everything is fixed-shape and jit/vmap/shard_map friendly.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.events import EventBatch, pack_words, unpack_words

DEFAULT_CELL_SIZE = 16  # paper: "grid size is fixed to 16x16"
DEFAULT_MIN_EVENTS = 5  # paper Table IV
DEFAULT_MAX_CLUSTERS = 32


@dataclasses.dataclass(frozen=True)
class GridConfig:
    width: int = 640
    height: int = 480
    cell_size: int = DEFAULT_CELL_SIZE
    min_events: int = DEFAULT_MIN_EVENTS
    max_clusters: int = DEFAULT_MAX_CLUSTERS

    @property
    def grid_w(self) -> int:
        return -(-self.width // self.cell_size)

    @property
    def grid_h(self) -> int:
        return -(-self.height // self.cell_size)

    @property
    def n_cells(self) -> int:
        return self.grid_w * self.grid_h


class Clusters(NamedTuple):
    """Fixed-capacity cluster set for one window (K = max_clusters slots)."""

    centroid_x: jax.Array  # (K,) float32
    centroid_y: jax.Array  # (K,) float32
    centroid_t: jax.Array  # (K,) float32 mean event time (us, window-rel)
    count: jax.Array  # (K,) int32 events contributing
    cell_x: jax.Array  # (K,) int32 grid cell column
    cell_y: jax.Array  # (K,) int32 grid cell row
    valid: jax.Array  # (K,) bool — count >= min_events

    def num_valid(self) -> jax.Array:
        return jnp.sum(self.valid.astype(jnp.int32), axis=-1)


def quantize(
    x: jax.Array, y: jax.Array, cell_size: int = DEFAULT_CELL_SIZE
) -> tuple[jax.Array, jax.Array]:
    """Stateless spatial quantization — the FPGA IP core's arithmetic.

    Power-of-two cell sizes lower to a shift (TPU VPU has no int division);
    this mirrors the DSP48 division in the paper's HLS core.
    """
    if cell_size & (cell_size - 1) == 0:
        shift = cell_size.bit_length() - 1
        return (x >> shift).astype(jnp.int32), (y >> shift).astype(jnp.int32)
    return (x // cell_size).astype(jnp.int32), (y // cell_size).astype(jnp.int32)


def quantize_packed(words: jax.Array, cell_size: int = DEFAULT_CELL_SIZE) -> jax.Array:
    """Wire-format-faithful quantization: 32-bit packed in, packed out.

    Matches the IP core end to end: unpack (bit slice) -> divide -> repack.
    """
    x, y = unpack_words(words)
    cx, cy = quantize(x, y, cell_size)
    return pack_words(cx, cy)


def cell_histogram(
    batch: EventBatch, config: GridConfig
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Scatter-accumulate per-cell statistics: count, sum_x, sum_y, sum_t.

    Events outside the sensor are masked out of the weights rather than
    clipped into a neighbouring cell (a clipped flat index would silently
    wrap ``x >= width`` onto the next row). The four statistics ride one
    scatter of (E, 4) rows instead of four separate scatters — XLA's CPU
    scatter loop is per-update, so packing cuts its iteration count 4x.
    """
    cx, cy = quantize(batch.x, batch.y, config.cell_size)
    inb = (
        (batch.x >= 0)
        & (batch.x < config.width)
        & (batch.y >= 0)
        & (batch.y < config.height)
    )
    w = (batch.valid & inb).astype(jnp.float32)
    flat = jnp.clip(cy * config.grid_w + cx, 0, config.n_cells - 1)
    stats = jnp.stack(
        [w, w * batch.x, w * batch.y, w * batch.t], axis=-1
    )  # (E, 4)
    acc = jnp.zeros((config.n_cells, 4), jnp.float32).at[flat].add(stats)
    count = acc[:, 0].astype(jnp.int32)
    return count, acc[:, 1], acc[:, 2], acc[:, 3]


def _top_k_cells(count: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """``lax.top_k`` with the identical contract, fast on CPU.

    XLA's CPU ``top_k`` lowers to a full variadic sort of all cells —
    ~2.7 ms for a vmapped (8, 1200) batch, which dominates the whole
    fleet step. K iterations of (argmax, mask) need only K linear passes
    and vectorize cleanly. The selection is exactly equivalent: values
    descend, and ties break to the lowest index (``argmax`` returns the
    first maximum, matching ``top_k``'s stable tie order), so every
    driver stays bit-identical whichever branch runs. Non-CPU backends
    keep the native ``top_k`` (their sort is fast and fused).
    """
    if jax.default_backend() != "cpu" or k > count.shape[-1]:
        return jax.lax.top_k(count, k)
    vals, idxs = [], []
    remaining = count
    for _ in range(k):
        i = jnp.argmax(remaining, axis=-1)
        v = jnp.take_along_axis(remaining, i[..., None], axis=-1)[..., 0]
        vals.append(v)
        idxs.append(i.astype(jnp.int32))
        remaining = jnp.where(
            jax.nn.one_hot(i, count.shape[-1], dtype=bool),
            jnp.iinfo(count.dtype).min,
            remaining,
        )
    return jnp.stack(vals, axis=-1), jnp.stack(idxs, axis=-1)


def clusters_from_histogram(
    count: jax.Array,
    sum_x: jax.Array,
    sum_y: jax.Array,
    sum_t: jax.Array,
    config: GridConfig,
) -> Clusters:
    """Threshold cells and emit the top-K clusters by event count."""
    k = config.max_clusters
    # top-k cells by count; invalid slots get count 0
    top_count, top_idx = _top_k_cells(count, k)
    valid = top_count >= config.min_events
    denom = jnp.maximum(top_count.astype(jnp.float32), 1.0)
    centroid_x = sum_x[top_idx] / denom
    centroid_y = sum_y[top_idx] / denom
    centroid_t = sum_t[top_idx] / denom
    cell_x = (top_idx % config.grid_w).astype(jnp.int32)
    cell_y = (top_idx // config.grid_w).astype(jnp.int32)
    return Clusters(
        centroid_x=jnp.where(valid, centroid_x, -1.0),
        centroid_y=jnp.where(valid, centroid_y, -1.0),
        centroid_t=jnp.where(valid, centroid_t, -1.0),
        count=jnp.where(valid, top_count, 0),
        cell_x=jnp.where(valid, cell_x, -1),
        cell_y=jnp.where(valid, cell_y, -1),
        valid=valid,
    )


def form_clusters(batch: EventBatch, config: GridConfig) -> Clusters:
    """The paper's client-side cluster formation, single pass, O(n)."""
    return clusters_from_histogram(*cell_histogram(batch, config), config)


def grid_cluster(batch: EventBatch, config: GridConfig = GridConfig()) -> Clusters:
    """End-to-end grid clustering for one event window (quantize + form)."""
    return form_clusters(batch, config)


# ---------------------------------------------------------------------------
# Neighbour merge (optional refinement; Schikuta's hierarchical step).
# ---------------------------------------------------------------------------

def merge_adjacent(clusters: Clusters, config: GridConfig) -> Clusters:
    """Merge clusters in 8-adjacent cells into the heaviest member.

    The paper's pipeline reports per-cell clusters; objects spanning a cell
    boundary appear as two adjacent clusters. This single sweep merges each
    cluster into its heaviest 8-neighbour (transitively dominated by the
    local maximum), weight-averaging centroids. Fixed shape, O(K^2).
    """
    k = clusters.count.shape[-1]
    dx = jnp.abs(clusters.cell_x[:, None] - clusters.cell_x[None, :])
    dy = jnp.abs(clusters.cell_y[:, None] - clusters.cell_y[None, :])
    adjacent = (dx <= 1) & (dy <= 1) & clusters.valid[:, None] & clusters.valid[None, :]
    counts = clusters.count.astype(jnp.float32)
    # Parent = heaviest adjacent cluster (ties broken by index).
    score = jnp.where(adjacent, counts[None, :], -1.0)
    parent = jnp.argmax(score - 1e-6 * jnp.arange(k)[None, :], axis=-1)
    parent = jnp.where(clusters.valid, parent, jnp.arange(k))
    # A root is its own parent.
    is_root = parent == jnp.arange(k)
    onehot = jax.nn.one_hot(parent, k, dtype=jnp.float32)  # (child, root)
    w = counts * clusters.valid
    # Full f32 passes: a default single bf16 pass on the TPU's MXU would
    # round the weighted coordinate and time sums.
    dot = lambda a: jnp.matmul(a, onehot, precision=jax.lax.Precision.HIGHEST)
    merged_count = dot(w).astype(jnp.int32)
    merged_x = dot(w * clusters.centroid_x)
    merged_y = dot(w * clusters.centroid_y)
    merged_t = dot(w * clusters.centroid_t)
    denom = jnp.maximum(merged_count.astype(jnp.float32), 1.0)
    valid = is_root & clusters.valid & (merged_count >= 1)
    return Clusters(
        centroid_x=jnp.where(valid, merged_x / denom, -1.0),
        centroid_y=jnp.where(valid, merged_y / denom, -1.0),
        centroid_t=jnp.where(valid, merged_t / denom, -1.0),
        count=jnp.where(valid, merged_count, 0),
        cell_x=jnp.where(valid, clusters.cell_x, -1),
        cell_y=jnp.where(valid, clusters.cell_y, -1),
        valid=valid,
    )
