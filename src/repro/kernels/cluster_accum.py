"""Fused quantize + cluster-accumulate Pallas kernel (beyond-paper).

The paper's Discussion (Sec. VI) proposes offloading aggregation and
centroid calculation to the FPGA fabric to cut total latency below 30 ms.
This kernel realizes that fusion on TPU: one pass over the event stream
produces, per grid cell, the event count and the coordinate/time sums the
centroid calculation needs — the client-side stage collapses to one
division.

TPU mapping: per event tile we build a one-hot cell-assignment matrix and
accumulate the four statistics with a single (4, TILE) @ (TILE, CELLS)
matmul — scatter-add re-expressed as MXU work, which is the TPU-idiomatic
replacement for the FPGA's BRAM read-modify-write loop (DESIGN.md Sec. 2).

Accumulators live in the output VMEM block across grid steps (constant
index_map), initialized at step 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

EVENT_TILE = 256  # events per grid step
LANE = 128


def _kernel(x_ref, y_ref, t_ref, valid_ref, out_ref, *, cell_size: int, grid_w: int, n_cells_padded: int, width: int, height: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.int32)  # (1, TILE)
    y = y_ref[...].astype(jnp.int32)
    t = t_ref[...].astype(jnp.float32)
    v = valid_ref[...].astype(jnp.float32)
    # Sensor-bounds mask mirrors core.grid_clustering.cell_histogram:
    # out-of-range events are dropped, never wrapped into another cell.
    inb = (x >= 0) & (x < width) & (y >= 0) & (y < height)
    v = v * inb.astype(jnp.float32)

    if cell_size & (cell_size - 1) == 0:
        shift = cell_size.bit_length() - 1
        cx = x >> shift
        cy = y >> shift
    else:
        cx = x // cell_size
        cy = y // cell_size
    flat = cy * grid_w + cx  # (1, TILE)
    flat = jnp.clip(flat, 0, n_cells_padded - 1)

    # One-hot (TILE, CELLS) via iota comparison; masked by validity.
    cells_iota = jax.lax.broadcasted_iota(jnp.int32, (EVENT_TILE, n_cells_padded), 1)
    onehot = (flat.reshape(EVENT_TILE, 1) == cells_iota).astype(jnp.float32)
    onehot = onehot * v.reshape(EVENT_TILE, 1)

    # Stats stacked: rows = [count, sum_x, sum_y, sum_t] -> (4, TILE).
    xf = x.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    stats = jnp.concatenate(
        [jnp.ones_like(xf), xf * v, yf * v, t * v], axis=0
    )  # (4, TILE); count row masked via onehot already
    # Full f32 passes: coordinates and timestamps must sum exactly, which a
    # single bf16 MXU pass would round.
    acc = jnp.dot(
        stats, onehot, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (4, CELLS)
    out_ref[...] += acc


def cluster_accum(
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
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused histogram/centroid accumulation over an event batch.

    Inputs are (N,) arrays with N a multiple of EVENT_TILE (ops.py pads).
    Returns (count int32, sum_x, sum_y, sum_t float32), each (grid_w*grid_h,).
    ``width``/``height`` bound the valid sensor area (default: the full
    grid extent), matching the core path's out-of-range masking.
    """
    n = x.shape[0]
    if n % EVENT_TILE:
        raise ValueError(f"N ({n}) must be a multiple of {EVENT_TILE}")
    width = grid_w * cell_size if width is None else width
    height = grid_h * cell_size if height is None else height
    n_cells = grid_w * grid_h
    n_cells_padded = -(-n_cells // LANE) * LANE
    grid = (n // EVENT_TILE,)

    def reshape_in(a, dtype):
        return a.astype(dtype).reshape(1, n)

    out = pl.pallas_call(
        lambda xr, yr, tr, vr, o: _kernel(
            xr, yr, tr, vr, o,
            cell_size=cell_size, grid_w=grid_w, n_cells_padded=n_cells_padded,
            width=width, height=height,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, EVENT_TILE), lambda i: (0, i)),
            pl.BlockSpec((1, EVENT_TILE), lambda i: (0, i)),
            pl.BlockSpec((1, EVENT_TILE), lambda i: (0, i)),
            pl.BlockSpec((1, EVENT_TILE), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((4, n_cells_padded), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((4, n_cells_padded), jnp.float32),
        interpret=interpret,
    )(
        reshape_in(x, jnp.int32),
        reshape_in(y, jnp.int32),
        reshape_in(t, jnp.float32),
        reshape_in(valid, jnp.float32),
    )
    count = out[0, :n_cells].astype(jnp.int32)
    return count, out[1, :n_cells], out[2, :n_cells], out[3, :n_cells]
