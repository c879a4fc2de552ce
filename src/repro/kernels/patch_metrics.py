"""Event->patch scatter Pallas kernel for the metrics stage (beyond-paper).

The paper's Discussion (Sec. VI) proposes pushing aggregation *and* the
quality metrics into the fabric. This kernel takes the heavy half of the
metrics stage, the way ``cluster_accum`` does for clustering (DESIGN.md
Sec. 6): one program per cluster slot scatters the window's events into
the cluster's 48x48 centroid-relative count patch. The scatter is one
(48, E) x (E, 48) MXU matmul of row and column one-hots — the TPU idiom
for the FPGA's BRAM scatter — so no sensor-sized buffer exists anywhere:
VMEM holds the event tile and one patch.

The kernel emits only exact integer count patches. Histogram counts, the
Sobel stencil and the six metrics run in XLA through the same code the
jnp event-space path runs (``repro.core.metrics.cluster_metrics_events``
with this kernel as its patch builder), so the kernel route is
bit-identical to the event path by construction, on any backend — the
same split the fixed-point megakernel makes (``window_pipeline.py``).

Inputs are per-event arrays padded to a lane multiple plus per-cluster
patch origins, which ride in SMEM; ``ops.patch_metrics_call`` handles
layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import metrics as M

LANE = 128
# Full f32 matmul passes: one-hot products must stay exact integers on the
# MXU, never a rounded bf16 pass.
EXACT = jax.lax.Precision.HIGHEST


def _kernel(x0_ref, y0_ref, x_ref, y_ref, w_ref, out_ref, *, window: int):
    e = x_ref.shape[-1]
    k = pl.program_id(0)
    rx = x_ref[...] - x0_ref[0, k]  # (1, E) int32
    ry = y_ref[...] - y0_ref[0, k]
    pix = jax.lax.broadcasted_iota(jnp.int32, (window, e), 0)
    # patch[r, q] = sum_e w_e [ry_e == r] [rx_e == q]: events outside the
    # patch match no row or column, so they drop out with no extra mask.
    rows = (pix == ry).astype(jnp.float32) * w_ref[...]
    cols = (pix == rx).astype(jnp.float32)
    out_ref[...] = jax.lax.dot_general(
        rows, cols, (((1,), (1,)), ((), ())),
        precision=EXACT, preferred_element_type=jnp.float32,
    )


def patch_counts(
    x: jax.Array,
    y: jax.Array,
    w: jax.Array,
    x0: jax.Array,
    y0: jax.Array,
    *,
    window: int = M.WINDOW,
    interpret: bool = False,
) -> jax.Array:
    """(K, window, window) float32 event-count patches for K clusters.

    Event arrays are (E,) with E a LANE multiple (ops.py pads with weight
    0); ``w`` is the per-event weight (validity); ``x0``/``y0`` are the
    (K,) patch origins, staged in SMEM as (1, K) rows: a 2-D block stays
    legal when ``vmap`` adds a batch dimension. One grid step per cluster
    slot; the two (window, E) one-hot blocks bound VMEM use (~100 KB at
    E=256).
    """
    e = x.shape[0]
    if e % LANE:
        raise ValueError(f"E ({e}) must be a multiple of {LANE}")
    k = x0.shape[0]
    ev_spec = pl.BlockSpec((1, e), lambda i: (0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        lambda *refs: _kernel(*refs, window=window),
        grid=(k,),
        in_specs=[smem, smem, ev_spec, ev_spec, ev_spec],
        out_specs=pl.BlockSpec((None, window, window), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, window, window), jnp.float32),
        interpret=interpret,
    )(
        x0.astype(jnp.int32).reshape(1, k),
        y0.astype(jnp.int32).reshape(1, k),
        x.astype(jnp.int32).reshape(1, e),
        y.astype(jnp.int32).reshape(1, e),
        w.astype(jnp.float32).reshape(1, e),
    )
