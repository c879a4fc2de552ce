"""Fused per-window Pallas megakernel for the fixed-point datapath.

One kernel launch per window *batch* — each grid step runs the entire
integer per-window stage chain that ``repro.core.fixed_point`` stages
through separate jnp ops (and that the float path spreads over multiple
kernel launches when ``use_kernels``/``metrics_impl="kernel"`` are on):

    ROI filter -> hot-pixel filter -> coincidence counts/leaders ->
    grid quantization -> 4-stat cell histogram -> top-K cell selection ->
    UQ10.8 centroids + exact patch origins -> per-cluster patch scatter,
    intensity histogram, Sobel, edge count, integer moment sums.

The kernel emits ONLY integer surfaces (cluster fields + per-cluster
metric sufficient statistics); the small float metric epilogue
(``fixed_point.fixed_metric_epilogue`` — log2/sqrt over exact integers,
the FPGA's LUT/CORDIC stage) runs as vmapped jnp in the caller's jit.
Keeping transcendentals out of the kernel is what makes fused-vs-staged
bit-identity robust: both paths feed the *identical* integers through the
*identical* epilogue code, so there is no float op whose lowering could
differ between the Pallas program and the staged program.

The TPU idioms follow ``patch_metrics.py``: event scatters become one-hot
compares + MXU matmuls, the pairwise (E, E) same-pixel block replaces the
sensor-sized histogram (exactly the event-space trick
``core.events.persistent_event_filter`` uses), and top-K is a loop of
(max, first-index, mask) passes — the same selection contract as
``grid_clustering._top_k_cells``. Each cluster's 48x48 count patch is
one (48, E) x (E, 48) matmul of row and column one-hots, so no flat
patch is ever reshaped across lanes. Every one-hot matmul runs at full
f32 precision (``EXACT``) and produces the same exact integers the
staged int32 scatters do (all sums stay below 2^24).
``tests/test_fixed_point.py`` pins the identity over randomized and
adversarial windows.

Layout: ``window_pipeline`` takes (W, E) int32 event arrays (E a LANE
multiple, wrapper-padded) and hands the kernel (W, 1, E) with a
(None, 1, E) block, which the TPU's (8, 128) tiling rule admits; outputs
are one (W, CL_ROWS, LANE) int32 block of
cluster fields (cluster slot k in lane k; row ``CL_FIELDS.index(f)`` =
field f; row 9 carries the per-window frame normalizer) and one
(W, K, LANE) int32 block of per-cluster surfaces (row k = cluster k:
lanes [0, bins) histogram counts, then s1, s2, s_g, s_e2, edges).

Valid-prefix contract: top-K takes cells in descending count order, so a
window's valid clusters (count >= min_events) are always the first
``n_valid = min(K, #cells with count >= min_events)`` slots. Both K-slot
loops (selection and per-cluster surfaces) run ``n_valid`` times, not K:
slots past the prefix hold the constant invalid column (count 0, cell -1,
centroids -1.0 in UQ10.8, origin (0, 0), valid 0, norm), and their
``surf`` rows are zero. The epilogue masks every metric by ``valid``, so
the unpacked outputs are bit-identical to the staged path, which computes
(and discards) those slots' surfaces.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import fixed_point as FX
from repro.core import metrics as M

LANE = 128
# Full f32 matmul passes: the one-hot products carry coordinates and
# timestamps and must stay exact integers, never a rounded bf16 pass.
EXACT = jax.lax.Precision.HIGHEST
CL_ROWS = 16
CL_FIELDS = (
    "count", "cell_x", "cell_y", "cq_x", "cq_y", "cq_t", "x0", "y0",
    "valid", "norm",
)
SURF_FIELDS = ("s1", "s2", "s_g", "s_e2", "edges")  # lanes bins..bins+4
# Pairwise (E, E) blocks bound the supported window capacity, exactly as
# events._PAIRWISE_MAX_EVENTS bounds the jnp pairwise branch.
MAX_EVENTS = 1024


def _kernel(
    x_ref, y_ref, t_ref, v_ref, cl_ref, surf_ref, *,
    roi: tuple[int, int, int, int],
    hot_pixel_max: int,
    cell_size: int,
    grid_w: int,
    grid_h: int,
    min_events: int,
    k: int,
    width: int,
    height: int,
    window: int,
    bins: int,
):
    e = x_ref.shape[-1]
    npix = window * window
    n_cells = grid_w * grid_h
    c_pad = -(-n_cells // LANE) * LANE
    x = x_ref[...]  # (1, E) int32
    y = y_ref[...]
    t = t_ref[...]
    v = v_ref[...] != 0

    # --- conditioning: ROI + hot-pixel filter (pairwise same-pixel) -------
    rx0, ry0, rx1, ry1 = roi
    v = v & (x >= rx0) & (x < rx1) & (y >= ry0) & (y < ry1)
    xi, xj = x.reshape(e, 1), x.reshape(1, e)
    yi, yj = y.reshape(e, 1), y.reshape(1, e)
    same = (xi == xj) & (yi == yj)  # (E, E) same-pixel
    hot = jnp.sum(same & v.reshape(1, e), axis=1, dtype=jnp.int32)
    v = v & (hot <= hot_pixel_max).reshape(1, e)

    # --- coincidence counts, leaders, frame normalizer --------------------
    inb = (x >= 0) & (x < width) & (y >= 0) & (y < height)
    w = v & inb  # (1, E)
    wj = w.reshape(1, e)
    c = jnp.sum(same & wj, axis=1, dtype=jnp.int32).reshape(1, e)
    row_i = jax.lax.broadcasted_iota(jnp.int32, (e, e), 0)
    col_j = jax.lax.broadcasted_iota(jnp.int32, (e, e), 1)
    earlier = same & wj & (col_j < row_i)
    leader = w & ~jnp.any(earlier, axis=1).reshape(1, e)
    norm_i = jnp.maximum(jnp.max(jnp.where(w, c, 0)), 1)

    # --- grid quantization + 4-stat cell histogram (one-hot matmul) -------
    if cell_size & (cell_size - 1) == 0:
        shift = cell_size.bit_length() - 1
        cx, cy = x >> shift, y >> shift
    else:
        cx, cy = x // cell_size, y // cell_size
    flat = jnp.clip(cy * grid_w + cx, 0, n_cells - 1)
    cell_iota = jax.lax.broadcasted_iota(jnp.int32, (e, c_pad), 1)
    cell_onehot = (flat.reshape(e, 1) == cell_iota).astype(jnp.float32)
    wf = w.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    tf = t.astype(jnp.float32)
    stats = jnp.concatenate([wf, wf * xf, wf * yf, wf * tf], axis=0)  # (4, E)
    # Exact: every per-cell sum is an integer below 2^24 (count <= E,
    # sum_x < E * width, sum_t < E * time_threshold).
    cell_stats = jnp.dot(
        stats, cell_onehot, precision=EXACT, preferred_element_type=jnp.float32
    ).astype(jnp.int32)  # (4, C_pad)
    counts = cell_stats[0:1, :]  # padded cells hold count 0

    # --- top-K cells + fixed-point cluster fields -------------------------
    # Valid slots are a prefix of length n_valid (module doc); both K-slot
    # loops run over it alone. Padded cells hold count 0.
    n_valid = jnp.minimum(
        jnp.sum((counts >= min_events).astype(jnp.int32)), k
    )
    lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)
    flat_iota = jax.lax.broadcasted_iota(jnp.int32, (1, c_pad), 1)
    neg = -FX.CENTROID_ONE
    # An invalid slot's origin is its centroid -1 clipped into the sensor: 0.
    invalid = jnp.stack([
        jnp.int32(f) for f in (0, -1, -1, neg, neg, neg, 0, 0, 0)
    ] + [norm_i] + [jnp.int32(0)] * (CL_ROWS - 10)).reshape(CL_ROWS, 1)
    # norm_i sits in every lane < k: the wrapper reads lane 0 even when
    # no slot is valid.
    cl = jnp.where(lane1 < k, invalid, 0)

    def select(kk, carry):
        remaining, cl = carry
        top = jnp.max(remaining)
        # First maximum (lowest index) — lax.top_k's stable tie order,
        # matching grid_clustering._top_k_cells.
        idx = jnp.min(jnp.where(remaining == top, flat_iota, c_pad))
        sel = flat_iota == idx
        remaining = jnp.where(sel, jnp.iinfo(jnp.int32).min, remaining)
        sx = jnp.sum(jnp.where(sel, cell_stats[1:2, :], 0))
        sy = jnp.sum(jnp.where(sel, cell_stats[2:3, :], 0))
        st = jnp.sum(jnp.where(sel, cell_stats[3:4, :], 0))
        den = jnp.maximum(top, 1)

        def q8(s):
            q = s // den
            r = s - q * den
            return q * FX.CENTROID_ONE + FX.round_div_half_even(
                r * FX.CENTROID_ONE, den
            )

        ox = FX.round_div_half_even(sx, den)
        oy = FX.round_div_half_even(sy, den)
        col = jnp.stack([
            top,
            idx % grid_w,
            idx // grid_w,
            q8(sx),
            q8(sy),
            q8(st),
            jnp.clip(ox - window // 2, 0, width - window),
            jnp.clip(oy - window // 2, 0, height - window),
            jnp.int32(1),
            norm_i,
        ] + [jnp.int32(0)] * (CL_ROWS - 10)).reshape(CL_ROWS, 1)
        return remaining, jnp.where(lane1 == kk, col, cl)

    _, cl = jax.lax.fori_loop(0, n_valid, select, (counts, cl))

    # --- per-cluster integer metric surfaces ------------------------------
    cf = c.astype(jnp.float32)
    bin_idx = jnp.clip((c * bins) // norm_i, 0, bins - 1)
    bins_iota = jax.lax.broadcasted_iota(jnp.int32, (e, bins), 1)
    bins_onehot = (bin_idx.reshape(e, 1) == bins_iota).astype(jnp.float32)
    pix = jax.lax.broadcasted_iota(jnp.int32, (window, e), 0)
    leadf = leader.astype(jnp.float32)
    rowk = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0)

    def per_cluster(kk, surf):
        sel = lane1 == kk

        def field(r):
            return jnp.sum(jnp.where(sel, cl[r:r + 1, :], 0))

        x0k, y0k = field(6), field(7)
        rx = x - x0k
        ry = y - y0k
        inp = (
            (rx >= 0) & (rx < window) & (ry >= 0) & (ry < window) & w
        ).astype(jnp.float32)  # (1, E)
        # patch[r, q] = sum_e inp_e [ry_e == r] [rx_e == q]: one
        # (window, E) x (E, window) matmul of row and column one-hots.
        rows = (pix == ry).astype(jnp.float32) * inp
        cols = (pix == rx).astype(jnp.float32)
        patch = jax.lax.dot_general(
            rows, cols, (((1,), (1,)), ((), ())),
            precision=EXACT, preferred_element_type=jnp.float32,
        ).astype(jnp.int32)  # (window, window)

        lead_inp = inp * leadf
        hist = jnp.dot(
            lead_inp, bins_onehot, precision=EXACT,
            preferred_element_type=jnp.float32,
        )  # (1, bins)
        occ = jnp.sum(lead_inp)
        hist = hist + (
            jax.lax.broadcasted_iota(jnp.int32, (1, bins), 1) == 0
        ) * (npix - occ)
        s1 = jnp.sum(inp).astype(jnp.int32)
        s2 = jnp.sum(lead_inp * (cf * cf)).astype(jnp.int32)

        gx, gy = FX.sobel_int(patch)
        g2 = gx * gx + gy * gy
        g2max = jnp.max(g2)
        edges = jnp.sum(16 * g2 > g2max, dtype=jnp.int32)
        s_g = jnp.sum(FX.isqrt(g2), dtype=jnp.int32)
        s_e2 = jnp.sum(g2, dtype=jnp.int32)

        row = jnp.concatenate([
            hist.astype(jnp.int32),
            jnp.stack([s1, s2, s_g, s_e2, edges]).reshape(1, 5),
            jnp.zeros((1, LANE - bins - 5), jnp.int32),
        ], axis=1)  # (1, LANE)
        return surf + jnp.where(rowk == kk, row, 0)

    # Rows at or past n_valid stay zero; the epilogue masks them by valid.
    surf = jax.lax.fori_loop(
        0, n_valid, per_cluster, jnp.zeros((k, LANE), jnp.int32)
    )

    cl_ref[...] = cl
    surf_ref[...] = surf


def window_pipeline(
    x: jax.Array,
    y: jax.Array,
    t: jax.Array,
    valid: jax.Array,
    *,
    roi: tuple[int, int, int, int],
    hot_pixel_max: int,
    cell_size: int,
    grid_w: int,
    grid_h: int,
    min_events: int,
    k: int,
    width: int,
    height: int,
    window: int = M.WINDOW,
    bins: int = M.HIST_BINS,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Run the fused per-window integer pipeline over a (W, E) batch.

    Returns ``(cl, surf)``: (W, CL_ROWS, LANE) int32 cluster fields in
    ``CL_FIELDS`` row order (slot k in lane k) and (W, K, LANE) int32
    per-cluster metric surfaces (histogram counts in lanes [0, bins),
    then ``SURF_FIELDS``). ``ops.window_pipeline_call`` unpacks both and
    applies the shared float epilogue.
    """
    n_windows, e = x.shape
    if e % LANE:
        raise ValueError(f"E ({e}) must be a multiple of {LANE}")
    if e > MAX_EVENTS:
        raise ValueError(
            f"E ({e}) exceeds the pairwise block bound ({MAX_EVENTS})"
        )
    if k > LANE:
        raise ValueError(f"max_clusters ({k}) must be <= {LANE}")
    if bins + len(SURF_FIELDS) > LANE:
        raise ValueError(f"bins ({bins}) too large for the surface row")

    ev_spec = pl.BlockSpec((None, 1, e), lambda i: (i, 0, 0))
    kernel = lambda *refs: _kernel(  # noqa: E731
        *refs,
        roi=roi, hot_pixel_max=hot_pixel_max, cell_size=cell_size,
        grid_w=grid_w, grid_h=grid_h, min_events=min_events, k=k,
        width=width, height=height, window=window, bins=bins,
    )
    return pl.pallas_call(
        kernel,
        name="window_pipeline",
        grid=(n_windows,),
        in_specs=[ev_spec] * 4,
        out_specs=[
            pl.BlockSpec((None, CL_ROWS, LANE), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, k, LANE), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_windows, CL_ROWS, LANE), jnp.int32),
            jax.ShapeDtypeStruct((n_windows, k, LANE), jnp.int32),
        ],
        interpret=interpret,
    )(*(
        a.astype(jnp.int32).reshape(n_windows, 1, e)
        for a in (x, y, t, valid)
    ))
