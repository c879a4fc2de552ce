"""Operations and HBM bytes of one call of the fused fixed-point window
kernel (``window_pipeline``), from its block shapes.

The model of ``benchmarks/roofline_report._megakernel_cost_model``, copied:
per window (one grid step) the pairwise (E, E) conditioning block, the
(E, C) cell one-hot matmul, K top-cell passes over the padded cells, and per
cluster the (E, 48*48) patch and (E, bins) histogram matmuls plus the Sobel
stencil. HBM traffic is the four (E,) event rows in and the two packed
output blocks out; every intermediate stays in VMEM. Every window of the
call's grid counts, padded windows included: the kernel does their work.
"""
LANE = 128  # lanes of the kernel's output blocks
CL_ROWS = 16  # rows of its cluster-field block


def cost(shape, cfg):
    """``shape`` is the call's window grid (any leading dims); returns
    ``(flops, hbm_bytes)`` of the whole call."""
    windows = 1
    for d in shape:
        windows *= int(d)
    e = cfg["batcher"]["capacity"]
    e = -(-e // LANE) * LANE
    cs = cfg["grid"]["cell_size"]
    n_cells = (-(-cfg["sensor"]["width"] // cs)) * (-(-cfg["sensor"]["height"] // cs))
    c_pad = -(-n_cells // LANE) * LANE
    k = cfg["grid"]["max_clusters"]
    npix = cfg["metrics"]["patch"] ** 2
    bins = cfg["metrics"]["bins"]
    flops = (
        5 * e * e
        + 2 * 4 * e * c_pad
        + k * 4 * c_pad
        + k * (3 * e * npix + 2 * e * bins + 20 * npix)
    )
    hbm = 4 * e * 4 + (CL_ROWS + k) * LANE * 4
    return float(windows * flops), float(windows * hbm)
