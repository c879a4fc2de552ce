"""Plain numpy reference of the station pipeline, written from the paper's
description and the configuration file alone.

It imports nothing of the program. Given one station's time-sorted events
it windows them by the dual threshold, conditions each window (ROI, hot
pixels), clusters it on the grid, computes the six quality metrics of every
cluster over its 48x48 count patch, and runs the alpha-beta tracker over the
windows. ``numerics`` in the configuration picks the float golden datapath
or the fixed-point one (int32 surfaces, Q10.8 centroids, float epilogue).

Every float operation runs in the dtype ``ft`` the caller passes: float32 is
the precision the configurations state, and the control runs the same code
in bfloat16. Integer surfaces are exact in either.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# Windows per vectorized block: bounds the (B, K, E) temporaries.
BLOCK = 256


@dataclasses.dataclass
class StationResult:
    """Per-window outputs of one station, leaves (W, ...)."""

    starts: np.ndarray  # (W,) stream index of the window's first event
    stops: np.ndarray  # (W,) exclusive stop
    t_start: np.ndarray  # (W,) first event time, us
    clusters: dict[str, np.ndarray]  # (W, K) each
    metrics: dict[str, np.ndarray]  # (W, K) each
    tracks: dict[str, np.ndarray]  # (W, T) each: state after the window; {} untracked


CLUSTER_FIELDS = (
    "centroid_x", "centroid_y", "centroid_t", "count", "cell_x", "cell_y", "valid",
)
METRIC_NAMES = (
    "shannon_entropy", "renyi_entropy", "differential_entropy",
    "local_contrast", "edge_density", "event_count",
)
TRACK_FIELDS = ("x", "y", "vx", "vy", "hits", "misses", "age", "active", "entropy")


def closed_bounds(t: np.ndarray, time_us: int, size: int) -> list[tuple[int, int]]:
    """Windows of a stream that may still continue: a window closes once an
    event at or past ``t0 + time_us`` is buffered, or ``size`` events have
    accumulated; the open remainder is not returned."""
    n = len(t)
    out = []
    start = 0
    while start < n:
        end_size = start + size
        end_time = int(np.searchsorted(t, t[start] + time_us, side="left"))
        if end_time > start:
            if end_time >= n and end_size > n:
                break
            end = min(end_size, end_time)
        else:
            if end_size > n:
                break
            end = end_size
        end = max(start + 1, min(end, n))
        out.append((start, end))
        start = end
    return out


def _ordered_sum(v: np.ndarray) -> np.ndarray:
    """Sum over the last axis by halving: add the two halves of the axis
    (an odd length first takes a zero) until one element is left."""
    while v.shape[-1] > 1:
        if v.shape[-1] % 2:
            v = np.concatenate([v, np.zeros_like(v[..., :1])], axis=-1)
        half = v.shape[-1] // 2
        v = v[..., :half] + v[..., half:]
    return v[..., 0]


def _round_div_half_even(num: np.ndarray, den: np.ndarray) -> np.ndarray:
    """Integer ``num / den`` rounded half to even (non-negative ``num``)."""
    q = num // den
    r = num - q * den
    up = (2 * r > den) | ((2 * r == den) & (q % 2 == 1))
    return q + up


def _sobel(p: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """3x3 Sobel cross-correlation of (..., H, W) with zero padding."""
    h, w = p.shape[-2:]
    pad = np.pad(p, [(0, 0)] * (p.ndim - 2) + [(1, 1), (1, 1)])

    def s(dy, dx):
        return pad[..., dy:dy + h, dx:dx + w]

    gx = (s(0, 2) - s(0, 0)) + 2 * (s(1, 2) - s(1, 0)) + (s(2, 2) - s(2, 0))
    gy = (s(2, 0) - s(0, 0)) + 2 * (s(2, 1) - s(0, 1)) + (s(2, 2) - s(0, 2))
    return gx, gy


def _shannon(hist: np.ndarray, ft) -> np.ndarray:
    c = hist.astype(ft)
    total = np.maximum(hist.sum(-1).astype(ft), ft(1))
    p = c / total[..., None]
    safe = np.maximum(p, ft(1e-12))
    terms = np.where(p > 0, p * np.log2(safe), ft(0))
    return -_ordered_sum(terms.astype(ft))


def _renyi(hist: np.ndarray, ft) -> np.ndarray:
    h = hist.astype(np.int64)
    total = np.maximum(h.sum(-1), 1).astype(ft)
    sq = (h * h).sum(-1).astype(ft)
    return -np.log2(np.maximum(sq / (total * total), ft(1e-12)))


def _window_block(cfg: dict, X, Y, T, V, ft) -> tuple[dict, dict]:
    """Conditioning, clustering and metrics of a (B, E) block of windows."""
    sensor, grid, met = cfg["sensor"], cfg["grid"], cfg["metrics"]
    width, height = sensor["width"], sensor["height"]
    cs, k, patch, bins = grid["cell_size"], grid["max_clusters"], met["patch"], met["bins"]
    gw, gh = -(-width // cs), -(-height // cs)
    n_cells = gw * gh
    fixed = cfg["numerics"] == "fixed"
    b, e = X.shape
    rx0, ry0, rx1, ry1 = cfg["roi"]

    # Conditioning: ROI, then pixels firing more than hot_pixel_max times.
    V = V & (X >= rx0) & (X < rx1) & (Y >= ry0) & (Y < ry1)
    pixel = np.arange(b)[:, None] * (1 << 32) + (Y << 16) + X  # (window, y, x)

    def coincidences(mask):
        """Per event in ``mask``: the events in ``mask`` at its pixel."""
        _, inv, cnt = np.unique(pixel[mask], return_inverse=True, return_counts=True)
        out = np.zeros((b, e), np.int64)
        out[mask] = cnt[inv]
        return out

    hot = coincidences(V)
    V = V & (hot <= cfg["hot_pixel_max"])
    w = V & (X >= 0) & (X < width) & (Y >= 0) & (Y < height)

    # Grid cells: count and coordinate sums of the kept events (exact ints).
    flat = np.clip((Y // cs) * gw + X // cs, 0, n_cells - 1)
    key = (np.arange(b)[:, None] * n_cells + flat)[w]
    def cell_sum(v):
        return np.bincount(key, weights=v[w], minlength=b * n_cells).reshape(
            b, n_cells
        ).astype(np.int64)
    count = cell_sum(np.ones_like(X))
    sx, sy, st = cell_sum(X), cell_sum(Y), cell_sum(T)

    # Top-K cells by count, ties to the lowest cell index.
    top = np.argsort(-count, axis=1, kind="stable")[:, :k]
    tc = np.take_along_axis(count, top, 1)
    valid = tc >= grid["min_events"]
    den = np.maximum(tc, 1)
    tsx, tsy, tst = (np.take_along_axis(a, top, 1) for a in (sx, sy, st))
    if fixed:
        one = 1 << cfg["centroid_frac"]

        def q8(s):
            q = s // den
            return q * one + _round_div_half_even((s - q * den) * one, den)

        scale = ft(1.0 / one)
        cent = [np.where(valid, q8(s).astype(ft) * scale, ft(-1)) for s in (tsx, tsy, tst)]
        ox = np.where(valid, _round_div_half_even(tsx, den), -1)
        oy = np.where(valid, _round_div_half_even(tsy, den), -1)
    else:
        denf = den.astype(ft)
        cent = [np.where(valid, s.astype(ft) / denf, ft(-1)) for s in (tsx, tsy, tst)]
        ox = np.round(cent[0]).astype(np.int64)
        oy = np.round(cent[1]).astype(np.int64)
    x0 = np.clip(ox - patch // 2, 0, width - patch)
    y0 = np.clip(oy - patch // 2, 0, height - patch)
    clusters = {
        "centroid_x": cent[0], "centroid_y": cent[1], "centroid_t": cent[2],
        "count": np.where(valid, tc, 0),
        "cell_x": np.where(valid, top % gw, -1),
        "cell_y": np.where(valid, top // gw, -1),
        "valid": valid,
    }

    # Frame normalizer: the most events any one pixel holds in the window.
    c = coincidences(w)
    norm_i = np.maximum(np.where(w, c, 0).max(-1), 1)  # (B,)

    # Metrics of the valid slots only (invalid slots report zeros): one
    # 48x48 count patch per valid cluster, from the kept events.
    wi, ki = np.nonzero(valid)
    npair = len(wi)
    rx = X[wi] - x0[wi, ki][:, None]  # (P, E)
    ry = Y[wi] - y0[wi, ki][:, None]
    inside = (rx >= 0) & (rx < patch) & (ry >= 0) & (ry < patch) & w[wi]
    pix = ((np.arange(npair)[:, None] * patch + ry) * patch + rx)[inside]
    cnt = np.bincount(pix, minlength=npair * patch * patch).reshape(
        npair, patch, patch
    )
    npix = patch * patch
    gx, gy = _sobel(cnt)
    g2 = gx * gx + gy * gy  # exact ints
    s1 = cnt.sum((1, 2))
    s2 = (cnt * cnt).sum((1, 2))
    s_e2 = g2.sum((1, 2))
    norm_p = norm_i[wi]
    nb = norm_p[:, None, None]

    # Intensity histogram of the patch, normalized by the frame maximum.
    if fixed:
        bin_ix = np.clip((cnt * bins) // nb, 0, bins - 1)
    else:
        val = cnt.astype(ft) / nb.astype(ft)
        bin_ix = np.clip((val * ft(bins)).astype(np.int64), 0, bins - 1)
    hkey = np.arange(npair)[:, None, None] * bins + bin_ix
    hist = np.bincount(hkey.ravel(), minlength=npair * bins).reshape(npair, bins)

    n = ft(npix)
    norm = norm_p.astype(ft)
    mean = s1.astype(ft) / n
    contrast = np.sqrt(np.maximum(s2.astype(ft) / n - mean * mean, ft(0))) / norm
    two_pi_e = ft(2.0 * np.pi * np.e)
    if fixed:
        r = np.floor(np.sqrt(g2.astype(np.float64))).astype(np.int64)
        r -= (r * r > g2).astype(np.int64)
        r += ((r + 1) * (r + 1) <= g2).astype(np.int64)
        s_g = r.sum((1, 2))
        g2max = g2.max((1, 2), initial=0)
        edges = (16 * g2 > g2max[:, None, None]).sum((1, 2))
        m1 = (s_g.astype(ft) / n) / norm
        m2 = (s_e2.astype(ft) / n) / (norm * norm)
        var_g = np.maximum(m2 - m1 * m1, ft(1e-12))
    else:
        nn = (norm * norm)[:, None, None]
        e2 = g2.astype(ft) / nn + ft(1e-12)
        g = np.sqrt(e2)
        s_g = _ordered_sum(g.reshape(npair, npix))
        se2 = s_e2.astype(ft) / (norm * norm) + ft(npix * 1e-12)
        m1 = s_g / n
        var_g = np.maximum(se2 / n - m1 * m1, ft(1e-12))
        e2max = e2.max((1, 2), initial=ft(0))
        den_e = np.maximum(np.sqrt(e2max), ft(1e-3))
        thr = (ft(met["edge_threshold"]) * den_e) * (ft(met["edge_threshold"]) * den_e)
        edges = (e2 > thr[:, None, None]).sum((1, 2))
    diff = ft(0.5) * np.log2(two_pi_e * var_g)
    per_pair = {
        "shannon_entropy": _shannon(hist, ft),
        "renyi_entropy": _renyi(hist, ft),
        "differential_entropy": diff,
        "local_contrast": contrast,
        "edge_density": edges.astype(ft) / n,
    }
    mets = {}
    for m, v in per_pair.items():
        full = np.zeros((b, k), ft)
        full[wi, ki] = v
        mets[m] = full
    mets["event_count"] = clusters["count"].astype(ft)
    mets = {m: np.where(valid, v, ft(0)).astype(ft) for m, v in mets.items()}
    return clusters, mets


def detections_used(taken: np.ndarray, assign: np.ndarray, matched: np.ndarray) -> np.ndarray:
    """The detections that tracks took this window, each at most once:
    ``taken``. (``assign``, each track's detection or -1, and ``matched``
    let a caller put another rule in this one's place.)"""
    return taken


def _track(cfg: dict, clusters: dict, shannon: np.ndarray, ft) -> dict:
    """Alpha-beta tracker over the windows: greedy nearest-neighbour
    association in track order within the gate, each detection used at
    most once; unassigned detections spawn tracks in free slots, the r-th
    free slot taking the r-th unassigned detection."""
    tr = cfg["tracker"]
    nt = tr["max_tracks"]
    gate, alpha, beta = ft(tr["gate"]), ft(tr["alpha"]), ft(tr["beta"])
    w_count, k = clusters["valid"].shape
    x = np.zeros(nt, ft); y = np.zeros(nt, ft)
    vx = np.zeros(nt, ft); vy = np.zeros(nt, ft)
    ent = np.zeros(nt, ft)
    hits = np.zeros(nt, np.int64); misses = np.zeros(nt, np.int64)
    age = np.zeros(nt, np.int64); active = np.zeros(nt, bool)
    out = {f: [] for f in TRACK_FIELDS}
    inf = ft(np.inf)
    for wi in range(w_count):
        cx = clusters["centroid_x"][wi]; cy = clusters["centroid_y"][wi]
        valid = clusters["valid"][wi]; me_all = shannon[wi]
        px = x + vx
        py = y + vy
        dx = px[:, None] - cx[None, :]
        dy = py[:, None] - cy[None, :]
        cost = np.where(active[:, None] & valid[None, :], np.sqrt(dx * dx + dy * dy), inf)
        taken = np.zeros(k, bool)
        assign = np.full(nt, -1)
        for ti in np.flatnonzero(active):  # an inactive track's costs are all inf
            row = np.where(taken, inf, cost[ti])
            j = int(np.argmin(row))
            if row[j] <= gate:
                taken[j] = True
                assign[ti] = j
        matched = assign >= 0
        ai = np.clip(assign, 0, k - 1)
        rx_ = cx[ai] - px
        ry_ = cy[ai] - py
        nx = np.where(matched, px + alpha * rx_, px)
        ny = np.where(matched, py + alpha * ry_, py)
        nvx = np.where(matched, vx + beta * rx_, vx)
        nvy = np.where(matched, vy + beta * ry_, vy)
        hits = np.where(matched, hits + 1, hits)
        misses = np.where(matched, 0, misses + active)
        ent = np.where(matched, ft(0.7) * ent + ft(0.3) * me_all[ai], ent)
        active = active & (misses <= tr["max_misses"])
        det_free = valid & ~detections_used(taken, assign, matched)
        free_slots = np.flatnonzero(~active)
        free_dets = np.flatnonzero(det_free)
        m = min(len(free_slots), len(free_dets))
        sl, dt = free_slots[:m], free_dets[:m]
        spawn = np.zeros(nt, bool)
        spawn[sl] = True
        nx[sl] = cx[dt]; ny[sl] = cy[dt]
        nvx[sl] = 0; nvy[sl] = 0
        hits[sl] = 1; misses[sl] = 0
        ent[sl] = me_all[dt]
        age = np.where(spawn, 0, age + active)
        active = active | spawn
        x, y, vx, vy = nx.astype(ft), ny.astype(ft), nvx.astype(ft), nvy.astype(ft)
        ent = ent.astype(ft)
        for f, v in zip(TRACK_FIELDS, (x, y, vx, vy, hits, misses, age, active, ent)):
            out[f].append(v.copy())
    empty = {"hits": np.int64, "misses": np.int64, "age": np.int64, "active": bool}
    return {
        f: np.stack(v) if v else np.zeros((0, nt), empty.get(f, ft))
        for f, v in out.items()
    }


def run_station(cfg: dict, events, n_fed: int, ft=np.float32) -> StationResult:
    """Reference outputs of every window a stream of ``n_fed`` events has
    closed; ``events`` is ``(x, y, t, p)`` with at least ``n_fed`` events."""
    x, y, t, _ = (np.asarray(a, np.int64)[:n_fed] for a in events)
    bat = cfg["batcher"]
    bounds = closed_bounds(t, bat["time_threshold_us"], bat["size_threshold"])
    cap = bat["capacity"]
    w_count = len(bounds)
    starts = np.array([a for a, _ in bounds], np.int64)
    stops = np.array([e for _, e in bounds], np.int64)
    t_start = t[starts] if w_count else np.zeros(0, np.int64)
    cl_parts, met_parts = [], []
    for b0 in range(0, w_count, BLOCK):
        sl = slice(b0, min(b0 + BLOCK, w_count))
        st, sp = starts[sl], stops[sl]
        nb = len(st)
        n = np.minimum(sp - st, cap)
        col = np.arange(cap)
        V = col[None, :] < n[:, None]
        src = np.where(V, st[:, None] + col[None, :], 0)
        X = np.where(V, x[src], 0)
        Y = np.where(V, y[src], 0)
        T = np.where(V, t[src] - t_start[sl][:, None], 0)
        cl, me = _window_block(cfg, X, Y, T, V, ft)
        cl_parts.append(cl)
        met_parts.append(me)
        del nb
    k = cfg["grid"]["max_clusters"]
    if cl_parts:
        clusters = {f: np.concatenate([c[f] for c in cl_parts]) for f in CLUSTER_FIELDS}
        metrics = {m: np.concatenate([c[m] for c in met_parts]) for m in METRIC_NAMES}
    else:
        clusters = {f: np.zeros((0, k)) for f in CLUSTER_FIELDS}
        metrics = {m: np.zeros((0, k)) for m in METRIC_NAMES}
    tracks = (
        _track(cfg, clusters, metrics["shannon_entropy"], ft)
        if cfg["with_tracking"] else {}
    )
    return StationResult(starts, stops, t_start, clusters, metrics, tracks)
