"""The comparison that decides ``correct``: what the timed path served for a
sample of stations, against the plain reference over the same events.

Up to four numbers; the configuration file gives a limit to each one it
compares:

* ``exact``: values that must agree bit for bit: the window count and
  bounds, every cluster's count, cell and validity, the tracks' hit, miss,
  age and activity counters, the sentinels of empty cluster slots, and the
  centroids where the configuration's datapath makes them exact (Q10.8);
* ``centroid_gap``: the widest relative gap of a valid cluster's float
  centroid, ``|a - b| / max(|b|, 1)`` (float datapath only);
* ``metric_gap``: the widest gap of the six metrics over valid clusters,
  ``|a - b| / max(|b|, median |b| of that metric)``;
* ``track_gap``: the widest gap of an active track's position, velocity
  and entropy, ``|a - b| / max(|b|, 1)``.
"""
from __future__ import annotations

import numpy as np

from tpubench import reference as R

NAMES = ("exact", "centroid_gap", "metric_gap", "track_gap")


def served_arrays(results: list) -> dict:
    """One station's served results (ScanResult per round, in order) as
    arrays of every window."""
    def cat(get):
        parts = [np.asarray(get(r)) for r in results]
        return np.concatenate(parts) if parts else np.zeros(0)

    out = {
        "starts": cat(lambda r: r.windows.starts),
        "stops": cat(lambda r: r.windows.stops),
        "t_start": cat(lambda r: r.t_start_us),
    }
    for f in R.CLUSTER_FIELDS:
        out[f] = cat(lambda r, f=f: getattr(r.clusters, f))
    for m in R.METRIC_NAMES:
        out[m] = cat(lambda r, m=m: r.metrics[m])
    if results and results[0].tracks is not None:
        for f in R.TRACK_FIELDS:
            out["track_" + f] = cat(lambda r, f=f: getattr(r.tracks, f))
    return out


def compare(cfg: dict, served: dict, ref: R.StationResult, detail: dict | None = None) -> dict:
    """Readings of one station (see module doc); ``detail``, when given,
    receives the reading of every field."""
    detail = {} if detail is None else detail
    fixed = cfg["numerics"] == "fixed"
    n_ref, n_got = len(ref.starts), len(served["starts"])
    n = min(n_ref, n_got)
    exact = abs(n_ref - n_got)

    def mismatches(name, a, b):
        a, b = np.asarray(a)[:n], np.asarray(b)[:n]
        bad = int(np.count_nonzero(a != b))
        detail[name] = detail.get(name, 0) + bad
        return bad

    exact += mismatches("starts", served["starts"], ref.starts)
    exact += mismatches("stops", served["stops"], ref.stops)
    exact += mismatches("t_start", served["t_start"], ref.t_start)
    for f in ("count", "cell_x", "cell_y", "valid"):
        exact += mismatches(f, served[f], ref.clusters[f])
    tracked = bool(ref.tracks)
    if tracked != ("track_hits" in served):
        raise ValueError("the served results and the reference disagree on tracking")
    for f in ("hits", "misses", "age", "active") if tracked else ():
        exact += mismatches("track_" + f, served["track_" + f], ref.tracks[f])
    valid = np.asarray(ref.clusters["valid"])[:n]
    cents = ("centroid_x", "centroid_y", "centroid_t")
    for f in cents + R.METRIC_NAMES:
        a = np.asarray(served[f])[:n]
        b = np.asarray(ref.clusters[f] if f in cents else ref.metrics[f])[:n]
        bad = int(np.count_nonzero((a != b) & ~valid))
        detail[f + ".empty"] = detail.get(f + ".empty", 0) + bad
        exact += bad

    def gap(a, b, scale, name=None):
        if not a.size:
            return 0.0
        g = np.abs(a.astype(np.float64) - b.astype(np.float64)) / scale
        worst = float(np.max(g))
        if name is not None:
            prev = detail.get(name, (0.0,))
            if worst >= prev[0]:
                i = int(np.argmax(g))
                detail[name] = (worst, float(a.reshape(-1)[i]), float(b.reshape(-1)[i]),
                                int(np.count_nonzero(g > 1e-6)))
        return worst

    centroid_gap = 0.0
    for f in cents:
        a = np.asarray(served[f])[:n][valid]
        b = np.asarray(ref.clusters[f])[:n][valid]
        if fixed:
            bad = int(np.count_nonzero(a != b))
            detail[f] = detail.get(f, 0) + bad
            exact += bad
        else:
            centroid_gap = max(centroid_gap, gap(a, b, np.maximum(np.abs(b), 1.0), f))
    metric_gap = 0.0
    for m in R.METRIC_NAMES:
        a = np.asarray(served[m])[:n][valid]
        b = np.asarray(ref.metrics[m])[:n][valid].astype(np.float64)
        if b.size:
            floor = max(float(np.median(np.abs(b))), 1e-6)
            metric_gap = max(metric_gap, gap(a, b, np.maximum(np.abs(b), floor), m))
    track_gap = 0.0
    active = np.asarray(ref.tracks["active"])[:n] if tracked else None
    for f in ("x", "y", "vx", "vy", "entropy") if tracked else ():
        a = np.asarray(served["track_" + f])[:n][active]
        b = np.asarray(ref.tracks[f])[:n][active]
        track_gap = max(track_gap, gap(a, b, np.maximum(np.abs(b), 1.0), "track_" + f))
    return {
        "exact": float(exact), "centroid_gap": centroid_gap,
        "metric_gap": metric_gap, "track_gap": track_gap,
        "windows": n_ref,
    }


def check(cfg: dict, stations: dict, ft=np.float32, detail: dict | None = None) -> dict:
    """Readings over the sample: ``stations`` maps a station to
    ``(events, n_fed, served results)``; each reading is the widest over the
    stations. Adds ``windows``, the windows compared."""
    total = {k: 0.0 for k in NAMES}
    windows = 0
    for events, n_fed, results in stations.values():
        ref = R.run_station(cfg, events, n_fed, ft)
        got = compare(cfg, served_arrays(results), ref, detail)
        windows += got.pop("windows")
        for k in NAMES:
            total[k] = max(total[k], got[k])
    total["windows"] = windows
    return total


def control(cfg: dict, stations: dict, ft, detail: dict | None = None) -> dict:
    """Readings of the reference computed in ``ft``, put in the program's
    place: its outputs are compared as if they had been served."""
    total = {k: 0.0 for k in NAMES}
    for events, n_fed, _ in stations.values():
        ref = R.run_station(cfg, events, n_fed, np.float32)
        low = R.run_station(cfg, events, n_fed, ft)
        got = compare(cfg, as_served(low), ref, detail)
        for k in NAMES:
            total[k] = max(total[k], got[k])
    return total


def as_served(res: R.StationResult) -> dict:
    out = {"starts": res.starts, "stops": res.stops, "t_start": res.t_start}
    out.update(res.clusters)
    out.update(res.metrics)
    out.update({"track_" + f: v for f, v in res.tracks.items()})
    return out


def verdict(readings: dict, limits: dict) -> tuple[bool, list[tuple[str, float, float]]]:
    """``correct`` and the (name, reading, limit) rows of the numbers the
    configuration's limits name."""
    rows = [(k, float(readings[k]), float(limits[k])) for k in NAMES if k in limits]
    return all(v <= lim for _, v, lim in rows), rows
