"""Accuracy evaluation (paper Sec. V-A: sampled detections vs ground truth).

Candidate truth-matching runs on device: a jit'd matcher evaluates every
(window, cluster slot, RSO) triple over the stacked scan outputs —
:func:`collect_candidates` is one scan dispatch plus one match dispatch
per recording, and :func:`collect_candidates_many` batches a whole
validation suite through ``vmap`` so :func:`threshold_sweep` executes in
O(1) device dispatches total. The numpy matcher
(:func:`collect_candidates_numpy`) and the per-cluster Python loop
(:func:`collect_candidates_loop`) are kept as oracles.

Precision contract: the device matcher evaluates gate distances in
float32 (x64 stays off) while the numpy oracle uses float64, so their
agreement is exact *except* for candidates within float32 rounding
(~1e-4 px after time rebasing) of the 14 px gate boundary — a
measure-zero set the continuous-valued synthetic suite never hits; the
score-equality tests pin the agreement on that suite, not a structural
bit-identity like the pipeline drivers'.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.events import WindowedEvents
from repro.core.pipeline.config import PipelineConfig
from repro.core.pipeline.scan import _many_scan_raw, run_recording_scan

from typing import TYPE_CHECKING

if TYPE_CHECKING:  # avoid circular import (data.synthetic uses core.events)
    from repro.data.synthetic import Recording


@dataclasses.dataclass
class DetectionScore:
    tp: int = 0  # cluster >= threshold and is a true RSO
    fp: int = 0  # cluster >= threshold but star/noise
    fn: int = 0  # candidate RSO cluster rejected by threshold
    tn: int = 0  # star/noise candidate correctly rejected

    @property
    def accuracy(self) -> float:
        total = self.tp + self.fp + self.fn + self.tn
        return (self.tp + self.tn) / total if total else 0.0

    @property
    def precision(self) -> float:
        d = self.tp + self.fp
        return self.tp / d if d else 0.0

    @property
    def recall(self) -> float:
        d = self.tp + self.fn
        return self.tp / d if d else 0.0


@dataclasses.dataclass
class Candidates:
    """Pipeline outputs collected once; thresholds are swept afterwards.

    Cluster level: every candidate cluster (>= candidate_floor events) with
    its event count and ground-truth flag (centroid within the gate radius
    of a true RSO position at the cluster's mean time).

    Object level: for every (window, visible RSO) pair, the best (max)
    count among clusters matched to that RSO — used for miss (FN) scoring,
    mirroring the paper's protocol of verifying detections against known
    RSO *trajectories* rather than counting sub-threshold fragments of an
    already-detected object as misses.
    """

    counts: np.ndarray  # (C,) candidate cluster event counts
    is_rso: np.ndarray  # (C,) bool
    object_best: np.ndarray  # (V,) best matched count per visible-object-window


def _floor_config(config: PipelineConfig, candidate_floor: int) -> PipelineConfig:
    floor_grid = dataclasses.replace(config.grid, min_events=candidate_floor)
    return dataclasses.replace(config, grid=floor_grid)


# ---------------------------------------------------------------------------
# Device-resident truth matching.
# ---------------------------------------------------------------------------

def track_table(tracks) -> np.ndarray:
    """Normalize an RSO trajectory table to (R, 6) float64
    ``[x0, y0, vx, vy, ax, ay]``.

    Legacy recordings carry (R, 4) constant-velocity rows; the scenario
    simulator's ballistic family adds constant-acceleration columns.
    Zero-padding the accel columns keeps every matcher bit-compatible
    with the 4-column era (``x + 0.0`` is exact in IEEE float).
    """
    a = np.asarray(tracks, np.float64)
    if a.size == 0:
        return np.zeros((0, 6))
    a = a.reshape(-1, a.shape[-1])
    if a.shape[-1] == 4:
        a = np.concatenate([a, np.zeros((a.shape[0], 2))], axis=1)
    return a


def track_positions(tracks: np.ndarray, ts):
    """Trajectory positions at times ``ts`` (seconds) for a (R, 6) table
    broadcast against ``ts[..., None]``; works for numpy and jnp inputs."""
    px = tracks[..., 0] + tracks[..., 2] * ts + 0.5 * tracks[..., 4] * ts * ts
    py = tracks[..., 1] + tracks[..., 3] * ts + 0.5 * tracks[..., 5] * ts * ts
    return px, py


def _match_core(counts, valid, cx, cy, ct, t_start, tracks, gate_px, max_samples):
    """Match every (window, slot) centroid against every RSO trajectory.

    Inputs are the stacked scan outputs for one recording: (W, K) cluster
    arrays, (W,) float32 window origins (microseconds, rebased to the
    recording's first window by :func:`_rebase_times` so float32 keeps
    sub-pixel trajectory precision over arbitrarily long streams), and
    (R, 6) [x0, y0, vx, vy, ax, ay] trajectories shifted to the same
    origin. Returns ``(is_rso (W, K), keep (W, K), best (W, R))`` where
    ``keep`` marks the window-major candidate prefix under
    ``max_samples`` and ``best`` is the max kept count matched to each
    (window, RSO) pair.
    """
    t_ev = t_start[:, None] + ct  # (W, K) us, recording-relative
    ts = t_ev[:, :, None] * 1e-6  # seconds, (W, K, 1)
    px, py = track_positions(tracks[None, None, :, :], ts)  # (W, K, R)
    dx = px - cx[:, :, None]
    dy = py - cy[:, :, None]
    matched = jnp.sqrt(dx * dx + dy * dy) <= gate_px  # (W, K, R)

    flat_valid = valid.reshape(-1)
    rank = jnp.cumsum(flat_valid.astype(jnp.int32)) - 1
    keep = (flat_valid & (rank < max_samples)).reshape(valid.shape)
    contrib = jnp.where(matched & keep[:, :, None], counts[:, :, None], 0)
    return matched.any(axis=-1), keep, contrib.max(axis=1)


_match_one = jax.jit(_match_core)
_match_many = jax.jit(jax.vmap(_match_core, in_axes=(0, 0, 0, 0, 0, 0, 0, None, 0)))

# Padding trajectory for vmapped matching over recordings with different
# RSO counts: parked far outside the sensor, zero velocity -> never gates.
_FAR_TRACK = (1e9, 1e9, 0.0, 0.0, 0.0, 0.0)


def _rebase_times(
    t_start_us: np.ndarray, tracks: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Rebase window origins to the recording's first window (host, f64).

    Absolute microsecond timestamps overflow int32 after ~36 min and lose
    float32 precision long before that; window origins *relative to the
    recording* stay small (resolution better than 1 us per 16 s of
    stream, i.e. sub-0.01 px at RSO speeds). Trajectory intercepts and
    velocities (the (R, 6) table may carry constant acceleration) are
    advanced to the same origin in float64 before the cast.
    """
    t_ref_us = int(t_start_us[0]) if len(t_start_us) else 0
    t_rel = (t_start_us - t_ref_us).astype(np.float32)
    shifted = track_table(tracks)
    if shifted.size:
        dt = t_ref_us * 1e-6
        shifted[:, 0] += shifted[:, 2] * dt + 0.5 * shifted[:, 4] * dt * dt
        shifted[:, 1] += shifted[:, 3] * dt + 0.5 * shifted[:, 5] * dt * dt
        shifted[:, 2] += shifted[:, 4] * dt
        shifted[:, 3] += shifted[:, 5] * dt
    return t_rel, shifted.astype(np.float32)


def _visible_objects(
    recording: Recording,
    stops: np.ndarray,
    n_rso: int,
    min_truth_events: int,
) -> np.ndarray:
    """(W, R) bool — (window, RSO) pairs with enough true events to count
    as visible (host-side: depends only on ground-truth labels).
    ``stops`` are the windows' exclusive slice stops into the recording
    (one per window, in stream order)."""
    from repro.data.synthetic import KIND_RSO

    w_count = len(stops)
    n_true = np.zeros((w_count, n_rso), np.int64)
    rso_ev = np.flatnonzero(np.asarray(recording.kind) == KIND_RSO)
    if rso_ev.size and w_count:
        # Dual-threshold windows partition the stream: event e lands in the
        # window whose stop is the first one strictly past e. Events past
        # the last stop (none, by construction) are dropped defensively.
        ev_w = np.searchsorted(stops, rso_ev, side="right")
        in_range = ev_w < w_count
        np.add.at(
            n_true,
            (ev_w[in_range], np.asarray(recording.obj)[rso_ev[in_range]]),
            1,
        )
    return n_true >= min_truth_events


def _assemble_candidates(
    recording: Recording,
    stops: np.ndarray,  # (W,) window slice stops
    counts: np.ndarray,  # (W, K)
    is_rso: np.ndarray,  # (W, K)
    keep: np.ndarray,  # (W, K)
    best: np.ndarray,  # (W, R)
    min_truth_events: int,
) -> Candidates:
    n_rso = best.shape[-1]
    keep_flat = keep.reshape(-1)
    counts_out = counts.reshape(-1)[keep_flat].astype(np.int32)
    is_rso_out = is_rso.reshape(-1)[keep_flat]
    visible = _visible_objects(recording, stops, n_rso, min_truth_events)
    return Candidates(
        counts_out,
        np.asarray(is_rso_out, bool),
        np.asarray(best[visible], np.int32),
    )


def collect_candidates(
    recording: Recording,
    config: PipelineConfig = PipelineConfig(),
    candidate_floor: int = 2,
    max_samples: int | None = None,
    gate_px: float = 14.0,
    min_truth_events: int = 3,
) -> Candidates:
    """Run the scanned pipeline ONCE over a recording and collect candidates.

    Truth matching runs on device over the stacked scan outputs (one
    matcher dispatch for all (window, slot, object) triples); only the
    ground-truth visibility bookkeeping — a function of the simulator
    labels, not of pipeline outputs — stays on host. Ordering,
    ``max_samples`` truncation, and object-level bookkeeping match
    :func:`collect_candidates_numpy` / :func:`collect_candidates_loop`
    (the oracles) exactly.
    """
    result = run_recording_scan(
        recording, _floor_config(config, candidate_floor), with_tracking=False
    )
    return match_candidates(
        recording, result.clusters, result.windows.t_start_us,
        result.windows.stops, max_samples, gate_px, min_truth_events,
    )


def match_candidates(
    recording: Recording,
    clusters,
    t_start_us: np.ndarray,
    stops: np.ndarray,
    max_samples: int | None = None,
    gate_px: float = 14.0,
    min_truth_events: int = 3,
) -> Candidates:
    """Truth-match one recording's stacked pipeline outputs.

    ``clusters`` has (W, K) leaves, ``t_start_us`` and ``stops`` are the
    windows' origins and exclusive slice stops — from any driver: the
    scan (:func:`collect_candidates`) or a served session's concatenated
    feeds, which score identically when they are bit-identical.
    """
    cl = clusters
    t_rel, tracks = _rebase_times(np.asarray(t_start_us), recording.rso_tracks)
    k = cl.count.shape[-1] if cl.count.ndim == 2 else 0
    ms = len(t_rel) * k if max_samples is None else max_samples
    is_rso, keep, best = _match_one(
        cl.count, cl.valid, cl.centroid_x, cl.centroid_y, cl.centroid_t,
        jnp.asarray(t_rel), jnp.asarray(tracks),
        jnp.float32(gate_px), ms,
    )
    return _assemble_candidates(
        recording, np.asarray(stops), np.asarray(cl.count), np.asarray(is_rso),
        np.asarray(keep), np.asarray(best), min_truth_events,
    )


def collect_candidates_many(
    recordings: list[Recording],
    config: PipelineConfig = PipelineConfig(),
    candidate_floor: int = 2,
    max_samples: int | None = None,
    gate_px: float = 14.0,
    min_truth_events: int = 3,
) -> list[Candidates]:
    """Candidates for a whole suite in O(1) device dispatches.

    One vmapped scan over all recordings (padded to a common window
    count) + one vmapped matcher call (trajectories padded to a common
    RSO count with far-away parked tracks). Per-recording results equal
    :func:`collect_candidates` exactly; padded windows carry no valid
    clusters and padded tracks never gate, so neither contributes.
    """
    if not recordings:
        return []
    floor_cfg = _floor_config(config, candidate_floor)
    windowed, (_, clusters, _, _) = _many_scan_raw(
        recordings, floor_cfg, with_tracking=False
    )
    k = clusters.count.shape[-1]
    w_max = clusters.count.shape[1]
    rebased = [
        _rebase_times(w.t_start_us, r.rso_tracks)
        for r, w in zip(recordings, windowed)
    ]
    tracks = [t for _, t in rebased]
    r_max = max((t.shape[0] for t in tracks), default=0)
    tracks_padded = np.stack(
        [
            np.concatenate(
                [t, np.tile(np.float32(_FAR_TRACK), (r_max - t.shape[0], 1))]
            ) if t.shape[0] < r_max else t
            for t in tracks
        ]
    ) if r_max else np.zeros((len(recordings), 0, 6), np.float32)
    t_starts = np.stack(
        [
            np.pad(t_rel, (0, w_max - len(t_rel))).astype(np.float32)
            for t_rel, _ in rebased
        ]
    )
    ms = np.asarray(
        [
            w.num_windows * k if max_samples is None else max_samples
            for w in windowed
        ],
        np.int32,
    )
    is_rso, keep, best = _match_many(
        clusters.count, clusters.valid, clusters.centroid_x,
        clusters.centroid_y, clusters.centroid_t,
        jnp.asarray(t_starts), jnp.asarray(tracks_padded),
        jnp.float32(gate_px), jnp.asarray(ms),
    )
    counts_np = np.asarray(clusters.count)
    is_rso_np, keep_np, best_np = (
        np.asarray(is_rso), np.asarray(keep), np.asarray(best)
    )
    out: list[Candidates] = []
    for r, (rec, w) in enumerate(zip(recordings, windowed)):
        n, n_rso = w.num_windows, tracks[r].shape[0]
        out.append(
            _assemble_candidates(
                rec, w.stops, counts_np[r, :n], is_rso_np[r, :n, :],
                keep_np[r, :n, :], best_np[r, :n, :n_rso], min_truth_events,
            )
        )
    return out


def collect_candidates_fleet(
    recordings: list[Recording],
    config: PipelineConfig = PipelineConfig(),
    candidate_floor: int = 2,
    max_samples: int | None = None,
    gate_px: float = 14.0,
    min_truth_events: int = 3,
    mesh=None,
) -> list[Candidates]:
    """Candidates for a whole constellation via the fleet engine, O(1)
    dispatches.

    Each recording becomes one fleet sensor; the suite runs as ONE
    vmapped feed (every sensor's closed windows) + ONE vmapped flush
    (trailing windows) + ONE vmapped matcher call over the stacked fleet
    outputs. Padded window slots (sensors close different window counts)
    carry no valid clusters, so the matcher's rank/keep bookkeeping
    skips them and per-recording results equal
    :func:`collect_candidates_many` exactly. ``mesh`` (a mesh with a
    ``sensor`` axis) shards the fleet carry across devices.
    """
    from repro.core.pipeline.fleet import FleetPipeline

    if not recordings:
        return []
    floor_cfg = _floor_config(config, candidate_floor)
    fleet = FleetPipeline(
        floor_cfg, n_sensors=len(recordings), with_tracking=False, mesh=mesh
    )
    head = fleet.feed([(r.x, r.y, r.t, r.p) for r in recordings])
    tail = fleet.flush()
    parts = [p for p in (head, tail) if p.clusters is not None]
    s_count = len(recordings)
    k = config.grid.max_clusters
    if not parts:  # nothing closed anywhere (all-empty recordings)
        return [
            Candidates(
                np.zeros(0, np.int32), np.zeros(0, bool), np.zeros(0, np.int32)
            )
            for _ in recordings
        ]
    if len(parts) == 2:
        cl = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b], axis=1),
            parts[0].clusters, parts[1].clusters,
        )
    else:
        cl = parts[0].clusters
    # Real-slot bookkeeping: sensor s occupies rows [0, n_head) of the
    # feed block and [w_head, w_head + n_tail) of the flush block.
    offsets = np.cumsum([0] + [p.clusters.count.shape[1] for p in parts])[:-1]
    w_total = cl.count.shape[1]
    t_grid = np.zeros((s_count, w_total), np.float32)
    rows_all, stops_all, tracks = [], [], []
    ms = np.zeros(s_count, np.int32)
    for s, rec in enumerate(recordings):
        t_start_us = np.concatenate([p.windows[s].t_start_us for p in parts])
        stops = np.concatenate([p.windows[s].stops for p in parts])
        rows = np.concatenate(
            [off + np.arange(int(p.n_windows[s])) for off, p in zip(offsets, parts)]
        ).astype(np.int64)
        t_rel, shifted = _rebase_times(t_start_us, rec.rso_tracks)
        t_grid[s, rows] = t_rel
        rows_all.append(rows)
        stops_all.append(stops)
        tracks.append(shifted)
        ms[s] = len(rows) * k if max_samples is None else max_samples
    r_max = max((t.shape[0] for t in tracks), default=0)
    tracks_padded = np.stack(
        [
            np.concatenate(
                [t, np.tile(np.float32(_FAR_TRACK), (r_max - t.shape[0], 1))]
            ) if t.shape[0] < r_max else t
            for t in tracks
        ]
    ) if r_max else np.zeros((s_count, 0, 6), np.float32)
    is_rso, keep, best = _match_many(
        cl.count, cl.valid, cl.centroid_x, cl.centroid_y, cl.centroid_t,
        jnp.asarray(t_grid), jnp.asarray(tracks_padded),
        jnp.float32(gate_px), jnp.asarray(ms),
    )
    counts_np = np.asarray(cl.count)
    is_rso_np, keep_np, best_np = (
        np.asarray(is_rso), np.asarray(keep), np.asarray(best)
    )
    out: list[Candidates] = []
    for s, rec in enumerate(recordings):
        rows, n_rso = rows_all[s], tracks[s].shape[0]
        out.append(
            _assemble_candidates(
                rec, stops_all[s], counts_np[s][rows], is_rso_np[s][rows],
                keep_np[s][rows], best_np[s][rows][:, :n_rso],
                min_truth_events,
            )
        )
    return out


# ---------------------------------------------------------------------------
# Threshold scoring / sweeps.
# ---------------------------------------------------------------------------

def score_threshold(cand: Candidates, thr: int) -> DetectionScore:
    passed = cand.counts >= thr
    return DetectionScore(
        tp=int(np.sum(passed & cand.is_rso)),
        fp=int(np.sum(passed & ~cand.is_rso)),
        fn=int(np.sum(cand.object_best < thr)),
        tn=int(np.sum(~passed & ~cand.is_rso)),
    )


def merge_candidates(cands: list[Candidates]) -> Candidates:
    return Candidates(
        np.concatenate([c.counts for c in cands]) if cands else np.zeros(0, np.int32),
        np.concatenate([c.is_rso for c in cands]) if cands else np.zeros(0, bool),
        np.concatenate([c.object_best for c in cands]) if cands else np.zeros(0, np.int32),
    )


def evaluate_detection(
    recording: Recording,
    config: PipelineConfig = PipelineConfig(),
    min_events: int | None = None,
    candidate_floor: int = 2,
    max_samples: int | None = None,
) -> DetectionScore:
    """Score the min_events detector against simulator ground truth
    (the paper's Fig. 10b / Sec. V-A protocol)."""
    thr = config.grid.min_events if min_events is None else min_events
    cand = collect_candidates(recording, config, candidate_floor, max_samples)
    return score_threshold(cand, thr)


def threshold_sweep(
    recordings: list[Recording],
    thresholds: tuple[int, ...] = (2, 3, 4, 5, 6, 8, 10),
    config: PipelineConfig = PipelineConfig(),
    max_samples_per_recording: int | None = None,
    driver: str = "scan",
) -> dict[int, DetectionScore]:
    """Accuracy vs min_events across a validation suite (paper Fig. 10b).

    The whole suite runs in O(1) device dispatches and thresholds are
    swept over the collected candidates on host (the O(n) single-pass
    property in action). ``driver="scan"`` (default) batches the suite
    through the vmapped offline scan (:func:`collect_candidates_many`);
    ``driver="fleet"`` routes it through the streaming fleet engine
    (:func:`collect_candidates_fleet`) — same scores exactly, but
    exercising the serving path, and shardable over a ``sensor`` mesh
    axis.
    """
    if driver == "scan":
        cands = collect_candidates_many(
            recordings, config, max_samples=max_samples_per_recording
        )
    elif driver == "fleet":
        cands = collect_candidates_fleet(
            recordings, config, max_samples=max_samples_per_recording
        )
    else:
        raise ValueError(f"unknown threshold_sweep driver: {driver!r}")
    cand = merge_candidates(cands)
    return {thr: score_threshold(cand, thr) for thr in thresholds}
