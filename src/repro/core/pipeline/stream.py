"""Resumable streaming pipeline engine: chunked feeds, per-window latency.

The paper's headline claim is deterministic sub-62 ms processing of a
*live* event-camera feed. ``StreamingPipeline`` is that driver: raw event
chunks of arbitrary size go in via :meth:`StreamingPipeline.feed`, and
every feed returns the clusters / metrics / tracks of the windows that
provably closed — windowed with exactly the dual-threshold semantics of
the offline drivers, so the concatenation of all feeds (plus a final
:meth:`flush`) is **bit-identical to ``run_recording_scan`` over the same
recording for any chunking**, including chunks that split a window.

The carry (:class:`StreamState`) holds everything the next feed needs:

* the dual-threshold batcher remainder — host-side events of the still
  open trailing window (no future event can be excluded from it yet),
* the window counter — the next atlas tag (epoch-local: it restarts
  when the tag encoding rolls over to a fresh epoch),
* the persistent window-tagged event atlas (event-space metrics path) —
  never cleared between feeds; stale pixels fail the tag check,
* the tracker :class:`~repro.core.tracking.TrackState`.

The device step (``make_stream_fn``) donates the atlas buffer, so a
steady-state feed allocates only its per-window outputs. Consequence: a
:class:`StreamState` is consumed by the feed that processes it — resume
from the *latest* state only; forking one saved state into two pipelines
would reuse a donated buffer.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metrics as M
from repro.core.events import (
    EventBatch,
    WindowedEvents,
    dense_wire_bytes,
    dual_threshold_bounds,
    dual_threshold_closed_bounds,
    monotone_merge,
    pack_bounds,
    pack_wire,
    ragged_wire_bytes,
)
from repro.core.grid_clustering import Clusters
from repro.core.pipeline.config import PipelineConfig
from repro.core.pipeline.scan import ScanResult, make_atlas, make_stream_fn
from repro.core.tracking import TrackState, init_tracks

_EMPTY = np.zeros(0, np.int64)


def tag_limit(config: PipelineConfig) -> int:
    """Windows addressable within one atlas tag epoch for this config.

    Tags are encoded as ``(tag + 1) << shift`` in int32 (``shift`` bits
    hold the per-pixel count); the streaming drivers must wrap to a fresh
    epoch — atlas re-zeroed so stale pixels cannot alias fresh tags —
    before the encoding overflows.
    """
    shift = max(config.batcher.capacity.bit_length(), 1)
    return (1 << (31 - shift)) - 2


def empty_scan_result(
    config: PipelineConfig,
    with_tracking: bool,
    tracks: TrackState,
    windows: WindowedEvents,
) -> ScanResult:
    """Zero-window ScanResult (a feed that closed nothing): empty stacked
    outputs with the caller's carry passed through as ``final_tracks``."""
    k = config.grid.max_clusters
    f32 = lambda: jnp.zeros((0, k), jnp.float32)
    i32 = lambda: jnp.zeros((0, k), jnp.int32)
    clusters = Clusters(
        centroid_x=f32(), centroid_y=f32(), centroid_t=f32(),
        count=i32(), cell_x=i32(), cell_y=i32(),
        valid=jnp.zeros((0, k), bool),
    )
    mets = {name: f32() for name in M.METRIC_NAMES}
    states = jax.tree.map(lambda a: jnp.zeros((0,) + a.shape, a.dtype), tracks)
    return ScanResult(
        t_start_us=windows.t_start_us,
        clusters=clusters,
        metrics=mets,
        tracks=states if with_tracking else None,
        final_tracks=tracks if with_tracking else None,
        windows=windows,
    )


@dataclasses.dataclass
class StreamState:
    """Everything carried between feeds; replaceable/savable as a unit."""

    pending: tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]  # x, y, t, p
    events_consumed: int  # stream index of pending[0]
    next_tag: int  # next atlas tag (epoch-local: resets at tag rollover)
    atlas: jax.Array  # persistent tagged event surface
    tracks: TrackState
    last_t: int | None = None  # newest absorbed timestamp (feed monotonicity)

    @property
    def pending_count(self) -> int:
        return len(self.pending[2])


class StreamingPipeline:
    """Incremental driver over a live event stream.

    >>> sp = StreamingPipeline(PipelineConfig())
    >>> for x, y, t, p in sensor_chunks():      # any chunk sizes
    ...     result = sp.feed(x, y, t, p)        # windows closed this feed
    >>> tail = sp.flush()                       # close the trailing window

    Each feed runs ONE jit'd (donated-carry) step over the newly closed
    windows; results are bit-identical to ``run_recording_scan`` over the
    concatenated stream. ``state`` may be saved and restored to resume a
    stream across processes (host remainder + device carry).
    """

    def __init__(
        self,
        config: PipelineConfig = PipelineConfig(),
        with_tracking: bool = True,
        state: StreamState | None = None,
        wire: str = "dense",
    ):
        if wire not in ("dense", "ragged"):
            raise ValueError(f"unknown wire mode: {wire!r}")
        self.config = config
        self.with_tracking = with_tracking
        self.wire = wire
        self._step = make_stream_fn(config, with_tracking)
        # Lazy import: fleet.py imports this module at load time, so the
        # wire machinery (shared with the fleet engine) has to come in at
        # construction, not at module import.
        from repro.core.pipeline.fleet import (
            WireStats, _pinned_host_sharding, _stage_wire, make_wire_fn,
        )

        self.wire_stats = WireStats()
        if wire == "ragged":
            self._wire = make_wire_fn(config.batcher.capacity, config.use_kernels)
        else:
            self._wire = None
        self._tag_limit = tag_limit(config)
        self.state = self.init_state() if state is None else state
        self._wire_staging = _pinned_host_sharding(self.state.atlas)
        self._stage_wire = _stage_wire

    def init_state(self) -> StreamState:
        return StreamState(
            pending=(_EMPTY, _EMPTY, _EMPTY, _EMPTY),
            events_consumed=0,
            next_tag=0,
            atlas=make_atlas(self.config),
            tracks=init_tracks(self.config.tracker),
        )

    def feed(
        self, x: np.ndarray, y: np.ndarray, t: np.ndarray, p: np.ndarray
    ) -> ScanResult:
        """Ingest a raw event chunk; process and return the closed windows.

        Events must be time-sorted within the chunk and non-decreasing
        across feeds; a chunk violating either raises ``ValueError``
        before any state changes (silent mis-windowing would otherwise
        corrupt every window downstream of the disorder). A feed may
        close zero windows (chunk too small/recent) — the result is then
        empty and the events wait in the batcher remainder. A feed that
        would close more windows than one tag epoch can address raises
        ``ValueError`` *without absorbing the chunk*, so the caller can
        re-feed it in smaller pieces.
        """
        merged = monotone_merge(
            self.state.pending, x, y, t, p, self.state.last_t
        )
        bounds, consumed = dual_threshold_closed_bounds(
            merged[2], self.config.batcher
        )
        return self._emit(merged, bounds, consumed)

    def feed_chunk(
        self, chunk: tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray] | None
    ) -> ScanResult:
        """:meth:`feed` over a packed ``(x, y, t, p)`` chunk tuple — the
        wire shape the fleet/service layers pass around (``None`` = idle,
        an empty feed). Lets a dedicated single-sensor pipeline consume
        the exact per-session chunk stream a
        :class:`~repro.serve.service.DetectionService` session receives,
        which is how the service's bit-identity contract is pinned."""
        if chunk is None:
            chunk = (_EMPTY, _EMPTY, _EMPTY, _EMPTY)
        return self.feed(*chunk)

    @property
    def backlog(self) -> int:
        """Events absorbed but not yet windowed (the batcher remainder)."""
        return self.state.pending_count

    def flush(self) -> ScanResult:
        """Close and process the trailing partial window (end of stream).

        After a flush the pipeline keeps accepting feeds — but the flushed
        window closed at the flush boundary, so only the full-stream
        equivalence of feeds *up to* the flush is preserved.
        """
        pending = self.state.pending
        bounds = dual_threshold_bounds(pending[2], self.config.batcher)
        return self._emit(pending, bounds, len(pending[2]))

    def _emit(
        self,
        pending: tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray],
        bounds: list[tuple[int, int]],
        consumed: int,
    ) -> ScanResult:
        n = len(bounds)
        if n > self._tag_limit:
            # More windows than one tag epoch can address: tags past the
            # limit would wrap the int32 encoding and silently alias stale
            # atlas pixels. Refuse before touching any state, so the
            # pipeline stays usable and the chunk can be re-fed in pieces.
            raise ValueError(
                f"feed closed {n} windows, more than one tag epoch "
                f"({self._tag_limit}) can address; split the feed"
            )
        st = self.state
        px, py, pt, pp = pending
        last_t = int(pt[-1]) if len(pt) else st.last_t
        cap = self.config.batcher.capacity
        bounds3 = [(s, e, int(pt[s])) for s, e in bounds]
        if self.wire == "ragged" and n:
            # Compressed ingest: pack the ragged wire on host, decode to
            # the dense (W, cap) planes device-side — bit-identical to
            # pack_bounds (see events.unpack_wire), one sensor row.
            wire, starts, stops, t_start, overflow = pack_wire(
                px, py, pt, pp, bounds3, cap
            )
            packed, valid = self._wire(
                *self._stage_wire(wire, self._wire_staging)
            )
            batch = EventBatch(
                packed[0, 0], packed[1, 0], packed[2, 0], packed[3, 0],
                valid[0],
            )
            windows = WindowedEvents(batch, t_start, starts, stops, overflow)
            self.wire_stats.rounds += 1
            self.wire_stats.events += int(
                np.minimum(stops - starts, cap).sum()
            )
            self.wire_stats.wire_bytes += ragged_wire_bytes(
                wire[0].shape[0], 1, n, wire[4].shape[1]
            )
            self.wire_stats.dense_bytes += dense_wire_bytes(1, n, cap)
        else:
            windows = pack_bounds(px, py, pt, pp, bounds3, cap)
            if n:
                b = dense_wire_bytes(1, n, cap)
                self.wire_stats.rounds += 1
                self.wire_stats.events += int(
                    np.minimum(windows.stops - windows.starts, cap).sum()
                )
                self.wire_stats.wire_bytes += b
                self.wire_stats.dense_bytes += b
        # Slice indices are stream-global, like pad_windows over the
        # whole recording.
        windows = windows._replace(
            starts=windows.starts + st.events_consumed,
            stops=windows.stops + st.events_consumed,
        )
        if n == 0:
            # Absorb the new events into the remainder even when nothing
            # closed yet.
            self.state = dataclasses.replace(
                st, pending=pending, last_t=last_t
            )
            return empty_scan_result(
                self.config, self.with_tracking, st.tracks, windows
            )

        atlas, tag0 = st.atlas, st.next_tag
        if tag0 + n > self._tag_limit:  # tag epoch rollover
            atlas, tag0 = jnp.zeros_like(atlas), 0
        final, clusters, mets, states, atlas = self._step(
            windows.batch, st.tracks, atlas, tag0
        )
        keep = consumed  # events consumed from the front of the remainder
        self.state = StreamState(
            pending=(px[keep:], py[keep:], pt[keep:], pp[keep:]),
            events_consumed=st.events_consumed + keep,
            next_tag=tag0 + n,
            atlas=atlas,
            tracks=final,
            last_t=last_t,
        )
        return ScanResult(
            t_start_us=windows.t_start_us,
            clusters=clusters,
            metrics=mets,
            tracks=states if self.with_tracking else None,
            final_tracks=final if self.with_tracking else None,
            windows=windows,
        )
