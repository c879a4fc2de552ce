"""Fleet engine: N live sensors through ONE vmapped/jitted step core.

The paper frames the architecture as a building block for *distributed
space surveillance networks*, and event-based SSA work (Afshar et al.
1911.08730; Ussa et al. 2007.11404) gets its payoff from many
co-observing sensors. :class:`FleetPipeline` is the serving-shaped
driver for that: the per-sensor streaming carry (:class:`StreamState`)
is lifted into a batched :class:`FleetState` — stacked event atlases,
stacked tracker states, and one host-side dual-threshold cursor per
sensor — and every :meth:`FleetPipeline.feed` drives *all* sensors
through a single ``jit(vmap(core))`` dispatch.

Design invariants:

* **Bit-identity.** Per-sensor outputs equal N independent
  :class:`~repro.core.pipeline.stream.StreamingPipeline` runs exactly —
  scores, tracks, window stats — for ANY interleaving of feeds
  (including idle sensors and chunks splitting a window). The step core
  is window-isolated, so batching sensors along a vmap axis cannot mix
  them; the only subtlety is ragged window counts per feed, handled by
  right-padding each sensor to the feed's max window count with
  all-invalid windows. Padded windows write nothing observable to the
  atlas (no valid events -> no leader pixels -> zero-encoded dump-row
  writes only, and a zero encoding fails every tag check) and the
  tracker carry for the next feed is re-selected at each sensor's last
  *real* window, so the padding coast never leaks into sensor state.
* **Tag accounting.** Tags advance per sensor by the number of real
  windows — identical to the single-sensor stream — even though padded
  windows transiently occupy the tags just past them; those tags carry
  no stale pixels, so their reuse next feed is safe. Epoch rollover
  (atlas slice re-zeroed, tag reset) is decided per sensor on host and
  applied by a tiny donated pre-step only on the rare feeds that roll.
* **Sharding.** Carries have the sensor dim leading, so they shard 1:1
  over the ``sensor`` mesh axis (:mod:`repro.distributed.sharding`):
  ``FleetPipeline(..., mesh=...)`` places the carry with
  ``NamedSharding`` and runs the step under the mesh so each device
  serves ``S / axis_size`` sensors with no cross-device collective. The
  stacked atlas is donated, like the single-sensor stream's.
* **Slot pool.** The batched carry is a pool of recyclable slots, not a
  frozen sensor roster: ``n_sensors`` is the pool *capacity*, an
  unoccupied slot is simply one that is always fed ``None`` (all-zero
  carry, rides along as all-invalid padding at negligible vmap cost),
  :meth:`FleetPipeline.reset_slots` zeroes a slot's carries so a
  departing sensor's slot can be handed to a new one (an all-zero slot
  carry IS the fresh-stream initial state, so a recycled slot is
  bit-identical to a brand-new :class:`StreamingPipeline`), and
  :meth:`FleetPipeline.grow` migrates the carry into a larger pool
  (zero-padded along the sensor dim, re-sharded). Because the step's
  compiled shape depends only on the pool capacity — never on which
  slots are occupied — attach/detach churn compiles nothing; only a
  capacity-tier promotion (:func:`tier_capacity`) does, at most once
  per tier. The session/service layer on top lives in
  :mod:`repro.serve` (DESIGN.md Sec. 11).
"""
from __future__ import annotations

import contextlib
import copy
import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.events import (
    SPILL_QUANTUM,
    SPILL_SENTINEL,
    EventBatch,
    WindowedEvents,
    dense_wire_bytes,
    dual_threshold_bounds,
    dual_threshold_closed_bounds,
    monotone_merge,
    pack_bounds_into,
    ragged_wire_bytes,
    spill_pad,
    unpack_wire,
    wire_pad,
)
from repro.core.grid_clustering import Clusters
from repro.core.pipeline.config import PipelineConfig
from repro.core.pipeline.scan import ScanResult, _make_core, atlas_shape
from repro.core.pipeline.stream import empty_scan_result, tag_limit
from repro.core.tracking import TrackState, init_tracks
from repro.distributed.sharding import (
    grow_fleet_carry,
    hint_fleet,
    hint_wire,
    shard_fleet_carry,
    shrink_fleet_carry,
)

_EMPTY = np.zeros(0, np.int64)
_EMPTY_CHUNK = (_EMPTY, _EMPTY, _EMPTY, _EMPTY)

# Slot-pool capacity tiers: a pool never grows by one — it is promoted to
# the next tier, so attach/detach churn triggers at most one fleet-step
# compile per tier instead of one per sensor-count (compile discipline is
# pinned by tests/test_serve_service.py). Past the last tier, capacity
# doubles.
DEFAULT_TIERS = (4, 8, 16, 32, 64)

# Test hook: one entry per fleet-step *trace* (== XLA compile), recording
# (S, W, capacity, uniform). Compiled-cache hits never run the traced
# Python, so appending inside the step body counts compiles exactly.
STEP_TRACES: list[tuple[int, int, int, bool]] = []


# Staging sets kept alive per packed-block shape: churny services visit a
# handful of (S, W, cap) shapes; beyond this the least recently used
# ring's buffers are dropped (they are plain numpy arrays — any round
# still in flight keeps its own device copies and bookkeeping copies).
_MAX_STAGING_SHAPES = 8


def tier_capacity(n: int, tiers: tuple[int, ...] = DEFAULT_TIERS) -> int:
    """Smallest tier capacity holding ``n`` slots (doubling past the end)."""
    if n < 1:
        raise ValueError(f"need at least one slot, got {n}")
    for cap in tiers:
        if n <= cap:
            return cap
    cap = tiers[-1]
    while cap < n:
        cap *= 2
    return cap


@dataclasses.dataclass
class SensorCursor:
    """Host-side per-sensor batcher cursor (the non-device slice of what
    used to be :class:`StreamState`)."""

    pending: tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]
    events_consumed: int = 0  # stream index of pending[0]
    next_tag: int = 0  # next atlas tag (epoch-local)
    last_t: int | None = None  # newest absorbed timestamp

    @property
    def pending_count(self) -> int:
        return len(self.pending[2])


@dataclasses.dataclass
class FleetState:
    """Batched streaming carry: one cursor per sensor on host, stacked
    (leading sensor dim) atlas + tracker carries on device."""

    cursors: list[SensorCursor]
    atlas: jax.Array  # (S, H+1, max(W, cap)) — donated by the step
    tracks: TrackState  # leaves (S, T)

    @property
    def n_sensors(self) -> int:
        return len(self.cursors)


@functools.lru_cache(maxsize=None)
def make_fleet_fn(config: PipelineConfig = PipelineConfig(), with_tracking: bool = True):
    """Jit'd fleet step: the single-sensor core vmapped over the sensor dim.

        (packed (4,S,W,cap) x/y/t/p, valid (S,W,cap), state (S,T),
         atlas (S,H+1,Wd), meta (2,S) tag0/n_valid) ->
            (final_state (S,T), clusters (S,W,K), mets (S,W,K),
             states (S,W,T), atlas_out)

    The event planes arrive as ONE packed int32 block (plus the bool
    validity mask and one (2, S) meta row): per-feed host->device
    transfers are the measurable per-round overhead on CPU, and packing
    turns seven dispatches into three; unpacking inside the jit is free.
    ``meta[1]`` (``n_valid``) is each sensor's real window count this
    feed — the returned carry is the per-window tracker state at window
    ``n_valid - 1`` (or the previous carry when a sensor closed
    nothing), so the padding coast past it never reaches the next feed.
    ``uniform`` (static) asserts every sensor closed exactly ``W``
    windows — the common co-observing round — so the carry is just the
    last per-window state and the ragged reselection gathers (a
    measurable slice of the per-feed critical path, ~0.5 ms on the
    2-core reference box) compile out entirely; host picks the variant
    per feed and both produce identical carries on uniform feeds.
    Tag-epoch rollover (atlas slice re-zeroed) happens host-side in
    :meth:`FleetPipeline._ingest` on the rare feeds that need it — doing
    it here would stream the whole stacked atlas through a select on
    EVERY feed, which costs more than the entire vmapped core on small
    feeds. The stacked atlas is donated; sensor-axis sharding hints keep
    the carry partitioned across devices when a mesh is active. Compiled
    once per (config, S, W, capacity); cached per config.
    """
    core = _make_core(config, with_tracking)
    vcore = jax.vmap(core)

    def step(packed, valid, state, atlas, meta, uniform):
        STEP_TRACES.append(
            (packed.shape[1], packed.shape[2], packed.shape[3], uniform)
        )
        stacked = EventBatch(packed[0], packed[1], packed[2], packed[3], valid)
        tag0, n_valid = meta[0], meta[1]
        atlas = hint_fleet(atlas)
        state = hint_fleet(state)
        stacked = hint_fleet(stacked)
        with jax.named_scope("window_core"):
            _, clusters, mets, states, atlas = vcore(stacked, state, atlas, tag0)
        with jax.named_scope("carry_reselect"):
            if uniform:
                final = jax.tree.map(lambda per_w: per_w[:, -1], states)
            else:
                s_ix = jnp.arange(n_valid.shape[0])
                last = jnp.maximum(n_valid - 1, 0)
                final = jax.tree.map(
                    lambda per_w, prev: jnp.where(
                        (n_valid > 0)[:, None], per_w[s_ix, last], prev
                    ),
                    states,
                    state,
                )
        return final, clusters, mets, states, hint_fleet(atlas)

    return jax.jit(step, donate_argnums=(3,), static_argnums=(5,))


@functools.lru_cache(maxsize=None)
def make_wire_fn(capacity: int, use_kernels: bool):
    """Jit'd ragged-wire decoder: compressed wire -> dense step inputs.

        (words (N,) uint32, dt (N,) uint16, pol (N/32,) uint32,
         offsets (S, W+1) int32, spill (5, M) int32) ->
            (packed (4, S, W, cap) int32, valid (S, W, cap) bool)

    Deliberately a SEPARATE jit in front of the fleet step, not fused
    into it: the wire length N varies with occupancy (bucketed to
    ``WIRE_QUANTUM``), and folding it into the step's compile key would
    break the one-compile-per-capacity-tier discipline the service pins
    (tests/test_serve_service.py). The decoder's outputs have exactly
    the dense staging shapes/dtypes, so the step's compiled cache is
    shared between both wire modes; decoder compiles are cheap (a few
    elementwise ops + one gather) and bounded by the occupancy buckets.
    ``use_kernels`` routes the word unpack through the Pallas
    ``event_unpack`` kernel (interpret mode off TPU), mirroring the
    quantize/accum routing; the jnp shift/mask path is the default.
    Sensor-axis sharding hints keep the reconstructed planes partitioned
    like the rest of the carry when a mesh is active.
    """
    if use_kernels:
        from repro.kernels.ops import event_unpack_call  # lazy, like config
        unpack_impl = event_unpack_call
    else:
        unpack_impl = None

    def decode(words, dt16, pol, offsets, spill):
        # Staged views may arrive in pinned host memory (see
        # _pinned_host_sharding); the decode itself runs in device memory.
        words, dt16, pol, offsets, spill = jax.device_put(
            (words, dt16, pol, offsets, spill), jax.memory.Space.Device
        )
        with jax.named_scope("unpack_wire"):
            packed, valid = unpack_wire(
                words, dt16, pol, offsets, spill, capacity, unpack_impl
            )
        packed, valid, _ = hint_wire(packed, valid, offsets)
        return packed, valid

    return jax.jit(decode)


def _pinned_host_sharding(carry: jax.Array):
    """Pinned-host staging placement on the device(s) that hold ``carry``.

    On accelerator backends whose devices expose a ``pinned_host``
    memory space (TPU/GPU runtimes), host->device DMA from pinned pages
    avoids a driver-side bounce copy; the ragged dispatch routes its
    wire views through this placement first. The placement follows the
    carry — its device, or its mesh with the wire replicated — so a
    fleet living on one chip of several stages and decodes there.
    CPU backends (host memory IS device memory) and runtimes without
    the memory space return ``None`` and the views ship as plain numpy:
    behaviour, and bits, are identical either way.
    """
    if jax.default_backend() == "cpu":
        return None
    sharding = carry.sharding
    devices = sharding.device_set
    if any(
        "pinned_host" not in {m.kind for m in d.addressable_memories()}
        for d in devices
    ):
        return None
    if len(devices) == 1:
        return jax.sharding.SingleDeviceSharding(
            next(iter(devices)), memory_kind="pinned_host"
        )
    return jax.sharding.NamedSharding(
        sharding.mesh, jax.sharding.PartitionSpec(), memory_kind="pinned_host"
    )


def _stage_wire(views: tuple, sharding) -> tuple:
    """Place the per-round wire views with ``sharding`` (from
    :func:`_pinned_host_sharding`); ``None`` ships them as numpy."""
    return views if sharding is None else jax.device_put(views, sharding)


@dataclasses.dataclass
class WireStats:
    """Host<->device transfer accounting, accumulated per round.

    ``wire_bytes`` counts what the active wire mode actually ships;
    ``dense_bytes`` is the dense-equivalent cost of the same rounds
    (identical, by construction, when ``wire="dense"``), so
    ``compression`` is the measured transfer reduction the ragged wire
    delivers at the workload's real occupancy. ``d2h_transfers`` and
    ``d2h_bytes`` count the copy-back: one transfer per stacked output
    leaf a round's first host read brings over, and those leaves' bytes
    (:meth:`FleetResult._host_view`; on the CPU backend the same leaves
    are host views, counted alike).
    """

    rounds: int = 0
    events: int = 0  # real (valid) events shipped
    wire_bytes: int = 0
    dense_bytes: int = 0
    spilled: int = 0  # events that took the exact int32 spill lane
    d2h_transfers: int = 0  # output leaves copied back to the host
    d2h_bytes: int = 0  # bytes of those leaves

    @property
    def compression(self) -> float:
        """Dense-equivalent bytes over shipped bytes (>= 1 when winning)."""
        return self.dense_bytes / self.wire_bytes if self.wire_bytes else 0.0

    @property
    def wire_bytes_per_round(self) -> float:
        return self.wire_bytes / self.rounds if self.rounds else 0.0

    def add(self, other: "WireStats") -> None:
        self.rounds += other.rounds
        self.events += other.events
        self.wire_bytes += other.wire_bytes
        self.dense_bytes += other.dense_bytes
        self.spilled += other.spilled
        self.d2h_transfers += other.d2h_transfers
        self.d2h_bytes += other.d2h_bytes


@functools.lru_cache(maxsize=None)
def _zero_sensors_fn():
    """Jit'd atlas-slice zeroing for tag-epoch rollover (donated, so the
    common no-rollover feed path never touches the stacked atlas)."""
    return jax.jit(
        lambda atlas, reset: jnp.where(reset[:, None, None], 0, atlas),
        donate_argnums=(0,),
    )


@functools.lru_cache(maxsize=None)
def _zero_slots_fn():
    """Jit'd whole-slot zeroing (atlas slice + tracker slice) for slot
    recycling. The atlas is donated like the step's; the tracker carry is
    not — the previous feed handed those buffers to the caller as
    ``final_tracks`` and zeroing in place would corrupt that result."""

    def zero(atlas, tracks, reset):
        atlas = jnp.where(reset[:, None, None], 0, atlas)
        tracks = jax.tree.map(
            lambda a: jnp.where(
                reset.reshape((-1,) + (1,) * (a.ndim - 1)), jnp.zeros_like(a), a
            ),
            tracks,
        )
        return atlas, tracks

    return jax.jit(zero, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _set_slot_fn():
    """Jit'd single-slot carry overwrite (atlas slice + tracker slice)
    for importing a migrated slot. The atlas is donated, mirroring
    :func:`_zero_slots_fn`; the tracker carry is not (the previous feed
    handed those buffers to callers as ``final_tracks``)."""

    def set_(atlas, tracks, slot, atlas_row, tracks_row):
        atlas = atlas.at[slot].set(atlas_row)
        tracks = jax.tree.map(
            lambda a, r: a.at[slot].set(r), tracks, tracks_row
        )
        return atlas, tracks

    return jax.jit(set_, donate_argnums=(0,))


@dataclasses.dataclass
class SlotCarry:
    """One slot's complete streaming carry, detached from its pool.

    The portable unit of cross-shard session migration (DESIGN.md
    Sec. 15): the host cursor plus host copies of the slot's atlas slice
    and tracker slice. Because the per-sensor carry IS the entire stream
    state, exporting a slot from one :class:`FleetPipeline` and importing
    it into a free slot of another (same :class:`PipelineConfig`) resumes
    the stream bit-identically — regardless of either pool's capacity,
    mesh, or slot index.
    """

    cursor: SensorCursor
    atlas: np.ndarray  # (H+1, Wd) int32 — the slot's atlas slice
    tracks: Any  # TrackState pytree, leaves (T, ...) numpy

    @property
    def pending_count(self) -> int:
        return self.cursor.pending_count


@dataclasses.dataclass
class FleetResult:
    """Stacked outputs of one fleet feed; per-sensor views on demand.

    Leaves keep the (S, W_max, ...) stacked layout — the shape the next
    O(1)-dispatch consumer (fleet evaluation, device-side reducers)
    wants — and :meth:`sensor` materializes the trimmed per-sensor
    :class:`ScanResult` lazily, so a latency-critical feed loop is not
    billed for S x leaves slice dispatches it never reads.
    """

    n_windows: np.ndarray  # (S,) real windows closed this feed
    windows: list[WindowedEvents]  # per-sensor host bookkeeping (real windows)
    clusters: Clusters | None  # leaves (S, W_max, K); None when no window closed
    metrics: dict[str, jax.Array] | None
    tracks: TrackState | None  # leaves (S, W_max, T)
    final_tracks: TrackState | None  # leaves (S, T) — corrected carry
    _config: PipelineConfig
    _with_tracking: bool
    _carry_tracks: TrackState  # (S, T) carry after this feed (empty-feed path)
    _host: tuple | None = None  # numpy copy of the stacked leaves, on demand
    _hot_rows: dict | None = None  # slot -> row into the gathered host leaves
    _stats: WireStats | None = None  # the fleet's; counts the copy-back
    _round: int = -1  # the fleet's round number (``WireStats.rounds``)

    @property
    def n_sensors(self) -> int:
        return len(self.windows)

    @property
    def total_windows(self) -> int:
        return int(self.n_windows.sum())

    def _host_view(self) -> tuple:
        """Stacked outputs pulled to host, once per feed.

        Materializing S per-sensor results by slicing device arrays costs
        S x leaves tiny dispatches — measured ~5x the whole vmapped step
        on an 8-slot CPU fleet. One ``np.asarray`` per stacked leaf (a
        single transfer each, amortized over every sensor) makes each
        ``sensor(s)`` call pure numpy views. Values are the same bits, so
        the bit-identity contract is untouched; the device-resident
        stacked attributes stay as they were for O(1)-dispatch consumers.

        When most slots closed no window this feed — a sparsely occupied
        slot pool, the steady churny-service shape — copying the full
        (S, W, ...) leaves bills every padding row. Instead the hot rows
        (``n_windows > 0``) are gathered device-side (one fused take per
        leaf) and only those cross to host; ``sensor(s)`` maps its slot
        through ``_hot_rows``. A slot with zero windows trims ``[:0]``
        from row 0, which yields the same empty arrays the full copy
        would. ``final_tracks`` is every slot's carry — idle slots
        included — so it always crosses in full.
        """
        if self._host is None:
            # The wait for the round and the transfers are timed apart:
            # the first leaf read would otherwise hide the wait.
            with TraceAnnotation("fleet.copy_wait", round=self._round):
                self.block_until_ready()
            with TraceAnnotation("fleet.copy_back", round=self._round):
                self._host, self._hot_rows = self._copy_back()
            if self._stats is not None:
                leaves = jax.tree.leaves(self._host)
                self._stats.d2h_transfers += len(leaves)
                self._stats.d2h_bytes += sum(a.nbytes for a in leaves)
        return self._host

    def _copy_back(self) -> tuple[tuple, dict | None]:
        """The stacked leaves on the host, and the hot-row map (``None``
        on the full path); see :meth:`_host_view`."""
        s_count = len(self.windows)
        hot = np.flatnonzero(np.asarray(self.n_windows) > 0)
        leaves = (self.clusters, self.metrics, self.tracks, self.final_tracks)
        if 2 * len(hot) >= s_count:
            # Mostly-hot fleet: plain per-leaf transfers beat the extra
            # gather dispatch per leaf.
            return jax.tree.map(np.asarray, leaves), None
        if jax.default_backend() == "cpu":
            # Host memory IS device memory: np.asarray is a zero-copy
            # view, so "transfer only the hot rows" means one numpy
            # fancy-index per leaf (copies just those rows, and releases
            # the full (S, W, ...) stacked buffers a long-held view would
            # pin). A device-side gather here would cost a dispatched
            # computation per leaf — measured ~90x the full view in
            # benchmarks/serve_latency.py.
            gather = lambda a: np.asarray(a)[hot]
        else:
            # Separate device memory: gather on device so only the
            # valid-window rows cross the wire.
            idx = jnp.asarray(hot)
            gather = lambda a: np.asarray(a[idx])
        host = (
            jax.tree.map(gather, self.clusters),
            jax.tree.map(gather, self.metrics),
            jax.tree.map(gather, self.tracks),
            jax.tree.map(np.asarray, self.final_tracks),
        )
        return host, {int(s): i for i, s in enumerate(hot)}

    def ready(self) -> bool:
        """True when the device step behind this feed has completed (its
        output buffers are materialized). Host views never block once
        this holds."""
        if self.clusters is None:
            return True
        return all(
            getattr(leaf, "is_ready", lambda: True)()
            for leaf in jax.tree.leaves(
                (self.clusters, self.metrics, self.tracks, self.final_tracks)
            )
        )

    def block_until_ready(self) -> "FleetResult":
        if self.clusters is not None:
            jax.block_until_ready(
                (self.clusters, self.metrics, self.tracks, self.final_tracks)
            )
        return self

    def sensor(self, s: int) -> ScanResult:
        """Trimmed per-sensor result, bit-identical to the equivalent
        ``StreamingPipeline.feed`` return."""
        n = int(self.n_windows[s])
        w = self.windows[s]
        if self.clusters is None:
            carry_s = jax.tree.map(lambda a: a[s], self._carry_tracks)
            return empty_scan_result(self._config, self._with_tracking, carry_s, w)
        clusters_h, mets_h, tracks_h, final_h = self._host_view()
        row = s if self._hot_rows is None else self._hot_rows.get(s, 0)
        trim = lambda a: a[row, :n]
        clusters = jax.tree.map(trim, clusters_h)
        mets = {k: trim(v) for k, v in mets_h.items()}
        return ScanResult(
            t_start_us=w.t_start_us,
            clusters=clusters,
            metrics=mets,
            tracks=jax.tree.map(trim, tracks_h) if self._with_tracking else None,
            final_tracks=(
                jax.tree.map(lambda a: a[s], final_h)
                if self._with_tracking
                else None
            ),
            windows=w,
        )

    def results(self) -> list[ScanResult]:
        return [self.sensor(s) for s in range(self.n_sensors)]


@dataclasses.dataclass
class PendingRound:
    """Handle to one dispatched (possibly still executing) fleet round.

    :meth:`FleetPipeline.feed_async` dispatches the jitted step and
    returns immediately — JAX async dispatch means the returned arrays
    are futures. The handle makes the pipeline explicit: :meth:`ready`
    polls the device without blocking, :meth:`wait` synchronizes, and
    :meth:`result` hands back the :class:`FleetResult` without forcing
    either (its host views synchronize lazily at first consumption, so N
    in-flight rounds consumed together cost one sync, not N).
    """

    _result: FleetResult

    def ready(self) -> bool:
        """Poll: has the device step behind this round completed?"""
        return self._result.ready()

    def wait(self) -> FleetResult:
        """Block until the round's device buffers are materialized."""
        return self._result.block_until_ready()

    def result(self) -> FleetResult:
        """The round's result; does not block (host views are lazy)."""
        return self._result

    @property
    def n_windows(self) -> np.ndarray:
        """(S,) windows closed this round — host data, never blocks."""
        return self._result.n_windows

    @property
    def total_windows(self) -> int:
        return self._result.total_windows


class _StagingSet:
    """One preallocated host-side staging buffer set for a packed-block
    shape: the (4, S, W, cap) event planes, the (S, W, cap) validity
    mask, and the (2, S) tag/n_valid meta rows. ``inflight`` is the
    round currently borrowing the buffers (its transfer must complete —
    gated on the round's *outputs*, see :class:`_StagingPool` — before
    they are refilled)."""

    __slots__ = ("packed", "valid", "meta", "inflight")

    def __init__(self, s: int, w: int, cap: int):
        self.packed = np.zeros((4, s, w, cap), np.int32)
        self.valid = np.zeros((s, w, cap), bool)
        self.meta = np.zeros((2, s), np.int32)
        self.inflight: PendingRound | None = None


class _RaggedStagingSet:
    """Staging buffers for the compressed ragged wire (DESIGN.md Sec. 16):
    1-D word/delta/polarity lanes sized for the worst case (every slot of
    every window full), the CSR offsets block, and a growable spill lane.
    Unlike the dense set, acquire never zero-fills these: every round
    rewrites each sensor's full offsets row and the decoder's masked
    gather makes stale bytes past the round's event total unobservable
    (see ``unpack_wire``); only the spill view is re-sentineled per round
    — a stale spill entry WOULD scatter into live wire positions."""

    __slots__ = (
        "words", "dt", "pbits", "pol", "offsets", "spill", "meta", "inflight"
    )

    def __init__(self, s: int, w: int, cap: int):
        n_max = wire_pad(s * w * cap)
        self.words = np.zeros(n_max, np.uint32)
        self.dt = np.zeros(n_max, np.uint16)
        self.pbits = np.zeros(n_max, np.uint8)  # packbits scratch
        self.pol = np.zeros(n_max // 32, np.uint32)
        self.offsets = np.zeros((s, w + 1), np.int32)
        self.spill = np.full((5, 4 * SPILL_QUANTUM), SPILL_SENTINEL, np.int32)
        self.inflight: PendingRound | None = None
        self.meta = np.zeros((2, s), np.int32)

    def reserve_spill(self, m_pad: int) -> None:
        """Grow the spill lane to hold ``m_pad`` entries (amortized)."""
        if m_pad > self.spill.shape[1]:
            grown = spill_pad(max(m_pad, 2 * self.spill.shape[1]))
            self.spill = np.full((5, grown), SPILL_SENTINEL, np.int32)


class _StagingPool:
    """Depth-deep ring of reusable staging sets per packed-block shape.

    Double buffering (``depth=2``) lets round N+1 pack on host while
    round N computes on device: the two rounds use disjoint buffer sets,
    and acquiring a set whose borrower is still executing blocks until
    that round's outputs are ready. Outputs-ready is the conservative
    reuse gate — the step cannot have finished without having consumed
    its inputs, so refilling the numpy planes can never race the
    host->device transfer even if the runtime aliased them. Rings are
    kept per shape with LRU eviction past ``_MAX_STAGING_SHAPES``.
    """

    def __init__(self, depth: int = 2):
        if depth < 1:
            raise ValueError(f"staging depth must be >= 1, got {depth}")
        self.depth = depth
        # (s, w, cap, wire) -> [ix, sets]
        self._rings: dict[tuple[int, int, int, str], list] = {}

    def acquire(self, s: int, w: int, cap: int, wire: str = "dense",
                round_ix: int = -1):
        """A staging set for shape ``(s, w, cap)``; ``round_ix`` (the
        round it will carry) tags the span of a wait for its borrower."""
        key = (s, w, cap, wire)
        ring = self._rings.pop(key, None)
        if ring is None:
            cls = _RaggedStagingSet if wire == "ragged" else _StagingSet
            ring = [0, [cls(s, w, cap) for _ in range(self.depth)]]
        self._rings[key] = ring  # reinsert: dict order is the LRU order
        while len(self._rings) > _MAX_STAGING_SHAPES:
            self._rings.pop(next(iter(self._rings)))
        ix, sets = ring
        ring[0] = (ix + 1) % self.depth
        st = sets[ix]
        if st.inflight is not None:
            with TraceAnnotation("fleet.staging_wait", round=round_ix):
                st.inflight.wait()
            st.inflight = None
        if wire == "dense":
            st.packed.fill(0)
            st.valid.fill(0)
        return st


class FleetPipeline:
    """Batched multi-sensor streaming driver (one step for the whole fleet).

    >>> fp = FleetPipeline(PipelineConfig(), n_sensors=8)
    >>> out = fp.feed([(x0, y0, t0, p0), None, (x2, y2, t2, p2), ...])
    >>> out.sensor(0).clusters  # == the equivalent StreamingPipeline feed
    >>> tail = fp.flush()       # close every sensor's trailing window

    ``feed`` takes one optional ``(x, y, t, p)`` chunk per sensor
    (``None`` = idle this feed) and runs ONE ``jit(vmap(core))`` step
    over every window that provably closed, fleet-wide. Passing
    ``mesh=`` (a mesh with a ``sensor`` axis) shards the carry and the
    step across devices. A chunk with out-of-order timestamps — within
    the chunk or against the sensor's stream — raises ``ValueError``
    before ANY sensor's state changes, as does a feed closing more
    windows than one tag epoch can address; the fleet stays usable and
    the same chunks can be re-fed.

    As a slot pool (see module docstring): ``n_sensors`` is the pool
    capacity, :meth:`reset_slots` zeroes departing slots for reuse,
    :meth:`grow` promotes the pool to a larger capacity with carry
    migration, and ``feed``'s ``final`` argument accepts a per-slot
    mask so one sensor's trailing window can be force-closed (sensor
    detach) without flushing the rest of the fleet.
    ``uniform_fast_path=False`` disables the static all-sensors-uniform
    step variant — dynamic-membership callers (the detection service)
    use it to pin compiles to exactly one step shape per (capacity,
    window-count) instead of two.

    ``feed`` dispatches asynchronously (the returned result's host views
    synchronize lazily); :meth:`feed_async` exposes the same round as an
    explicit :class:`PendingRound` handle so a pipelined caller can keep
    several rounds in flight and poll/await them. Host packing writes
    into ``staging_depth`` preallocated staging buffer sets per packed
    shape (double buffering by default) instead of allocating per round;
    a set is refilled only after the round borrowing it has completed.

    ``wire`` selects the host->device ingest format (DESIGN.md Sec. 16):
    ``"ragged"`` (the default) ships the compressed event wire — packed
    coordinate words, 16-bit window-relative deltas, a polarity
    bitplane, CSR offsets, and an exact spill lane — and reconstructs
    the dense staging planes in a separate jit'd decoder in front of the
    step, bit-identically; ``"dense"`` ships the (4, S, W, cap) planes
    directly. Both modes share the step's compiled cache; per-round
    transfer sizes accumulate in :attr:`wire_stats` either way.
    """

    def __init__(
        self,
        config: PipelineConfig = PipelineConfig(),
        n_sensors: int = 1,
        with_tracking: bool = True,
        mesh=None,
        state: FleetState | None = None,
        uniform_fast_path: bool = True,
        staging_depth: int = 2,
        wire: str = "ragged",
    ):
        if n_sensors < 1:
            raise ValueError(f"n_sensors must be >= 1, got {n_sensors}")
        if wire not in ("dense", "ragged"):
            raise ValueError(f"unknown wire mode: {wire!r}")
        self.config = config
        self.n_sensors = n_sensors
        self.with_tracking = with_tracking
        self.mesh = mesh
        self.uniform_fast_path = uniform_fast_path
        self.wire = wire
        self.wire_stats = WireStats()
        self._step = make_fleet_fn(config, with_tracking)
        self._wire = (
            make_wire_fn(config.batcher.capacity, config.use_kernels)
            if wire == "ragged"
            else None
        )
        self._tag_limit = tag_limit(config)
        self._staging = _StagingPool(staging_depth)
        self.state = self.init_state() if state is None else state
        if state is not None and state.n_sensors != n_sensors:
            raise ValueError(
                f"state has {state.n_sensors} sensors, pipeline expects {n_sensors}"
            )
        self._wire_staging = _pinned_host_sharding(self.state.atlas)

    def init_state(self) -> FleetState:
        s = self.n_sensors
        atlas = jnp.zeros((s,) + atlas_shape(self.config), jnp.int32)
        tracks = jax.tree.map(
            lambda a: jnp.zeros((s,) + a.shape, a.dtype),
            init_tracks(self.config.tracker),
        )
        atlas, tracks = shard_fleet_carry((atlas, tracks), self.mesh)
        return FleetState(
            cursors=[SensorCursor(pending=_EMPTY_CHUNK) for _ in range(s)],
            atlas=atlas,
            tracks=tracks,
        )

    def _mesh_ctx(self):
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def feed(self, chunks, final=False) -> FleetResult:
        """Ingest one chunk per sensor; process every closed window in one
        vmapped step. ``chunks[s]`` is ``(x, y, t, p)`` or ``None``.

        ``final`` may be a bool (flush every sensor's trailing partial
        window, as :meth:`flush` does) or a per-sensor boolean mask —
        masked slots are force-closed this feed (sensor detach) while
        the rest keep batching normally.
        """
        return self._ingest(chunks, final=final).result()

    def feed_async(self, chunks, final=False) -> PendingRound:
        """:meth:`feed`, as an explicit pipelined round: the jitted step
        is dispatched without synchronizing and a :class:`PendingRound`
        handle is returned. Validation errors still raise here, at the
        dispatch boundary, before any state mutation — a raised feed
        leaves the fleet untouched and re-feedable, exactly like the
        synchronous path. Rounds complete in dispatch order (one device
        stream), so interleaving ``feed_async`` with ``reset_slots`` /
        ``grow`` / ``shrink`` is safe: an earlier round's outputs are
        never perturbed by later carry surgery (outputs are not donated).
        """
        return self._ingest(chunks, final=final)

    def flush(self) -> FleetResult:
        """Force-close every sensor's trailing partial window."""
        return self._ingest([None] * self.n_sensors, final=True).result()

    def flush_slots(self, slots) -> FleetResult:
        """Force-close the trailing partial window of ``slots`` only."""
        final = np.zeros(self.n_sensors, bool)
        final[list(slots)] = True
        return self._ingest([None] * self.n_sensors, final=final).result()

    def reset_slots(self, slots) -> None:
        """Zero the named slots' carries (cursor + atlas slice + tracker
        slice) so they can be recycled by new sensors.

        An all-zero slot carry is exactly the fresh-stream initial state
        (``init_tracks`` is all zeros; a zero atlas is all-stale), so a
        recycled slot behaves bit-identically to a brand-new
        :class:`~repro.core.pipeline.stream.StreamingPipeline`. Any
        unflushed remainder on the slot is dropped — flush first
        (:meth:`flush_slots`) if the trailing window matters.
        """
        slots = list(slots)
        if not slots:
            return
        mask = np.zeros(self.n_sensors, bool)
        mask[slots] = True  # IndexError on out-of-range slots, pre-mutation
        st = self.state
        for s in np.flatnonzero(mask):
            st.cursors[s] = SensorCursor(pending=_EMPTY_CHUNK)
        with self._mesh_ctx():
            atlas, tracks = _zero_slots_fn()(st.atlas, st.tracks, jnp.asarray(mask))
        self.state = FleetState(cursors=st.cursors, atlas=atlas, tracks=tracks)

    def export_slot(self, slot: int) -> SlotCarry:
        """Copy one slot's complete carry out of the pool (host arrays).

        The returned :class:`SlotCarry` is self-contained: the host
        cursor (with its unwindowed remainder) plus host copies of the
        slot's atlas and tracker slices. Forces the slices to host, so
        it blocks until any round still computing this slot's carry has
        completed (rounds never run concurrently with carry surgery on
        the same buffers anyway — outputs are not donated). The slot
        itself is left untouched; callers recycling it afterwards use
        :meth:`reset_slots`, exactly like a detach.
        """
        if not 0 <= slot < self.n_sensors:
            raise IndexError(
                f"slot {slot} out of range for a {self.n_sensors}-slot pool"
            )
        st = self.state
        return SlotCarry(
            cursor=copy.copy(st.cursors[slot]),
            # Slicing materializes a fresh device buffer, so the host
            # copy can never alias a donated carry buffer.
            atlas=np.asarray(st.atlas[slot]),
            tracks=jax.tree.map(lambda a: np.asarray(a[slot]), st.tracks),
        )

    def import_slot(self, slot: int, carry: SlotCarry) -> None:
        """Install an exported carry into ``slot`` (cross-shard adopt).

        The target slot's previous carry is overwritten — callers hand
        in a free (reset) slot. Shapes are validated against this pool's
        config before any mutation, so a carry exported under a
        different :class:`PipelineConfig` is refused atomically. The
        new carry is written under the pool's mesh, so it lands sharded
        over the ``sensor`` axis like every other slot.
        """
        if not 0 <= slot < self.n_sensors:
            raise IndexError(
                f"slot {slot} out of range for a {self.n_sensors}-slot pool"
            )
        want = atlas_shape(self.config)
        if tuple(carry.atlas.shape) != want:
            raise ValueError(
                f"carry atlas shape {carry.atlas.shape} does not match this "
                f"pool's config ({want}); same PipelineConfig required"
            )
        st = self.state
        ref = jax.tree.map(lambda a: a.shape[1:], st.tracks)
        got = jax.tree.map(lambda a: tuple(a.shape), carry.tracks)
        if jax.tree.leaves(ref) != jax.tree.leaves(got):
            raise ValueError(
                f"carry tracker shapes {jax.tree.leaves(got)} do not match "
                f"this pool's ({jax.tree.leaves(ref)})"
            )
        st.cursors[slot] = copy.copy(carry.cursor)
        with self._mesh_ctx():
            atlas, tracks = _set_slot_fn()(
                st.atlas,
                st.tracks,
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(carry.atlas),
                jax.tree.map(jnp.asarray, carry.tracks),
            )
        self.state = FleetState(cursors=st.cursors, atlas=atlas, tracks=tracks)

    def grow(self, new_capacity: int) -> None:
        """Promote the pool to ``new_capacity`` slots, migrating the carry.

        Existing slots keep their state verbatim (zero-padding along the
        leading sensor dim cannot perturb them — the step is vmapped, so
        sensors never mix); new slots arrive zeroed, i.e. free. The
        carry is re-placed under the mesh so slot-pool carries keep
        sharding over the ``sensor`` axis after promotion. Compiles
        nothing by itself; the next feed compiles the step at the new
        capacity (once per capacity, the tier-promotion budget).
        """
        if new_capacity < self.n_sensors:
            raise ValueError(
                f"cannot shrink pool from {self.n_sensors} to {new_capacity} "
                "slots; detach sensors instead"
            )
        if new_capacity == self.n_sensors:
            return
        st = self.state
        atlas, tracks = grow_fleet_carry(
            (st.atlas, st.tracks), new_capacity, self.mesh
        )
        cursors = st.cursors + [
            SensorCursor(pending=_EMPTY_CHUNK)
            for _ in range(new_capacity - len(st.cursors))
        ]
        self.n_sensors = new_capacity
        self.state = FleetState(cursors=cursors, atlas=atlas, tracks=tracks)

    def shrink(self, new_capacity: int, occupied=()) -> None:
        """Demote the pool to ``new_capacity`` slots, migrating the carry.

        The inverse of :meth:`grow`, for reclaiming capacity after
        evictions: the dropped tail slots must all be free (every slot in
        ``occupied`` must be ``< new_capacity``), so surviving slots keep
        their state verbatim — slicing the leading sensor dim cannot
        perturb them, exactly as zero-padding cannot in :meth:`grow`.
        Any unflushed remainder on a dropped slot is discarded (callers
        flush or reset departing slots first). Compiles nothing by
        itself; the next feed compiles the step at the new capacity,
        which is a shape already warmed if this tier was visited on the
        way up.
        """
        if new_capacity < 1:
            raise ValueError(f"need at least one slot, got {new_capacity}")
        if new_capacity > self.n_sensors:
            raise ValueError(
                f"cannot shrink pool from {self.n_sensors} to {new_capacity} "
                "slots; use grow"
            )
        high = [s for s in occupied if s >= new_capacity]
        if high:
            raise ValueError(
                f"occupied slots {sorted(high)} do not fit a "
                f"{new_capacity}-slot pool; migrate or evict them first"
            )
        if new_capacity == self.n_sensors:
            return
        st = self.state
        atlas, tracks = shrink_fleet_carry(
            (st.atlas, st.tracks), new_capacity, self.mesh
        )
        self.n_sensors = new_capacity
        self.state = FleetState(
            cursors=st.cursors[:new_capacity], atlas=atlas, tracks=tracks
        )

    def _ingest(self, chunks, final) -> PendingRound:
        st = self.state
        s_count = st.n_sensors
        if len(chunks) != s_count:
            raise ValueError(
                f"feed expects {s_count} per-sensor chunks, got {len(chunks)}"
            )
        if isinstance(final, bool):
            final = np.full(s_count, final, bool)
        else:
            final = np.asarray(final, bool)
            if final.shape != (s_count,):
                raise ValueError(
                    f"final mask must have shape ({s_count},), got {final.shape}"
                )
        batcher = self.config.batcher
        # The round this feed dispatches, on every span it records; host
        # spans split ``feed``'s time into windowing, waiting for a staging
        # set, packing, staging and dispatch (a compile lands in dispatch).
        rnd = self.wire_stats.rounds
        merged_all, bounds_all, consumed_all = [], [], []
        # Phase A (fallible): validate + window every sensor BEFORE any
        # state mutation, so a bad chunk rejects the whole feed atomically.
        with TraceAnnotation("fleet.window", round=rnd):
            for s, (cur, chunk) in enumerate(zip(st.cursors, chunks)):
                x, y, t, p = _EMPTY_CHUNK if chunk is None else chunk
                merged = monotone_merge(
                    cur.pending, x, y, t, p, cur.last_t, label=f"sensor {s}"
                )
                if final[s]:
                    bounds = dual_threshold_bounds(merged[2], batcher)
                    consumed = len(merged[2])
                else:
                    bounds, consumed = dual_threshold_closed_bounds(merged[2], batcher)
                merged_all.append(merged)
                bounds_all.append(bounds)
                consumed_all.append(consumed)
        n_valid = np.asarray([len(b) for b in bounds_all], np.int32)
        w_max = int(n_valid.max())
        if w_max > self._tag_limit:
            raise ValueError(
                f"feed closed {w_max} windows on one sensor, more than one "
                f"tag epoch ({self._tag_limit}) can address; split the feed"
            )

        # Phase B (infallible): pack all sensors into one (4, S, W_max,
        # cap) x/y/t/p block (single host->device transfer), resolve
        # tags/rollover, commit cursors. The block lives in a reusable
        # staging set (acquire blocks iff the set's previous borrower is
        # still executing — the pipelined-depth backpressure point), so
        # the steady state allocates nothing per round.
        cap = batcher.capacity
        ragged = self.wire == "ragged"
        staging = (
            self._staging.acquire(
                s_count, w_max, cap, wire=self.wire, round_ix=rnd
            )
            if w_max
            else None
        )
        if staging is None or ragged:
            bx = by = bt = bp = bv = None
        else:
            bx, by, bt, bp = staging.packed
            bv = staging.valid
        wire_base = 0  # running write cursor into the shared wire lanes
        spill_blocks: list[np.ndarray] = []
        events_total = 0
        tag0 = np.zeros(s_count, np.int32)
        reset = np.zeros(s_count, bool)
        windows_list: list[WindowedEvents] = []
        with TraceAnnotation("fleet.pack", round=rnd):
            for s, (cur, merged, bounds, consumed) in enumerate(
                zip(st.cursors, merged_all, bounds_all, consumed_all)
            ):
                mt = merged[2]
                bounds3 = [(a, b, int(mt[a])) for a, b in bounds]
                if staging is None:
                    starts = stops = t_start = np.zeros(0, np.int64)
                    overflow = np.zeros(0, np.int64)
                    zeros = np.zeros((0, cap), np.int32)
                    row = EventBatch(
                        zeros, zeros, zeros, zeros, np.zeros((0, cap), bool)
                    )
                elif ragged:
                    starts, stops, t_start, overflow, wire_base, entries = (
                        pack_bounds_into(
                            *merged, bounds3,
                            out=(staging.words, staging.dt, staging.pbits,
                                 staging.offsets[s]),
                            layout="ragged", base=wire_base, capacity=cap,
                        )
                    )
                    if entries.shape[1]:
                        spill_blocks.append(entries)
                    n = len(bounds)
                    # Bookkeeping rows are fresh dense planes (the ragged
                    # wire has no per-window rows to copy out): same packer,
                    # same bits, and like the dense path's copies they stay
                    # stable for the round's lifetime.
                    rx = np.zeros((n, cap), np.int32)
                    ry = np.zeros((n, cap), np.int32)
                    rt = np.zeros((n, cap), np.int32)
                    rp = np.zeros((n, cap), np.int32)
                    rv = np.zeros((n, cap), bool)
                    if n:
                        pack_bounds_into(*merged, bounds3, rx, ry, rt, rp, rv)
                    row = EventBatch(rx, ry, rt, rp, rv)
                else:
                    starts, stops, t_start, overflow = pack_bounds_into(
                        *merged, bounds3, out=(bx[s], by[s], bt[s], bp[s], bv[s])
                    )
                    n = len(bounds)
                    # Per-sensor bookkeeping rows are COPIES of the packed
                    # rows, not views: the staging planes are refilled two
                    # rounds later, but the WindowedEvents a caller holds
                    # must stay stable for the round's lifetime.
                    row = EventBatch(
                        bx[s, :n].copy(), by[s, :n].copy(), bt[s, :n].copy(),
                        bp[s, :n].copy(), bv[s, :n].copy(),
                    )
                events_total += int(np.minimum(stops - starts, cap).sum())
                n = len(bounds)
                base = cur.events_consumed
                windows_list.append(
                    WindowedEvents(
                        row, t_start, starts + base, stops + base, overflow
                    )
                )
                t0 = cur.next_tag
                if t0 + w_max > self._tag_limit:  # tag epoch rollover
                    reset[s], t0 = True, 0
                tag0[s] = t0
                cur.pending = tuple(a[consumed:] for a in merged)
                cur.events_consumed = base + consumed
                cur.next_tag = t0 + n
                cur.last_t = int(mt[-1]) if len(mt) else cur.last_t

        if w_max == 0:
            return PendingRound(FleetResult(
                n_windows=n_valid,
                windows=windows_list,
                clusters=None, metrics=None, tracks=None, final_tracks=None,
                _config=self.config,
                _with_tracking=self.with_tracking,
                _carry_tracks=st.tracks,
            ))

        with TraceAnnotation("fleet.stage", round=rnd):
            staging.meta[0] = tag0
            staging.meta[1] = n_valid
            if ragged:
                n_pad = wire_pad(wire_base)
                m = 0
                if spill_blocks:
                    entries = np.concatenate(spill_blocks, axis=1)
                    m = entries.shape[1]
                m_pad = spill_pad(m)
                staging.reserve_spill(m_pad)
                # Re-sentinel the whole view EVERY round: a stale spill entry
                # from a previous borrower points at live wire positions and
                # would overwrite real events in the decoder's scatter.
                staging.spill[:, :m_pad] = SPILL_SENTINEL
                if m:
                    staging.spill[:, :m] = entries
                    self.wire_stats.spilled += m
                if wire_base:
                    packed_bits = np.packbits(
                        staging.pbits[:wire_base], bitorder="little"
                    )
                    staging.pol.view(np.uint8)[: len(packed_bits)] = packed_bits
                views = _stage_wire((
                    staging.words[:n_pad], staging.dt[:n_pad],
                    staging.pol[: n_pad // 32], staging.offsets,
                    staging.spill[:, :m_pad],
                ), self._wire_staging)
                wire_b = ragged_wire_bytes(n_pad, s_count, w_max, m_pad)
            else:
                wire_b = dense_wire_bytes(s_count, w_max, cap)
        with TraceAnnotation("fleet.dispatch", round=rnd), self._mesh_ctx():
            atlas_in = st.atlas
            if reset.any():  # rare: tag-epoch rollover on some sensor(s)
                atlas_in = _zero_sensors_fn()(atlas_in, jnp.asarray(reset))
            if ragged:
                packed_in, valid_in = self._wire(*views)
            else:
                packed_in, valid_in = staging.packed, bv
            final_tracks, clusters, mets, states, atlas = self._step(
                packed_in, valid_in, st.tracks, atlas_in, staging.meta,
                self.uniform_fast_path and bool((n_valid == w_max).all()),
            )
        self.wire_stats.rounds += 1
        self.wire_stats.events += events_total
        self.wire_stats.wire_bytes += wire_b
        self.wire_stats.dense_bytes += dense_wire_bytes(s_count, w_max, cap)
        self.state = FleetState(
            cursors=st.cursors, atlas=atlas, tracks=final_tracks
        )
        pending = PendingRound(FleetResult(
            n_windows=n_valid,
            windows=windows_list,
            clusters=clusters,
            metrics=mets,
            tracks=states if self.with_tracking else None,
            final_tracks=final_tracks,
            _config=self.config,
            _with_tracking=self.with_tracking,
            _carry_tracks=final_tracks,
            _stats=self.wire_stats,
            _round=rnd,
        ))
        staging.inflight = pending
        return pending
