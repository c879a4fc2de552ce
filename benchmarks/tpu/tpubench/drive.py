"""The client loops that drive the service, and what they record.

``open`` traffic (live cadence): every beat of ``chunk_ms``, each station
delivers the chunk of sky the beat covers, whether or not the service has
kept up, and one forced ``pump`` follows. Between beats the loop polls the
rounds in flight and reads every station's result of each finished round.
``closed`` traffic (backlog): the loop feeds each station's next chunk and
pumps as fast as the service takes them, keeping the service's in-flight
depth of rounds.

Each step of the loop is a ``TraceAnnotation`` span (``trace.SPANS``), so a
profiled run sees the host's work on the device trace's clock.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
from jax.profiler import TraceAnnotation

clock = time.perf_counter


@dataclasses.dataclass
class Round:
    index: int  # chunk index b the round carries
    feeds: list  # ServedFeed per station that had queued events
    handle: object  # the service's PendingRound
    host_time: float = float("nan")  # when every result was on the host
    windows: int = 0
    events: int = 0
    shape: tuple[int, int] = (0, 0)  # (slots, windows per slot) of the step


@dataclasses.dataclass
class Record:
    rounds: list = dataclasses.field(default_factory=list)
    lateness: list = dataclasses.field(default_factory=list)  # beat start - due, s
    # Latency samples (open loop): per collected feed, station, stops, stamp.
    closes: list = dataclasses.field(default_factory=list)
    results: dict = dataclasses.field(default_factory=dict)  # station -> [ScanResult]
    t0: float = 0.0  # window start
    t_end: float = 0.0  # window end (no beat issued after)
    t_done: float = 0.0  # last result of the window on the host
    first_chunk: int = 0  # chunk index of the window's first beat
    next_chunk: int = 0  # first chunk not fed
    # Host seconds of each loop step, per round, in the window and its drain.
    steps: dict = dataclasses.field(
        default_factory=lambda: {k: [] for k in ("generator", "feed", "pump", "result")})


class Driver:
    """Feeds ``streams`` (attached as ``sids``) through ``svc``."""

    def __init__(self, svc, sids, streams, traffic, keep: set[int]):
        self.svc = svc
        self.sids = sids
        self.streams = streams
        self.traffic = traffic
        self.station_of = {sid: i for i, sid in enumerate(sids)}
        self.keep = keep  # stations whose results the check replays
        self.depth = svc.max_inflight_rounds
        self.pending: list[Round] = []
        self.rec = Record(results={i: [] for i in sorted(keep)})
        self.timed = False  # record latency samples for collected rounds

    # --- one round ------------------------------------------------------
    def _round(self, b: int) -> None:
        t0 = clock()
        with TraceAnnotation("generator"):
            chunks = [s.chunk(b) for s in self.streams]
        t1 = clock()
        with TraceAnnotation("feed"):
            for sid, ch in zip(self.sids, chunks):
                self.svc.feed(sid, *ch)
        t2 = clock()
        with TraceAnnotation("pump", round=b):
            feeds = self.svc.pump(force=True)
        t3 = clock()
        if self.timed:
            steps = self.rec.steps
            steps["generator"].append(t1 - t0)
            steps["feed"].append(t2 - t1)
            steps["pump"].append(t3 - t2)
        if feeds:
            rnd = Round(b, feeds, self.svc.last_round)
            nw = rnd.handle.n_windows
            rnd.shape = (len(nw), int(nw.max()))
            rnd.windows = int(nw.sum())
            self.pending.append(rnd)
            self.rec.rounds.append(rnd)
        self.rec.next_chunk = b + 1

    def _collect(self, block: bool) -> bool:
        """Bring the oldest round's results onto the host if it is done
        (or wait for it when ``block``); True if one was collected."""
        if not self.pending:
            return False
        rnd = self.pending[0]
        if not block and not rnd.handle.ready():
            return False
        t0 = clock()
        with TraceAnnotation("result", round=rnd.index):
            results = [fd.result for fd in rnd.feeds]
            rnd.host_time = clock()
        if self.timed:
            self.rec.steps["result"].append(rnd.host_time - t0)
        self.pending.pop(0)
        events = 0
        for fd, res in zip(rnd.feeds, results):
            st = self.station_of[fd.sid]
            stops = res.windows.stops
            if len(stops):
                events += int(stops[-1] - res.windows.starts[0])
                if self.timed:
                    self.rec.closes.append((st, stops, rnd.host_time))
            if st in self.keep:
                self.rec.results[st].append(res)
        rnd.events = events
        rnd.feeds = rnd.handle = None  # the round's device buffers go now
        return True

    def _bound_pending(self) -> None:
        while len(self.pending) > self.depth:
            self._collect(block=True)

    # --- phases ---------------------------------------------------------
    def warmup(self, rounds: int) -> None:
        """Unpaced rounds over the first chunks: every shape compiles here."""
        for b in range(rounds):
            self._round(b)
            while self._collect(block=False):
                pass
            self._bound_pending()
        self.drain()

    def drain(self) -> None:
        with TraceAnnotation("drain"):
            while self.pending:
                self._collect(block=True)

    def window(self, seconds: float) -> Record:
        rec = self.rec
        self.timed = True
        rec.first_chunk = b = rec.next_chunk
        chunk_s = self.traffic.chunk_us / 1e6
        rec.t0 = t0 = clock()
        rec.t_end = t_end = t0 + seconds
        if self.traffic.loop == "open":
            while True:
                due = t0 + (b - rec.first_chunk + 1) * chunk_s
                if due > t_end:
                    break
                with TraceAnnotation("wait_beat"):
                    while clock() < due:
                        if not self._collect(block=False):
                            time.sleep(min(max(due - clock(), 0.0), 2e-4))
                start = clock()
                if start > t_end:
                    break
                rec.lateness.append(start - due)
                self._round(b)
                self._bound_pending()
                b += 1
        else:
            while clock() < t_end:
                self._round(b)
                while self._collect(block=False):
                    pass
                self._bound_pending()
                b += 1
        self.drain()
        rec.t_done = clock()
        self.timed = False
        return rec


def latencies_ms(rec: Record, streams, chunk_us: int) -> np.ndarray:
    """Per closed window: from the wall-clock creation of its last event
    (sky time mapped through the beat schedule) to its result on the host.
    Windows whose last event predates the window's first beat are left out:
    their sky was generated unpaced during warm-up."""
    sky0 = rec.first_chunk * chunk_us
    out = []
    for st, stops, host in rec.closes:
        t_last = streams[st].time_of(np.asarray(stops) - 1)
        t_last = t_last[t_last >= sky0]
        out.append(host - (rec.t0 + (t_last - sky0) * 1e-6))
    return np.concatenate(out) * 1e3 if out else np.zeros(0)
