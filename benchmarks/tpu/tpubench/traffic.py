"""The one traffic generator: station streams and their chunk schedule,
built from a traffic file's parameters and the run's seed.

Every station draws one tile of sky from a scenario family (stations cycle
``families``). When and how many events arrive is seeded by the station's
index alone, where they land by the run seed and the index: every seed
gives the same work (events per chunk, windows per round) in another
layout, so the spread between seeds is that of the system. The tile holds
``tile_s - gap_ms`` of events and its stream repeats it every ``tile_s``:
the quiet gap is longer than the time cut of a window, so the windowing of
every tile starts afresh at its first event and every round after the first
tile repeats the shapes of a round of the first tile. The stream is cut
into chunks of ``chunk_ms`` on a grid aligned with the tiles; chunk ``b``
covers sky time ``[b * chunk, (b + 1) * chunk)``.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

from tpubench import sky

TRAFFIC_DIR = Path(__file__).resolve().parent.parent / "traffic"


@dataclasses.dataclass(frozen=True)
class Traffic:
    name: str
    loop: str  # "open": a chunk per station on every beat; "closed": as fast as served
    chunk_ms: float
    stations: int
    families: tuple[str, ...]
    tile_s: float
    gap_ms: float
    check_stations: int  # stations the correctness check replays, drawn from the seed
    trace_s: float  # length of the window of a traced run

    @property
    def chunk_us(self) -> int:
        return int(round(self.chunk_ms * 1000))

    @property
    def tile_us(self) -> int:
        return int(round(self.tile_s * 1e6))

    @property
    def chunks_per_tile(self) -> int:
        return self.tile_us // self.chunk_us

    @property
    def warmup_rounds(self) -> int:
        """Rounds that visit every shape the stream ever produces: the
        first tile and the first chunk of the second."""
        return self.chunks_per_tile + 1


def load(name: str, directory: Path = TRAFFIC_DIR) -> Traffic:
    path = directory / f"{name}.json"
    raw = json.loads(path.read_text())
    t = Traffic(
        name=name,
        loop=raw["loop"],
        chunk_ms=float(raw["chunk_ms"]),
        stations=int(raw["stations"]),
        families=tuple(raw["families"]),
        tile_s=float(raw["tile_s"]),
        gap_ms=float(raw["gap_ms"]),
        check_stations=int(raw["check_stations"]),
        trace_s=float(raw["trace_s"]),
    )
    if t.loop not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed', got {t.loop!r}")
    if t.tile_us % t.chunk_us:
        raise ValueError(f"{path}: tile_s must be a whole number of chunks")
    unknown = [f for f in t.families if f not in sky.FAMILIES]
    if unknown:
        raise ValueError(f"{path}: unknown families {unknown}")
    if not 1 <= t.check_stations <= t.stations:
        raise ValueError(f"{path}: check_stations must lie in [1, stations]")
    return t


ARRIVALS_SEED = 20260420  # the arrivals of station i: [ARRIVALS_SEED, i]


def station_seed(seed: int, index: int) -> np.random.SeedSequence:
    """Seed of station ``index`` under run seed ``seed`` (any integer)."""
    return np.random.SeedSequence([int(seed) % (1 << 64), index])


class StationStream:
    """One station's endless tiled event stream, served chunk by chunk."""

    def __init__(self, traffic: Traffic, family: str, seed, arrivals, width: int,
                 height: int):
        scen = dataclasses.replace(
            sky.FAMILIES[family],
            duration_s=traffic.tile_s - traffic.gap_ms / 1000.0,
        )
        self.family = family
        self.x, self.y, self.t, self.p = sky.make_events(
            scen, seed, width, height, arrivals_seed=arrivals
        )
        self.tile_us = traffic.tile_us
        self.n_tile = len(self.t)
        edges = np.arange(traffic.chunks_per_tile + 1) * traffic.chunk_us
        self.edges = np.searchsorted(self.t, edges, side="left")
        self.per_tile = traffic.chunks_per_tile

    def chunk(self, b: int):
        """Events of chunk ``b`` as ``(x, y, t, p)`` int64 arrays."""
        k, j = divmod(b, self.per_tile)
        a, e = self.edges[j], self.edges[j + 1]
        t = self.t[a:e] + k * self.tile_us if k else self.t[a:e]
        return self.x[a:e], self.y[a:e], t, self.p[a:e]

    def events_before(self, b: int) -> int:
        """Stream index of the first event of chunk ``b``."""
        k, j = divmod(b, self.per_tile)
        return k * self.n_tile + int(self.edges[j])

    def prefix(self, n: int):
        """The first ``n`` events of the stream as ``(x, y, t, p)``."""
        k = -(-n // self.n_tile) if n else 0
        tile = np.arange(k)
        t = (self.t[None, :] + tile[:, None] * self.tile_us).reshape(-1)[:n]
        rep = lambda a: np.tile(a, k)[:n]  # noqa: E731
        return rep(self.x), rep(self.y), t, rep(self.p)

    def time_of(self, index: np.ndarray) -> np.ndarray:
        """Sky time (us) of stream events by index."""
        k, j = np.divmod(np.asarray(index, np.int64), self.n_tile)
        return self.t[j] + k * self.tile_us


def make_streams(traffic: Traffic, seed: int, width: int, height: int) -> list[StationStream]:
    fams = traffic.families
    return [
        StationStream(traffic, fams[i % len(fams)], station_seed(seed, i),
                      np.random.SeedSequence([ARRIVALS_SEED, i]), width, height)
        for i in range(traffic.stations)
    ]


def check_sample(traffic: Traffic, seed: int) -> list[int]:
    """Stations whose outputs the correctness check replays, from the seed."""
    rng = np.random.default_rng(station_seed(seed, -1 % (1 << 32)))
    return sorted(
        int(i) for i in rng.choice(traffic.stations, traffic.check_stations, replace=False)
    )
