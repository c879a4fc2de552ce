"""Profiler capture and its reduction to a digest the metric readers use.

The host spans are the benchmark's own ``TraceAnnotation``s (``SPANS``), which
set the traced window, and the program's own inside them (``PROGRAM_SPANS``),
on the profiler's clock like the device events. A digest holds, within the
traced window: the host spans, the device's programs (``XLA Modules``) and
its operations (``XLA Ops``), each as (name, start, end) in seconds from the
start of the capture. The same digest, saved as JSON, is the fixture the
tests reduce.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import re
import shutil
from pathlib import Path

import numpy as np

SPANS = ("generator", "feed", "pump", "result", "wait_beat", "drain")
# The service's and the fleet driver's spans, one set per round.
PROGRAM_SPANS = (
    "service.take", "service.retire", "fleet.window", "fleet.staging_wait", "fleet.pack",
    "fleet.stage", "fleet.dispatch", "fleet.copy_wait", "fleet.copy_back",
)
_SHAPE = re.compile(r"^\S+ = \(?[a-z0-9]+\[([0-9,]*)\]")


@dataclasses.dataclass
class Digest:
    window: tuple[float, float]  # traced window, seconds
    spans: list  # [name, start, end, round or -1]
    modules: list  # [name, start, end] device programs
    op_names: list  # distinct operation texts (first line of the HLO)
    ops: np.ndarray  # (N, 3): name index, start, end

    def to_json(self) -> dict:
        return {
            "window": list(self.window), "spans": self.spans,
            "modules": self.modules, "op_names": self.op_names,
            "ops": self.ops.tolist(),
        }

    @classmethod
    def from_json(cls, raw: dict) -> "Digest":
        ops = np.asarray(raw["ops"], np.float64).reshape(-1, 3)
        return cls(tuple(raw["window"]), raw["spans"], raw["modules"],
                   raw["op_names"], ops)

    def save(self, path: Path) -> None:
        with gzip.open(path, "wt") as f:
            json.dump(self.to_json(), f)

    @classmethod
    def load(cls, path: Path) -> "Digest":
        with gzip.open(path, "rt") as f:
            return cls.from_json(json.load(f))


def start(directory: Path) -> None:
    import jax

    shutil.rmtree(directory, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # Python calls would swamp the host spans
    jax.profiler.start_trace(str(directory), profiler_options=opts)


def stop(directory: Path) -> Digest:
    """Stop the capture and reduce it to the window from the first host
    span to the end of the last."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.stop_trace()
    files = sorted(directory.glob("**/*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {directory}")
    data = ProfileData.from_file(str(files[-1]))
    try:
        return _reduce(data)
    finally:
        shutil.rmtree(directory, ignore_errors=True)


def _reduce(data) -> Digest:
    spans, modules = [], []
    names: dict[str, int] = {}
    ops: list[tuple[int, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS or ev.name in PROGRAM_SPANS:
                        rnd = -1
                        for key, val in ev.stats:
                            if key == "round":
                                rnd = int(val)
                        t0 = ev.start_ns * 1e-9
                        spans.append([ev.name, t0, t0 + ev.duration_ns * 1e-9, rnd])
        elif plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        t0 = ev.start_ns * 1e-9
                        modules.append(
                            [ev.name.split("(")[0], t0, t0 + ev.duration_ns * 1e-9]
                        )
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        text = ev.name.split("\n", 1)[0]
                        ix = names.setdefault(text, len(names))
                        t0 = ev.start_ns * 1e-9
                        ops.append((ix, t0, t0 + ev.duration_ns * 1e-9))
    spans.sort(key=lambda s: s[1])
    loop = [s for s in spans if s[0] in SPANS]
    lo, hi = (loop[0][1], max(s[2] for s in loop)) if loop else (0.0, 0.0)
    spans = [s for s in spans if s[2] > lo and s[1] < hi]
    modules = [m for m in modules if m[2] > lo and m[1] < hi]
    arr = np.asarray(ops, np.float64).reshape(-1, 3)
    arr = arr[(arr[:, 2] > lo) & (arr[:, 1] < hi)]
    used = np.unique(arr[:, 0].astype(np.int64))
    order = sorted(names, key=names.get)
    remap = np.full(len(order), -1, np.int64)
    remap[used] = np.arange(len(used))
    if len(arr):
        arr[:, 0] = remap[arr[:, 0].astype(np.int64)]
    return Digest((lo, hi), spans, modules, [order[i] for i in used], arr)


def merge(intervals) -> np.ndarray:
    """Union of (start, end) intervals as sorted disjoint rows."""
    a = np.asarray(intervals, np.float64).reshape(-1, 2)
    if not len(a):
        return a
    a = a[np.argsort(a[:, 0])]
    out = [list(a[0])]
    for s, e in a[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    a = np.clip(np.asarray(intervals, np.float64).reshape(-1, 2), lo, hi)
    return a[a[:, 1] > a[:, 0]]


def length(intervals: np.ndarray) -> float:
    a = np.asarray(intervals).reshape(-1, 2)
    return float((a[:, 1] - a[:, 0]).sum())


def intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersection of two disjoint sorted interval sets."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            out.append((lo, hi))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return np.asarray(out, np.float64).reshape(-1, 2)


def complement(a: np.ndarray, lo: float, hi: float) -> np.ndarray:
    out, cur = [], lo
    for s, e in clip(a, lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return np.asarray(out, np.float64).reshape(-1, 2)


def op_shape(text: str) -> tuple[int, ...] | None:
    """Dimensions of an operation's (first) result, from its HLO text."""
    m = _SHAPE.match(text)
    if not m or not m.group(1):
        return None
    return tuple(int(d) for d in m.group(1).split(","))
