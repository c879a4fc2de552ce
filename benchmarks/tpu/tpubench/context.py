"""What a per-layer metric reader sees, and how readers are found.

A reader is ``metrics/<metric name>.py`` with ``read(ctx) -> float | None``;
``None`` means the run had nothing to read and the metric is left out of the
result line. A kernel's operations and bytes come from
``costs/<kernel>.py`` (``cost(shape, cfg) -> (flops, bytes)``); the chip's
peaks from ``peaks.json``, keyed by ``device_kind``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import numpy as np

from tpubench import trace as T

HERE = Path(__file__).resolve().parent.parent
METRICS_DIR = HERE / "metrics"
COSTS_DIR = HERE / "costs"
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def _load_module(path: Path, tag: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {tag} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"tpubench_{tag}_" + path.stem.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, directory: Path = METRICS_DIR):
    """The ``read`` function of metric ``name``."""
    return _load_module(directory / f"{name}.py", "metric").read


def kernel_cost(kernel: str, directory: Path = COSTS_DIR):
    """The ``cost(shape, cfg)`` function of kernel ``kernel``."""
    return _load_module(directory / f"{kernel}.py", "cost").cost


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """Published peaks of ``device_kind``; an unknown chip is an error."""
    table = json.loads(path.read_text())["chips"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


class Context:
    """A traced run as its metric readers see it."""

    def __init__(self, digest: T.Digest, cfg: dict, traffic, programs: dict,
                 device_kind: str):
        self.digest = digest
        self.cfg = cfg
        self.traffic = traffic
        self.programs = programs  # {"step": "jit_step", "decode": "jit_decode"}
        self.device_kind = device_kind
        self.lo, self.hi = digest.window
        ops = digest.ops
        self.busy = T.clip(T.merge(ops[:, 1:3]), self.lo, self.hi)

    # --- host spans -----------------------------------------------------
    def spans(self, name: str) -> list:
        return [s for s in self.digest.spans if s[0] == name]

    @property
    def n_rounds(self) -> int:
        """Rounds dispatched in the traced window (one ``pump`` span each)."""
        return len(self.spans("pump"))

    def per_round_ms(self, seconds: float) -> float | None:
        return seconds * 1e3 / self.n_rounds if self.n_rounds else None

    def span_s(self, *names: str, outside: str | None = None) -> float:
        """Seconds of the window under any span of ``names``, less what lies
        under the spans named ``outside``."""
        rows = T.clip(T.merge([s[1:3] for n in names for s in self.spans(n)]), self.lo, self.hi)
        if outside is not None:
            rows = T.intersect(rows, T.complement(
                T.merge([s[1:3] for s in self.spans(outside)]), self.lo, self.hi))
        return T.length(rows)

    def has_spans(self, *names: str) -> bool:
        return any(s[0] in names for s in self.digest.spans)

    def round_intervals(self) -> np.ndarray:
        """Union over rounds of [start of its pump, end of its result]."""
        start = {s[3]: s[1] for s in self.spans("pump")}
        end = {s[3]: s[2] for s in self.spans("result")}
        rows = [(start[r], end[r]) for r in start if r in end]
        return T.clip(T.merge(rows), self.lo, self.hi)

    # --- device ---------------------------------------------------------
    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    @property
    def busy_s(self) -> float:
        return T.length(self.busy)

    def idle_share(self, intervals: np.ndarray | None = None) -> float | None:
        """Share of ``intervals`` (default: the window) in which no device
        operation ran, in %."""
        if intervals is None:
            intervals = np.asarray([[self.lo, self.hi]])
        total = T.length(intervals)
        if total <= 0:
            return None
        return 100.0 * (1.0 - T.length(T.intersect(self.busy, intervals)) / total)

    def module_s(self, program: str) -> float:
        """Device seconds of the named program (``step``/``decode``)."""
        name = self.programs[program]
        rows = [m[1:3] for m in self.digest.modules if m[0] == name]
        return T.length(T.clip(np.asarray(rows).reshape(-1, 2), self.lo, self.hi))

    def ops_matching(self, needle: str) -> list[tuple[str, float, float]]:
        """Device operations whose HLO text contains ``needle``."""
        hits = {i for i, n in enumerate(self.digest.op_names) if needle in n}
        return [
            (self.digest.op_names[int(i)], s, e)
            for i, s, e in self.digest.ops if int(i) in hits
        ]

    def peaks(self) -> dict:
        return peaks(self.device_kind)

    def kernel_cost(self, kernel: str):
        return kernel_cost(kernel)
