"""Where the host's time went in the measured window.

``Watch`` takes, around the window, the CPU time of the loop's thread and
of the process (``getrusage``) and the generation-2 collections (a
``gc.callbacks`` hook). ``steps_ms`` gives the loop's steps per round and
``slices`` the events completed in each slice of the window. ``run_cell``
prints them as one ``# host:`` log line; no metric reads it.
"""
from __future__ import annotations

import gc
import resource
import time

import numpy as np

clock = time.perf_counter
SLICE_S = 5.0
_CPU = ("ru_utime", "ru_stime")


class Watch:
    """CPU time of the calling thread and of the process, and the
    generation-2 collections, from ``__enter__`` to ``__exit__``."""

    def __enter__(self) -> "Watch":
        self.gc2_n, self.gc2_s, self._gc_t = 0, 0.0, None
        gc.callbacks.append(self._on_gc)
        self.thread0 = resource.getrusage(resource.RUSAGE_THREAD)
        self.self0 = resource.getrusage(resource.RUSAGE_SELF)
        self.t0 = clock()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = clock()
        self.thread1 = resource.getrusage(resource.RUSAGE_THREAD)
        self.self1 = resource.getrusage(resource.RUSAGE_SELF)
        gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._gc_t = clock()
        elif self._gc_t is not None:
            self.gc2_n += 1
            self.gc2_s += clock() - self._gc_t
            self._gc_t = None

    def fields(self) -> dict:
        wall = self.t1 - self.t0
        d = {k[3:]: getattr(self.thread1, k) - getattr(self.thread0, k) for k in _CPU}
        proc_s = sum(getattr(self.self1, k) - getattr(self.self0, k) for k in _CPU)
        return {
            "wall_s": wall,
            "thread": dict(d, cpu_share=(d["utime"] + d["stime"]) / wall),
            "process_cpu_s": proc_s,
            "gc2": {"count": self.gc2_n, "ms": self.gc2_s * 1e3},
        }


def slices(rounds, t0: float, t_end: float) -> dict:
    """Events completed in each ``SLICE_S`` slice of the window from ``t0``
    to ``t_end``, by each round's ``host_time``; rounds finished in the
    drain after ``t_end`` count in the last slice, which runs to the last
    of them."""
    width = SLICE_S
    n = max(1, int(np.ceil((t_end - t0) / width - 1e-9)))
    events = np.zeros(n, np.int64)
    t_last = t_end
    for r in rounds:
        i = min(int((r.host_time - t0) // width), n - 1)
        events[max(i, 0)] += r.events
        t_last = max(t_last, r.host_time)
    ends = np.minimum(t0 + width * np.arange(1, n + 1), t_end)
    ends[-1] = t_last
    secs = np.diff(np.concatenate([[t0], ends]))
    return {
        "slice_s": width, "slice_events": events.tolist(),
        "slice_events_per_s": (events / np.maximum(secs, 1e-9)).tolist(),
    }


def steps_ms(steps: dict) -> dict:
    """Median and 90th percentile per round of each loop step, in ms."""
    return {
        name: [float(np.percentile(v, 50)) * 1e3, float(np.percentile(v, 90)) * 1e3]
        for name, v in steps.items() if len(v)
    }
