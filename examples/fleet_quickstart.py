"""Fleet quickstart: a multi-sensor constellation behind one jitted step.

Builds a scenario-diverse 4-sensor sky (a crossing pair, a GEO
slow-mover, a tumbling RSO, and a ballistic arc — each sensor with its
own pointing jitter), then streams all four through ONE
``FleetPipeline``: every ``feed`` takes one 20 ms chunk per sensor and
drives the whole fleet through a single vmapped/jitted step with
per-sensor carries (batcher remainder, tagged event atlas, tracker
state) riding along between rounds. Per-sensor results are bit-identical
to running four independent ``StreamingPipeline`` objects — the fleet
just pays one dispatch instead of four.

  PYTHONPATH=src python examples/fleet_quickstart.py
"""
import dataclasses
import time

import numpy as np

from repro.core.pipeline import FleetPipeline, PipelineConfig
from repro.core.tracking import confirmed
from repro.data.evas import iter_chunks
from repro.data.synthetic import SCENARIO_FAMILIES, make_fleet_recordings
from repro.launch.compile_cache import enable_compile_cache

CHUNK_US = 20_000  # feed 20 ms per sensor per round
FAMILIES = ("crossing", "geo_slow", "tumbling", "ballistic")


def main() -> None:
    enable_compile_cache()
    print(f"Generating a {len(FAMILIES)}-sensor scenario-diverse sky (2 s)...")
    recs = [
        dataclasses.replace(
            make_fleet_recordings(
                1, scenario=SCENARIO_FAMILIES[fam], seed0=31 * s, duration_s=2.0
            )[0],
            name=f"sensor{s}-{fam}",
        )
        for s, fam in enumerate(FAMILIES)
    ]
    for rec in recs:
        print(f"  {rec.name:<22} {len(rec):>7,} events")

    # Slice every sensor's stream into 20 ms rounds (None = exhausted).
    per_sensor = [list(iter_chunks(r, CHUNK_US)) for r in recs]
    n_rounds = max(len(c) for c in per_sensor)

    cfg = PipelineConfig()  # paper defaults: 16px cells, min_events=5
    fleet = FleetPipeline(cfg, n_sensors=len(recs), with_tracking=True)

    windows = 0
    detections = 0
    latencies = []
    for i in range(n_rounds):
        chunks = [c[i] if i < len(c) else None for c in per_sensor]
        t0 = time.perf_counter()
        out = fleet.feed(chunks)  # ONE step for the whole fleet
        n_det = (
            int(np.asarray(out.clusters.valid).sum())
            if out.clusters is not None else 0
        )
        latencies.append((time.perf_counter() - t0) * 1e3)
        windows += out.total_windows
        detections += n_det
    tail = fleet.flush()  # close every sensor's trailing window
    windows += tail.total_windows

    print(
        f"Processed {windows} windows across {len(recs)} sensors "
        f"in {len(latencies)} fleet feeds."
    )
    print(f"Clusters passing min_events=5: {detections}")
    lat = np.asarray(latencies[3:])  # skip jit warmup rounds
    print(
        f"Steady-state fleet feed latency: p50={np.percentile(lat, 50):.1f} ms "
        f"p99={np.percentile(lat, 99):.1f} ms (paper budget: 62 ms)"
    )

    final = fleet.state.tracks  # leaves (S, T): stacked per-sensor carries
    for s, rec in enumerate(recs):
        conf = np.asarray(confirmed(
            type(final)(*(np.asarray(leaf[s]) for leaf in final)), cfg.tracker
        ))
        ids = np.flatnonzero(conf)
        line = ", ".join(
            f"({float(final.x[s, i]):5.0f},{float(final.y[s, i]):5.0f}) "
            f"hits={int(final.hits[s, i])}"
            for i in ids
        ) or "none"
        print(f"  sensor {s} ({rec.name}): {len(ids)} confirmed tracks: {line}")


if __name__ == "__main__":
    main()
