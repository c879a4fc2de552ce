"""Serve detections: dynamic sensor sessions over the slot-pooled fleet.

A ground-station scenario: sensors come and go while the service keeps
one slot-pooled fleet step hot. Three sensors attach up front (the pool
opens at the 4-slot tier); mid-run two more stations join — the fifth
attach promotes the pool to the 8-slot tier with carry migration, live
sessions unaffected — and one of the originals drops out, its slot
zeroed and recycled. Chunks are micro-batched under the paper's
dual-threshold admission policy (20 ms / 250 events, Sec. III-A), so
however many sessions are live, each round costs ONE vmapped fleet
dispatch. Every session's outputs are bit-identical to a dedicated
single-sensor ``StreamingPipeline`` fed the same chunks.

  PYTHONPATH=src python examples/serve_detections.py
"""
import dataclasses

import numpy as np

from repro.core.pipeline import PipelineConfig
from repro.core.tracking import confirmed
from repro.data.evas import iter_chunks
from repro.data.synthetic import SCENARIO_FAMILIES, make_fleet_recordings
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import DetectionService

CHUNK_US = 20_000  # live cadence: one 20 ms chunk per sensor per round
FAMILIES = ("crossing", "geo_slow", "tumbling", "ballistic", "jitter")


def _recording(idx: int):
    fam = FAMILIES[idx % len(FAMILIES)]
    rec = make_fleet_recordings(
        1, scenario=SCENARIO_FAMILIES[fam], seed0=17 * idx, duration_s=1.5
    )[0]
    return dataclasses.replace(rec, name=f"station{idx}-{fam}")


def main() -> None:
    enable_compile_cache()
    config = PipelineConfig()  # paper defaults: 16px cells, 20 ms / 250 ev
    svc = DetectionService(config, tiers=(4, 8, 16))
    print(f"DetectionService up: tier capacity {svc.capacity} slots")

    feeds: dict[int, object] = {}  # sid -> chunk iterator (live cadence)
    recs: dict[int, object] = {}

    def join(idx: int) -> int:
        rec = _recording(idx)
        sid = svc.attach(rec.name)
        feeds[sid] = iter_chunks(rec, CHUNK_US)
        recs[sid] = rec
        print(
            f"  + {rec.name} attached as session {sid} "
            f"(slot {svc.session(sid).slot}, pool {svc.capacity} slots, "
            f"{len(rec):,} events)"
        )
        return sid

    first = [join(i) for i in range(3)]
    windows = dets = 0
    for rnd in range(110):
        if rnd == 25:  # two stations join mid-run -> tier promotion at #5
            join(3), join(4)
            print(f"    (pool promoted: capacity {svc.capacity}, "
                  f"promotions {svc.promotions})")
        if rnd == 40:  # one original drops out; its slot is recycled
            tail = svc.detach(first[0])
            windows += tail.num_windows
            st = svc.session(first[0]).stats
            print(
                f"  - session {first[0]} detached: {st.windows} windows, "
                f"p50 service latency {st.latency_percentile(50):.1f} ms"
            )
        for sid, chunks in list(feeds.items()):
            if svc.session(sid).state != "live":
                continue
            chunk = next(chunks, None)  # each session streams its own clock
            if chunk is not None:
                for fd in svc.feed(sid, *chunk):  # admission may fire
                    windows += fd.result.num_windows
                    dets += int(np.asarray(fd.result.clusters.valid).sum())
        for fd in svc.pump(force=True):  # drain the round deterministically
            windows += fd.result.num_windows
            dets += int(np.asarray(fd.result.clusters.valid).sum())

    print(f"\nProcessed {windows} windows, {dets} detections.")
    print("(early sessions' p99 includes the one-off cold-compile rounds; "
          "benchmarks/serve_latency.py gates the warmed steady state)")
    for sid in sorted(recs):
        sess = svc.session(sid)
        if sess.state == "live":
            final = svc.detach(sid)
            n_conf = int(np.asarray(confirmed(final.final_tracks, config.tracker)).sum())
        else:
            n_conf = 0
        st = sess.stats
        print(
            f"  {sess.name:<22} {st.events:>8,} events  {st.windows:>4} windows  "
            f"p99 latency {st.latency_percentile(99):6.1f} ms  "
            f"confirmed tracks at detach: {n_conf}"
        )


if __name__ == "__main__":
    main()
