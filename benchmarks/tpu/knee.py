"""Find the live-cadence knee of a configuration: the most attached stations
at which the service keeps up with 20 ms beats (the generator's lateness does
not grow) and the p95 latency stays within the paper's 62 ms budget.

    python benchmarks/tpu/knee.py --config paper_vga_float --stations 4,8,12,16 \\
        --seconds 8 --seed 7

Runs the ``live`` traffic at each station count in one process, on a TPU,
and prints one JSON line per point: stations, latency p50/p95, events/s,
the generator's lateness p95 and its slope over the window (ms per s: a
growing backlog), and the beats issued against those due.
"""
import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

from tpubench import bench, drive  # noqa: E402

BUDGET_MS = 62.0


def point(plan, stations, seed, seconds):
    plan = dict(plan, traffic=dataclasses.replace(plan["traffic"], stations=stations,
                                                  check_stations=1))
    st = bench.prepare(plan, seed)
    rec = st["drv"].window(seconds)
    lat = drive.latencies_ms(rec, st["streams"], plan["traffic"].chunk_us)
    late = np.asarray(rec.lateness) * 1e3
    beats_due = int(seconds / (plan["traffic"].chunk_us / 1e6))
    t = np.arange(len(late)) * plan["traffic"].chunk_us / 1e6
    slope = float(np.polyfit(t, late, 1)[0]) if len(late) > 2 else float("nan")
    events = sum(r.events for r in rec.rounds if r.index >= rec.first_chunk)
    return {
        "stations": stations,
        "latency_p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
        "latency_p95_ms": float(np.percentile(lat, 95)) if len(lat) else None,
        "events_per_s": events / (rec.t_done - rec.t0),
        "lateness_p95_ms": float(np.percentile(late, 95)) if len(late) else None,
        "lateness_slope_ms_per_s": slope,
        "beats": len(late), "beats_due": beats_due,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--stations", required=True, help="comma-separated counts")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(bench.ROOT / "src"))
    bench.enable_cache()
    plan = bench.plan_for(args.config, "live")
    rows = []
    for s in (int(v) for v in args.stations.split(",")):
        t0 = time.perf_counter()
        row = point(plan, s, args.seed, args.seconds)
        row["wall_s"] = time.perf_counter() - t0
        row["keeps_up"] = bool(
            row["latency_p95_ms"] is not None and row["latency_p95_ms"] <= BUDGET_MS
            and row["beats"] >= row["beats_due"] - 1 and row["lateness_slope_ms_per_s"] < 1.0
        )
        rows.append(row)
        print(json.dumps(row), flush=True)
    ok = [r["stations"] for r in rows if r["keeps_up"]]
    print(json.dumps({"config": args.config, "knee_stations": max(ok) if ok else None}))


if __name__ == "__main__":
    main()
