"""Readings that the correctness limits are set from, on a TPU.

    python benchmarks/tpu/calibrate.py --config paper_vga_fixed_untracked \\
        --traffic backlog --seeds 1,2,3 --seconds 10 --control 3

Runs the configuration under the traffic once per seed, all in one process,
exactly as a run of the cell does, and prints one JSON line per seed: the
end-to-end metrics, every number the check compared (the lower readings)
and the worst gap of every field. Then, on the first ``--control`` seeds'
sampled stations, the control: the reference computed in bfloat16, one
precision below the configuration's float32, put in the program's place
(the upper readings), with the verdict the limits give it: ``correct``
has to read false. The configuration need not be in the benchmark, so the
same command shows a program fault on a configuration left out of it.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

from tpubench import bench, reference  # noqa: E402


def slot0_overwrite(taken, assign, matched):
    """The program tracker's rule for the detections that tracks took: each
    track writes its match flag at ``assign`` clipped into range, in track
    order, so an unmatched track (``assign`` -1) clears detection 0 and a
    matched detection 0 spawns a second track. For showing that fault
    (PERF.md, fault 1) apart from the tracker's rounding; never the
    benchmark's rule."""
    used = np.zeros(len(taken), bool)
    for ti in range(len(assign)):  # last write wins, as in the program's scatter
        used[np.clip(assign[ti], 0, len(taken) - 1)] = matched[ti]
    return used


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=0,
                    help="seeds, from the first, whose stations the control replays")
    ap.add_argument("--rule", default="intent", choices=("intent", "slot0_overwrite"),
                    help="the reference tracker's rule for used detections: its own, "
                         "or the program's known fault (slot0_overwrite)")
    args = ap.parse_args(argv)
    if args.rule == "slot0_overwrite":
        reference.detections_used = slot0_overwrite
    sys.path.insert(0, str(bench.ROOT / "src"))
    bench.enable_cache()
    import ml_dtypes

    from tpubench import check

    plan = bench.plan_for(args.config, args.traffic)
    limits = plan["cfg"]["check"]["limits"]
    worst = {k: 0.0 for k in limits}
    controls = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        probe = {}
        t0 = time.perf_counter()
        out = bench.run_cell(plan, seed, args.seconds, False, t0, log=lambda *a: None,
                             probe=probe)
        row = {
            "seed": seed, "correct": out["correct"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "readings": {k: probe["readings"][k] for k in check.NAMES},
            "windows": probe["readings"]["windows"],
            "detail": probe["detail"], "wall_s": time.perf_counter() - t0,
        }
        for k in worst:
            worst[k] = max(worst[k], row["readings"][k])
        print(json.dumps(row, default=float), flush=True)
        if i < args.control:
            detail = {}
            low = check.control(plan["cfg"], probe["stations"], ml_dtypes.bfloat16, detail)
            controls.append(low)
            correct, rows = check.verdict(low, limits)
            print(json.dumps({"seed": seed, "control": "bfloat16", "correct": correct,
                              "check": rows, "readings": low, "detail": detail},
                             default=float), flush=True)
    summary = {"config": args.config, "traffic": args.traffic, "rule": args.rule,
               "limits": limits, "lower": worst}
    if controls:
        summary["upper"] = {k: min(c[k] for c in controls) for k in limits}
    print(json.dumps(summary, default=float), flush=True)


if __name__ == "__main__":
    main()
