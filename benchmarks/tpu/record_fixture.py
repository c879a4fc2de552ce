"""Record the reduced trace of one traced run as a test fixture.

    python3 benchmarks/tpu/record_fixture.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``run.py --trace 1`` does, over a traced window of
``--seconds`` (a few rounds), and writes the digest the metric readers see
to ``fixtures/<cell>.json.gz``, which the tests reduce. Needs a TPU.
"""
import argparse
import os
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from tpubench import bench  # noqa: E402
from tpubench import trace as T  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(bench.ROOT / "src"))
    out = HERE / "fixtures" / f"{args.workload}.json.gz"
    stop = T.stop

    def stop_and_save(directory):
        digest = stop(directory)
        digest.save(out)
        return digest

    T.stop = stop_and_save
    bench.enable_cache()
    try:
        res = bench.run_cell(bench.cell_plan(bench.load_spec(), args.workload), args.seed,
                             args.seconds, True, T_START)
    except bench.NoChip as e:
        print(f"record_fixture: {e}", file=sys.stderr)
        return 1
    print(f"record_fixture: wrote {out} ({out.stat().st_size} bytes); "
          f"correct {res['correct']}, metrics {res['metrics']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
