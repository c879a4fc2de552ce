"""Run one cell of the benchmark once and print its result line.

    python benchmarks/tpu/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Refuses to run anywhere but a TPU. The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last ``check``: every number the
correctness check compared, beside its limit. See ``tpubench/bench.py``.
"""
import os
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

# The TPU runtime writes its logs under /tmp unless told otherwise; a run
# writes only inside its checkout and the directories it is given.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

sys.path.insert(0, str(Path(__file__).resolve().parent))

from tpubench.bench import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
