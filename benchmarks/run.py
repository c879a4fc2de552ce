"""Benchmark aggregator: paper tables/figures + the gated perf benches.

Two kinds of entries share this single entrypoint:

* **table/figure modules** (``table1`` .. ``roofline``) — each run in a
  child process, printing ``name,us_per_call,derived`` CSV rows (the
  paper-reproduction numbers). A module that raises fails the run.
* **gated benches** (``scan`` / ``stream`` / ``fleet``) — run as
  subprocesses writing ``BENCH_<name>.json`` at the repo root. Every
  payload carries a uniform ``bench`` block — ``{name, p50_ms, p99_ms,
  gates:[{name, value, threshold, op, pass}]}`` — which this aggregator
  collects into one summary table. Benches that account wire traffic
  also report ``bytes_per_round``; the summary prints it as a column
  and shows ``WARN`` (never an error) for payloads missing the field. Each bench's own exit code is the
  gate authority (env knobs like ``BENCH_NO_FAIL`` /
  ``BENCH_GATE_SPEEDUP`` / ``BENCH_GATE_EVENT`` pass through and mean
  the same thing here as when a bench is run directly); the aggregator
  exits nonzero iff any subprocess did.

The aggregator itself never imports JAX: a device belongs to one process
at a time, so every entry runs in its own child, one after another.

Select subsets by key::

  PYTHONPATH=src python -m benchmarks.run table1 fig10   # paper tables
  PYTHONPATH=src python -m benchmarks.run scan stream fleet serve
  PYTHONPATH=src python -m benchmarks.run                # everything
"""
from __future__ import annotations

import json
import subprocess
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent

MODULES = {
    "table1": "benchmarks.table1_algorithms",
    "table3": "benchmarks.table3_latency",
    "table4": "benchmarks.table4_system",
    "table5": "benchmarks.table5_scaling",
    "fig10": "benchmarks.fig10_threshold",
    "fig5_8": "benchmarks.fig5_8_entropy",
    "roofline": "benchmarks.roofline_report",
}

# Gated benches: script + the BENCH_*.json it writes (uniform `bench`
# block inside). Registered here so one command runs the whole gate set.
BENCHES = {
    "scan": ("scan_throughput.py", "BENCH_scan.json"),
    "stream": ("stream_latency.py", "BENCH_stream.json"),
    "fleet": ("fleet_throughput.py", "BENCH_fleet.json"),
    "serve": ("serve_latency.py", "BENCH_serve.json"),
    "ingest": ("serve_saturation.py", "BENCH_ingest.json"),
    "chaos": ("chaos_soak.py", "BENCH_chaos.json"),
    "constellation": ("constellation_scaling.py", "BENCH_constellation.json"),
}


# Child-process body for one table/figure module: import it, run its
# ``bench()``, print the CSV rows. An exception exits the child nonzero.
_MODULE_CHILD = (
    "import importlib, sys\n"
    "from benchmarks._common import emit\n"
    "emit(importlib.import_module(sys.argv[1]).bench())\n"
)


def _run_module(key: str) -> bool:
    """Run one table/figure module in a child; True iff it succeeded."""
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, "-c", _MODULE_CHILD, MODULES[key]], cwd=REPO_ROOT
    )
    ok = proc.returncode == 0
    status = "done" if ok else f"FAILED (exit {proc.returncode})"
    print(f"# {key} {status} in {time.time() - t0:.1f}s", file=sys.stderr)
    return ok


def _run_bench(key: str) -> tuple[dict | None, bool]:
    """Run one gated bench as a subprocess.

    Returns ``(bench block, ok)``: the bench's own exit code decides
    ``ok`` (so its gate knobs behave identically under the aggregator),
    and the block is parsed from the written json when available.
    """
    script, json_name = BENCHES[key]
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).parent / script)], cwd=REPO_ROOT
    )
    print(f"# {key} done in {time.time() - t0:.1f}s", file=sys.stderr)
    path = REPO_ROOT / json_name
    block = json.loads(path.read_text()).get("bench") if path.exists() else None
    return block, proc.returncode == 0


def main() -> None:
    selected = sys.argv[1:] or [*MODULES, *BENCHES]
    unknown = [k for k in selected if k not in MODULES and k not in BENCHES]
    if unknown:
        sys.exit(f"unknown benchmark keys: {unknown}; "
                 f"choose from {[*MODULES, *BENCHES]}")

    if any(k in MODULES for k in selected):
        print("name,us_per_call,derived", flush=True)
    summaries: list[tuple[str, dict | None, bool]] = []
    failed_modules = []
    for key in selected:
        if key in MODULES:
            if not _run_module(key):
                failed_modules.append(key)
        else:
            block, ok = _run_bench(key)
            summaries.append((key, block, ok))

    if not summaries:
        if failed_modules:
            sys.exit(f"table/figure modules failed: {failed_modules}")
        return
    print(f"\n{'bench':<18} {'p50 ms':>9} {'p99 ms':>9} {'bytes/round':>12}  gates")
    failed = False
    for key, block, ok in summaries:
        failed |= not ok
        if block is None:
            print(f"{key:<18} {'-':>9} {'-':>9} {'-':>12}  ERROR (no BENCH json)")
            continue
        bpr = block.get("bytes_per_round")
        if bpr is None:
            # Older BENCH json predating the wire-format accounting: the
            # column is advisory, so a missing field warns but never fails.
            bpr_col = "WARN"
        else:
            bpr_col = f"{bpr:.0f}"
        gates = "; ".join(
            f"{g['name']} {g['value']} {g['op']} {g['threshold']} "
            f"[{'PASS' if g['pass'] else 'FAIL'}]"
            for g in block.get("gates", [])
        )
        print(
            f"{block['name']:<18} {block['p50_ms']:>9} {block['p99_ms']:>9} "
            f"{bpr_col:>12}  {gates}{'' if ok else '  << exit 1'}"
        )
    if failed_modules:
        print(f"table/figure modules failed: {failed_modules}")
    if failed or failed_modules:
        sys.exit(1)


if __name__ == "__main__":
    main()
