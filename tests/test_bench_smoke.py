"""Smoke tests for the benchmark entry points (ISSUE 6 satellite).

The benchmarks are release tooling, not tier-1 hot paths, so regressions
there historically surfaced only when someone cut a BENCH json. These
tests import the modules the way ``benchmarks.run`` does and pin:

* ``table5_scaling.bench`` on a single node count produces a well-formed
  non-FAILED row (the subprocess snippet still runs),
* ``roofline_report.window_report`` emits the float/fixed/megakernel
  rows with sane magnitudes, and its ``bench()`` degrades to the
  ``roofline/missing`` row when no dryrun records exist,
* the ``benchmarks.run`` aggregator survives a gated bench that writes
  no ``BENCH_*.json`` (ERROR row + exit 1), fails when a table module
  raises, and rejects unknown keys.
"""
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:  # `import benchmarks.<mod>` package imports
    sys.path.insert(0, str(REPO))

from benchmarks import roofline_report, run  # noqa: E402


def test_table5_bench_single_node():
    from benchmarks import table5_scaling

    rows = table5_scaling.bench(node_counts=(1,))
    assert len(rows) == 1
    name, us, derived = rows[0]
    assert name == "table5/nodes1"
    assert derived != "FAILED"
    assert us > 0.0
    assert "efficiency1.00" in derived  # single node defines the baseline


@pytest.fixture(scope="module")
def window_report():
    return roofline_report.window_report(n_windows=2, capacity=128)


def test_window_report_rows(window_report):
    rows = window_report["rows"]
    assert set(rows) == {"float_staged", "fixed_staged", "megakernel_model"}
    for name, r in rows.items():
        assert r["flops"] > 0 and r["bytes"] > 0, name
    # The whole point of the fused launch: one launch, HBM traffic far
    # below either staged path.
    assert rows["megakernel_model"]["launches"] == 1.0
    assert window_report["mega_over_fixed_bytes"] <= 0.01
    assert rows["megakernel_model"]["bytes"] < rows["float_staged"]["bytes"]


def test_window_markdown_table(window_report):
    table = roofline_report.window_markdown_table(window_report)
    for needle in ("float_staged", "fixed_staged", "megakernel_model",
                   "mega/fixed bytes"):
        assert needle in table


def test_roofline_bench_missing_records(monkeypatch, tmp_path, window_report):
    monkeypatch.setattr(roofline_report, "RESULTS", tmp_path)
    monkeypatch.setattr(
        roofline_report, "window_report", lambda **kw: window_report
    )
    rows = roofline_report.bench()
    names = [r[0] for r in rows]
    assert "roofline/missing" in names  # graceful no-dryrun fallback
    assert any(n.startswith("roofline/window/") for n in names)


def test_run_aggregator_missing_bench_json(monkeypatch, capsys):
    # A gated bench whose subprocess dies before writing its json must
    # produce the ERROR summary row and a nonzero aggregator exit.
    monkeypatch.setattr(
        run, "BENCHES",
        {"ghost": ("does_not_exist_bench.py", "BENCH_ghost_missing.json")},
    )
    monkeypatch.setattr(sys, "argv", ["run.py", "ghost"])
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert exc.value.code == 1
    out = capsys.readouterr().out
    assert "ERROR (no BENCH json)" in out


def test_run_aggregator_fails_when_a_module_raises(monkeypatch):
    # A table module that raises must fail the run, not print an ERROR
    # row and exit 0.
    monkeypatch.setattr(run, "MODULES", {"ghost": "benchmarks.no_such_module"})
    monkeypatch.setattr(sys, "argv", ["run.py", "ghost"])
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert exc.value.code not in (0, None)
    assert "ghost" in str(exc.value.code)


def test_run_aggregator_rejects_unknown_key(monkeypatch):
    monkeypatch.setattr(sys, "argv", ["run.py", "bogus_key"])
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert "bogus_key" in str(exc.value.code)
