"""The per-layer readers' values on the two recorded chip traces, pinned.

A change to the trace reduction (``tpubench/trace.py``) or to the context
the readers see must leave what the accepted metrics read unchanged: the
loop's own spans set the traced window, and each reader reads the same
spans and device events as before. The values are what the accepted
readers return on the fixtures (TPU v5 lite, a few rounds of the fixed
route, tracking on).
"""
from __future__ import annotations

from pathlib import Path

import pytest

from tpubench import bench, context
from tpubench import trace as T

FIXTURES = Path(__file__).resolve().parent / "fixtures"

PINNED = {
    ("paper_vga_fixed.live", "feed_ms.live"): 0.12861068750000543,
    ("paper_vga_fixed.live", "fleet_step_device_ms.live"): 0.3182781249999996,
    ("paper_vga_fixed.live", "pump_ms.live"): 4.587993874999998,
    ("paper_vga_fixed.live", "round_device_idle_share.live"): 98.07727221612157,
    ("paper_vga_fixed.backlog", "device_idle_share.backlog"): 0.023241899584747205,
    ("paper_vga_fixed.backlog", "fleet_step_device_ms.backlog"): 55.09006699999999,
    ("paper_vga_fixed.backlog", "pump_ms.backlog"): 45.24270575,
    ("paper_vga_fixed.backlog", "result_ms.backlog"): 14.846602749999994,
    ("paper_vga_fixed.backlog", "window_pipeline_roofline.backlog"): 0.5579547309669073,
    ("paper_vga_fixed.backlog", "wire_decode_device_ms.backlog"): 5.861055499999996,
}


def _context(fixture: str) -> context.Context:
    digest = T.Digest.load(FIXTURES / f"{fixture}.json.gz")
    config, traffic_name = fixture.split(".")
    plan = bench.plan_for(config, traffic_name)
    return context.Context(digest, plan["cfg"], plan["traffic"],
                           {"step": "jit_step", "decode": "jit_decode"}, "TPU v5 lite")


@pytest.mark.parametrize("fixture,metric", sorted(PINNED), ids="/".join)
def test_reader_value_on_a_recorded_trace_is_pinned(fixture, metric):
    got = context.reader(metric)(_context(fixture))
    assert got == pytest.approx(PINNED[fixture, metric], rel=1e-9, abs=0)
