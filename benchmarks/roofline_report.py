"""Aggregate dry-run JSON artifacts into the EXPERIMENTS.md roofline
table, and report HLO bytes/flops for the per-window stage chain
(float vs fixed vs fused megakernel). Also exposes the baseline rows as
benchmark CSV.

The window report is the "before/after" evidence for the megakernel PR:
``launch.hlo_analysis.analyze`` over the jit-compiled staged float and
staged fixed window-batch steps (real post-optimization HLO counts), plus
an analytic cost model for the fused Pallas kernel — interpret-mode
Pallas shows up in HLO as an opaque custom call, so its bytes/flops are
derived from the kernel's block shapes instead (one (W, E) pass, VMEM-
resident intermediates, one (CL_ROWS + K, LANE) output block per
window). ``benchmarks/scan_throughput.py`` embeds these numbers next to
the measured megakernel speedup gate in ``BENCH_scan.json``.
"""
from __future__ import annotations

import json
from pathlib import Path

RESULTS = Path(__file__).resolve().parent / "dryrun_results"


def load_records(mesh: str | None = None, variant: str | None = "") -> list[dict]:
    """variant="" -> baselines only; None -> everything."""
    recs = []
    for f in sorted(RESULTS.glob("*.json")):
        r = json.loads(f.read_text())
        if mesh and r.get("mesh") != mesh:
            continue
        if variant is not None and r.get("variant", "") != variant:
            continue
        recs.append(r)
    return recs


def fmt_s(x: float) -> str:
    if x >= 1:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x * 1e3:.1f}ms"
    return f"{x * 1e6:.0f}us"


def markdown_table(mesh: str = "single", variant: str | None = "") -> str:
    rows = [
        "| arch | shape | t_compute | t_memory | t_collective | bottleneck "
        "| useful/HLO | peak GB/dev |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in load_records(mesh, variant):
        if not r.get("ok"):
            rows.append(f"| {r['arch']} | {r['shape']} | FAILED: {r.get('error','')[:40]} |")
            continue
        t = r["roofline"]
        peak = r.get("memory", {}).get("temp_size_in_bytes", 0) / 1e9
        ratio = r.get("useful_flops_ratio")
        rows.append(
            f"| {r['arch']} | {r['shape']} | {fmt_s(t['t_compute_s'])} "
            f"| {fmt_s(t['t_memory_s'])} | {fmt_s(t['t_collective_s'])} "
            f"| {t['bottleneck']} | {ratio:.2f} | {peak:.1f} |"
            if ratio is not None else
            f"| {r['arch']} | {r['shape']} | {fmt_s(t['t_compute_s'])} "
            f"| {fmt_s(t['t_memory_s'])} | {fmt_s(t['t_collective_s'])} "
            f"| {t['bottleneck']} | - | {peak:.1f} |"
        )
    return "\n".join(rows)


# ---------------------------------------------------------------------------
# Per-window stage-chain report: float vs fixed vs fused megakernel.
# ---------------------------------------------------------------------------

def _compile_window_step(config, n_windows: int, capacity: int):
    """Jit-compile the (un-tracked) window-batch step for HLO analysis."""
    import jax
    import jax.numpy as jnp

    from repro.core.events import EventBatch
    from repro.core.pipeline.scan import _fresh_carry_core
    from repro.core.tracking import init_tracks

    core = _fresh_carry_core(config, with_tracking=False)
    stacked = EventBatch(
        x=jnp.zeros((n_windows, capacity), jnp.int32),
        y=jnp.zeros((n_windows, capacity), jnp.int32),
        t=jnp.zeros((n_windows, capacity), jnp.int32),
        p=jnp.zeros((n_windows, capacity), jnp.int32),
        valid=jnp.zeros((n_windows, capacity), bool),
    )
    return jax.jit(core).lower(stacked, init_tracks(config.tracker)).compile()


def _megakernel_cost_model(
    config, n_windows: int, capacity: int
) -> dict[str, float]:
    """Analytic bytes/flops for the fused Pallas window kernel.

    Interpret-mode Pallas appears in HLO as an opaque custom call, so the
    fused step's roofline terms come from its block shapes instead: per
    grid step (= per window), the pairwise (E, E) conditioning block,
    the (E, C) cell one-hot matmul, K per-cluster (E, patch^2) +
    (E, bins) matmuls and the Sobel stencil. HBM traffic is just the
    event arrays in and the two packed output blocks out — every
    intermediate lives in VMEM, which is the point of fusing.
    """
    from repro.core import metrics as M
    from repro.kernels import window_pipeline as wp

    e = capacity
    grid = config.grid
    k = grid.max_clusters
    c_pad = -(-grid.n_cells // wp.LANE) * wp.LANE
    npix = M.WINDOW * M.WINDOW
    bins = M.HIST_BINS
    per_window_flops = (
        5 * e * e  # same-pixel compares, hot counts, coincidence, leaders
        + 2 * 4 * e * c_pad  # 4-stat cell one-hot matmul
        + k * 4 * c_pad  # top-K (max, first-index, mask) passes
        + k * (3 * e * npix + 2 * e * bins + 20 * npix)  # per-cluster stage
    )
    hbm_bytes = n_windows * (
        4 * e * 4  # x, y, t, valid int32 in
        + (wp.CL_ROWS + k) * wp.LANE * 4  # cluster + surface blocks out
    )
    return {
        "flops": float(n_windows * per_window_flops),
        "bytes": float(hbm_bytes),
        "launches": 1.0,
    }


def window_report(n_windows: int = 8, capacity: int = 256) -> dict:
    """Bytes/flops for the per-window stage chain, before/after fusing.

    Rows: the staged float path and the staged fixed path (both measured
    from jit-compiled post-optimization HLO via ``launch.hlo_analysis`` —
    "traffic" there is inter-fusion operand+result bytes, the HLO proxy
    for HBM round-trips between launches), and the fused megakernel
    (analytic model, HBM-only by construction: intermediates never leave
    VMEM — see :func:`_megakernel_cost_model`). All figures cover one
    ``n_windows``-window batch step at the given capacity.
    """
    from repro.core.pipeline.config import PipelineConfig
    from repro.launch.hlo_analysis import analyze

    report: dict = {"n_windows": n_windows, "capacity": capacity, "rows": {}}
    for name, config in (
        ("float_staged", PipelineConfig()),
        ("fixed_staged", PipelineConfig(numerics="fixed")),
    ):
        stats = analyze(
            _compile_window_step(config, n_windows, capacity).as_text()
        )
        report["rows"][name] = {
            "flops": stats["flops"],
            "bytes": stats["bytes"],
            "launches": float(n_windows),  # one logical step per window
        }
    report["rows"]["megakernel_model"] = _megakernel_cost_model(
        PipelineConfig(numerics="fixed", metrics_impl="megakernel"),
        n_windows, capacity,
    )
    fl = report["rows"]["float_staged"]
    fx = report["rows"]["fixed_staged"]
    mk = report["rows"]["megakernel_model"]
    report["fixed_over_float_bytes"] = fx["bytes"] / max(fl["bytes"], 1.0)
    report["fixed_over_float_flops"] = fx["flops"] / max(fl["flops"], 1.0)
    report["mega_over_fixed_bytes"] = mk["bytes"] / max(fx["bytes"], 1.0)
    return report


def window_markdown_table(report: dict | None = None) -> str:
    report = window_report() if report is None else report
    rows = [
        f"Per-window stage chain, W={report['n_windows']} x "
        f"E={report['capacity']} batch step:",
        "",
        "| path | MFLOPs | traffic MB | launches |",
        "|---|---|---|---|",
    ]
    for name, r in report["rows"].items():
        rows.append(
            f"| {name} | {r['flops'] / 1e6:.2f} | {r['bytes'] / 1e6:.2f} "
            f"| {r['launches']:.0f} |"
        )
    rows.append("")
    rows.append(
        f"fixed/float bytes: {report['fixed_over_float_bytes']:.2f}x, "
        f"fixed/float flops: {report['fixed_over_float_flops']:.2f}x, "
        f"mega/fixed bytes: {report['mega_over_fixed_bytes']:.3f}x"
    )
    return "\n".join(rows)


def bench() -> list[tuple[str, float, str]]:
    rows = []
    wr = window_report(n_windows=4, capacity=256)
    for name, r in wr["rows"].items():
        rows.append(
            (f"roofline/window/{name}", 0.0,
             f"mflops{r['flops'] / 1e6:.2f}_mb{r['bytes'] / 1e6:.2f}")
        )
    rows.append(
        ("roofline/window/mega_over_fixed_bytes",
         wr["mega_over_fixed_bytes"], "hbm_traffic_ratio")
    )
    recs = load_records("single")
    if not recs:
        return rows + [("roofline/missing", 0.0, "run launch.dryrun first")]
    n_ok = sum(r["ok"] for r in recs)
    rows.append(("roofline/cells_single_pod", 0.0, f"{n_ok}of{len(recs)}_ok"))
    multi = load_records("multi")
    rows.append(
        ("roofline/cells_multi_pod", 0.0,
         f"{sum(r['ok'] for r in multi)}of{len(multi)}_ok")
    )
    for r in recs:
        if not r.get("ok"):
            continue
        t = r["roofline"]
        bound = max(t["t_compute_s"], t["t_memory_s"], t["t_collective_s"])
        frac = t["t_compute_s"] / bound if bound else 0.0
        rows.append(
            (f"roofline/{r['arch']}/{r['shape']}", bound * 1e6,
             f"{t['bottleneck']}_computefrac{frac:.2f}")
        )
    return rows


if __name__ == "__main__":
    print(window_markdown_table())
    print()
    if load_records("single"):
        print(markdown_table())
    else:
        print("(no dryrun_results yet — run launch.dryrun for the mesh table)")
