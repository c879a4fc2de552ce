"""One run of one cell: set-up, warm-up, the measured window, the
correctness check, and the result line.

    python benchmarks/tpu/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything the cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file, ``traffic/<traffic>.json``, ``metrics/<metric>.py``
for each per-layer metric and ``costs/<kernel>.py``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent.parent  # benchmarks/tpu
ROOT = HERE.parent.parent  # the checkout
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_plan(spec: dict, workload: str, root: Path = ROOT, traffic_dir: Path | None = None) -> dict:
    """The cell ``workload`` with its configuration, traffic and metrics."""
    from tpubench import traffic as TR

    traffic_dir = TR.TRAFFIC_DIR if traffic_dir is None else traffic_dir
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    e2e = [
        m for m in spec["end_to_end"]
        if workload in m.get("workloads", [workload])
    ]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [
        m for m in spec["per_layer"]
        if workload in m.get("workloads", [workload] if m["moves"] in e2e_names else [])
    ]
    return {
        "cell": cell, "cfg": cfg, "traffic": TR.load(cell["traffic"], traffic_dir),
        "end_to_end": e2e, "per_layer": per_layer,
    }


def plan_for(config: str, traffic_name: str) -> dict:
    """The plan of ``config`` under ``traffic_name`` on one chip, whether or
    not the benchmark names that cell (calibration, knee sweeps, tests):
    ``cell_plan`` over ``BENCHMARK.json`` with the cell's entry put in, so
    its metrics are the benchmark's own."""
    spec = load_spec()
    name = f"{config}.{traffic_name}"
    conf = {"name": config, "file": (HERE / "configs" / f"{config}.json").relative_to(ROOT).as_posix()}
    cell = {"name": name, "config": config, "traffic": traffic_name, "chips": 1}
    spec = dict(
        spec,
        configs=[c for c in spec["configs"] if c["name"] != config] + [conf],
        workloads=[w for w in spec["workloads"] if w["name"] != name] + [cell],
    )
    return cell_plan(spec, name)


def devices(chips: int, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def enable_cache() -> None:
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def prepare(plan: dict, seed: int, require_tpu: bool = True, log=print) -> dict:
    """Set-up of a run: the chip, the system, the streams, the warm-up."""
    import jax

    from tpubench import drive, system
    from tpubench import traffic as TR

    cache = {}  # persistent-cache events during the set-up, by name

    def count(name, **_):
        if "compilation_cache" in name:
            cache[name] = cache.get(name, 0) + 1

    jax.monitoring.register_event_listener(count)
    t0 = time.perf_counter()
    cell, cfg, traffic = plan["cell"], plan["cfg"], plan["traffic"]
    devs = devices(cell["chips"], require_tpu)
    t1 = time.perf_counter()
    sysm = system.build(cfg)
    streams = TR.make_streams(traffic, seed, cfg["sensor"]["width"], cfg["sensor"]["height"])
    keep = TR.check_sample(traffic, seed)
    svc = sysm.service()
    sids = [svc.attach(f"station{i}-{s.family}") for i, s in enumerate(streams)]
    drv = drive.Driver(svc, sids, streams, traffic, set(keep))
    t2 = time.perf_counter()
    drv.warmup(traffic.warmup_rounds)
    t3 = time.perf_counter()
    jax.monitoring.unregister_event_listener(count)
    log(f"# set-up s: devices {t1 - t0:.3f}, sky and service {t2 - t1:.3f}, warm-up "
        f"{traffic.warmup_rounds} rounds {t3 - t2:.3f}; compile cache events {cache}")
    return {"devs": devs, "sys": sysm, "streams": streams, "keep": keep,
            "svc": svc, "drv": drv, "compiles": sysm.compile_counts()}


def run_cell(plan: dict, seed: int, seconds: float, traced: bool, t_start: float,
             require_tpu: bool = True, log=print, probe: dict | None = None) -> dict:
    """Run one cell once; returns the result object (the last line).
    ``probe``, when given, receives the check's input and per-field detail."""
    from tpubench import check as C
    from tpubench import context, drive, host
    from tpubench import trace as T

    cell, cfg, traffic = plan["cell"], plan["cfg"], plan["traffic"]
    st = prepare(plan, seed, require_tpu, log)
    devs, sysm, streams, keep, svc, drv = (
        st[k] for k in ("devs", "sys", "streams", "keep", "svc", "drv")
    )
    dev = devs[0]
    compiles0 = st.pop("compiles")
    del st
    gc.collect()
    setup_s = time.perf_counter() - t_start

    window = min(seconds, traffic.trace_s) if traced else seconds
    if traced:
        T.start(TRACE_DIR)
    with host.Watch() as watch:
        rec = drv.window(window)
    digest = T.stop(TRACE_DIR) if traced else None
    compiles1 = sysm.compile_counts()
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))

    rounds = [r for r in rec.rounds if r.index >= rec.first_chunk]
    events = sum(r.events for r in rounds)
    elapsed = rec.t_done - rec.t0
    wire = svc.wire_stats
    log(f"# cell {cell['name']} seed {seed} device {dev.device_kind} x{len(devs)}")
    log(f"# window {window:.3f} s + drain {rec.t_done - rec.t_end:.4f} s; rounds "
        f"{len(rounds)}; windows {sum(r.windows for r in rounds)}; events {events}")
    log(f"# compiles inside the window: step {compiles1['step'] - compiles0['step']}, "
        f"decode {compiles1['decode'] - compiles0['decode']}")
    log(f"# wire: rounds {wire.rounds} wire_bytes {wire.wire_bytes} dense_bytes "
        f"{wire.dense_bytes} compression {wire.compression:.4f} spilled {wire.spilled} "
        f"d2h_transfers {wire.d2h_transfers} d2h_bytes {wire.d2h_bytes} cluster_slots "
        f"{wire.cluster_slots} clusters_valid {wire.clusters_valid}")
    log("# host: " + json.dumps(dict(
        steps_ms=host.steps_ms(rec.steps), **host.slices(rounds, rec.t0, rec.t_end),
        **watch.fields())))
    shapes = sorted({r.shape for r in rounds})
    log(f"# step shapes (slots, windows): {shapes}")
    metrics = {}
    if traffic.loop == "open":
        late = np.asarray(rec.lateness) * 1e3
        if len(late):
            log(f"# generator lateness ms: p50 {np.percentile(late, 50):.4f} "
                f"p95 {np.percentile(late, 95):.4f} max {late.max():.4f}")
    lat = drive.latencies_ms(rec, streams, traffic.chunk_us) if traffic.loop == "open" else []
    if len(lat):
        log(f"# latency ms over {len(lat)} windows: p50 {np.percentile(lat, 50):.4f} "
            f"p95 {np.percentile(lat, 95):.4f} max {np.max(lat):.4f}")
    if not traced:
        values = {"setup_s": setup_s, "events_per_s": events / elapsed if elapsed > 0 else 0.0}
        if len(lat):
            values["latency_p50_ms"] = float(np.percentile(lat, 50))
            values["latency_p95_ms"] = float(np.percentile(lat, 95))
        for m in plan["end_to_end"]:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    device = {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
        "memory_peak_bytes": peak,
    }
    breakdown = None
    if traced:
        ctx = context.Context(digest, cfg, traffic, sysm.program_names(), dev.device_kind)
        for m in plan["per_layer"]:
            v = context.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = ctx.busy_s
        device["window_s"] = ctx.window_s
        breakdown = _breakdown(ctx)

    # Correctness: the sampled stations' served outputs against the plain
    # reference, once the program's state is freed.
    served = {st: rec.results[st] for st in keep}
    n_fed = {st: streams[st].events_before(rec.next_chunk) for st in keep}
    attempted = sum(r.windows for r in rounds)
    del drv, svc, rec, rounds
    gc.collect()
    t_check = time.perf_counter()
    stations = {st: (streams[st].prefix(n_fed[st]), n_fed[st], served[st]) for st in keep}
    detail = {}
    readings = C.check(cfg, stations, detail=detail)
    if probe is not None:
        probe.update(stations=stations, detail=detail, readings=readings)
    correct, rows = C.verdict(readings, cfg["check"]["limits"])
    log(f"# check: stations {keep}, windows {readings['windows']}, "
        f"{time.perf_counter() - t_check:.2f} s")
    out = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": 0 if correct else int(attempted),
        "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = {name: {"value": v, "limit": lim} for name, v, lim in rows}
    return out


def _breakdown(ctx) -> dict:
    """The device operations that took most time, and the device's idle
    time split by the host span open over each gap."""
    from tpubench import trace as T

    d = ctx.digest
    per_op: dict[str, float] = {}
    ops = d.ops[np.argsort(d.ops[:, 1], kind="stable")] if len(d.ops) else d.ops
    # An operation that another one starts inside (a while loop, a call)
    # holds its body's operations: only the innermost count, so no time
    # is counted twice.
    leaf = np.ones(len(ops), bool)
    if len(ops) > 1:
        leaf[:-1] = ops[1:, 1] >= ops[:-1, 2]
    mods = sorted(d.modules, key=lambda m: m[1])
    m_start = np.asarray([m[1] for m in mods])
    for (ix, s, e) in ops[leaf]:
        j = int(np.searchsorted(m_start, s, side="right")) - 1
        mod = mods[j][0] if j >= 0 and s <= mods[j][2] else "?"
        op = d.op_names[int(ix)].split(" = ", 1)[0].lstrip("%")
        key = f"{mod}/{op}"
        per_op[key] = per_op.get(key, 0.0) + (e - s)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    # Idle time goes to the innermost host span open over it: the
    # program's spans, then the loop's own steps, the waits that hold them
    # last.
    remaining = T.complement(ctx.busy, ctx.lo, ctx.hi)
    idle: dict[str, float] = {}
    loop = ("result", "pump", "feed", "generator", "drain", "wait_beat")
    for name in T.PROGRAM_SPANS + loop:
        spans = T.merge([sp[1:3] for sp in d.spans if sp[0] == name])
        covered = T.intersect(remaining, spans)
        if T.length(covered) > 0:
            idle[name] = T.length(covered)
            remaining = T.intersect(remaining, T.complement(spans, ctx.lo, ctx.hi))
    if T.length(remaining) > 0:
        idle["none"] = T.length(remaining)
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {
        "device_ops": [[k, v] for k, v in top_ops],
        "idle_gaps": [[k, v] for k, v in top_idle],
    }


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"benchmark: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    plan = cell_plan(load_spec(), args.workload)
    enable_cache()
    try:
        out = run_cell(plan, args.seed, args.seconds, bool(args.trace), t_start)
    except NoChip as e:
        print(f"benchmark: {e}; there is no CPU fallback", file=sys.stderr)
        return 1
    for name, c in out["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0

