"""Chip smoke test: the served detection path, end to end, on a TPU.

    python chip_smoke.py             # one chip: default route + 3 kernel routes
    python chip_smoke.py --chips 4   # four chips: the multi-chip path only

One chip (no arguments). Sixteen ground stations on the default 640x480
sensor, drawn from the scenario families ``examples/serve_detections.py``
serves, stream 2 s of sky each in 20 ms chunks through
``DetectionService`` — attach, feed, pump, detach — on the default
``PipelineConfig()`` with its ragged wire, then again on each Pallas
kernel route: ``use_kernels`` (``cluster_accum`` + ``event_unpack``),
``metrics_impl="kernel"`` (``patch_metrics``) and the fixed-point
megakernel (``window_pipeline``). Each phase checks that:

* every session's concatenated outputs equal ``run_recording_scan`` of
  the same recording on the same config, bit for bit (the megakernel
  route also against the staged ``numerics="fixed"`` scan);
* the served detections score at least the CPU accuracy of the same
  recordings (:data:`CPU_ACCURACY`) under the repo's truth matching;
* no fleet-step shape compiled twice (one compile per capacity tier and
  window count);
* on kernel routes, the compiled fleet step holds a ``tpu_custom_call``,
  so no kernel ran in interpret mode.

Four chips (``--chips 4``). The same stations through
``ConstellationService(n_shards=4)`` — each shard's carry asserted on its
own chip — and through a ``FleetPipeline`` on a 4-device ``sensor`` mesh,
each compared bit for bit with one ``DetectionService`` on one chip.

The script refuses to run anywhere but a TPU: there is no CPU fallback.
Its last stdout line is one JSON object naming the device, printed only
when every check passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

N_STATIONS = 16
DURATION_S = 2.0
CHUNK_US = 20_000
# The scenario families examples/serve_detections.py serves.
FAMILIES = ("crossing", "geo_slow", "tumbling", "ballistic", "jitter")
KERNEL_ROUTES = ("use_kernels", "kernel", "megakernel")

# Served-detection accuracy of the 16 stations above on each route, from
# a CPU run of this script's phases (interpret-mode kernels): tp 2906,
# fp 115, fn 226 on every route. The served outputs hold only clusters at
# or above min_events, so tn is 0 by construction. The chip must score at
# least as well on the same recordings.
CPU_ACCURACY = {
    "default": 0.8949799815214043,
    "use_kernels": 0.8949799815214043,
    "kernel": 0.8949799815214043,
    "megakernel": 0.8949799815214043,
}


def route_configs():
    """Route name -> (served config, extra scan references)."""
    from repro.core.pipeline import PipelineConfig

    fixed = PipelineConfig(numerics="fixed")
    return {
        "default": (PipelineConfig(), ()),
        "use_kernels": (PipelineConfig(use_kernels=True), ()),
        "kernel": (PipelineConfig(metrics_impl="kernel"), ()),
        "megakernel": (
            dataclasses.replace(fixed, metrics_impl="megakernel"), (fixed,)
        ),
    }


def make_stations(n: int = N_STATIONS, duration_s: float = DURATION_S):
    """``n`` seeded station recordings cycling :data:`FAMILIES`."""
    from repro.data.synthetic import SCENARIO_FAMILIES, make_fleet_recordings

    recs = []
    for i in range(n):
        fam = FAMILIES[i % len(FAMILIES)]
        rec = make_fleet_recordings(
            1, scenario=SCENARIO_FAMILIES[fam], seed0=17 * i,
            duration_s=duration_s,
        )[0]
        recs.append(dataclasses.replace(rec, name=f"station{i}-{fam}"))
    return recs


def _chunks(recs):
    from repro.data.evas import iter_chunks

    return [list(iter_chunks(r, CHUNK_US)) for r in recs]


def _admission():
    """Rounds fire only on ``pump(force=True)``: one fleet step per 20 ms
    round, whatever the host clock does, so compiled shapes repeat."""
    from repro.serve import AdmissionConfig

    return AdmissionConfig(max_delay_s=float("inf"), max_items=1 << 62)


def _session_id(fd) -> int:
    # ConstellationFeed carries the global id; ServedFeed the local one.
    return fd.gid if hasattr(fd, "gid") else fd.sid


def serve(recs, config, *, service=None):
    """Stream ``recs`` through one service; returns (parts, info, service).

    ``parts[i]`` lists station i's served results in order (the detach
    tail last). ``service`` defaults to a fresh ``DetectionService``; a
    ``ConstellationService`` works the same way (global ids)."""
    from repro.serve import DetectionService

    svc = service or DetectionService(config, admission=_admission())
    ids = [svc.attach(rec.name) for rec in recs]
    parts = {sid: [] for sid in ids}
    chunks = _chunks(recs)
    rounds = max(len(c) for c in chunks)
    for j in range(rounds):
        for sid, cl in zip(ids, chunks):
            if j < len(cl):
                for fd in svc.feed(sid, *cl[j]):
                    parts[_session_id(fd)].append(fd.result)
        for fd in svc.pump(force=True):
            parts[_session_id(fd)].append(fd.result)
    for sid in ids:  # ascending slots: no tier demotion before the last
        parts[sid].append(svc.detach(sid))
    info = {
        "stations": len(recs),
        "rounds": rounds,
        "events": int(sum(len(r) for r in recs)),
        "windows": int(sum(p.num_windows for sid in ids for p in parts[sid])),
    }
    return [parts[sid] for sid in ids], info, svc


def reference(recs, config):
    """``run_recording_scan`` of every recording on ``config``.

    Each recording's windows are right-padded with empty windows to the
    longest one's count, so all sixteen share one compiled scan, and the
    outputs are cut back to the real windows: the scan is causal, so the
    empty tail changes nothing before it."""
    import jax
    import numpy as np

    from repro.core.events import pad_windows
    from repro.core.pipeline import run_recording_scan

    wins = [pad_windows(r.x, r.y, r.t, r.p, config.batcher) for r in recs]
    w_max = max(w.num_windows for w in wins)
    out = []
    for rec, w in zip(recs, wins):
        n = w.num_windows
        pad = lambda a: np.pad(  # noqa: E731
            np.asarray(a), [(0, w_max - n)] + [(0, 0)] * (a.ndim - 1)
        )
        res = run_recording_scan(
            rec, config, windows=w._replace(batch=jax.tree.map(pad, w.batch))
        )
        cut = lambda a: a[:n]  # noqa: E731
        out.append(dataclasses.replace(
            res,
            clusters=jax.tree.map(cut, res.clusters),
            metrics={k: cut(v) for k, v in res.metrics.items()},
            tracks=jax.tree.map(cut, res.tracks),
            final_tracks=jax.tree.map(lambda a: a[n - 1], res.tracks),
        ))
    return out


def mismatches(parts, scan) -> list[str]:
    """Every output that differs between a session's concatenated served
    results and the scan (with the largest difference for numeric
    fields); empty when every bit agrees."""
    import jax
    import numpy as np

    cat = lambda xs: np.concatenate([np.asarray(x) for x in xs])  # noqa: E731
    checks = [
        ("windows", [sum(p.num_windows for p in parts)], [scan.num_windows]),
        ("t_start_us", cat(p.t_start_us for p in parts), scan.t_start_us),
        ("stops", cat(p.windows.stops for p in parts), scan.windows.stops),
    ]
    for f in scan.clusters._fields:
        checks.append((f"clusters.{f}", cat(getattr(p.clusters, f) for p in parts),
                       getattr(scan.clusters, f)))
    for k in scan.metrics:
        checks.append((f"metrics.{k}", cat(p.metrics[k] for p in parts),
                       scan.metrics[k]))
    for f in scan.tracks._fields:
        checks.append((f"tracks.{f}", cat(getattr(p.tracks, f) for p in parts),
                       getattr(scan.tracks, f)))
    last = jax.tree.leaves(parts[-1].final_tracks)
    for i, (a, b) in enumerate(zip(last, jax.tree.leaves(scan.final_tracks))):
        checks.append((f"final_tracks[{i}]", a, b))
    bad = []
    for name, got, want in checks:
        got = np.ascontiguousarray(got)
        want = np.ascontiguousarray(want)
        if got.shape != want.shape:
            bad.append(f"{name} shape {got.shape} != {want.shape}")
        elif not np.array_equal(got.view(np.uint8), want.view(np.uint8)):
            diff = np.abs(got.astype(np.float64) - want.astype(np.float64))
            bad.append(f"{name} max|diff| {np.nanmax(diff):.3g}")
    return bad


def score(recs, results, config):
    """Served-detection score over all stations: truth matching of each
    station's concatenated outputs, thresholded at ``min_events``."""
    import jax
    import numpy as np

    from repro.core.pipeline import match_candidates, merge_candidates, score_threshold

    cands = []
    for rec, parts in zip(recs, results):
        clusters = jax.tree.map(
            lambda *xs: np.concatenate([np.asarray(x) for x in xs]),
            *[p.clusters for p in parts],
        )
        cands.append(match_candidates(
            rec, clusters,
            np.concatenate([p.t_start_us for p in parts]),
            np.concatenate([p.windows.stops for p in parts]),
        ))
    return score_threshold(merge_candidates(cands), config.grid.min_events)


def step_compiles(traces):
    """Fleet-step traces -> ({tier: [window counts]}, repeated shapes)."""
    per_tier: dict[int, list[int]] = {}
    for s, w, _, _ in traces:
        per_tier.setdefault(s, []).append(w)
    repeats = sorted({t for t in traces if traces.count(t) > 1})
    return {s: sorted(ws) for s, ws in sorted(per_tier.items())}, repeats


def step_has_custom_call(config, traces) -> bool:
    """Whether the compiled fleet step (at the first traced shape) holds a
    Pallas TPU kernel rather than an interpreted one."""
    import jax
    import jax.numpy as jnp

    from repro.core.pipeline.fleet import make_fleet_fn
    from repro.core.pipeline.scan import atlas_shape
    from repro.core.tracking import init_tracks

    s, w, cap, uniform = traces[0]
    sds = jax.ShapeDtypeStruct
    tracks = jax.tree.map(
        lambda a: sds((s,) + a.shape, a.dtype), init_tracks(config.tracker)
    )
    lowered = make_fleet_fn(config).lower(
        sds((4, s, w, cap), jnp.int32), sds((s, w, cap), jnp.bool_), tracks,
        sds((s,) + atlas_shape(config), jnp.int32), sds((2, s), jnp.int32),
        uniform,
    )
    return "tpu_custom_call" in lowered.compile().as_text()


def run_route(name, recs, config, extra_refs=()):
    """One served phase on ``config``; returns its record (``ok`` plus
    every figure printed)."""
    from repro.core.pipeline import fleet as fleet_mod

    # A fresh step jit, so every compile this phase needs is traced (and
    # counted) here, whatever ran before it in the process.
    fleet_mod.make_fleet_fn.cache_clear()
    fleet_mod.STEP_TRACES.clear()
    results, info, _ = serve(recs, config)
    traces = list(fleet_mod.STEP_TRACES)
    rec = {"route": name, **info}
    mism = {}
    for ref_cfg in (config, *extra_refs):
        tag = "scan" if ref_cfg is config else f"scan[{ref_cfg.numerics}]"
        bad = [
            f"station{i}:{m}" for i, (parts, scan) in
            enumerate(zip(results, reference(recs, ref_cfg)))
            for m in mismatches(parts, scan)
        ]
        mism[tag] = bad
    sc = score(recs, results, config)
    per_tier, repeats = step_compiles(traces)
    rec.update(
        bit_identical={k: not v for k, v in mism.items()},
        mismatches={k: v[:12] for k, v in mism.items() if v},
        tp=sc.tp, fp=sc.fp, fn=sc.fn, tn=sc.tn, accuracy=sc.accuracy,
        step_compiles=per_tier, repeated_compiles=repeats,
    )
    if name in KERNEL_ROUTES:
        rec["tpu_custom_call"] = step_has_custom_call(config, traces)
    cpu = CPU_ACCURACY.get(name)
    rec["accuracy_ok"] = cpu is not None and sc.accuracy >= cpu
    rec["ok"] = (
        all(rec["bit_identical"].values())
        and rec["accuracy_ok"]
        and not repeats
        and rec.get("tpu_custom_call", True)
    )
    return rec


def run_four_chips(recs, devices):
    """The multi-chip path against one ``DetectionService`` on one chip."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.pipeline import FleetPipeline, PipelineConfig
    from repro.serve import ConstellationService

    config = PipelineConfig()
    one_chip, info, _ = serve(recs, config)
    cs = ConstellationService(
        config, n_shards=len(devices), devices=devices, admission=_admission()
    )
    shard_results, _, cs = serve(recs, config, service=cs)
    shard_devices = [
        sorted(d.id for d in cs.shard(i).service.atlas.devices())
        for i in range(cs.n_shards)
    ]
    fleet = FleetPipeline(
        config, n_sensors=len(recs), mesh=Mesh(np.array(devices), ("sensor",))
    )
    mesh_results = [[] for _ in recs]
    chunks = _chunks(recs)
    for j in range(max(len(c) for c in chunks)):
        out = fleet.feed([c[j] if j < len(c) else None for c in chunks])
        for i in range(len(recs)):
            mesh_results[i].append(out.sensor(i))
    tail = fleet.flush()
    for i in range(len(recs)):
        mesh_results[i].append(tail.sensor(i))
    atlas_devices = sorted(d.id for d in fleet.state.atlas.devices())

    def compare(results):
        bad = []
        for i, (got, want) in enumerate(zip(results, one_chip)):
            # The one-chip session's concatenation is the reference.
            ref = _concat(want)
            bad += [f"station{i}:{m}" for m in mismatches(got, ref)]
        return bad

    shard_bad = compare(shard_results)
    mesh_bad = compare(mesh_results)
    distinct = (
        all(len(ds) == 1 for ds in shard_devices)
        and len({ds[0] for ds in shard_devices}) == len(devices)
    )
    rec = {
        "route": "four_chips", **info,
        "shard_devices": shard_devices,
        "shards_on_distinct_chips": distinct,
        "constellation_bit_identical": not shard_bad,
        "mesh_devices": atlas_devices,
        "mesh_bit_identical": not mesh_bad,
        "mismatches": (shard_bad + mesh_bad)[:12],
    }
    rec["ok"] = (
        distinct and not shard_bad and not mesh_bad
        and len(atlas_devices) == len(devices)
    )
    return rec


def _concat(parts):
    """One ScanResult-shaped view of a session's served parts."""
    import jax
    import numpy as np

    cat = lambda *xs: np.concatenate([np.asarray(x) for x in xs])  # noqa: E731
    first = parts[0]
    windows = first.windows._replace(
        batch=jax.tree.map(cat, *[p.windows.batch for p in parts]),
        t_start_us=cat(*[p.windows.t_start_us for p in parts]),
        starts=cat(*[p.windows.starts for p in parts]),
        stops=cat(*[p.windows.stops for p in parts]),
        overflow=None,
    )
    return dataclasses.replace(
        first,
        t_start_us=cat(*[p.t_start_us for p in parts]),
        clusters=jax.tree.map(cat, *[p.clusters for p in parts]),
        metrics={k: cat(*[p.metrics[k] for p in parts]) for k in first.metrics},
        tracks=jax.tree.map(cat, *[p.tracks for p in parts]),
        final_tracks=parts[-1].final_tracks,
        windows=windows,
    )


def _print(rec):
    print(json.dumps(rec, default=str), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}; "
              "there is no CPU fallback", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    print(f"# device {dev.device_kind} x{len(devices)}; compile cache "
          f"{enable_compile_cache()}", flush=True)
    recs = make_stations()
    records = []
    phases = (
        [lambda: run_four_chips(recs, devices[:4])] if args.chips == 4 else [
            functools.partial(run_route, name, recs, config, extra)
            for name, (config, extra) in route_configs().items()
        ]
    )
    for phase in phases:
        t0 = time.perf_counter()
        records.append(phase())
        records[-1]["seconds"] = round(time.perf_counter() - t0, 1)
        _print(records[-1])
    stats = dev.memory_stats() or {}
    print(f"# peak_bytes_in_use {stats.get('peak_bytes_in_use')}", flush=True)
    if not all(r["ok"] for r in records):
        failed = [r["route"] for r in records if not r["ok"]]
        print(f"chip_smoke: FAILED {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
