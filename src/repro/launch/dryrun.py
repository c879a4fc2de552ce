import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- everything below may import jax -------------------------------------
"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory/cost/roofline artifacts.

This file proves the distribution config is coherent without hardware:
``.lower().compile()`` must succeed for the 16x16 single-pod mesh AND the
2x16x16 multi-pod mesh for every assigned architecture x input shape.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out benchmarks/dryrun_results
"""
import argparse
import dataclasses
import json
import time
import traceback
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import LM_SHAPES, ModelConfig, applicable_shapes, get_config, list_archs
from repro.distributed import sharding as S
from repro.launch import roofline as R
from repro.launch.mesh import TARGET_DEVICE_KIND, make_production_mesh
from repro.models.transformer import decode_step, init_cache, init_params, prefill
from repro.train.optimizer import init_opt_state
from repro.train.train_step import TrainConfig, make_train_step

DEFAULT_OUT = Path("benchmarks/dryrun_results")


def sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ModelConfig, shape_name: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    spec = LM_SHAPES[shape_name]
    b, s = spec.global_batch, spec.seq_len
    if spec.kind == "decode":
        s_in = 1
    else:
        s_in = s
    inputs: dict = {}
    if cfg.frontend is not None:
        inputs["embeds"] = sds((b, s_in, cfg.d_model), jnp.bfloat16)
    else:
        inputs["tokens"] = sds((b, s_in), jnp.int32)
    if cfg.pos_kind == "mrope" and spec.kind != "decode":
        inputs["mrope_positions"] = sds((3, b, s_in), jnp.int32)
    return inputs


def _rules(mesh, kind: str, features: frozenset = frozenset()) -> S.ShardingRules:
    multi = "pod" in mesh.axis_names
    if kind == "train":
        return S.MULTIPOD_TRAIN_RULES if multi else S.TRAIN_RULES
    if "tp2d" in features:
        return S.MULTIPOD_SERVE_2D_RULES if multi else S.SERVE_2D_RULES
    return S.MULTIPOD_SERVE_RULES if multi else S.SERVE_RULES


def _batch_sharding(mesh, rules, tree):
    """NamedShardings for an input dict (batch-dim over dp)."""

    def leaf(path, x):
        name = path[-1].key if path else ""
        if name == "mrope_positions":
            spec = P(None, rules.dp if len(rules.dp) > 1 else rules.dp[0], None)
        else:
            spec = S.batch_spec(rules, extra_dims=x.ndim - 1)
        # divisibility fallback
        dp_size = 1
        for a in rules.dp:
            dp_size *= dict(zip(mesh.axis_names, mesh.devices.shape)).get(a, 1)
        bdim = 1 if name == "mrope_positions" else 0
        if x.shape[bdim] % dp_size != 0:
            spec = P(*([None] * x.ndim))
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(leaf, tree)


# Cache sharding rules by leaf name (right-aligned, divisibility-checked).
_CACHE_ROLES = {
    "k_page": ("dp", None, None, None),
    "v_page": ("dp", None, None, None),
    "page_pos": (None,),
    "k": ("dp", None, "tp", None),
    "v": ("dp", None, "tp", None),
    "c_kv": ("dp", None, "tp"),
    "k_rope": ("dp", None, None),
    "pos": (None,),
    "h": ("dp", "tp"),
    "conv": ("dp", None, "tp"),
    "c": ("dp", None, None, None),
    "n": ("dp", None, None),
    "m": ("dp", None),
}

# Hillclimb variant: shard the cache SEQUENCE dim over the model axis
# (context parallelism for decode). The head-count dim of GQA caches is
# rarely divisible by 16; the 32k sequence always is.
_CACHE_ROLES_SEQ = dict(
    _CACHE_ROLES,
    k=("dp", "tp", None, None),
    v=("dp", "tp", None, None),
    c_kv=("dp", "tp", None),
    k_rope=("dp", "tp", None),
)


def _cache_sharding(mesh, rules, cache_tree, roles_table=None):
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    table = roles_table or _CACHE_ROLES

    def role_axes(role):
        if role == "dp":
            return tuple(a for a in rules.dp if a in axis_sizes)
        if role == "tp":
            parts = rules.tp if isinstance(rules.tp, tuple) else (rules.tp,)
            return tuple(a for a in parts if a in axis_sizes)
        return ()

    def leaf(path, x):
        name = path[-1].key if path and isinstance(path[-1], jax.tree_util.DictKey) else ""
        roles = table.get(name)
        if roles is None:
            return NamedSharding(mesh, P())
        nd = x.ndim
        spec: list = [None] * nd
        for i, role in enumerate(roles):
            dim = nd - len(roles) + i
            if dim < 0 or role is None:
                continue
            axes = role_axes(role)
            total = 1
            for a in axes:
                total *= axis_sizes[a]
            if axes and x.shape[dim] % total == 0:
                spec[dim] = axes if len(axes) > 1 else axes[0]
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(leaf, cache_tree)


def build_cell(cfg: ModelConfig, shape_name: str, mesh, variant: str = ""):
    """Returns (fn, args, in_shardings, out_shardings, donate) for a cell.

    ``variant`` is a comma-separated optimization feature list recorded in
    EXPERIMENTS.md SPerf: cache_seq (sequence-parallel decode cache),
    serve_bf16 (bf16 weights for inference), tp2d (2D tensor parallelism
    for tiny-batch serving), moe_hint (MoE dispatch sharding constraints).
    """
    features = frozenset(f for f in variant.split(",") if f)
    import repro.models.moe as _moe
    _moe.USE_SHARDING_HINTS = "moe_hint" in features
    import repro.models.attention as _attn
    _attn.CACHE_DTYPE_DOTS = "bf16_dots" in features
    import repro.models.transformer as _tf
    _tf.PAGED_DECODE = 256 if "paged" in features else 0
    _attn.Q_CHUNK = 1024 if "flash_chunks" in features else 512
    _attn.KV_CHUNK = 4096 if "flash_chunks" in features else 1024
    spec = LM_SHAPES[shape_name]
    kind = spec.kind
    rules = _rules(mesh, kind, features)
    if "moe_ep_only" in features:
        rules = dataclasses.replace(rules, moe_ep_only=True)
    if "moe_hint" in features or "moe_ep_only" in features:
        pass  # hints flag handled above via USE_SHARDING_HINTS
    params_sds = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    if "serve_bf16" in features and kind != "train":
        params_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            params_sds,
        )
    cache_roles = _CACHE_ROLES_SEQ if "cache_seq" in features else None
    pspecs = S.partition_params(params_sds, rules, mesh)
    pshard = jax.tree.map(lambda sp: NamedSharding(mesh, sp), pspecs)
    inputs = input_specs(cfg, shape_name)
    in_batch_shard = _batch_sharding(mesh, rules, inputs)

    if kind == "train":
        batch = dict(inputs)
        b, s = spec.global_batch, spec.seq_len
        batch["labels"] = sds((b, s), jnp.int32)
        bshard = _batch_sharding(mesh, rules, batch)
        opt_sds = jax.eval_shape(partial(init_opt_state), params_sds)
        oshard = {
            "step": NamedSharding(mesh, P()),
            "mu": pshard,
            "nu": pshard,
        }
        fn = make_train_step(cfg, TrainConfig())
        metrics_sds = jax.eval_shape(fn, params_sds, opt_sds, batch)[2]
        mshard = jax.tree.map(lambda _: NamedSharding(mesh, P()), metrics_sds)
        return (
            fn,
            (params_sds, opt_sds, batch),
            (pshard, oshard, bshard),
            (pshard, oshard, mshard),
            (0, 1),
        )

    if kind == "prefill":
        fn = partial(prefill, cfg=cfg, cache_len=spec.seq_len)
        logits_sds, cache_sds = jax.eval_shape(fn, params_sds, inputs)
        cshard = _cache_sharding(mesh, rules, cache_sds, cache_roles)
        lshard = NamedSharding(
            mesh, S.batch_spec(rules, extra_dims=1)
            if logits_sds.shape[0] % _dp_size(mesh, rules) == 0 else P()
        )
        return (
            fn,
            (params_sds, inputs),
            (pshard, in_batch_shard),
            (lshard, cshard),
            (),
        )

    # decode
    cache_sds = jax.eval_shape(
        lambda: init_cache(cfg, spec.global_batch, spec.seq_len,
                           stacked="flat_cache" not in features)
    )
    if "serve_bf16" in features:
        cache_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16)
            if x.dtype == jnp.dtype("float32") and x.ndim >= 3 else x,
            cache_sds,
        )
    cshard = _cache_sharding(mesh, rules, cache_sds, cache_roles)
    pos_sds = sds((), jnp.int32)
    unroll_mode = "carry" if "cache_carry" in features else ("unroll" in features)
    fn = partial(decode_step, cfg=cfg, unroll=unroll_mode)
    lshard = NamedSharding(
        mesh, S.batch_spec(rules, extra_dims=1)
        if spec.global_batch % _dp_size(mesh, rules) == 0 else P()
    )
    return (
        fn,
        (params_sds, inputs, cache_sds, pos_sds),
        (pshard, in_batch_shard, cshard, NamedSharding(mesh, P())),
        (lshard, cshard),
        (2,),
    )


def _dp_size(mesh, rules) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    total = 1
    for a in rules.dp:
        total *= sizes.get(a, 1)
    return total


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path,
             variant: str = "") -> dict:
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.devices.size
    rec: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "variant": variant, "n_devices": int(n_dev), "ok": False,
    }
    t0 = time.time()
    try:
        fn, args, in_shardings, out_shardings, donate = build_cell(cfg, shape_name, mesh, variant)
        with jax.set_mesh(mesh):
            jitted = jax.jit(
                fn,
                in_shardings=in_shardings,
                out_shardings=out_shardings,
                donate_argnums=donate,
            )
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        print(mem)
        cost = compiled.cost_analysis()
        raw_cost = {
            k: v for k, v in (cost or {}).items() if k in ("flops", "bytes accessed")
        }
        print(raw_cost)
        terms = R.extract_terms(compiled, n_dev, TARGET_DEVICE_KIND)
        spec = LM_SHAPES[shape_name]
        tokens = spec.global_batch * (spec.seq_len if spec.kind != "decode" else 1)
        mf = R.model_flops(
            cfg.param_count(), tokens,
            cfg.active_param_count() if cfg.n_experts else None,
            kind=spec.kind,
        )
        rec.update(
            ok=True,
            t_lower_s=round(t_lower, 1),
            t_compile_s=round(t_compile, 1),
            memory={
                k: int(getattr(mem, k))
                for k in (
                    "argument_size_in_bytes",
                    "output_size_in_bytes",
                    "temp_size_in_bytes",
                    "peak_memory_in_bytes",
                )
                if hasattr(mem, k)
            },
            roofline=terms.as_dict(),
            raw_cost_analysis=raw_cost,
            model_flops=mf,
            useful_flops_ratio=(
                (mf / (terms.flops * n_dev)) if terms.flops else None
            ),
        )
    except Exception as e:  # noqa: BLE001 - record and continue
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["wall_s"] = round(time.time() - t0, 1)
    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = f"__{variant.replace(',', '+')}" if variant else ""
    fname = f"{arch.replace('/', '_')}__{shape_name}__{mesh_kind}{suffix}.json"
    (out_dir / fname).write_text(json.dumps(rec, indent=2))
    status = "OK" if rec["ok"] else f"FAIL ({rec.get('error', '')[:120]})"
    print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}: {status} "
          f"({rec['wall_s']}s)")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(DEFAULT_OUT))
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--variant", default="")
    args = ap.parse_args()
    out_dir = Path(args.out)

    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    n_ok = n_fail = 0
    for arch in archs:
        cfg = get_config(arch)
        shapes = (
            applicable_shapes(cfg) if (args.all or args.shape is None) else [args.shape]
        )
        for shape_name in shapes:
            for mesh_kind in meshes:
                suffix = f"__{args.variant.replace(',', '+')}" if args.variant else ""
                fname = out_dir / f"{arch.replace('/', '_')}__{shape_name}__{mesh_kind}{suffix}.json"
                if args.skip_existing and fname.exists():
                    prev = json.loads(fname.read_text())
                    if prev.get("ok"):
                        print(f"[dryrun] skip existing OK: {fname.name}")
                        n_ok += 1
                        continue
                rec = run_cell(arch, shape_name, mesh_kind, out_dir, args.variant)
                n_ok += rec["ok"]
                n_fail += not rec["ok"]
    print(f"[dryrun] done: {n_ok} ok, {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
