"""Sharding rules, HLO cost analysis, and a small-mesh dry-run integration
test (the full 512-device dry-run runs via launch/dryrun.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (
    SERVE_RULES,
    TRAIN_RULES,
    MULTIPOD_TRAIN_RULES,
    partition_params,
)
from repro.launch.hlo_analysis import (
    Analyzer,
    _parse_shape,
    _shape_bytes,
    analyze,
    parse_module,
)

MOCK_HLO = """\
HloModule test

%wrapped_add (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  ROOT %add.1 = f32[8,8]{1,0} add(%p0, %p1)
}

%body (arg: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %arg = (s32[], f32[8,16]) parameter(0)
  %iv = s32[] get-tuple-element(%arg), index=0
  %x = f32[8,16]{1,0} get-tuple-element(%arg), index=1
  %w = f32[16,16]{1,0} constant({...})
  %dot.1 = f32[8,16]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ag = f32[8,16]{1,0} all-gather(%dot.1), channel_id=1, replica_groups=[4]<=[4], dimensions={1}
  %one = s32[] constant(1)
  %next = s32[] add(%iv, %one)
  ROOT %tup = (s32[], f32[8,16]) tuple(%next, %ag)
}

%cond (arg: (s32[], f32[8,16])) -> pred[] {
  %arg = (s32[], f32[8,16]) parameter(0)
  %iv = s32[] get-tuple-element(%arg), index=0
  %lim = s32[] constant(5)
  ROOT %lt = pred[] compare(%iv, %lim), direction=LT
}

ENTRY %main (a: f32[8,16], b: f32[8,8]) -> f32[8,16] {
  %a = f32[8,16]{1,0} parameter(0)
  %b = f32[8,8]{1,0} parameter(1)
  %zero = s32[] constant(0)
  %t = (s32[], f32[8,16]) tuple(%zero, %a)
  %loop = (s32[], f32[8,16]) while(%t), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %out = f32[8,16]{1,0} get-tuple-element(%loop), index=1
}
"""


def test_parse_shapes():
    assert _parse_shape("f32[8,16]{1,0}") == [("f32", [8, 16])]
    assert _parse_shape("(s32[], f32[2,3])") == [("s32", []), ("f32", [2, 3])]
    assert _shape_bytes([("bf16", [4, 4])]) == 32
    assert _shape_bytes([("s32", [])]) == 4


def test_analyzer_loop_multiplier():
    comps = parse_module(MOCK_HLO)
    assert set(comps) >= {"body", "cond", "main"}
    out = analyze(MOCK_HLO)
    # dot: 2*8*16*16 = 4096 flops x 5 trips = 20480
    assert out["flops"] == 4096 * 5
    # all-gather result 8*16*4 = 512B x 5 trips
    assert out["coll_breakdown"]["all-gather"] == 512 * 5


def test_analyzer_on_real_compiled_module():
    def f(w, x):
        def body(c, _):
            return jnp.tanh(c @ w), None
        c, _ = jax.lax.scan(body, x, None, length=9)
        return c.sum()

    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        jax.ShapeDtypeStruct((8, 64), jnp.float32),
    ).compile()
    out = analyze(compiled.as_text())
    expect = 2 * 8 * 64 * 64 * 9
    assert out["flops"] == pytest.approx(expect, rel=0.01)


# ---------------------------------------------------------------------------
# partition specs
# ---------------------------------------------------------------------------

class _FakeMesh:
    def __init__(self, shape, names):
        self.axis_names = names
        import numpy as _np

        self.devices = _np.empty(shape)


def test_partition_params_rules():
    mesh = _FakeMesh((16, 16), ("data", "model"))
    params = {
        "embed": jax.ShapeDtypeStruct((50304, 2560), jnp.float32),
        "cycles": {"blk0": {
            "inner": {"wq": jax.ShapeDtypeStruct((16, 2560, 2560), jnp.float32)},
            "moe": {"wi_gate": jax.ShapeDtypeStruct((16, 64, 2048, 1408), jnp.float32)},
            "norm1": jax.ShapeDtypeStruct((16, 2560), jnp.float32),
        }},
    }
    specs = partition_params(params, TRAIN_RULES, mesh)
    assert specs["embed"] == P("model", "data")
    # stacked scan dim -> leading None
    assert specs["cycles"]["blk0"]["inner"]["wq"] == P(None, "data", "model")
    # moe: experts over ep(model), fsdp on d
    assert specs["cycles"]["blk0"]["moe"]["wi_gate"] == P(None, "model", "data", None)
    assert specs["cycles"]["blk0"]["norm1"] == P()


def test_partition_divisibility_fallback():
    mesh = _FakeMesh((16, 16), ("data", "model"))
    params = {"embed": jax.ShapeDtypeStruct((73448, 2560), jnp.float32)}
    specs = partition_params(params, TRAIN_RULES, mesh)
    # 73448 % 16 != 0 -> vocab dim replicated, d still sharded
    assert specs["embed"] == P(None, "data")


def test_serve_rules_no_fsdp():
    mesh = _FakeMesh((16, 16), ("data", "model"))
    params = {"wq": jax.ShapeDtypeStruct((2048, 2048), jnp.float32)}
    assert partition_params(params, SERVE_RULES, mesh)["wq"] == P(None, "model")
    assert partition_params(params, TRAIN_RULES, mesh)["wq"] == P("data", "model")


# ---------------------------------------------------------------------------
# small-mesh dry-run integration (8 fake devices in a subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape_kind", ["train", "decode"])
def test_dryrun_small_mesh(subproc, shape_kind):
    out = subproc(f"""
import dataclasses, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import get_config
from repro.distributed import sharding as S
from repro.launch.mesh import make_mesh
from repro.launch.dryrun import _batch_sharding, _cache_sharding
from repro.models.transformer import init_params, init_cache, decode_step
from repro.train.optimizer import init_opt_state
from repro.train.train_step import TrainConfig, make_train_step
from functools import partial

cfg = dataclasses.replace(
    get_config("llama3.2-1b"), n_layers=4, d_model=256, n_heads=8,
    n_kv_heads=4, d_ff=512, vocab=1024, head_dim=32,
)
mesh = make_mesh((4, 2), ("data", "model"))
rules = S.TRAIN_RULES
params_sds = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
pspec = S.partition_params(params_sds, rules, mesh)
pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec)

kind = {shape_kind!r}
with jax.set_mesh(mesh):
    if kind == "train":
        batch = {{
            "tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
            "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32),
        }}
        bshard = _batch_sharding(mesh, rules, batch)
        opt_sds = jax.eval_shape(init_opt_state, params_sds)
        oshard = {{"step": NamedSharding(mesh, P()), "mu": pshard, "nu": pshard}}
        fn = make_train_step(cfg, TrainConfig())
        compiled = jax.jit(fn, in_shardings=(pshard, oshard, bshard)).lower(
            params_sds, opt_sds, batch).compile()
    else:
        cache_sds = jax.eval_shape(lambda: init_cache(cfg, 8, 128))
        cshard = _cache_sharding(mesh, S.SERVE_RULES, cache_sds)
        pshard = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            S.partition_params(params_sds, S.SERVE_RULES, mesh))
        inp = {{"tokens": jax.ShapeDtypeStruct((8, 1), jnp.int32)}}
        ishard = _batch_sharding(mesh, S.SERVE_RULES, inp)
        fn = partial(decode_step, cfg=cfg)
        compiled = jax.jit(
            fn, in_shardings=(pshard, ishard, cshard, NamedSharding(mesh, P())),
        ).lower(params_sds, inp, cache_sds, jax.ShapeDtypeStruct((), jnp.int32)).compile()
mem = compiled.memory_analysis()
assert mem is not None
print("DRYRUN-{shape_kind} OK")
""", device_count=8)
    assert f"DRYRUN-{shape_kind} OK" in out


def test_full_dryrun_results_are_green():
    """If the full-scale dry-run has produced results, none may be failed."""
    import json
    from pathlib import Path

    res = Path(__file__).resolve().parent.parent / "benchmarks" / "dryrun_results"
    files = list(res.glob("*.json"))
    if not files:
        pytest.skip("full dry-run not yet executed")
    bad = []
    for f in files:
        rec = json.loads(f.read_text())
        if not rec.get("ok"):
            bad.append((f.name, rec.get("error")))
    assert not bad, bad
