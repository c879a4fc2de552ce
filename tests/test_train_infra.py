"""Optimizer, checkpointing, fault tolerance, compression, serving."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis or deterministic fallback

from repro.distributed.compression import (
    dequantize_int8,
    ef_int8_roundtrip,
    quantize_int8,
)
from repro.distributed.fault_tolerance import (
    ElasticRunner,
    FailureEvent,
    HeartbeatMonitor,
    StragglerTracker,
)
from repro.train.checkpoint import CheckpointManager
from repro.train.optimizer import (
    OptConfig,
    adamw_update,
    clip_by_global_norm,
    init_opt_state,
    schedule,
)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_matches_manual_scalar():
    cfg = OptConfig(lr=0.1, b1=0.9, b2=0.99, eps=1e-8, weight_decay=0.0,
                    warmup_steps=0, total_steps=10**9, clip_norm=0.0)
    p = {"w": jnp.asarray(2.0)}
    g = {"w": jnp.asarray(0.5)}
    state = init_opt_state(p)
    new_p, state, _ = adamw_update(g, state, p, cfg)
    # manual: mu=0.05, nu=0.0025; mhat=0.5, vhat=0.25 -> upd = 0.5/0.5 = 1
    lr0 = float(schedule(jnp.asarray(1), cfg))
    assert float(new_p["w"]) == pytest.approx(2.0 - lr0 * 1.0, rel=1e-5)


def test_schedule_warmup_and_decay():
    cfg = OptConfig(lr=1e-3, warmup_steps=10, total_steps=100)
    lrs = [float(schedule(jnp.asarray(s), cfg)) for s in range(0, 101, 5)]
    assert lrs[0] == 0.0
    assert max(lrs) == pytest.approx(1e-3, rel=0.01)
    assert lrs[-1] == pytest.approx(1e-4, rel=0.05)  # min_lr_ratio


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 3.0), "b": jnp.full((4,), 4.0)}  # norm = 10
    clipped, norm = clip_by_global_norm(g, 5.0)
    assert float(norm) == pytest.approx(10.0, rel=1e-5)
    new_norm = float(
        jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(clipped)))
    )
    assert new_norm == pytest.approx(5.0, rel=1e-5)


def test_training_reduces_loss():
    from repro.launch.train import train

    _, log = train(arch="llama3.2-1b", preset="tiny", steps=30, batch=8,
                   seq=64, lr=3e-3, log_every=29)
    assert log[-1]["loss"] < log[0]["loss"] - 0.1
    assert np.isfinite(log[-1]["loss"])


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 8)), "b": jnp.zeros((8,))},
        "opt": {"step": jnp.asarray(3), "mu": {"w": jnp.ones((8, 8))}},
    }


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path, keep_n=2)
    state = _state()
    mgr.save(7, state, meta={"note": "test"})
    step, restored = mgr.restore(jax.tree.map(jnp.zeros_like, state))
    assert step == 7
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_checkpoint_async_and_gc(tmp_path):
    mgr = CheckpointManager(tmp_path, keep_n=2)
    for s in (1, 2, 3, 4):
        mgr.save_async(s, _state(s))
    mgr.wait()
    steps = sorted(int(p.name.split("_")[1]) for p in tmp_path.glob("step_*"))
    assert steps == [3, 4]  # keep_n=2
    assert mgr.latest_step() == 4


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, {"w": jnp.zeros((4,))})
    with pytest.raises(ValueError):
        mgr.restore({"w": jnp.zeros((5,))})


def test_checkpoint_elastic_restore_resharded(tmp_path, subproc):
    """Checkpoint written on 1 device restores onto an 8-device mesh with
    different sharding — the elastic-scaling path."""
    mgr = CheckpointManager(tmp_path)
    mgr.save(2, {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)})
    out = subproc(f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train.checkpoint import CheckpointManager
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
mgr = CheckpointManager({str(tmp_path)!r})
step, state = mgr.restore(
    {{"w": jnp.zeros((8, 8))}},
    shardings={{"w": NamedSharding(mesh, P("data", "model"))}},
)
assert step == 2
np.testing.assert_allclose(np.asarray(state["w"]).ravel(), np.arange(64))
print("SHARDS", len(state["w"].sharding.device_set))
""", device_count=8)
    assert "SHARDS 8" in out


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------

def test_heartbeat_monitor():
    t = [0.0]
    mon = HeartbeatMonitor(["n0", "n1"], timeout_s=10, clock=lambda: t[0])
    t[0] = 5.0
    mon.beat("n0")
    t[0] = 12.0
    assert mon.failed_nodes() == ["n1"]
    assert mon.healthy_nodes() == ["n0"]


def test_straggler_tracker():
    tr = StragglerTracker(factor=2.0)
    for _ in range(10):
        for n in ("a", "b", "c"):
            tr.record(n, 1.0)
        tr.record("slow", 5.0)
    assert tr.stragglers() == ["slow"]


def test_elastic_runner_recovers_from_failure(tmp_path):
    """Simulated node loss at step 7: runner rebuilds 'mesh', restores the
    step-5 checkpoint, and finishes all 12 steps."""
    ckpt = CheckpointManager(tmp_path, keep_n=3)
    fail_once = {"armed": True}

    def failure_hook(step):
        if step == 7 and fail_once["armed"]:
            fail_once["armed"] = False
            return FailureEvent(step, "node_lost", "simulated")
        return None

    def step_fn(state, batch):
        new = {"x": state["x"] + batch}
        return new, {"loss": float(batch), "x": float(new["x"])}

    runner = ElasticRunner(
        mesh_factory=lambda n_failures: f"mesh<{8 - n_failures}>",
        make_state=lambda mesh: {"x": jnp.asarray(0.0)},
        step_fn=step_fn,
        ckpt=ckpt,
        ckpt_every=5,
        failure_hook=failure_hook,
    )
    batches = [jnp.asarray(1.0)] * 12
    state, log = runner.run(batches)
    assert runner.restarts == 1
    assert [e.kind for e in runner.events] == ["node_lost"]
    # all 12 batches contributed exactly once in the final lineage:
    # steps 0..5 checkpointed, replay 6..11 => x == 12
    assert float(state["x"]) == 12.0


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------

def test_int8_quantization_error_bound():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(256,)).astype(np.float32))
    q, s = quantize_int8(x)
    err = float(jnp.abs(dequantize_int8(q, s) - x).max())
    assert err <= float(s) / 2 + 1e-7


def test_error_feedback_preserves_signal():
    """With EF, the *sum* of compressed grads over steps tracks the true
    sum (bias-free compression)."""
    rng = np.random.default_rng(1)
    g_true = jnp.asarray(rng.normal(size=(64,)).astype(np.float32) * 1e-3)
    opt_state = {}
    total = jnp.zeros((64,))
    for _ in range(50):
        g_c, opt_state = ef_int8_roundtrip({"g": g_true}, opt_state)
        total = total + g_c["g"]
    np.testing.assert_allclose(
        np.asarray(total), np.asarray(g_true * 50), rtol=0.02, atol=1e-4
    )


def test_ring_allreduce_int8_matches_mean(subproc):
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.distributed.compression import ring_allreduce_int8
mesh = make_mesh((4,), ("dp",))
x = np.random.default_rng(0).normal(size=(4, 128)).astype(np.float32)
fn = jax.shard_map(
    partial(ring_allreduce_int8, axis_name="dp", axis_size=4),
    mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
)
out = np.asarray(fn(jnp.asarray(x)))
expect = x.mean(0, keepdims=True)
for r in range(4):
    np.testing.assert_allclose(out[r], expect[0], atol=2 * np.abs(x).max() / 127)
print("RING OK")
""", device_count=4)
    assert "RING OK" in out


# ---------------------------------------------------------------------------
# serving engine (dual-threshold batching = the paper's policy)
# ---------------------------------------------------------------------------

def test_dual_threshold_batcher_semantics():
    from repro.serve.lm import DualThresholdBatcher, EngineConfig, Request

    t = [0.0]
    b = DualThresholdBatcher(
        EngineConfig(max_delay_s=0.02, max_batch=4), clock=lambda: t[0]
    )
    for i in range(3):
        b.submit(Request(rid=i, tokens=[1]))
    assert not b.ready()  # 3 < 4 and no time elapsed
    t[0] = 0.025
    assert b.ready()  # time threshold fired
    assert len(b.pop_batch()) == 3
    for i in range(5):
        b.submit(Request(rid=i, tokens=[1]))
    assert b.ready()  # size threshold fired immediately
    assert len(b.pop_batch()) == 4
    assert len(b.queue) == 1


def test_serving_engine_generates():
    from repro.launch.serve import serve_demo

    stats = serve_demo(arch="llama3.2-1b", n_requests=6, prompt_len=8,
                       max_new=4, max_batch=3)
    assert stats["requests"] == 6
    assert stats["tokens_generated"] == 24


def test_heartbeat_register_forget_roster():
    t = [0.0]
    mon = HeartbeatMonitor(timeout_s=10, clock=lambda: t[0])
    assert mon.nodes == []
    mon.register("a")
    t[0] = 2.0
    mon.register("b")
    assert "a" in mon and "ghost" not in mon
    assert mon.nodes == ["a", "b"]
    assert mon.last_beat_s("a") == 0.0 and mon.last_beat_s("b") == 2.0
    with pytest.raises(ValueError, match="already registered"):
        mon.register("a")
    with pytest.raises(KeyError, match="unregistered"):
        mon.beat("ghost")  # a typo'd id must not create a phantom node
    mon.forget("a")
    assert "a" not in mon
    with pytest.raises(KeyError):
        mon.forget("a")
    t[0] = 20.0
    assert mon.failed_nodes() == ["b"]  # forgotten nodes never count


def test_straggler_fleet_median_even_count_unbiased():
    tr = StragglerTracker()
    for i, v in enumerate([1.0, 2.0, 3.0, 4.0]):
        tr.record(f"n{i}", v)
    # Mean of the two middle EMAs — the upper-middle element alone (3.0)
    # would inflate the straggler threshold by 20% here.
    assert tr.fleet_median() == pytest.approx(2.5)


@settings(max_examples=50, deadline=None)
@given(st.lists(st.floats(0.1, 1e3), min_size=1, max_size=25))
def test_straggler_fleet_median_matches_numpy_oracle(vals):
    tr = StragglerTracker()
    for i, v in enumerate(vals):
        tr.record(i, v)  # first record seeds the EMA at the value itself
    assert tr.fleet_median() == pytest.approx(float(np.median(vals)))


def test_straggler_forget_and_ema_accessor():
    tr = StragglerTracker()
    tr.record("a", 1.0)
    tr.record("b", 100.0)
    assert tr.ema("b") == pytest.approx(100.0)
    assert tr.ema("ghost") is None
    tr.forget("b")
    assert tr.fleet_median() == pytest.approx(1.0)
    tr.forget("ghost")  # no-op, departed nodes may be forgotten twice
    assert tr.stragglers() == []
    assert StragglerTracker().fleet_median() == 0.0
