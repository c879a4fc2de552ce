"""Parameter/activation sharding rules (DP / FSDP / TP / EP).

Specs are derived per-leaf from the parameter's *name* (right-aligned
against the leaf shape so scan-stacking extra leading dims works
transparently) with divisibility checks against the concrete mesh: a dim
that does not divide by its axis size falls back to replication rather
than failing to lower. This keeps one rule set valid across all 10
architectures (40-head MLA, 12-head VLM, 4-head xLSTM, ...).

Axis semantics:
  dp   — batch data parallelism (('pod','data') on the multi-pod mesh)
  fsdp — weight/optimizer sharding over the data axis (ZeRO-3 style)
  tp   — tensor parallelism over the model axis; also hosts EP (experts)
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    dp: tuple[str, ...] = ("data",)
    fsdp: str | None = "data"
    tp: str | None = "model"
    ep: str | None = "model"
    # Pure expert parallelism: shard expert weights ONLY over ep. The
    # default additionally FSDPs the contracting d_model dim, which makes
    # every expert einsum a partial-sum all-reduce of the (E, C, ff)
    # dispatch tensor (EXPERIMENTS.md §Perf HC2).
    moe_ep_only: bool = False


TRAIN_RULES = ShardingRules()
MULTIPOD_TRAIN_RULES = ShardingRules(dp=("pod", "data"))
SERVE_RULES = ShardingRules(fsdp=None)
MULTIPOD_SERVE_RULES = ShardingRules(dp=("pod", "data"), fsdp=None)
# 2D tensor parallelism for tiny-batch serving (long-context decode with
# global_batch=1 leaves the data axis idle — fold it into TP).
SERVE_2D_RULES = ShardingRules(fsdp=None, tp=("model", "data"))
MULTIPOD_SERVE_2D_RULES = ShardingRules(
    dp=("pod",), fsdp=None, tp=("model", "data")
)


# Right-aligned axis-role specs per parameter name. Roles: 'fsdp', 'tp',
# 'ep', None. Names not listed replicate.
_BASE: dict[str, tuple] = {
    # embeddings / heads
    "embed": ("tp", "fsdp"),
    "lm_head": ("fsdp", "tp"),
    # attention
    "wq": ("fsdp", "tp"),
    "wk": ("fsdp", "tp"),
    "wv": ("fsdp", "tp"),
    "wo": ("tp", "fsdp"),
    # MLA
    "w_dq": ("fsdp", "tp"),
    "w_uq": ("fsdp", "tp"),
    "w_dkv": ("fsdp", "tp"),
    "w_uk": ("fsdp", "tp"),
    "w_uv": ("fsdp", "tp"),
    "w_kr": ("fsdp", None),
    # FFN
    "wi_gate": ("fsdp", "tp"),
    "wi_up": ("fsdp", "tp"),
    "w_down": ("tp", "fsdp"),
    # router
    "router": ("fsdp", None),
    # RG-LRU
    "w_gate_branch": ("fsdp", "tp"),
    "w_main": ("fsdp", "tp"),
    "w_input_gate": ("fsdp", "tp"),
    "w_rec_gate": ("fsdp", "tp"),
    "w_out": ("tp", "fsdp"),
    "conv_w": (None, "tp"),
    "conv_b": ("tp",),
    "log_lambda": ("tp",),
    # xLSTM
    "w_up": ("fsdp", "tp"),
    "w_up_gate": ("fsdp", "tp"),
    "w_igate": ("fsdp", None),
    "w_fgate": ("fsdp", None),
    "w_gates": ("fsdp", "tp"),
    "r_gates": (None, None, "tp"),
    "skip_scale": ("tp",),
}

# Names whose leaves live under a 'moe' subtree get an extra leading expert
# dim sharded over ep.
_MOE_BASE: dict[str, tuple] = {
    "wi_gate": ("ep", "fsdp", None),
    "wi_up": ("ep", "fsdp", None),
    "wo": ("ep", None, "fsdp"),
}

_MOE_BASE_EP_ONLY: dict[str, tuple] = {
    "wi_gate": ("ep", None, None),
    "wi_up": ("ep", None, None),
    "wo": ("ep", None, None),
}


def _role_to_axis(role, rules: ShardingRules):
    if role is None:
        return None
    return getattr(rules, role)


def _resolve(roles: tuple, shape: tuple[int, ...], rules: ShardingRules, axis_sizes: dict[str, int]) -> P:
    """Right-align roles against shape; drop non-dividing axes. Axis
    entries may be tuples (multi-axis sharding, e.g. 2D TP for serving)."""
    ndim = len(shape)
    spec: list = [None] * ndim
    for i, role in enumerate(roles):
        dim = ndim - len(roles) + i
        if dim < 0:
            continue
        axis = _role_to_axis(role, rules)
        if axis is None:
            continue
        parts = axis if isinstance(axis, tuple) else (axis,)
        present = tuple(a for a in parts if a in axis_sizes)
        if not present:
            continue
        size = 1
        for a in present:
            size *= axis_sizes[a]
        if shape[dim] % size != 0:
            continue
        spec[dim] = present if len(present) > 1 else present[0]
    return P(*spec)


def partition_params(
    params: Any, rules: ShardingRules, mesh: Mesh | None = None
) -> Any:
    """PartitionSpec tree for a parameter pytree (works on ShapeDtypeStructs)."""
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh is not None else {}

    def leaf_spec(path, leaf):
        names = [
            p.key for p in path if isinstance(p, jax.tree_util.DictKey)
        ]
        name = names[-1] if names else ""
        in_moe = "moe" in names[:-1]
        moe_table = _MOE_BASE_EP_ONLY if rules.moe_ep_only else _MOE_BASE
        table = moe_table if (in_moe and name in moe_table) else _BASE
        roles = table.get(name)
        if roles is None:
            return P()
        return _resolve(roles, leaf.shape, rules, axis_sizes)

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def batch_spec(rules: ShardingRules, extra_dims: int = 1) -> P:
    """Spec for (B, ...) inputs: batch over dp axes, rest replicated."""
    dp = rules.dp if len(rules.dp) > 1 else rules.dp[0]
    return P(dp, *([None] * extra_dims))


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


# ---------------------------------------------------------------------------
# Fleet (multi-sensor streaming) carry sharding.
# ---------------------------------------------------------------------------

# The streaming fleet engine stacks per-sensor carries (event atlas,
# tracker state) along a leading sensor dim and drives them through one
# vmapped step. Sensors are embarrassingly parallel — no cross-sensor
# collective anywhere in the step — so the whole carry shards 1:1 over a
# dedicated mesh axis and each device serves S / axis_size sensors.
SENSOR_AXIS = "sensor"


def shard_fleet_carry(tree: Any, mesh: Mesh | None) -> Any:
    """Place a stacked fleet carry pytree on ``mesh``, sensor-sharded.

    Every leaf has the sensor dim leading; leaves whose sensor count does
    not divide the axis (or meshes without a ``sensor`` axis) fall back
    to replication, mirroring :func:`partition_params`' divisibility
    rule. With ``mesh=None`` this is the identity, so the fleet engine
    runs unchanged on a single host.
    """
    if mesh is None or SENSOR_AXIS not in mesh.axis_names:
        return tree
    size = dict(zip(mesh.axis_names, mesh.devices.shape))[SENSOR_AXIS]

    def place(leaf):
        ok = getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] % size == 0
        return jax.device_put(
            leaf, NamedSharding(mesh, P(SENSOR_AXIS) if ok else P())
        )

    return jax.tree.map(place, tree)


def grow_fleet_carry(tree: Any, new_size: int, mesh: Mesh | None) -> Any:
    """Migrate a stacked fleet carry into a larger slot pool.

    Every leaf is zero-padded along the leading sensor dim to
    ``new_size`` (zeroed slots are exactly the fresh-sensor initial
    state) and the grown pytree is re-placed with
    :func:`shard_fleet_carry`, so a capacity-tier promotion keeps the
    carry sharded over the ``sensor`` axis — including the case where
    the old capacity did not divide the axis but the new one does.
    """

    def pad(leaf):
        extra = new_size - leaf.shape[0]
        if extra < 0:
            raise ValueError(
                f"fleet carry has {leaf.shape[0]} slots, cannot shrink to "
                f"{new_size}"
            )
        if extra == 0:
            return leaf
        return jnp.concatenate(
            [leaf, jnp.zeros((extra,) + leaf.shape[1:], leaf.dtype)], axis=0
        )

    return shard_fleet_carry(jax.tree.map(pad, tree), mesh)


def shrink_fleet_carry(tree: Any, new_size: int, mesh: Mesh | None) -> Any:
    """Migrate a stacked fleet carry into a *smaller* slot pool.

    The inverse of :func:`grow_fleet_carry`, for capacity-tier demotion
    after evictions shrink the live set: every leaf keeps its first
    ``new_size`` slots verbatim (the caller guarantees the dropped tail
    slots are free, i.e. already zeroed) and the sliced pytree is
    re-placed with :func:`shard_fleet_carry` so the demoted carry keeps
    sharding over the ``sensor`` axis.
    """
    if new_size < 1:
        raise ValueError(f"need at least one slot, got {new_size}")

    def cut(leaf):
        if leaf.shape[0] < new_size:
            raise ValueError(
                f"fleet carry has {leaf.shape[0]} slots, cannot take "
                f"{new_size}"
            )
        return leaf[:new_size]

    return shard_fleet_carry(jax.tree.map(cut, tree), mesh)


def hint_fleet(tree: Any) -> Any:
    """Sensor-axis sharding hint over every leaf of a stacked fleet pytree
    (identity without an active mesh; see :func:`hint`)."""
    return jax.tree.map(lambda a: hint(a, SENSOR_AXIS), tree)


def hint_wire(packed: jax.Array, valid: jax.Array, offsets: jax.Array):
    """Sensor-axis hints for the ragged-wire decoder surfaces.

    The 1-D wire streams (words/dt/pol/spill) are occupancy-ordered, not
    sensor-partitioned, so they stay replicated; the CSR ``offsets``
    (S, W+1) and the reconstructed dense ``packed`` (4, S, W, cap) /
    ``valid`` (S, W, cap) planes carry the sensor dim and shard over the
    ``sensor`` mesh axis like every other fleet carry leaf — the gather
    that builds them is then partitioned per device's sensor slice.
    Identity without an active mesh, like :func:`hint`.
    """
    return (
        hint(packed, None, SENSOR_AXIS),
        hint(valid, SENSOR_AXIS),
        hint(offsets, SENSOR_AXIS),
    )


# ---------------------------------------------------------------------------
# Activation sharding hints (no-ops without a mesh context).
# ---------------------------------------------------------------------------

def _current_axis_sizes() -> dict[str, int] | None:
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return None
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def hint(x: jax.Array, *axes) -> jax.Array:
    """with_sharding_constraint over the active mesh: identity without
    one, and axes absent from the mesh or not dividing the dim replicate.
    A constraint the mesh cannot take raises."""
    sizes = _current_axis_sizes()
    if sizes is None:
        return x
    spec: list = []
    for dim, a in enumerate(axes):
        if a is None:
            spec.append(None)
            continue
        parts = a if isinstance(a, tuple) else (a,)
        present = tuple(p for p in parts if p in sizes)
        total = 1
        for p in present:
            total *= sizes[p]
        if present and x.shape[dim] % total == 0:
            spec.append(present if len(present) > 1 else present[0])
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))
