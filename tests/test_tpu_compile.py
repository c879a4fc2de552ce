"""Compile the Pallas kernels and the fleet step for a described TPU v5e.

Nothing runs: the TPU compiler, installed with JAX, compiles for a
``v5e:2x2`` topology that is described, not attached, and refuses what
the chip would refuse (block shapes off the (8, 128) tiling, primitives
Mosaic cannot lower, programs past VMEM or HBM). Interpret mode — what
every other test runs — catches none of that. Each kernel is compiled
with ``interpret=False`` at the pipeline's real widths (E=256 events per
window, K=32 clusters, a 640x480 sensor with 16-px cells, 8 windows per
batch), and the fleet step at 16 slots on the default route and on each
kernel route, where ``vmap`` over the sensors adds a grid dimension to
every ``pallas_call``.

The topology is described inside a fixture (only one process at a time
may load the TPU library), and the persistent compilation cache is off
while it is in use: a program compiled for a described chip cannot be
read back without one.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.events import wire_pad
from repro.core.pipeline import PipelineConfig
from repro.core.pipeline import fleet as fleet_mod
from repro.core.pipeline.scan import atlas_shape
from repro.core.tracking import init_tracks
from repro.kernels import cluster_accum as ca
from repro.kernels import event_unpack as eu
from repro.kernels import ops
from repro.kernels import patch_metrics as pm
from repro.kernels import window_pipeline as wp

E, K, W_BATCH, SLOTS = 256, 32, 8, 16
FIXED = PipelineConfig(numerics="fixed")
ROUTES = {
    "default": PipelineConfig(),
    "use_kernels": PipelineConfig(use_kernels=True),
    "kernel": PipelineConfig(metrics_impl="kernel"),
    "megakernel": dataclasses.replace(FIXED, metrics_impl="megakernel"),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip
    )


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_case(name, sds):
    grid = PipelineConfig().grid
    i32, f32 = jnp.int32, jnp.float32
    if name == "cluster_accum":
        fn = lambda x, y, t, v: ca.cluster_accum(  # noqa: E731
            x, y, t, v, cell_size=grid.cell_size, grid_w=grid.grid_w,
            grid_h=grid.grid_h, width=grid.width, height=grid.height,
            interpret=False,
        )
        return fn, (sds((E,), i32), sds((E,), i32), sds((E,), f32),
                    sds((E,), f32))
    if name == "event_unpack":
        rows = wire_pad(SLOTS * W_BATCH * E) // eu.BLOCK_COLS
        fn = lambda w: eu.event_unpack(w, interpret=False)  # noqa: E731
        return fn, (sds((rows, eu.BLOCK_COLS), jnp.uint32),)
    if name == "patch_metrics":
        fn = lambda x, y, w, x0, y0: pm.patch_counts(  # noqa: E731
            x, y, w, x0, y0, interpret=False
        )
        return fn, (sds((E,), i32), sds((E,), i32), sds((E,), f32),
                    sds((K,), i32), sds((K,), i32))
    cfg, g = ROUTES["megakernel"], ROUTES["megakernel"].grid
    fn = lambda x, y, t, v: wp.window_pipeline(  # noqa: E731
        x, y, t, v, roi=tuple(cfg.roi), hot_pixel_max=cfg.hot_pixel_max,
        cell_size=g.cell_size, grid_w=g.grid_w, grid_h=g.grid_h,
        min_events=g.min_events, k=g.max_clusters, width=g.width,
        height=g.height, interpret=False,
    )
    return fn, tuple(sds((W_BATCH, E), i32) for _ in range(4))


@pytest.mark.parametrize(
    "name", ["cluster_accum", "event_unpack", "patch_metrics", "window_pipeline"]
)
def test_kernel_compiles_for_v5e(sds, name):
    fn, args = _kernel_case(name, sds)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_fleet_step_compiles_for_v5e(sds, monkeypatch, route):
    # The kernels pick interpret mode from the backend while they trace;
    # on this CPU host that would compile no kernel at all.
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    monkeypatch.setattr(fleet_mod, "STEP_TRACES", [])
    config = ROUTES[route]
    cap, s, w = config.batcher.capacity, SLOTS, 2
    tracks = jax.tree.map(
        lambda a: sds((s,) + a.shape, a.dtype), init_tracks(config.tracker)
    )
    # A fresh jit (not the cached make_fleet_fn) keeps this trace, made
    # with kernels forced on, out of every other test's step cache.
    step = fleet_mod.make_fleet_fn.__wrapped__(config)
    text = step.lower(
        sds((4, s, w, cap), jnp.int32), sds((s, w, cap), jnp.bool_), tracks,
        sds((s,) + atlas_shape(config), jnp.int32), sds((2, s), jnp.int32),
        False,
    ).compile().as_text()
    assert ("tpu_custom_call" in text) == (route != "default")
    if config.use_kernels:  # the ragged-wire decoder routes event_unpack
        n = wire_pad(s * w * cap)
        decode = fleet_mod.make_wire_fn.__wrapped__(cap, True)
        text = decode.lower(
            sds((n,), jnp.uint32), sds((n,), jnp.uint16),
            sds((n // 32,), jnp.uint32), sds((s, w + 1), jnp.int32),
            sds((5, 256), jnp.int32),
        ).compile().as_text()
        assert "tpu_custom_call" in text
