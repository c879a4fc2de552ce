"""ARACHNID-style multi-camera array as SPMD (paper Sec. V-D/V-E).

Each event camera pairs with one processing node; the paper scales 1->8
nodes with linear throughput and invariant latency (Table V). Here the
node axis is a JAX mesh axis: `shard_map` runs the SAME per-node pipeline
on every shard — one device = one EBC-FPGA node.

  PYTHONPATH=src python examples/multi_node_array.py --nodes 8
(requires XLA_FLAGS=--xla_force_host_platform_device_count=8; the script
sets it before importing jax.)
"""
import argparse
import os
import sys

N_NODES = 8
if "--nodes" in sys.argv:
    N_NODES = int(sys.argv[sys.argv.index("--nodes") + 1])
os.environ.setdefault(
    "XLA_FLAGS", f"--xla_force_host_platform_device_count={N_NODES}"
)

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core.events import EventBatch  # noqa: E402
from repro.core.grid_clustering import GridConfig, grid_cluster  # noqa: E402
from repro.data.synthetic import make_recording  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=N_NODES)
    ap.add_argument("--windows", type=int, default=64)
    args = ap.parse_args()
    nodes = min(args.nodes, jax.device_count())
    mesh = make_mesh((nodes,), ("node",))
    grid = GridConfig()

    # One synthetic recording per camera node, stacked: (nodes, W, E).
    print(f"Simulating {nodes} camera nodes x {args.windows} windows...")
    cap = 256
    batches = []
    for n in range(nodes):
        rec = make_recording(seed=100 + n, duration_s=args.windows * 0.02, n_rsos=1 + n % 3)
        from repro.core.events import window_batches
        xs = np.zeros((args.windows, cap), np.int32)
        ys = np.zeros((args.windows, cap), np.int32)
        ts = np.zeros((args.windows, cap), np.int32)
        ps = np.zeros((args.windows, cap), np.int32)
        vs = np.zeros((args.windows, cap), bool)
        for w, (b, _) in enumerate(window_batches(rec.x, rec.y, rec.t, rec.p, capacity=cap)):
            if w >= args.windows:
                break
            xs[w], ys[w], ts[w], ps[w], vs[w] = (
                np.asarray(b.x), np.asarray(b.y), np.asarray(b.t),
                np.asarray(b.p), np.asarray(b.valid),
            )
        batches.append((xs, ys, ts, ps, vs))
    stacked = EventBatch(*[
        jnp.asarray(np.stack([b[i] for b in batches])) for i in range(5)
    ])  # each leaf: (nodes, W, E)

    sharding = NamedSharding(mesh, P("node"))
    stacked = jax.tree.map(lambda x: jax.device_put(x, sharding), stacked)

    @jax.jit
    def per_node_pipeline(batch: EventBatch):
        # vmap over windows inside each node shard; shard_map over nodes.
        def node_fn(b):
            b = jax.tree.map(lambda a: a[0], b)  # shard-local node dim
            out = jax.vmap(lambda eb: grid_cluster(eb, grid).count)(b)
            return out[None]

        return jax.shard_map(
            node_fn, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P("node"), batch),),
            out_specs=P("node"),
        )(batch)

    counts = per_node_pipeline(stacked)
    counts.block_until_ready()
    t0 = time.time()
    counts = per_node_pipeline(stacked)
    counts.block_until_ready()
    dt = time.time() - t0
    ev_total = int(np.asarray(stacked.valid).sum())
    print(f"nodes={nodes} windows={args.windows} events={ev_total:,}")
    print(f"aggregate throughput: {ev_total / dt / 1e6:.2f} MEv/s "
          f"({dt * 1e3:.1f} ms for the array)")
    k = np.asarray(counts)
    print(f"clusters >= {grid.min_events} events: {(k >= grid.min_events).sum()} across array")


if __name__ == "__main__":
    main()
