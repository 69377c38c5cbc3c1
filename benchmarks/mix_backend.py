"""Mix-backend benchmark: stacked vs shard_map (fused / unfused) gossip.

For a sweep of per-node model sizes, times jitted ``W^k`` mixes under the
stacked backend and BOTH shard_map schedules on an 8-virtual-device node
mesh — ``shard_map`` is the fused halo-panel megakernel path (one Pallas
launch for all k hops), ``shard_map_unfused`` the hop-by-hop schedule it
replaced — and reports hops/sec plus each backend's *estimated bytes moved
per hop*.  A second sweep holds the size at ``tiny_64k`` (where launch
latency dominates and the fusion matters most) and scales the hop count
k in {1, 2, 3, 5} for all three schedules.  The unfused column is ring-only:
dense topologies take the all-gather path, identical under both flags.

Because the device count must be forced before jax initializes, ``run()``
re-executes this file in a worker subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` and collects JSON
from stdout; ``benchmarks/run.py mix`` saves it to
``experiments/bench/mix_backend.json``.

On this CPU container the timing is a *schedule* benchmark (one host backs
all 8 devices, so wall-clock gains are modest); the bytes-per-hop column is
the hardware-independent signal the perf trajectory tracks.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_DEVICES = 8
N_NODES = 16          # two node rows per device: only edge rows hit the wire
STEPS = 3
REPEATS = 6           # timed mixes per block
BLOCKS = 5            # best-of-BLOCKS guards against host load spikes

# per-node leaf layouts: (name, [(leaf shape sans node axis), ...])
SIZES = [
    ("tiny_64k", [(128, 128), (16384,)]),
    ("small_512k", [(256, 512), (8, 128, 128), (131072,)]),
    ("medium_2m", [(512, 1024), (16, 256, 256), (524288,)]),
]


def _worker() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.comms.backend import ShardMapBackend, StackedBackend
    from repro.core.gossip import GossipSpec

    mesh = Mesh(np.asarray(jax.devices())[:N_DEVICES].reshape(N_DEVICES),
                ("node",))
    backends = {"stacked": StackedBackend(),
                "shard_map": ShardMapBackend(mesh, axis="node", fuse="on"),
                "shard_map_unfused": ShardMapBackend(mesh, axis="node",
                                                     fuse="off")}

    def _make_tree(leaf_shapes):
        key = jax.random.PRNGKey(0)
        return {f"l{i}": jax.random.normal(jax.random.fold_in(key, i),
                                           (N_NODES, *shp), jnp.float32)
                for i, shp in enumerate(leaf_shapes)}

    def _time_row(size, tree, topology, bname, be, k):
        spec = GossipSpec(topology=topology, n_nodes=N_NODES, k_steps=k)
        fn = jax.jit(lambda t, _be=be, _s=spec, _k=k: _be.mix(_s, t, _k))
        out = jax.block_until_ready(fn(tree))       # compile + warm
        dt = float("inf")
        for _ in range(BLOCKS):
            t0 = time.time()
            for _ in range(REPEATS):
                out = jax.block_until_ready(fn(out))
            dt = min(dt, (time.time() - t0) / REPEATS)
        params = sum(int(l.size) for l in jax.tree.leaves(tree)) // N_NODES
        return {
            "size": size, "params_per_node": params,
            "topology": topology, "backend": bname, "k": k,
            "us_per_mix": dt * 1e6,
            "hops_per_sec": k / dt,
            "est_bytes_per_hop": be.est_hop_bytes(spec, tree),
        }

    rows = []
    t_all = time.time()
    for name, leaf_shapes in SIZES:
        tree = _make_tree(leaf_shapes)
        for topology in ("ring", "full"):
            for bname, be in backends.items():
                if bname == "shard_map_unfused" and topology != "ring":
                    continue    # dense path is flag-independent
                rows.append(_time_row(name, tree, topology, bname, be,
                                      STEPS))
    # hop-count sweep at the latency-dominated size: hops/sec vs k
    sweep_tree = _make_tree(dict(SIZES)["tiny_64k"])
    k_sweep = []
    for k in (1, 2, 3, 5):
        for bname, be in backends.items():
            k_sweep.append(_time_row("tiny_64k", sweep_tree, "ring",
                                     bname, be, k))
    return {"n_devices": N_DEVICES, "n_nodes": N_NODES,
            "rows": rows, "k_sweep": k_sweep,
            "us_total": (time.time() - t_all) * 1e6}


def run() -> dict:
    """Time the sweep in an 8-virtual-device CPU worker process.

    A process that holds a TPU cannot hand it to a child, so on a TPU
    backend this raises at once instead of spawning the worker."""
    import jax
    if jax.default_backend() == "tpu":
        raise RuntimeError("benchmarks/mix_backend.py times 8 virtual CPU "
                           "devices in a child process; it cannot run while "
                           "this process holds a TPU")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count="
                         f"{N_DEVICES}",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(_REPO_ROOT, "src"), _REPO_ROOT]))
    out = subprocess.run([sys.executable, os.path.abspath(__file__),
                          "--worker"], env=env, capture_output=True,
                         text=True, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(f"mix_backend worker failed:\n{out.stderr[-3000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


if __name__ == "__main__":
    if "--worker" in sys.argv:
        for _p in (os.path.join(_REPO_ROOT, "src"), _REPO_ROOT):
            if _p not in sys.path:
                sys.path.insert(0, _p)
        print(json.dumps(_worker()))
    else:
        print(json.dumps(run(), indent=1))
