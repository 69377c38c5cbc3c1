"""On-chip smoke test: the program's main path at smollm-135m's published
widths on a TPU, through the entry points a user calls.

    python chip_smoke.py                # one chip: kernels, train, serve
    python chip_smoke.py --four-chips   # four chips: shard_map vs stacked ring

One chip (the default):

1. device check — a TPU must be attached; ``REPRO_KERNEL_IMPL`` must be
   unset (it could route the kernels to their jnp oracles);
2. kernels vs their oracles on the chip — flash attention forward and its
   q/k/v gradients, paged decode at the serving shape;
3. train — ``repro.launch.train.main`` runs 3 full-width DRSGDA steps on 4
   node-stacked replicas; the same step is compiled once more to show that
   flash attention is a compiled Pallas kernel in it;
4. serve — ``repro.launch.serve.main`` answers a batch of requests through
   the paged decode service.

``--four-chips`` runs only the decentralized ring across a 4-device node
mesh: 8 smollm-135m replicas, 2 node rows per chip, gossiping through the
shard_map backend (fused ring kernel), compared with the stacked backend on
the same mesh, in fp32 and with int8-compressed hops.

Everything runs in this one process, which holds the chip; a failing
phase ends the run with a non-zero exit code.  The last line of standard
output is the result, ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import math
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Oracle comparisons draw inputs that bf16 represents exactly and evaluate
# the oracle at HIGHEST matmul precision.  Then the kernels' q.k products
# are exact even if the MXU takes f32 operands in one bf16 pass, and what
# is left is the rounding of the softmax weights before p @ v: at most
# 2**-9 relative per weight, so |err| <= 2**-9 * max|v| ~ 9e-3 for unit
# normal v (max ~4.5 over these draws).  A mis-tiled or mis-masked kernel
# is off by O(0.1) or more.
KERNEL_TOL = 1e-2
# The Pallas path's backward is the VJP of the same oracle at the same
# q, k, v, so only the fusion of two XLA programs differs: f32 rounding.
GRAD_RTOL = 1e-4
# Training feasibility bar, as repro.launch.train checks it.
STIEFEL_TOL = 1e-2
# shard_map vs stacked, relative, on loss and consensus.  Both compute the
# same mix and differ only in the order of f32 adds across the exchange
# (~1e-7), but the model's matmuls take one bf16 pass on the chip, where a
# 1-ulp change can flip a weight's bf16 rounding; the step amplifies that
# to ~1e-5 in the loss and ~1e-4 in consensus (a difference of nearly equal
# replicas) within 2 steps.  int8 hops draw a stochastic rounding per
# element, where such a change can move an element by one quantization
# step (1/127 of its row's max).  A wrong neighbour, weight or row moves
# consensus by O(1e-2) or more.
RING_RTOL = {"fp32": 1e-3, "int8": 1e-2}

ARCH = "smollm-135m"
NODES, BATCH, SEQ, STEPS = 4, 2, 512, 3          # train: 4 x (2 x 512)
SLOTS, PROMPT, NEW, PAGE = 4, 128, 32, 128       # serve
TRAIN_ARGS = ["--arch", ARCH, "--optimizer", "drsgda", "--nodes", str(NODES),
              "--batch-per-node", str(BATCH), "--seq-len", str(SEQ),
              "--steps", str(STEPS), "--eval-every", str(STEPS)]
SERVE_ARGS = ["--arch", ARCH, "--batch", str(SLOTS), "--prompt-len",
              str(PROMPT), "--new-tokens", str(NEW), "--page-size", str(PAGE)]


class PhaseError(RuntimeError):
    pass


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


def _capture(fn, argv) -> tuple[int, list[str]]:
    """Run an entry point's ``main(argv)``, echo its output, return
    (rc, output lines)."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = fn(argv)
    lines = buf.getvalue().splitlines()
    for line in lines:
        print(f"  | {line}")
    return rc, lines


def device_check(n_chips: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    print(f"device platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    _check(d.platform == "tpu", f"no TPU attached (platform {d.platform!r})")
    _check(len(devs) >= n_chips, f"{n_chips} chips needed, {len(devs)} found")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def _bf16_exact(key, shape):
    import jax
    import jax.numpy as jnp
    return jax.random.normal(key, shape, jnp.float32) \
        .astype(jnp.bfloat16).astype(jnp.float32)


def kernels_phase(cfg) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    h, hkv, hd, b, s = cfg.n_heads, cfg.n_kv_heads, cfg.hd, BATCH, SEQ
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    q = _bf16_exact(ks[0], (b, s, h, hd))
    k = _bf16_exact(ks[1], (b, s, hkv, hd))
    v = _bf16_exact(ks[2], (b, s, hkv, hd))
    w = _bf16_exact(ks[3], (b, s, h, hd))

    def fwd(impl):
        return jax.jit(lambda q, k, v: ops.flash_attention(q, k, v,
                                                           impl=impl))

    def loss(impl):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(ops.flash_attention(q, k, v, impl=impl)
                                    * w), argnums=(0, 1, 2)))

    got = fwd("pallas")(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = fwd("ref")(q, k, v)
    err = float(jnp.max(jnp.abs(got - want)))
    print(f"flash_attention fwd (B={b} S={s} H={h} Hkv={hkv} hd={hd}): "
          f"max_abs_err={err!r} tol={KERNEL_TOL}", flush=True)
    _check(err <= KERNEL_TOL, "flash attention forward disagrees with ref")

    with jax.default_matmul_precision("highest"):
        g_got = loss("pallas")(q, k, v)
        g_want = loss("ref")(q, k, v)
    for name, a, r in zip("qkv", g_got, g_want):
        err = float(jnp.max(jnp.abs(a - r)))
        scale = float(jnp.max(jnp.abs(r)))
        print(f"flash_attention d{name}: max_abs_err={err!r} "
              f"max_abs_ref={scale!r} rtol={GRAD_RTOL}", flush=True)
        _check(err <= GRAD_RTOL * scale and math.isfinite(scale),
               f"flash attention d{name} disagrees with ref")

    # the serving shape of SERVE_ARGS (the pool sized as serve.py sizes it)
    slots, ps, m = SLOTS, PAGE, -(-(PROMPT + NEW) // PAGE)
    n_pages = slots * m * 2 + 1
    qd = _bf16_exact(ks[4], (slots, h, hd))
    kp = _bf16_exact(ks[5], (n_pages, hkv, hd, ps))
    vp = _bf16_exact(ks[6], (n_pages, hkv, hd, ps))
    seq = np.asarray([m * ps, PROMPT + 1, PROMPT // 2 + 13, 1], np.int32)
    bt = np.full((slots, m), -1, np.int32)
    nxt = 1
    for i, n in enumerate(seq):
        for j in range(-(-int(n) // ps)):
            bt[i, j] = nxt
            nxt += 2                    # scattered, not contiguous, pages
    bt, seq = jnp.asarray(bt), jnp.asarray(seq)

    def paged(impl):
        return jax.jit(lambda *a: ops.paged_decode_attention(*a, impl=impl))

    got = paged("pallas")(qd, kp, vp, bt, seq)
    with jax.default_matmul_precision("highest"):
        want = paged("ref")(qd, kp, vp, bt, seq)
    err = float(jnp.max(jnp.abs(got - want)))
    print(f"paged_decode (slots={slots} page={ps} pages/slot={m} H={h} "
          f"Hkv={hkv} hd={hd}): max_abs_err={err!r} tol={KERNEL_TOL}",
          flush=True)
    _check(err <= KERNEL_TOL, "paged decode disagrees with its oracle")


def train_phase(cfg) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.gda import GDAHyper
    from repro.launch import roofline, train
    from repro.launch.steps import TrainSpec, abstract_train_state, \
        build_trainer

    rc, lines = _capture(train.main, TRAIN_ARGS)
    rows = [json.loads(x) for x in lines if x.startswith('{"step"')]
    _check(rc == 0 and bool(rows), f"train.main returned {rc}")
    last = rows[-1]
    print(f"train: steps={last['step']} nodes={NODES} loss={last['loss']!r} "
          f"stiefel_residual={last['stiefel_residual']!r}", flush=True)
    _check(last["step"] == STEPS, f"train did not take {STEPS} steps")
    _check(math.isfinite(last["loss"]), "train loss is not finite")
    _check(last["stiefel_residual"] < STIEFEL_TOL,
           "train left the Stiefel manifold")

    # the same step (train.py's default hyper), lowered and compiled once
    # more to inspect it
    hyper = GDAHyper(alpha=0.5, beta=0.02, eta=0.05)
    opt, _ = build_trainer(cfg, NODES, TrainSpec(optimizer="drsgda",
                                                 hyper=hyper))
    batch = {"tokens": jax.ShapeDtypeStruct((NODES, BATCH, SEQ), jnp.int32),
             "group_ids": jax.ShapeDtypeStruct((NODES, BATCH), jnp.int32)}
    state = abstract_train_state(cfg, opt, NODES, batch)
    compiled = opt.make_step(donate=True).lower(state, batch).compile()
    calls = roofline.kernel_calls(compiled.as_text())
    mem = compiled.memory_analysis()
    print(f"train step HLO: tpu_custom_call {calls}; arguments "
          f"{mem.argument_size_in_bytes} B, temporaries "
          f"{mem.temp_size_in_bytes} B", flush=True)
    _check(calls.get("flash_attention", 0) > 0,
           "flash attention is not a Pallas kernel in the train step")


def serve_phase() -> None:
    from repro.launch import serve

    rc, lines = _capture(serve.main, SERVE_ARGS)
    _check(rc == 0 and bool(lines), f"serve.main returned {rc}")
    res = json.loads(lines[-1])
    want = SLOTS * NEW
    print(f"serve: mode={res['mode']} tokens={res.get('tokens')} "
          f"(want {want})", flush=True)
    _check(res["mode"] == "paged", "serve did not take the paged path")
    _check(res.get("tokens") == want, "serve returned the wrong token count")


def _ring_programs(cfg, mesh, nodes: int, params, batch0):
    """One mode's programs, compiled together: the initial state
    (``launch.steps.init_train_state`` from the given weights, laid out by
    ``partition.train_state_shardings``) and the DRSGDA step of each
    backend.  Both backends start from the same state: it does not depend
    on how mixes run.  The compiles run in threads, as XLA compiles
    without the GIL and an int8 step takes the compiler minutes."""
    import concurrent.futures as cf

    import jax

    from repro.core.gda import broadcast_to_nodes
    from repro.launch.steps import TrainSpec, abstract_train_state, \
        build_trainer
    from repro.objectives import lm as lm_obj
    from repro.sharding import partition

    opts = {b: build_trainer(cfg, nodes, TrainSpec(
                optimizer="drsgda", mix_backend=b), mesh=mesh)[0]
            for b in ("shard_map", "stacked")}
    opt = opts["shard_map"]
    shapes = abstract_train_state(cfg, opt, nodes, batch0)
    shardings = partition.train_state_shardings(shapes, mesh, False)
    state = jax.tree.map(lambda a, sh: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sh), shapes, shardings)

    def init(params, b0):
        params = partition.project_params_to_manifold(
            params, opt.problem.manifold_map)
        return opt.init(broadcast_to_nodes(params, nodes),
                        lm_obj.init_y(cfg, nodes), b0)

    lowered = {"init": jax.jit(init, out_shardings=shardings).lower(
        params, batch0)}
    for b, o in opts.items():
        lowered[b] = o.make_step(donate=True).lower(state, batch0)
    with cf.ThreadPoolExecutor(len(lowered)) as pool:
        futures = {k: pool.submit(low.compile) for k, low in lowered.items()}
        compiled = {k: f.result() for k, f in futures.items()}
    return opts, compiled


def _ring_run(opt, step, state, batches, show_shardings: bool) -> list[dict]:
    """DRSGDA steps from ``state``; per-step loss and consensus."""
    import jax

    from repro.sharding import partition

    print(f"  backend={opt.backend!r}", flush=True)
    if show_shardings:
        for path, leaf in jax.tree_util.tree_leaves_with_path(state):
            print(f"  {partition.path_of(path)} {leaf.shape} "
                  f"{leaf.sharding.spec} on "
                  f"{len(leaf.sharding.device_set)} devices")
    rows = []
    for t, batch in enumerate(batches):
        state, met = step(state, batch)
        rows.append({"step": t + 1, "loss": float(met.loss),
                     "consensus_x": float(met.consensus_x)})
    return rows


def four_chip_phase(cfg) -> None:
    import jax
    import jax.numpy as jnp

    from repro.data.synthetic import TokenStream
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as T
    from repro.sharding import partition

    nodes = 8
    mesh = make_host_mesh(node=4)
    print(f"ring: {nodes} nodes over mesh {dict(mesh.shape)}", flush=True)
    stream = TokenStream(n_nodes=nodes, batch_per_node=BATCH, seq_len=SEQ,
                         vocab_size=cfg.vocab_size, n_groups=cfg.n_groups,
                         seed=0)

    def batch_at(t):
        host = {k: jnp.asarray(v) for k, v in stream.batch(t).items()}
        return jax.device_put(
            host, partition.train_batch_shardings(host, mesh, False))

    batches = [batch_at(t) for t in range(STEPS + 1)]
    # the random weights are drawn once, eagerly as launch/train.py draws
    # them (one jitted draw of all 135M takes the chip's compiler ~80 s),
    # and kept on the host between runs
    params = jax.device_get(T.init_params(jax.random.PRNGKey(0), cfg))
    # int8 first: it is the longer compile
    for mode in ("int8", "fp32"):
        mcfg = cfg if mode == "fp32" else dataclasses.replace(
            cfg, comm_compressor="int8", comm_quant_hops="all")
        opts, programs = _ring_programs(mcfg, mesh, nodes, params,
                                        batches[0])
        runs = {b: _ring_run(opt, programs[b],
                             programs["init"](params, batches[0]),
                             batches[1:], show_shardings=(
                                 mode == "fp32" and b == "shard_map"))
                for b, opt in opts.items()}
        for a, r in zip(runs["shard_map"], runs["stacked"]):
            for key in ("loss", "consensus_x"):
                diff = abs(a[key] - r[key])
                rel = diff / max(abs(r[key]), 1e-30)
                print(f"ring {mode} step {a['step']} {key}: "
                      f"shard_map={a[key]!r} stacked={r[key]!r} "
                      f"abs_diff={diff!r} rel_diff={rel!r} "
                      f"rtol={RING_RTOL[mode]}", flush=True)
        for a, r in zip(runs["shard_map"], runs["stacked"]):
            for key in ("loss", "consensus_x"):
                rel = abs(a[key] - r[key]) / max(abs(r[key]), 1e-30)
                _check(math.isfinite(a[key]) and rel <= RING_RTOL[mode],
                       f"shard_map and stacked disagree ({mode}, {key}, "
                       f"step {a['step']})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 8-node ring across four chips")
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_KERNEL_IMPL"):
        print("chip_smoke: unset REPRO_KERNEL_IMPL; it overrides the "
              "kernel dispatch under test", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro beside {__file__}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = configs.get_config(ARCH)
    try:
        device = device_check(4 if args.four_chips else 1)
        if args.four_chips:
            four_chip_phase(cfg)
        else:
            kernels_phase(cfg)
            train_phase(cfg)
            serve_phase()
    except PhaseError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
