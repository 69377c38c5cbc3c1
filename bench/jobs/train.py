"""Training cell: the program's DRSGDA step, driven back to back.

Set-up builds one trainer (``build_trainer`` + ``make_step(donate=True)``),
draws the weights from the seed on the device and initializes the state
in one jitted call, then drives that same step through its first
``check_steps`` steps on fresh batches placed as an input pipeline places
them.  Those steps compile the step and give the readings the plain
reference is compared with: each step's loss, per-leaf norms of the
gradient the optimizer holds after step 1, and per-leaf norms of the
change of the parameters after the last of them.  The window then runs
the same step on from there until ``seconds`` have passed, each step's
batch placed before it is dispatched, the previous step's loss awaited
after it (one step in flight).
"""
from __future__ import annotations

import statistics
import time

import jax
import jax.numpy as jnp

from bench import flops, harness, traffic, weights
from bench.reference import drsgda, llama
from bench.trace import WINDOW

#: the numbers compared with the reference, each with a limit in
#: ``bench/limits/<cell>.json``
READINGS = ("loss_rel", "grad_norm_gap", "change_norm_gap")


def _place(batch: dict) -> dict:
    return {k: jax.device_put(v) for k, v in batch.items()}


def build(cell: harness.Cell, seed: int):
    """The trainer, its jitted step, the feed and the initial state."""
    from repro.core.gda import GDAHyper, broadcast_to_nodes
    from repro.launch.steps import TrainSpec, build_trainer
    from repro.models import transformer as T

    tr = cell.traffic
    sz = weights.sizes_of(cell.config)
    cfg = harness.program_config(cell, n_groups=tr["n_groups"],
                                 rho=tr["rho"])
    nodes, groups = tr["nodes"], tr["n_groups"]
    opt, _ = build_trainer(cfg, nodes, TrainSpec(
        optimizer=tr["optimizer"], topology=tr["topology"],
        mix_backend=tr["mix_backend"], hyper=GDAHyper(**tr["hyper"])))
    weights.check_layout(
        jax.eval_shape(lambda k: weights.draw(k, sz, jnp.float32, True),
                       jax.random.PRNGKey(0)),
        jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.PRNGKey(0)))

    @jax.jit
    def init(key, batch0):
        params = weights.draw(key, sz, jnp.float32, orthonormal=True)
        y0 = jnp.full((nodes, groups), 1.0 / groups, jnp.float32)
        return opt.init(broadcast_to_nodes(params, nodes), y0, batch0)

    feed = traffic.TokenFeed(nodes=nodes, batch_per_node=tr["batch_per_node"],
                             seq_len=tr["seq_len"], vocab=sz["vocab"],
                             n_groups=groups, hetero=tr["hetero"], seed=seed)
    key = weights.key_for(seed)
    state = init(key, _place(feed.batch(0)))
    return sz, opt.make_step(donate=True), feed, key, state


def first_steps(step, state, feed, key, sz, tr) -> tuple:
    """The first ``check_steps`` steps, with the readings taken on the way."""
    read = {"loss": []}
    for t in range(1, tr["check_steps"] + 1):
        state, met = step(state, _place(feed.batch(t)))
        read["loss"].append(float(met.loss))
        if t == 1:
            read["grad"] = drsgda.leaf_norms(state.gx_prev)
            read["grad"]["y"] = drsgda.leaf_norms(state.gy_prev)[""]
    x0 = weights.draw_on_device(key, sz, jnp.float32, orthonormal=True)
    read["change"] = drsgda.change_norms(state.x, state.y, x0,
                                         tr["n_groups"])
    return state, read


def reference(feed, key, sz, tr, num=llama.F32) -> dict:
    batches = [tuple(jnp.asarray(b[k]) for k in ("tokens", "group_ids"))
               for b in (feed.batch(t) for t in range(tr["check_steps"] + 1))]
    params0 = weights.draw_on_device(key, sz, jnp.float32, orthonormal=True)
    return drsgda.run(params0, batches, sz, tr["hyper"], tr["nodes"],
                      tr["n_groups"], tr["rho"], num=num,
                      steps=tr["check_steps"])


def _worst_leaf_gap(got: dict, want: dict, keys) -> float:
    med = statistics.median(want[k] for k in keys)
    return max(abs(got[k] - want[k]) / max(want[k], med, 1e-30)
               for k in keys)


def readings(got: dict, want: dict) -> dict:
    """The numbers compared: the worst step's relative loss gap, and by the
    worst leaf, the gap between program and reference norms of the
    gradient after step 1 and of the change after the last check step,
    each against the larger of that leaf's and the median leaf's norm.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's move by round-off alone, and sit out the change."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(got["loss"], want["loss"]))
    gkeys = sorted(want["grad"])
    gmed = statistics.median(want["grad"][k] for k in gkeys)
    ckeys = [k for k in sorted(want["change"])
             if want["grad"].get(k, gmed) >= 1e-3 * gmed]
    return {"loss_rel": loss,
            "grad_norm_gap": _worst_leaf_gap(got["grad"], want["grad"], gkeys),
            "change_norm_gap": _worst_leaf_gap(got["change"], want["change"],
                                               ckeys)}


def run(cell: harness.Cell, seed: int, seconds: float, rec, t_start: float,
        devices) -> dict:
    tr = cell.traffic
    sz, step, feed, key, state = build(cell, seed)
    state, got = first_steps(step, state, feed, key, sz, tr)
    jax.block_until_ready(state)
    setup_s = time.perf_counter() - t_start

    tokens_per_step = tr["nodes"] * tr["batch_per_node"] * tr["seq_len"]
    t, n, pending = tr["check_steps"] + 1, 0, None
    rec.start()
    with rec.span(WINDOW):
        t0 = time.perf_counter()
        while True:
            with rec.span("batch.prepare"):
                batch = _place(feed.batch(t))
            with rec.span("step.dispatch"):
                state, met = step(state, batch)
            if pending is not None:
                with rec.span("step.wait"):
                    pending.block_until_ready()
            pending, n, t = met.loss, n + 1, t + 1
            if time.perf_counter() - t0 >= seconds:
                break
        with rec.span("step.wait"):
            jax.block_until_ready((pending, state))
        elapsed = time.perf_counter() - t0
    rec.stop()
    peak = harness.memory_peak_bytes(devices)
    del state, met, pending, batch

    t_ref = time.perf_counter()
    want = reference(feed, key, sz, tr)
    t_ref = time.perf_counter() - t_ref
    seq_flops = flops.train_flops_per_seq(sz, tr["seq_len"] - 1)
    return {
        "setup_s": setup_s,
        "end_to_end": {"train_tokens_per_s": n * tokens_per_step / elapsed},
        "readings": readings(got, want),
        "attempted": n, "failed": 0, "memory_peak_bytes": peak,
        "layer_ctx": {"steps": n, "sizes": sz, "traffic": tr,
                      "step_flops": seq_flops * tr["nodes"]
                      * tr["batch_per_node"]},
        "detail": {"program": got, "reference": want,
                   "reference_s": t_ref, "window_s": elapsed},
    }


def _on(chip, tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=chip), tree)


def aot(cell: harness.Cell, chip, report) -> None:
    """Lower the cell's programs for a described device ``chip`` and hand
    each to ``report(name, lowered)`` (``bench/aot_check.py``)."""
    from repro.core.gda import GDAHyper, broadcast_to_nodes
    from repro.launch.steps import TrainSpec, build_trainer

    tr = cell.traffic
    sz = weights.sizes_of(cell.config)
    cfg = harness.program_config(cell, n_groups=tr["n_groups"],
                                 rho=tr["rho"])
    n, g = tr["nodes"], tr["n_groups"]
    opt, _ = build_trainer(cfg, n, TrainSpec(
        optimizer=tr["optimizer"], topology=tr["topology"],
        mix_backend=tr["mix_backend"], hyper=GDAHyper(**tr["hyper"])))
    batch = {"tokens": jax.ShapeDtypeStruct(
                 (n, tr["batch_per_node"], tr["seq_len"]), jnp.int32),
             "group_ids": jax.ShapeDtypeStruct((n, tr["batch_per_node"]),
                                               jnp.int32)}

    def init(key, b0):
        params = weights.draw(key, sz, jnp.float32, orthonormal=True)
        return opt.init(broadcast_to_nodes(params, n),
                        jnp.full((n, g), 1.0 / g, jnp.float32), b0)

    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    report("init", jax.jit(init).lower(_on(chip, key), _on(chip, batch)))
    state = jax.eval_shape(init, key, batch)
    report("step", opt.make_step(donate=True).lower(_on(chip, state),
                                                    _on(chip, batch)))
    r_init, r_step = drsgda._programs(
        tuple(sorted(sz.items())), llama.F32,
        tuple(sorted(tr["hyper"].items())), g, tr["rho"])
    x0 = jax.eval_shape(lambda: jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (n,) + a.shape),
        weights.draw(jax.random.PRNGKey(0), sz, jnp.float32)))
    y0 = jax.ShapeDtypeStruct((n, g), jnp.float32)
    args = [_on(chip, x0), _on(chip, y0), _on(chip, batch["tokens"]),
            _on(chip, batch["group_ids"])]
    report("reference init", r_init.lower(*args))
    rstate = jax.eval_shape(r_init, x0, y0, batch["tokens"],
                            batch["group_ids"])
    report("reference step", r_step.lower(_on(chip, rstate), *args[2:]))


def calibrate(cell: harness.Cell, seeds, control: bool, faults, seconds,
              emit) -> None:
    """Per seed, the readings of the program (and of the bfloat16 control,
    and of each named fault of ``bench/faults.py``) against the reference
    (``bench/calibrate.py``)."""
    import contextlib

    from bench import faults as F

    bf16 = llama.Numerics(dtype="bfloat16", precision="default")
    tr = cell.traffic
    for fault in [None] + list(faults):
        for seed in seeds:
            t = time.perf_counter()
            with (F.TRAIN[fault]() if fault else contextlib.nullcontext()):
                sz, step, feed, key, state = build(cell, seed)
                state, got = first_steps(step, state, feed, key, sz, tr)
            del state, step
            want = reference(feed, key, sz, tr)
            row = {"seed": seed, "fault": fault,
                   "program": readings(got, want)}
            if control and fault is None:
                row["control"] = readings(
                    reference(feed, key, sz, tr, num=bf16), want)
            row["seconds"] = time.perf_counter() - t
            emit(**row)
