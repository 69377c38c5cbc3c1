"""Telemetry benchmark: overhead, bit-identity and counter agreement.

One DRGDA workload (N nodes, ring, the toy Stiefel minimax problem from the
optimizer tests) is run three ways:

* **off** — ``telemetry=None``: the pre-obs program;
* **on**  — counters threaded + io_callback flush every FLUSH_EVERY steps;
* **phases** — the same step split into separately-jitted compute / retract
  / mix / metric pieces, timed per phase (the step-time breakdown
  §Telemetry reports).  Split programs are not the fused step: the fused
  step's own phases are its device scopes (``gda.grad``, ``gda.retract``,
  ``gda.track``, ``gda.mix``, ``gda.metrics``, ``repro.obs.trace.scope``),
  read from a profiler trace of the chip by ``bench/scopes.py``; what
  becomes of these split programs is ROADMAP Design 10.

Checks performed (all land in experiments/bench/obs.json):

* wall-clock overhead of obs on vs off (<5% acceptance at the default
  cadence);
* the two final states are bit-identical (counters never touch the math);
* counter-derived bytes/hop equals the backend's ``est_hop_bytes`` oracle —
  the same number ``benchmarks/mix_backend.py`` records — within 1%;
* kernel Estimates snapshot for the traced step (per-traced-call; multiply
  by executed steps for run totals).
"""
from __future__ import annotations

import json
import os
import tempfile
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_NODES = 8
# sized so one step is O(2ms) on this container — small enough to keep the
# bench fast, big enough that the obs cost (a fixed ~100us/step dispatch +
# flush tax) is measured against a realistic step, not a toy one
D, R, G = 192, 16, 3
RHO = 1.0
BLOCK = FLUSH_EVERY = 50   # timed blocks of one flush window each
REPEATS = 14


def _problem():
    import jax.numpy as jnp
    import numpy as np
    from repro.core.minimax import MinimaxProblem, project_simplex

    a = np.stack([np.random.RandomState(i).randn(D, D) for i in range(G)])
    a = jnp.asarray((a + np.swapaxes(a, 1, 2)) / 2, jnp.float32)

    def loss_fn(x, y, batch):
        ag = a + batch
        lg = -jnp.einsum("dr,gde,er->g", x["w"], ag, x["w"])
        return jnp.dot(y, lg) - RHO * jnp.sum((y - 1.0 / G) ** 2)

    def y_star(x, batches):
        ag = a + jnp.mean(batches, axis=0)
        lg = -jnp.einsum("dr,gde,er->g", x["w"], ag, x["w"])
        return project_simplex(1.0 / G + lg / (2 * RHO))

    return MinimaxProblem(loss_fn=loss_fn, project_y=project_simplex,
                          manifold_map={"w": "stiefel"}, y_star=y_star)


def _setup():
    import jax
    import jax.numpy as jnp
    from repro.core import manifolds as M
    from repro.core.gda import broadcast_to_nodes

    prob = _problem()
    batches = 0.1 * jax.random.normal(jax.random.PRNGKey(6),
                                      (N_NODES, G, D, D))
    x0 = broadcast_to_nodes(
        {"w": M.random_stiefel(jax.random.PRNGKey(5), D, R)}, N_NODES)
    y0 = jnp.full((N_NODES, G), 1.0 / G)
    return prob, x0, y0, batches


def _prep(opt, x0, y0, batches):
    """Warm both executables (flush path on call 1, quiet path on call 2)
    and return (step, state0)."""
    import jax
    state0 = opt.init(x0, y0, batches)
    step = opt.make_step(donate=False)
    s, m = step(state0, batches)
    jax.block_until_ready(m.loss)
    s, m = step(s, batches)
    jax.block_until_ready(m.loss)
    return step, state0


def _block(step, state0, batches, steps=BLOCK):
    """One timed block of ``steps`` calls from state0; since BLOCK ==
    FLUSH_EVERY, every obs-on block pays exactly one flush call.  Returns
    (final_state, seconds/step)."""
    import jax
    state = state0
    t0 = time.time()
    for _ in range(steps):
        state, m = step(state, batches)
    jax.block_until_ready(m.loss)
    return state, (time.time() - t0) / steps


def _phase_breakdown(opt, prob, x0, y0, batches):
    """compute / retract / mix / metric wall-clock per call, each phase
    jitted separately (approximates the in-step split)."""
    import jax
    import jax.numpy as jnp
    from repro.core.gda import _vmapped_loss_and_rgrads

    state = opt.init(x0, y0, batches)
    h = opt.hyper

    def compute(x, y, b):
        return _vmapped_loss_and_rgrads(prob, x, y, b)

    def retract(x, u):
        return jax.tree.map(
            lambda m, xl, ul: m.retract(
                xl, -h.beta * ul, m.resolve_retraction(h.retraction)),
            prob.manifold_map, x, u)

    def mix(x):
        return opt.backend.mix(opt.gossip, x, opt.k)

    def metric(x, y, b):
        from repro.core.metric import convergence_metric
        return convergence_metric(prob, x, y, b)["M_t"]

    phases = {
        "compute": (jax.jit(compute), (state.x, state.y, batches)),
        "retract": (jax.jit(retract), (state.x, state.u)),
        "mix": (jax.jit(mix), (state.x,)),
        "metric": (jax.jit(metric), (state.x, state.y, batches)),
    }
    out = {}
    for name, (fn, args) in phases.items():
        jax.block_until_ready(fn(*args))     # compile
        t0 = time.time()
        for _ in range(20):
            r = fn(*args)
        jax.block_until_ready(r)
        out[name] = (time.time() - t0) / 20 * 1e6
    total = sum(out.values())
    return {"us_per_call": out,
            "fraction": {k: v / total for k, v in out.items()}}


def run() -> dict:
    import jax
    import numpy as np
    from repro.core.gda import DRGDA, GDAHyper
    from repro.core.gossip import GossipSpec
    from repro.obs import Telemetry, estimates as obs_est, unpack
    from repro.obs import events as obs_events
    from repro.obs import telemetry as obs_telemetry
    from repro.obs import trace as obs_trace

    prob, x0, y0, batches = _setup()
    spec = GossipSpec(topology="ring", n_nodes=N_NODES)
    out_dir = tempfile.mkdtemp(prefix="obs_bench_")
    tel = Telemetry(run="bench", out_dir=out_dir, flush_every=FLUSH_EVERY)

    # warmed steppers, then tightly interleaved off/on timed blocks;
    # min-over-blocks is the noise-robust estimator on this shared container
    # (load spikes only ever add time).  Each block restarts from state0, so
    # both arms execute the identical 50-step trajectory every time.
    opt_off = DRGDA(prob, spec, GDAHyper())
    opt_on = DRGDA(prob, spec, GDAHyper(), telemetry=tel)
    step_off, s_off0 = _prep(opt_off, x0, y0, batches)
    step_on, s_on0 = _prep(opt_on, x0, y0, batches)
    t_off, t_on = [], []
    for _ in range(REPEATS):
        state_off, dt = _block(step_off, s_off0, batches)
        t_off.append(dt)
        state_on, dt = _block(step_on, s_on0, batches)
        t_on.append(dt)
    dt_off, dt_on = float(np.min(t_off)), float(np.min(t_on))
    overhead = (dt_on - dt_off) / dt_off * 100.0

    bit_identical = all(
        bool((a == b).all()) for a, b in
        zip(jax.tree.leaves(state_on.x), jax.tree.leaves(state_off.x)))

    # counter-derived bytes/hop vs the mix-backend oracle.  DRGDA mixes four
    # slots per step (x, y, u with k hops; v with 1): expected bytes/hop is
    # the hop-weighted mean of the per-slot est_hop_bytes.
    obs = unpack(state_on.obs)
    k = opt_on.k
    per_slot = {s: opt_on.backend.est_hop_bytes(spec, t) for s, t in
                (("x", x0), ("y", y0), ("u", x0), ("v", y0))}
    hops = {"x": k, "y": k, "u": k, "v": 1}
    expect = sum(per_slot[s] * hops[s] for s in per_slot) / sum(hops.values())
    got = float(obs.wire_bytes) / float(obs.hops)
    rel_err = abs(got - expect) / expect

    # kernel Estimates for one traced step (per-traced-call semantics)
    obs_est.GLOBAL.reset()
    with obs_est.collect() as kc:
        opt2 = DRGDA(prob, spec, GDAHyper(retraction="polar_fused"))
        st2 = opt2.init(x0, y0, batches)
        jax.block_until_ready(opt2.make_step(donate=False)(st2, batches))
    kernel_snapshot = kc.snapshot()

    # event-log artifacts: schema-validate + trace round-trip
    n_events = obs_events.validate_log(tel.events_path)
    paths = tel.export()
    payload = json.load(open(paths["trace"]))
    rt = obs_trace.Trace.from_chrome_trace(payload)
    counters = obs_telemetry.read_counter_series(tel.events_path)

    return {
        "n_nodes": N_NODES, "block": BLOCK, "repeats": REPEATS,
        "flush_every": FLUSH_EVERY,
        "us_per_step_off": dt_off * 1e6,
        "us_per_step_on": dt_on * 1e6,
        "overhead_pct": overhead,
        "bit_identical": bit_identical,
        "counters": {kk: float(v) for kk, v in obs.as_dict().items()},
        "bytes_per_hop": got,
        "bytes_per_hop_expected": expect,
        "bytes_per_hop_rel_err": rel_err,
        "per_slot_est_hop_bytes": per_slot,
        "per_slot_hops": hops,
        "n_flushes": len(counters),
        "n_events": n_events,
        "trace_roundtrip_events": len(rt.events),
        "phase_breakdown": _phase_breakdown(opt_on, prob, x0, y0, batches),
        "kernel_estimates": kernel_snapshot,
        "artifacts": paths,
    }


if __name__ == "__main__":
    for _p in (_REPO_ROOT, os.path.join(_REPO_ROOT, "src")):
        if _p not in os.sys.path:
            os.sys.path.insert(0, _p)
    print(json.dumps(run(), indent=1))
