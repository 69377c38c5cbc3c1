"""Training launcher.

Two regimes:

* ``--device-grid host``   (default here): run REAL steps on the local
  device(s) with a reduced config — the end-to-end driver used by
  examples/decentralized_llm_pretrain.py and the integration tests.
* ``--device-grid pod|2pod``: build the production mesh and execute the
  jitted SPMD step (requires the corresponding real TPU slice; on this CPU
  container use ``repro.launch.dryrun`` instead, which only lowers).

Example:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
      --steps 50 --optimizer drsgda --nodes 8
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint, configs
from repro.core.gda import GDAHyper
from repro.core.metric import convergence_metric
from repro.data.synthetic import TokenStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import TrainSpec, build_trainer, init_train_state
from repro.obs import Telemetry


def _span(telemetry, name, **tags):
    import contextlib
    if telemetry is None:
        return contextlib.nullcontext()
    return telemetry.span(name, **tags)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=configs.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--optimizer", default="drsgda",
                    choices=["drgda", "drsgda", "gt-gda", "gnsd-a", "dm-hsgd"])
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch-per-node", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument("--beta", type=float, default=0.02)
    ap.add_argument("--eta", type=float, default=0.05)
    ap.add_argument("--topology", default="ring",
                    choices=["ring", "full", "torus", "star"])
    ap.add_argument("--eval-every", type=int, default=10)
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-json", default="")
    ap.add_argument("--telemetry", action="store_true",
                    help="thread wire counters through the jitted step and "
                         "stream the convergence dashboard to an event log")
    ap.add_argument("--telemetry-dir", default="experiments/telemetry")
    ap.add_argument("--telemetry-run", default="",
                    help="run name for the event log / trace files "
                         "(default: <optimizer>-<arch>)")
    ap.add_argument("--churn", default="static",
                    choices=["static", "random"],
                    help="elastic-gossip churn schedule (random: seeded "
                         "per-round leave/rejoin Markov draws)")
    ap.add_argument("--churn-leave-rate", type=float, default=0.05)
    ap.add_argument("--churn-join-rate", type=float, default=0.5)
    ap.add_argument("--tau", type=int, default=0,
                    help="elastic stale-hop tolerance (rounds)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    telemetry = None
    if args.telemetry:
        telemetry = Telemetry(
            run=args.telemetry_run or f"{args.optimizer}-{args.arch}",
            out_dir=args.telemetry_dir, flush_every=args.eval_every)

    cfg = configs.get_config(args.arch, smoke=args.smoke)
    hyper = GDAHyper(alpha=args.alpha, beta=args.beta, eta=args.eta)
    elastic = None
    if args.churn != "static" or args.tau > 0:
        from repro.comms.elastic import ChurnSchedule, ElasticSpec
        elastic = ElasticSpec(
            churn=ChurnSchedule(kind=args.churn,
                                leave_rate=args.churn_leave_rate,
                                join_rate=args.churn_join_rate),
            tau=args.tau, seed=args.seed)
    spec = TrainSpec(optimizer=args.optimizer, topology=args.topology,
                     elastic=elastic, telemetry=telemetry, hyper=hyper)
    opt, problem = build_trainer(cfg, args.nodes, spec)

    stream = TokenStream(n_nodes=args.nodes, batch_per_node=args.batch_per_node,
                         seq_len=args.seq_len, vocab_size=cfg.vocab_size,
                         n_groups=cfg.n_groups, n_codebooks=cfg.n_codebooks,
                         seed=args.seed)

    def to_jax(b):
        out = {k: jnp.asarray(v) for k, v in b.items()}
        if cfg.frontend is not None:
            key = jax.random.PRNGKey(hash((args.seed, "fe")) % (2 ** 31))
            out["frontend_embeds"] = 0.1 * jax.random.normal(
                key, (args.nodes, args.batch_per_node, cfg.frontend.n_tokens,
                      cfg.frontend.embed_dim))
        return out

    batch0 = to_jax(stream.batch(0))
    with _span(telemetry, "init"):
        state = init_train_state(jax.random.PRNGKey(args.seed), cfg, opt,
                                 args.nodes, batch0)
    step_fn = opt.make_step(donate=True)

    history = []
    t_start = time.time()
    with _span(telemetry, "train", steps=args.steps):
        for t in range(args.steps):
            batch = to_jax(stream.batch(t + 1))
            state, metrics = step_fn(state, batch)
            if (t + 1) % args.eval_every == 0 or t == args.steps - 1:
                with _span(telemetry, "eval", step=t + 1):
                    m = convergence_metric(problem, state.x, state.y, batch)
                row = {
                    "step": t + 1,
                    "loss": float(metrics.loss),
                    "grad_norm_x": float(metrics.grad_norm_x),
                    "consensus_x": float(metrics.consensus_x),
                    "M_t": float(m["M_t"]),
                    "stiefel_residual": float(m["stiefel_residual"]),
                    "wall_s": round(time.time() - t_start, 1),
                }
                history.append(row)
                print(json.dumps(row), flush=True)
                if telemetry is not None:
                    telemetry.dashboard(problem, state.x, state.y, batch,
                                        step=t + 1,
                                        extra={"loss": row["loss"]})
                    mem = getattr(state.comm, "elastic", None)
                    if mem is not None:
                        act = np.asarray(mem.active)
                        prev = np.asarray(mem.prev_active)
                        telemetry.event("membership", {
                            "live": int(act.sum()),
                            "joins": int(((act > 0) & (prev == 0)).sum()),
                            "leaves": int(((act == 0) & (prev > 0)).sum()),
                            "active": act.astype(int).tolist(),
                        }, step=t + 1)
            if args.checkpoint_every and (t + 1) % args.checkpoint_every == 0 \
                    and args.checkpoint_dir:
                with _span(telemetry, "checkpoint", step=t + 1):
                    checkpoint.save(args.checkpoint_dir, t + 1, state.x)

    if telemetry is not None:
        paths = telemetry.export()
        print(json.dumps({"telemetry": paths}), flush=True)
    if args.log_json:
        with open(args.log_json, "w") as f:
            json.dump(history, f, indent=1)
    # success = finite loss and preserved feasibility
    ok = np.isfinite(history[-1]["loss"]) and \
        history[-1]["stiefel_residual"] < 1e-2
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
