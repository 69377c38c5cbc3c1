"""Serving benchmark: continuous vs static batching + replica sync.

A burst of mixed-length requests (ragged prompts, ragged token budgets) is
decoded through ``repro.serve`` on the smollm-135m reduced config:

* **batch-size sweep** — tokens/sec and p50/p99 request latency vs
  ``n_slots`` under continuous batching;
* **continuous vs static** — same workload, same slots; static admission
  (drain the whole wave before refilling) is the ablation, continuous
  refills slots the moment one frees — the throughput gap is the paper
  point of the scheduler;
* **paged kernel accuracy** — the block-table gather kernel
  (``pallas_interpret``) vs its NumPy-style oracle on ragged slots;
* **replica sync** — a 2-replica EF-int8 gossip run: perturb, sync, report
  the cross-replica drift trace + wire bytes.

The run emits ``serve`` + ``replica`` telemetry events and validates the
event log against ``obs/event_schema.json`` (the CI smoke gate).  Payload
lands in experiments/bench/serve.json via ``benchmarks/run.py serve``.

Run:  PYTHONPATH=src python benchmarks/serve.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ARCH = "smollm-135m"
PAGE_SIZE = 8
N_PAGES = 257                # 256 usable pages + the dump page
MAX_PAGES_PER_SLOT = 8       # 64-token max context per slot
SEED = 0


def _requests(n: int, seed: int):
    """Mixed workload: ragged prompts (4..28) and strongly ragged budgets
    (4..32), all arriving at t=0 — the shape static batching handles
    worst: every wave is held hostage by its longest request."""
    import numpy as np
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, 200, rng.integers(4, 29)).tolist(),
                    max_new_tokens=int(rng.integers(4, 33)))
            for _ in range(n)]


def _drive(engine, spec, n_slots, refill, requests, telemetry=None):
    from repro.serve import ContinuousBatchingScheduler, serve_requests
    sched = ContinuousBatchingScheduler(n_slots, spec, refill=refill)
    t0 = time.perf_counter()
    fin = serve_requests(engine, sched, requests)
    wall = time.perf_counter() - t0
    import numpy as np
    lats = np.asarray([r.latency for r in fin])
    ttfts = np.asarray([r.ttft for r in fin])
    n_tok = sum(len(r.tokens) for r in fin)
    res = {
        "n_requests": len(fin), "n_tokens": n_tok,
        "wall_s": round(wall, 3),
        "tok_per_s": round(n_tok / wall, 1),
        "p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 1),
        "p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 1),
        "ttft_p50_ms": round(float(np.percentile(ttfts, 50)) * 1e3, 1),
        "steps": engine.steps_run,
    }
    if telemetry is not None:
        telemetry.event("serve", {
            "kind": "summary", "refill": refill, "n_slots": n_slots, **res})
    return res


def _kernel_check():
    """Paged-decode Pallas kernel (interpret) vs oracle on ragged slots."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    s, hkv, g, hd, ps, m = 5, 2, 3, 32, 8, 6
    n_pages = 24
    q = jnp.asarray(rng.normal(size=(s, hkv * g, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, hkv, hd, ps)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, hkv, hd, ps)), jnp.float32)
    seq = [1, 7, 13, 0, 40]
    bt = np.full((s, m), -1, np.int32)
    nxt = 1
    for i, sl in enumerate(seq):
        for j in range(-(-sl // ps)):
            bt[i, j] = nxt
            nxt += 1
    bt, seq = jnp.asarray(bt), jnp.asarray(seq, jnp.int32)
    want = ops.paged_decode_attention(q, kp, vp, bt, seq, impl="ref")
    got = ops.paged_decode_attention(q, kp, vp, bt, seq,
                                     impl="pallas_interpret",
                                     pages_per_block=2)
    return float(jnp.abs(got - want).max())


def run(smoke: bool = False) -> dict:
    import jax
    from repro import configs
    from repro.models import transformer as T
    from repro.obs.telemetry import Telemetry
    from repro.obs import events
    from repro.serve import PagedKVSpec, ReplicaGroup, ServeEngine

    cfg = configs.get_config(ARCH, smoke=True)
    params = T.init_params(jax.random.PRNGKey(SEED), cfg)
    spec = PagedKVSpec(page_size=PAGE_SIZE, n_pages=N_PAGES,
                       max_pages_per_slot=MAX_PAGES_PER_SLOT)

    out_dir = os.path.join(_REPO_ROOT, "experiments", "bench", "serve_run")
    tel = Telemetry(run="serve_bench", out_dir=out_dir)
    if os.path.exists(tel.events_path):     # fresh log per run
        os.remove(tel.events_path)

    n_req = 8 if smoke else 32
    slot_sweep = (2,) if smoke else (1, 2, 4, 8)

    def engine(n_slots):
        from repro.serve import Request
        e = ServeEngine(cfg, params, kv_spec=spec, n_slots=n_slots,
                        temperature=0.0, seed=SEED, telemetry=None)
        # warm the prefill/step jit caches so timings measure decode, not
        # compiles: one prompt per page-count bucket the workload can hit
        # (prompt lens 4..28 at page_size 8 -> 1..4 pages)
        warm = [Request(prompt=[1] * n, max_new_tokens=2)
                for n in range(PAGE_SIZE // 2,
                               MAX_PAGES_PER_SLOT * PAGE_SIZE - 20,
                               PAGE_SIZE)]
        _drive(e, spec, n_slots, "continuous", warm)
        e.steps_run = e.tokens_generated = 0
        return e

    per_batch = {}
    for n_slots in slot_sweep:
        per_batch[n_slots] = _drive(engine(n_slots), spec, n_slots,
                                    "continuous", _requests(n_req, 1), tel)

    n_race = max(slot_sweep)
    cont = _drive(engine(n_race), spec, n_race, "continuous",
                  _requests(n_req, 2), tel)
    stat = _drive(engine(n_race), spec, n_race, "static",
                  _requests(n_req, 2), tel)
    speedup = cont["tok_per_s"] / max(stat["tok_per_s"], 1e-9)

    kernel_max_err = _kernel_check()

    rg = ReplicaGroup(params, 2, seed=SEED, telemetry=tel)
    drift0 = rg.perturb(0.02)
    trace = rg.sync(rounds=2 if smoke else 4)

    tel.export()
    n_events = events.validate_log(tel.events_path)

    payload = {
        "arch": cfg.name, "page_size": PAGE_SIZE, "n_pages": N_PAGES,
        "max_pages_per_slot": MAX_PAGES_PER_SLOT, "smoke": smoke,
        "per_batch": {str(k): v for k, v in per_batch.items()},
        "continuous": cont, "static": stat,
        "speedup_vs_static": round(speedup, 3),
        "kernel_max_err": kernel_max_err,
        "replica": {
            "n_replicas": 2, "drift_injected": drift0,
            "drift_trace": trace, "drift_final": trace[-1],
            "wire": rg.wire_stats(),
        },
        "n_events": n_events,
        "events_path": os.path.relpath(tel.events_path, _REPO_ROOT),
        "us_per_token": round(1e6 * cont["wall_s"] / cont["n_tokens"], 1),
    }
    return payload


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    res = run(smoke=args.smoke)
    print(json.dumps(res, indent=1))
    assert res["kernel_max_err"] < 2e-5, res["kernel_max_err"]
    assert res["replica"]["drift_final"] < res["replica"]["drift_injected"]
    if not args.smoke:
        assert res["speedup_vs_static"] > 1.0, res["speedup_vs_static"]
    return 0


if __name__ == "__main__":
    import sys
    for _p in (_REPO_ROOT, os.path.join(_REPO_ROOT, "src")):
        if _p not in sys.path:
            sys.path.insert(0, _p)
    raise SystemExit(main())
