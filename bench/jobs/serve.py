"""Serving cell: the program's paged decode service under open-loop traffic.

Set-up draws the weights from the seed on the device, in the dtype the
configuration serves, builds one ``ServeEngine`` and warms every shape the
cell's traffic uses: the prefill (and its page scatter) of each prompt page
count in the mix, and the decode wave.  The window is the program's own
loop, ``serve_requests``, over the requests due in ``seconds``; requests
due in the window are drained after it, up to ``drain_cap_s``.  A
scheduler subclass notes the time of every token as the loop hands it
over and delegates everything else; an engine subclass adds the
benchmark's host spans.

Afterwards, with the engine freed, a sample of finished requests drawn
from the seed (the one with the most output tokens always in it) runs
through the plain float32 reference over prompt and served tokens; the
reading is the widest gap by which a served token's reference logit lies
below the reference's best at that position.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, traffic, weights
from bench.reference import llama
from bench.trace import WINDOW

#: the number compared with the reference, with its limit in
#: ``bench/limits/<cell>.json``
READINGS = ("served_gap",)


def _engine_classes(rec):
    from repro.serve import ContinuousBatchingScheduler, ServeEngine

    class TimedEngine(ServeEngine):
        """The program's engine with the benchmark's host spans; each wave
        notes its live slots and the positions they hold, and (``pages``)
        the pool's pages reserved and those holding tokens."""

        sched = None
        waves: list
        pages: list

        def admit(self, slot, prompt, pages):
            with rec.span("serve.admit"):
                return super().admit(slot, prompt, pages)

        def step(self):
            if self.sched is not None:
                live = [s.request for s in self.sched.slots
                        if s.request is not None]
                self.waves.append((len(live), sum(
                    len(r.prompt) + len(r.tokens) for r in live)))
                ps = self.spec.page_size
                self.pages.append((
                    self.spec.n_pages - 1 - self.sched.pool.n_free,
                    sum(-(-(len(r.prompt) + len(r.tokens)) // ps)
                        for r in live)))
            with rec.span("serve.step"):
                return super().step()

    class TimedScheduler(ContinuousBatchingScheduler):
        """The program's scheduler, noting each token's time; past ``cap``
        it stops admitting and ends what runs (those requests failed)."""

        def __init__(self, n_slots, spec, cap, close):
            super().__init__(n_slots, spec)
            self.cap, self.close = cap, close
            self.times: dict = {}
            self.failed: set = set()
            self.backlog = None     # requests waiting when the window closed

        def admit(self, now):
            with rec.span("serve.sched"):
                if self.backlog is None and now >= self.close:
                    self.backlog = len(self.queue)
                if now > self.cap:
                    while self.queue:
                        self.failed.add(self.queue.popleft().rid)
                    return []
                return super().admit(now)

        def on_token(self, slot_idx, token, now):
            req = self.slots[slot_idx].request
            self.times.setdefault(req.rid, []).append(now)
            if now > self.cap:
                self.failed.add(req.rid)
                req.max_new_tokens = len(req.tokens) + 1
            return super().on_token(slot_idx, token, now)

    return TimedEngine, TimedScheduler


def kv_spec(tr: dict):
    from repro.serve import PagedKVSpec
    return PagedKVSpec(
        page_size=tr["page_size"], n_pages=tr["pool_pages"] + 1,
        max_pages_per_slot=traffic.pages_for(tr["max_context"],
                                             tr["page_size"]))


def build(cell: harness.Cell, seed: int, seconds: float, rec):
    """Weights, engine, and the warm-up of every shape the mix uses."""
    from repro.models import transformer as T

    tr = cell.traffic
    sz = weights.sizes_of(cell.config)
    cfg = harness.program_config(cell)
    dtype = jnp.dtype(cell.config["dtype"])
    weights.check_layout(
        jax.eval_shape(lambda k: weights.draw(k, sz, dtype), jax.random.PRNGKey(0)),
        jax.eval_shape(lambda k: T.init_params(k, cfg), jax.random.PRNGKey(0)))
    params = weights.draw_on_device(weights.key_for(seed), sz, dtype)
    Engine, Sched = _engine_classes(rec)
    engine = Engine(cfg, params, kv_spec=kv_spec(tr), n_slots=tr["slots"],
                    temperature=tr["temperature"], seed=0)
    engine.waves, engine.pages = [], []
    ps = tr["page_size"]
    for i, npg in enumerate(traffic.prompt_page_counts(tr, seconds)):
        engine.admit(0, [1] * (npg * ps), list(range(1, npg + 1)))
        if i == 0:
            engine.step()
        engine.release(0)
    jax.block_until_ready(engine.pools)
    engine.waves.clear()
    engine.pages.clear()
    return sz, params, engine, Sched


def window(engine, Sched, tr: dict, seconds: float, seed: int, vocab: int,
           rec) -> dict:
    """One window of the open-loop mix through ``serve_requests``."""
    from repro.serve import Request, serve_requests

    mix = traffic.request_mix(tr, seconds, seed, vocab)
    reqs = [Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                    arrival=r.arrival) for r in mix]
    sched = Sched(tr["slots"], engine.spec, cap=seconds + tr["drain_cap_s"],
                  close=seconds)
    engine.sched = sched
    with rec.span(WINDOW):
        serve_requests(engine, sched, reqs)
    engine.sched = None
    return {"requests": reqs, "times": sched.times, "failed": sched.failed,
            "backlog": sched.backlog or 0}


def latency(reqs, times: dict, failed: set, seconds: float,
            cap: float) -> dict:
    """TTFT from due time, every inter-token gap, and the output rate: all
    output tokens of the window's requests over the time from the window's
    open until the last of them came (beside it, for the record, the
    tokens that came inside the window over the window).  A failed
    request misses every limit: its TTFT counts as at least the wait from
    due time to the cap."""
    ttft, itl, inside, total, last = [], [], 0, 0, 0.0
    for r in reqs:
        ts = times.get(r.rid, [])
        ok = r.rid not in failed and len(ts) == r.max_new_tokens
        ttft.append(ts[0] - r.arrival if ok else max(
            cap - r.arrival, ts[0] - r.arrival if ts else 0.0))
        itl.extend(np.diff(ts).tolist())
        inside += sum(1 for t in ts if t <= seconds)
        total += len(ts)
        last = max([last, *ts])
    return {"serve_ttft_p95_ms": 1e3 * traffic.percentile(ttft, 95),
            "serve_itl_p95_ms": 1e3 * traffic.percentile(itl, 95)
            if itl else 0.0,
            "serve_output_tokens_per_s": total / last if last else 0.0,
            "output_tokens_in_window_per_s": inside / seconds}


def occupancy(waves: list, pages: list, tr: dict) -> dict:
    """Live slots and pages over the waves of a run, beside the pool:
    pages reserved (each request's worst case, taken at admission) and
    pages holding tokens (the positions the live slots hold)."""
    if not waves:
        return {}
    live = [n for n, _ in waves]
    reserved, held = zip(*pages)
    return {"live_slots_mean": float(np.mean(live)),
            "live_slots_max": max(live),
            "pages_reserved_mean": float(np.mean(reserved)),
            "pages_reserved_max": max(reserved),
            "pages_holding_tokens_mean": float(np.mean(held)),
            "pages_holding_tokens_max": max(held),
            "pool_pages": tr["pool_pages"]}


def sample(reqs, failed: set, seed: int, tr: dict) -> list:
    """Finished requests to check: the one with the most output tokens,
    then others drawn from the seed until ``min_tokens`` are covered."""
    done = [r for r in reqs if r.rid not in failed]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.tokens), -len(r.prompt), r.rid))
    out, rest = [done[0]], done[1:]
    order = np.random.default_rng([int(seed) % 2**63, 5]).permutation(
        len(rest))
    s = tr["sample"]
    for i in order:
        if sum(len(r.tokens) for r in out) >= s["min_tokens"] \
                or len(out) >= s["max_requests"]:
            break
        out.append(rest[i])
    return out


@functools.lru_cache(maxsize=None)
def _gap_fn(sz_items, num):
    sz = dict(sz_items)

    @jax.jit
    def gaps(params, toks, pos, served):
        ref = llama.forward(params, toks[None], sz, llama.F32)[0][pos]
        best = ref.max(-1)
        g_served = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
        if num == llama.F32:
            return g_served, g_served
        low = llama.forward(params, toks[None], sz, num)[0][pos]
        pick = jnp.argmax(low, -1)
        g_low = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        return g_served, g_low
    return gaps


def served_gaps(params, reqs: list, sz: dict, tr: dict,
                num=llama.F32) -> dict:
    """The widest gap, over the served tokens of ``reqs``, between the
    reference's best logit and its logit of the served token; and (for a
    lower-precision ``num``) of the token that precision puts first.  One
    shape for every request: the sequence padded to ``max_context``, the
    served positions to the longest output."""
    gaps = _gap_fn(tuple(sorted(sz.items())), num)
    out = {"served": 0.0, "control": 0.0, "tokens": 0}
    for r in reqs:
        seq = (list(r.prompt) + list(r.tokens))[:-1]
        toks = np.zeros((tr["max_context"],), np.int32)
        toks[:len(seq)] = seq
        pos = np.zeros((tr["output"]["max"],), np.int32)
        served = np.zeros_like(pos)
        k = len(r.tokens)
        pos[:k] = np.arange(len(r.prompt) - 1, len(r.prompt) - 1 + k)
        served[:k] = r.tokens
        g, c = gaps(params, jnp.asarray(toks), jnp.asarray(pos),
                    jnp.asarray(served))
        out["served"] = max(out["served"], float(jnp.max(g[:k])))
        out["control"] = max(out["control"], float(jnp.max(c[:k])))
        out["tokens"] += k
    return out


def run(cell: harness.Cell, seed: int, seconds: float, rec, t_start: float,
        devices) -> dict:
    tr = cell.traffic
    sz, params, engine, Sched = build(cell, seed, seconds, rec)
    setup_s = time.perf_counter() - t_start
    rec.start()
    w = window(engine, Sched, tr, seconds, seed, sz["vocab"], rec)
    rec.stop()
    peak = harness.memory_peak_bytes(devices)
    waves, pages = list(engine.waves), list(engine.pages)
    del engine
    reqs, failed = w["requests"], w["failed"]
    lat = latency(reqs, w["times"], failed, seconds,
                  seconds + tr["drain_cap_s"])
    chk = sample(reqs, failed, seed, tr)
    t_ref = time.perf_counter()
    gap = served_gaps(params, chk, sz, tr)
    t_ref = time.perf_counter() - t_ref
    return {
        "setup_s": setup_s, "end_to_end": lat,
        "readings": {"served_gap": gap["served"]},
        "ok": not failed and bool(chk),
        "attempted": len(reqs), "failed": len(failed),
        "memory_peak_bytes": peak,
        "layer_ctx": {"waves": waves, "sizes": sz, "traffic": tr,
                      "itemsize": jnp.dtype(cell.config["dtype"]).itemsize},
        "detail": {"checked_requests": len(chk), "reference_s": t_ref,
                   "waves": len(waves), "checked_tokens": gap["tokens"],
                   "backlog_at_close": w["backlog"],
                   **occupancy(waves, pages, tr), **lat},
    }


def aot(cell: harness.Cell, chip, report) -> None:
    """Lower the decode wave and the largest prefill for a described device
    ``chip`` and hand each to ``report(name, lowered)``."""
    from repro.serve import ServeEngine, kv_cache

    def on(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    tr = cell.traffic
    sz = weights.sizes_of(cell.config)
    cfg = harness.program_config(cell)
    dtype = jnp.dtype(cell.config["dtype"])
    spec = kv_spec(tr)
    params = on(jax.eval_shape(lambda: weights.draw(jax.random.PRNGKey(0),
                                                    sz, dtype)))
    pools = on(jax.eval_shape(lambda: kv_cache.init_pools(cfg, spec, dtype)))
    eng = ServeEngine.__new__(ServeEngine)
    eng.cfg, eng.temperature, eng._prefill_fns = cfg, tr["temperature"], {}
    s, m = tr["slots"], spec.max_pages_per_slot
    i32 = jnp.int32
    report("decode wave", jax.jit(eng._step_impl, donate_argnums=(4,)).lower(
        params, on(jax.ShapeDtypeStruct((s,), i32)),
        on(jax.ShapeDtypeStruct((s,), i32)),
        on(jax.ShapeDtypeStruct((s, m), i32)), pools,
        on(jax.eval_shape(lambda: jax.random.PRNGKey(0)))))
    cl = max(traffic.prompt_page_counts(tr, 30.0)) * tr["page_size"]
    report(f"prefill {cl} tokens", eng._prefill_fn(cl).lower(
        params, on(jax.ShapeDtypeStruct((1, cl), i32)),
        on(jax.ShapeDtypeStruct((), i32))))


def calibrate(cell: harness.Cell, seeds, control: bool, faults, seconds,
              emit) -> None:
    """Per seed, a window of ``seconds`` at the cell's load with the
    engine's weights redrawn from the seed, and the widest served-token gap
    of the program (and of the float8 control, and of each named fault of
    ``bench/faults.py``) against the reference (``bench/calibrate.py``)."""
    import contextlib

    from bench import faults as F
    from bench.trace import Recorder

    fp8 = llama.Numerics(fp8=True)
    tr = cell.traffic
    rec = Recorder(False)
    dtype = jnp.dtype(cell.config["dtype"])
    for fault in [None] + list(faults):
        with (F.SERVE[fault]() if fault else contextlib.nullcontext()):
            sz, params, engine, Sched = build(cell, seeds[0], seconds, rec)
            for seed in seeds:
                t = time.perf_counter()
                del params
                engine.params = None
                engine.params = params = weights.draw_on_device(
                    weights.key_for(seed), sz, dtype)
                w = window(engine, Sched, tr, seconds, seed, sz["vocab"],
                           rec)
                chk = sample(w["requests"], w["failed"], seed, tr)
                gap = served_gaps(params, chk, sz, tr,
                                  num=fp8 if control else llama.F32)
                row = {"seed": seed, "fault": fault,
                       "failed": len(w["failed"]), "tokens": gap["tokens"],
                       "program": {"served_gap": gap["served"]}}
                if control and fault is None:
                    row["control"] = {"served_gap": gap["control"]}
                row["seconds"] = time.perf_counter() - t
                emit(**row)
            del engine


def sweep(cell: harness.Cell, rates, seconds: float, seed: int,
          emit) -> None:
    """Offered rate against what the engine sustains (``bench/sweep.py``):
    per rate, one window of the cell's mix at that rate on one engine, with
    the backlog of due requests when the window closed."""
    from bench.trace import Recorder

    rec = Recorder(False)
    sz, params, engine, Sched = build(cell, seed, seconds, rec)
    for rate in rates:
        tr = dict(cell.traffic, rate_per_s=rate)
        for npg in traffic.prompt_page_counts(tr, seconds):
            engine.admit(0, [1] * (npg * tr["page_size"]),
                         list(range(1, npg + 1)))
            engine.release(0)
        engine.waves.clear()
        engine.pages.clear()
        t = time.perf_counter()
        w = window(engine, Sched, tr, seconds, seed, sz["vocab"], rec)
        lat = latency(w["requests"], w["times"], w["failed"], seconds,
                      seconds + tr["drain_cap_s"])
        live = [n for n, _ in engine.waves]
        emit(rate=rate, requests=len(w["requests"]), failed=len(w["failed"]),
             backlog_at_close=w["backlog"], waves=len(live),
             mean_live=float(np.mean(live)) if live else 0.0,
             wall_s=time.perf_counter() - t, **lat)
