"""Decode engine: one jitted fused step over paged KV pools.

The step is {embed slot tokens, paged flash-attention decode through every
layer, sample, scatter new K/V into pages} — a single ``jax.jit`` with the
pools donated, so steady-state decode is one dispatch per token wave
regardless of how many slots are live.  Slot liveness never reaches the
device: inactive slots carry an all ``-1`` block-table row, their writes
land on the dump page and their sampled tokens are ignored host-side.

Prefill runs through ``models.transformer.forward(mode="prefill")`` per
admitted request, bucketed to whole pages (``ceil(len/page_size)`` pages →
one retrace per distinct page count, not per length; right-padding is safe
because causal masking keeps pad positions out of the sampled logits and
only the first ``len`` cache rows are scattered into pages).

:func:`serve_requests` is the reference serving loop wiring this engine to
a :class:`~repro.serve.scheduler.ContinuousBatchingScheduler`.

Program spans (:func:`repro.obs.trace.span`, on the profiler's clock) mark
the host's part: ``engine.prefill`` (prefill program and first token),
``engine.scatter`` (the prompt's K/V into its pages), per decode wave
``engine.wave.inputs`` (key split, uploads of tokens, positions and block
table), ``engine.wave.launch`` and ``engine.wave.fetch`` (the sampled
tokens back on the host); in the loop ``loop.sched`` (admission),
``loop.tokens`` (hand-off of tokens, releases) and ``loop.wait`` (sleep
until the next arrival).  The wave's sampling runs in the device scope
``model.head``.
"""
from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import transformer
from repro.obs import trace as obs_trace
from repro.serve import kv_cache
from repro.serve.kv_cache import PagedKVSpec
from repro.serve.scheduler import ContinuousBatchingScheduler, Request

Array = jax.Array


def _sample(logits: Array, key: Array, temperature: float) -> Array:
    if temperature > 0:
        return jax.random.categorical(key, logits / temperature, axis=-1) \
            .astype(jnp.int32)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


class ServeEngine:
    """Device state (pools, block table, slot tokens) + the jitted step."""

    def __init__(self, cfg: ModelConfig, params, *,
                 kv_spec: Optional[PagedKVSpec] = None, n_slots: int = 4,
                 temperature: float = 0.0, seed: int = 0, telemetry=None):
        kv_cache.validate_config(cfg)
        self.cfg = cfg
        self.params = params
        self.spec = kv_spec or PagedKVSpec()
        self.n_slots = n_slots
        self.temperature = float(temperature)
        self.telemetry = telemetry
        self._key = jax.random.PRNGKey(seed)
        dtype = params["embed"].dtype
        self.pools = kv_cache.init_pools(cfg, self.spec, dtype)
        m = self.spec.max_pages_per_slot
        self._bt = np.full((n_slots, m), -1, np.int32)
        self._positions = np.zeros((n_slots,), np.int32)
        self._tokens = np.zeros((n_slots,), np.int32)
        self._active = np.zeros((n_slots,), bool)
        self._prefill_fns: dict[int, object] = {}
        self._scatter = jax.jit(
            functools.partial(kv_cache.scatter_prompt, cfg=cfg,
                              page_size=self.spec.page_size),
            donate_argnums=(0,))
        self._step = jax.jit(self._step_impl, donate_argnums=(4,))
        self.steps_run = 0
        self.tokens_generated = 0

    # -- jitted bodies ------------------------------------------------------

    def _step_impl(self, params, tok, positions, bt, pools, key):
        logits, new_pools = transformer.decode_step(
            params, self.cfg, tok, (positions, bt), pools)
        with obs_trace.scope("model.head"):
            nxt = _sample(logits, key, self.temperature)
        return nxt, new_pools

    def _prefill_fn(self, cache_len: int):
        fn = self._prefill_fns.get(cache_len)
        if fn is None:
            def body(params, tokens, last):
                logits, _, caches = transformer.forward(
                    params, self.cfg, tokens, mode="prefill",
                    cache_len=cache_len)
                return logits[0, last], caches
            fn = self._prefill_fns[cache_len] = jax.jit(body)
        return fn

    # -- slot lifecycle -----------------------------------------------------

    def admit(self, slot: int, prompt: list[int],
              pages: list[int]) -> int:
        """Prefill ``prompt`` into ``pages`` (the slot's full reservation)
        and return the first sampled token."""
        ps = self.spec.page_size
        length = len(prompt)
        assert 0 < length and not self._active[slot], (slot, length)
        npg = self.spec.pages_for(length)
        assert len(pages) >= npg, (len(pages), npg)
        cache_len = npg * ps

        tokens = np.zeros((1, cache_len), np.int32)
        tokens[0, :length] = prompt
        with obs_trace.span("engine.prefill"):
            last_logits, caches = self._prefill_fn(cache_len)(
                self.params, jnp.asarray(tokens),
                jnp.asarray(length - 1, jnp.int32))
            self._key, k = jax.random.split(self._key)
            first = int(_sample(last_logits[None], k,
                                self.temperature)[0])
        with obs_trace.span("engine.scatter"):
            self.pools = self._scatter(
                self.pools, caches, jnp.asarray(pages[:npg], jnp.int32))

        self._bt[slot] = -1
        self._bt[slot, :len(pages)] = pages
        self._positions[slot] = length
        self._tokens[slot] = first
        self._active[slot] = True
        self.tokens_generated += 1
        if self.telemetry is not None:
            self.telemetry.event("serve", {
                "kind": "admit", "slot": slot, "prompt_len": length,
                "pages": len(pages)})
        return first

    def release(self, slot: int) -> None:
        self._bt[slot] = -1
        self._positions[slot] = 0
        self._tokens[slot] = 0
        self._active[slot] = False

    # -- the decode wave ----------------------------------------------------

    def step(self) -> np.ndarray:
        """One fused decode step for every slot; returns the (n_slots,)
        sampled tokens (garbage at inactive slots — callers consult the
        scheduler for liveness)."""
        with obs_trace.span("engine.wave.inputs"):
            self._key, k = jax.random.split(self._key)
            tok = jnp.asarray(self._tokens)
            pos = jnp.asarray(self._positions)
            bt = jnp.asarray(self._bt)
        with obs_trace.span("engine.wave.launch"):
            nxt, self.pools = self._step(self.params, tok, pos, bt,
                                         self.pools, k)
        with obs_trace.span("engine.wave.fetch"):
            nxt = np.asarray(nxt)
        act = self._active
        self._tokens[act] = nxt[act]
        self._positions[act] += 1
        self.steps_run += 1
        self.tokens_generated += int(act.sum())
        return nxt


def serve_requests(engine: ServeEngine,
                   sched: ContinuousBatchingScheduler,
                   requests: list[Request], *,
                   clock=None, idle_sleep: float = 1e-4) -> list[Request]:
    """Drive the engine until every request finishes.

    ``clock`` defaults to ``time.monotonic``; request ``arrival`` fields are
    offsets from the loop's start on that clock."""
    clock = clock or time.monotonic
    t0 = clock()
    now = lambda: clock() - t0
    for r in sorted(requests, key=lambda r: r.arrival):
        sched.submit(r)

    while not sched.idle:
        with obs_trace.span("loop.sched"):
            admitted = sched.admit(now())
        for slot, req in admitted:
            first = engine.admit(slot, req.prompt, sched.slots[slot].pages)
            with obs_trace.span("loop.tokens"):
                if sched.on_token(slot, first, now()) is not None:
                    engine.release(slot)
        if sched.n_active == 0:
            with obs_trace.span("loop.wait"):
                time.sleep(idle_sleep)      # waiting on future arrivals
            continue
        toks = engine.step()
        with obs_trace.span("loop.tokens"):
            t = now()
            for slot in sched.active_slots():
                if sched.on_token(slot, int(toks[slot]), t) is not None:
                    engine.release(slot)
    return sched.finished
