"""Serving subsystem tests: paged kernel, engine equivalence, the decode
wave's carried pools, scheduler, replica gossip sync, tune registration,
PRNG hygiene."""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import Stage
from repro.kernels import ops, ref
from repro.kernels import paged_decode as pd
from repro.launch.serve import generate
from repro.models import transformer as T
from repro.serve import (ContinuousBatchingScheduler, PagedKVSpec,
                         ReplicaGroup, Request, ServeEngine, kv_cache,
                         serve_requests)


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.get_config("smollm-135m", smoke=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _paged_case(seed=0, s=5, hkv=2, g=1, hd=32, ps=8, m=6):
    rng = np.random.default_rng(seed)
    n_pages = s * m + 1
    q = jnp.asarray(rng.normal(size=(s, hkv * g, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, hkv, hd, ps)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, hkv, hd, ps)), jnp.float32)
    seq = [1, 7, 13, 0, min(m * ps, 40)][:s]
    bt = np.full((s, m), -1, np.int32)
    nxt = 1
    for i, sl in enumerate(seq):
        for j in range(-(-sl // ps)):
            bt[i, j] = nxt
            nxt += 1
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(seq, jnp.int32)


# ---------------------------------------------------------------------------
# paged-decode kernel vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("g", [1, 2])
@pytest.mark.parametrize("window", [None, 5])
def test_paged_kernel_matches_oracle(g, window):
    q, kp, vp, bt, seq = _paged_case(g=g)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, seq, window=window)
    s, h, hd = q.shape
    hkv = kp.shape[1]
    got = pd.paged_decode_shgd(
        q.reshape(s, hkv, h // hkv, hd), kp, vp, bt, seq, window=window,
        interpret=True).reshape(s, h, hd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_paged_kernel_empty_slot_zeros():
    q, kp, vp, bt, seq = _paged_case()
    assert int(seq[3]) == 0
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, seq)
    got = ops.paged_decode_attention(q, kp, vp, bt, seq,
                                     impl="pallas_interpret")
    assert float(jnp.abs(got[3]).max()) == 0.0
    assert float(jnp.abs(want[3]).max()) == 0.0


def test_paged_dispatch_pads_ragged_table():
    # pages_per_block that doesn't divide M: ops pads the table with -1
    q, kp, vp, bt, seq = _paged_case(m=5)
    want = ops.paged_decode_attention(q, kp, vp, bt, seq, impl="ref")
    got = ops.paged_decode_attention(q, kp, vp, bt, seq,
                                     impl="pallas_interpret",
                                     pages_per_block=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# engine: paged decode == contiguous decode
# ---------------------------------------------------------------------------


def test_engine_matches_contiguous_greedy(smoke_model):
    cfg, params = smoke_model
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(3), (1, 12), 0, cfg.vocab_size))
    want = np.asarray(
        generate(cfg, params, jnp.asarray(prompt), 8, temperature=0.0))[0]
    spec = PagedKVSpec(page_size=4, n_pages=33, max_pages_per_slot=5)
    engine = ServeEngine(cfg, params, kv_spec=spec, n_slots=2,
                         temperature=0.0)
    sched = ContinuousBatchingScheduler(2, spec)
    fin = serve_requests(engine, sched,
                         [Request(prompt=prompt[0].tolist(),
                                  max_new_tokens=8)])
    assert fin[0].tokens == want.tolist()


def test_engine_greedy_matches_full_forward_argmax(smoke_model):
    # decode with the paged cache == argmax over a from-scratch full forward
    cfg, params = smoke_model
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(4), (1, 9), 0, cfg.vocab_size))
    spec = PagedKVSpec(page_size=4, n_pages=33, max_pages_per_slot=5)
    engine = ServeEngine(cfg, params, kv_spec=spec, n_slots=1,
                         temperature=0.0)
    sched = ContinuousBatchingScheduler(1, spec)
    fin = serve_requests(engine, sched,
                         [Request(prompt=prompt[0].tolist(),
                                  max_new_tokens=6)])
    seq = prompt[0].tolist()
    for tok in fin[0].tokens:
        logits, _, _ = T.forward(params, cfg, jnp.asarray([seq]),
                                 mode="eval", last_logits_only=True)
        lg = np.asarray(logits[0, -1])
        top2 = np.sort(lg)[-2:]
        # only compare where argmax is numerically unambiguous
        if top2[1] - top2[0] > 1e-3:
            assert int(np.argmax(lg)) == tok
        seq.append(tok)


def test_engine_ragged_batch(smoke_model):
    # two ragged requests decoded together == each decoded alone
    cfg, params = smoke_model
    prompts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(10 + i), (n,), 0, cfg.vocab_size)).tolist()
        for i, n in enumerate((5, 14))]
    spec = PagedKVSpec(page_size=4, n_pages=33, max_pages_per_slot=6)

    def run(prompt_list, n_slots):
        engine = ServeEngine(cfg, params, kv_spec=spec, n_slots=n_slots,
                             temperature=0.0)
        sched = ContinuousBatchingScheduler(n_slots, spec)
        fin = serve_requests(engine, sched, [
            Request(prompt=p, max_new_tokens=7) for p in prompt_list])
        return {tuple(r.prompt): r.tokens for r in fin}

    together = run(prompts, 2)
    for p in prompts:
        alone = run([p], 1)
        assert together[tuple(p)] == alone[tuple(p)]


# ---------------------------------------------------------------------------
# the decode wave: pools carried whole through the layer scan
# ---------------------------------------------------------------------------


def _two_block_variant(cfg):
    """The smoke config with two GQA blocks per scanned supercell."""
    blk = cfg.stages[0].blocks[0]
    return dataclasses.replace(
        cfg, stages=(Stage(blocks=(blk, blk), repeat=2),))


@pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
@pytest.mark.parametrize("cell", ["one_block", "two_blocks"])
def test_carried_pools_write_only_live_rows(smoke_model, monkeypatch, impl,
                                            cell):
    """Waves of the paged ``decode_step`` over pools pre-filled with a
    sentinel: slots start at different waves (ragged), slot 1 never starts
    (an all ``-1`` row), slot 0 crosses a page boundary.  Only the live
    slots' (layer, page, offset) rows and layer 0's dump page may change, and
    every layer's written K/V and every live logit equal the contiguous
    cache's ``decode_step`` on the same tokens."""
    cfg, params = smoke_model
    if cell == "two_blocks":
        cfg = _two_block_variant(cfg)
        params = T.init_params(jax.random.PRNGKey(1), cfg)
    ps, n_pages, m, sentinel = 4, 11, 3, 3.0
    start = [0, None, 2, 5]                  # wave at which each slot starts
    n_waves = 7                              # slot 0 reaches position 6
    pages = {0: [1, 2], 2: [4, 5], 3: [7]}   # page 3, 6, 8.. never written
    bt = np.full((len(start), m), -1, np.int32)
    for s_, pg in pages.items():
        bt[s_, :len(pg)] = pg
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (len(start), n_waves))

    spec = PagedKVSpec(page_size=ps, n_pages=n_pages, max_pages_per_slot=m)
    pools = jax.tree.map(lambda a: jnp.full_like(a, sentinel),
                         kv_cache.init_pools(cfg, spec, jnp.float32))
    caches = T.init_cache(cfg, len(start), ps * m, jnp.float32)
    contiguous = jax.jit(functools.partial(T.decode_step, cfg=cfg))
    monkeypatch.setenv("REPRO_KERNEL_IMPL", impl)
    paged = jax.jit(functools.partial(T.decode_step, cfg=cfg))

    for t in range(n_waves):
        live = [st is not None and t >= st for st in start]
        pos = np.asarray([t - st if lv else 0
                          for st, lv in zip(start, live)], np.int32)
        tok = np.asarray([toks[i, p] for i, p in enumerate(pos)], np.int32)
        bt_t = np.where(np.asarray(live)[:, None], bt, -1)
        want, caches = contiguous(params, token=jnp.asarray(tok),
                                  position=jnp.asarray(pos), caches=caches)
        got, pools = paged(params, token=jnp.asarray(tok),
                           position=(jnp.asarray(pos), jnp.asarray(bt_t)),
                           caches=pools)
        np.testing.assert_allclose(np.asarray(got)[live],
                                   np.asarray(want)[live], atol=1e-4)

    lengths = {s_: n_waves - st for s_, st in enumerate(start)
               if st is not None}
    for blk, pool in pools["s0"].items():
        for name, leaf in pool.items():
            leaf = np.asarray(leaf)          # (R, P, Hkv, hd, ps)
            ref_kv = np.asarray(caches["s0"][blk][name[0]])  # (R, B, L, ...)
            changed = (leaf != sentinel).any(axis=(2, 3))    # (R, P, ps)
            allowed = np.zeros_like(changed)
            # inactive slots write page 0 of the flattened pool: layer 0's
            # dump page, from every layer
            allowed[0, 0] = True
            for s_, n in lengths.items():
                for p in range(n):
                    pg, off = bt[s_, p // ps], p % ps
                    allowed[:, pg, off] = True
                    np.testing.assert_allclose(leaf[:, pg, :, :, off],
                                               ref_kv[:, s_, p], atol=1e-5)
            assert not (changed & ~allowed).any(), (blk, name)


def test_wave_carries_pools_and_aliases_them(smoke_model):
    """The wave's layer scan has no pool-shaped array among its scanned
    inputs or outputs (the pools ride its carry), and the compiled wave
    still aliases the donated pools to its outputs."""
    cfg, params = smoke_model
    assert cfg.stages[0].repeat >= 2
    spec = PagedKVSpec(page_size=4, n_pages=9, max_pages_per_slot=3)
    engine = ServeEngine(cfg, params, kv_spec=spec, n_slots=2)
    args = (params, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.full((2, 3), -1, jnp.int32), engine.pools,
            jax.random.PRNGKey(0))
    page = jax.tree.leaves(engine.pools)[0].shape[2:]    # (Hkv, hd, ps)

    def pool_shaped(v):
        shape = getattr(v.aval, "shape", ())
        return tuple(shape[-3:]) == page and \
            int(np.prod(shape[:-3])) >= spec.n_pages

    def scans(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from scans(sub)

    carried = 0
    for eqn in scans(jax.make_jaxpr(engine._step_impl)(*args).jaxpr):
        n_in = eqn.params["num_consts"] + eqn.params["num_carry"]
        xs, ys = eqn.invars[n_in:], eqn.outvars[eqn.params["num_carry"]:]
        assert not any(pool_shaped(v) for v in (*xs, *ys))
        carried += sum(pool_shaped(v)
                       for v in eqn.invars[eqn.params["num_consts"]:n_in])
    assert carried == 2                      # the K and V pools, one scan

    hlo = engine._step.lower(*args).compile().as_text()
    header = hlo.splitlines()[0]
    aliased = {int(p) for p in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    first_pool = len(jax.tree.leaves(params)) + 3       # tok, positions, bt
    assert aliased == set(range(
        first_pool, first_pool + len(jax.tree.leaves(engine.pools))))


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------


def _spec(ps=4, n_pages=9, m=4):
    return PagedKVSpec(page_size=ps, n_pages=n_pages, max_pages_per_slot=m)


def test_scheduler_admit_evict_refill():
    spec = _spec()                       # 8 usable pages, 2 pages/request
    sched = ContinuousBatchingScheduler(2, spec)
    reqs = [Request(prompt=[1] * 4, max_new_tokens=4, arrival=0.0)
            for _ in range(4)]
    for r in reqs:
        sched.submit(r)
    adm = sched.admit(now=0.0)
    assert [s for s, _ in adm] == [0, 1]
    assert sched.pool.n_free == 4
    # slot 0 finishes its budget -> evicted, pages released, refilled
    for i in range(4):
        done = sched.on_token(0, 7, now=0.1 + i * 0.01)
    assert done is reqs[0] and done.latency > 0
    assert sched.pool.n_free == 6
    adm = sched.admit(now=0.2)
    assert [s for s, _ in adm] == [0] and adm[0][1] is reqs[2]
    # EOS eviction
    sched.slots[1].request.eos_id = 9
    assert sched.on_token(1, 9, now=0.3) is reqs[1]


def test_scheduler_respects_arrivals_and_pages():
    spec = _spec(n_pages=5)              # only 4 usable pages
    sched = ContinuousBatchingScheduler(2, spec)
    sched.submit(Request(prompt=[1] * 8, max_new_tokens=8, arrival=0.0))  # 4p
    sched.submit(Request(prompt=[1] * 4, max_new_tokens=4, arrival=5.0))
    adm = sched.admit(now=0.0)
    assert len(adm) == 1 and sched.pool.n_free == 0
    # head-of-queue hasn't arrived yet -> nothing admitted even at now=1
    assert sched.admit(now=1.0) == []
    for i in range(8):
        sched.on_token(0, 3, now=2.0 + i * 0.1)
    assert sched.admit(now=4.0) == []    # arrival still in the future
    assert len(sched.admit(now=5.0)) == 1


def test_scheduler_static_mode_drains_before_refill():
    spec = _spec(n_pages=17)
    sched = ContinuousBatchingScheduler(2, spec, refill="static")
    for _ in range(3):
        sched.submit(Request(prompt=[1] * 4, max_new_tokens=2, arrival=0.0))
    assert len(sched.admit(now=0.0)) == 2
    sched.on_token(0, 1, 0.1)
    done = sched.on_token(0, 1, 0.2)
    assert done is not None
    assert sched.admit(now=0.3) == []    # slot 1 still running
    sched.on_token(1, 1, 0.4)
    sched.on_token(1, 1, 0.5)
    assert len(sched.admit(now=0.6)) == 1


def test_scheduler_rejects_oversized_request():
    with pytest.raises(ValueError):
        ContinuousBatchingScheduler(1, _spec()).submit(
            Request(prompt=[1] * 20, max_new_tokens=20))


# ---------------------------------------------------------------------------
# replica gossip sync
# ---------------------------------------------------------------------------


def test_replica_sync_reduces_drift_monotonically(smoke_model):
    cfg, params = smoke_model
    group = ReplicaGroup(params, 2, seed=0)
    assert group.drift() == 0.0
    d0 = group.perturb(0.02)
    assert d0 > 0.01
    trace = group.sync(rounds=4)
    assert all(b <= a * (1 + 1e-6) for a, b in zip(trace, trace[1:]))
    assert trace[-1] < 0.2 * d0
    wire = group.wire_stats()
    assert wire["rounds"] == 4
    assert wire["wire_bytes"] < 0.5 * wire["raw_bytes"]   # int8 on the wire


def test_replica_params_usable_by_engine(smoke_model):
    cfg, params = smoke_model
    group = ReplicaGroup(params, 2, seed=0)
    group.perturb(0.01)
    spec = PagedKVSpec(page_size=4, n_pages=17, max_pages_per_slot=4)
    engine = ServeEngine(cfg, group.replica(0), kv_spec=spec, n_slots=1,
                         temperature=0.0)
    sched = ContinuousBatchingScheduler(1, spec)
    fin = serve_requests(engine, sched,
                         [Request(prompt=[1, 2, 3], max_new_tokens=3)])
    assert len(fin[0].tokens) == 3


# ---------------------------------------------------------------------------
# tune registration (flash_attention + paged_decode)
# ---------------------------------------------------------------------------


def test_tune_search_covers_attention_kernels(tmp_path, monkeypatch):
    from repro.kernels import tune
    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path))
    monkeypatch.setenv("REPRO_TUNE", "search")
    e1 = tune.autotune("flash_attention", (1, 32, 32, 2, 16), "float32")
    e2 = tune.autotune("paged_decode", (2, 4, 8, 16), "float32")
    for e in (e1, e2):
        # every candidate oracle-gated, default included
        assert all("accurate" in c for c in e["candidates"])
        assert all(c["accurate"] for c in e["candidates"])
    assert tune.lookup("flash_attention", (1, 32, 32, 2, 16),
                       "float32") == e1["config"]
    assert tune.lookup("paged_decode", (2, 4, 8, 16),
                       "float32") == e2["config"]


# ---------------------------------------------------------------------------
# PRNG hygiene in the legacy generate loop
# ---------------------------------------------------------------------------


def test_generate_sampling_keys_are_distinct(smoke_model, monkeypatch):
    cfg, params = smoke_model
    seen = []
    orig = jax.random.categorical

    def spy(key, *a, **kw):
        seen.append(np.asarray(jax.random.key_data(key)).tolist())
        return orig(key, *a, **kw)

    monkeypatch.setattr(jax.random, "categorical", spy)
    prompt = jnp.asarray(np.zeros((1, 4), np.int32))
    generate(cfg, params, prompt, 4, temperature=1.0)
    assert len(seen) == 4
    assert len({tuple(k) for k in seen}) == 4   # no key reuse
