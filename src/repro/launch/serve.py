"""Serving driver: batched autoregressive decode of a (consensus) model.

GQA architectures are served by the paged decode service; every other
architecture by the contiguous-cache loop (``--legacy`` forces it).  The
choice is made from the config before anything runs.  Reduced configs
(``--smoke``) run on a CPU; on a TPU the full widths run with compiled
Pallas kernels (``chip_smoke.py``).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_serve_step
from repro.models import transformer as T
from repro.serve import kv_cache


def generate(cfg, params, prompt_tokens, n_new: int, *,
             frontend_embeds=None, temperature: float = 0.0, seed: int = 0):
    """Greedy/temperature sampling loop: prefill then n_new decode steps."""
    b, s = prompt_tokens.shape[:2]
    cache_len = s + n_new
    logits, _, caches = T.forward(params, cfg, prompt_tokens,
                                  frontend_embeds=frontend_embeds,
                                  mode="prefill", cache_len=cache_len,
                                  last_logits_only=True)
    serve_step = jax.jit(make_serve_step(cfg))
    key = jax.random.PRNGKey(seed)

    def sample(lg, key):
        if temperature <= 0.0:
            return jnp.argmax(lg, axis=-1)
        return jax.random.categorical(key, lg / temperature, axis=-1)

    # split before the first draw — sampling with `key` itself and then
    # splitting it would correlate the first token with later ones
    key, sub = jax.random.split(key)
    tok = sample(logits[:, -1], sub)
    out = [tok]
    for i in range(n_new - 1):
        key, sub = jax.random.split(key)
        pos = jnp.full((b,), s + i, jnp.int32)
        if frontend_embeds is not None:
            lg, caches = serve_step(params, tok, pos, caches,
                                    frontend_embeds=frontend_embeds)
        else:
            lg, caches = serve_step(params, tok, pos, caches)
        tok = sample(lg, sub)
        out.append(tok)
    return jnp.stack(out, axis=1)


def _serve_engine(cfg, params, args) -> dict:
    """The paged decode service (``repro.serve``): continuous batching over
    a fixed-slot batch with block-table paged KV pools."""
    from repro.serve import (ContinuousBatchingScheduler, PagedKVSpec,
                             Request, ServeEngine, serve_requests)
    ps = args.page_size
    spec = PagedKVSpec(
        page_size=ps,
        n_pages=args.batch * (-(-(args.prompt_len + args.new_tokens) // ps))
        * 2 + 1,
        max_pages_per_slot=-(-(args.prompt_len + args.new_tokens) // ps))
    engine = ServeEngine(cfg, params, kv_spec=spec, n_slots=args.batch,
                         temperature=args.temperature, seed=args.seed)
    sched = ContinuousBatchingScheduler(args.batch, spec)
    key = jax.random.PRNGKey(args.seed + 1)
    reqs = [Request(prompt=jax.random.randint(
                jax.random.fold_in(key, i), (args.prompt_len,), 0,
                cfg.vocab_size).tolist(),
                    max_new_tokens=args.new_tokens)
            for i in range(args.batch)]
    t0 = time.time()
    fin = serve_requests(engine, sched, reqs)
    dt = time.time() - t0
    n_tok = sum(len(r.tokens) for r in fin)
    return {
        "arch": cfg.name, "mode": "paged", "batch": args.batch,
        "new_tokens": args.new_tokens, "tokens": n_tok,
        "wall_s": round(dt, 2),
        "tok_per_s": round(n_tok / dt, 1),
        "sample": fin[0].tokens[:8],
    }


def _serve_legacy(cfg, params, args) -> dict:
    """Contiguous-cache batched decode (the pre-paging path; still the only
    one for MLA / SSM / cross-attention architectures)."""
    key = jax.random.PRNGKey(args.seed)
    shape = (args.batch, args.prompt_len) if cfg.n_codebooks == 1 else \
        (args.batch, args.prompt_len, cfg.n_codebooks)
    prompt = jax.random.randint(key, shape, 0, cfg.vocab_size)
    fe = None
    if cfg.frontend is not None:
        fe = 0.1 * jax.random.normal(
            key, (args.batch, cfg.frontend.n_tokens, cfg.frontend.embed_dim))
    t0 = time.time()
    toks = generate(cfg, params, prompt, args.new_tokens,
                    frontend_embeds=fe, temperature=args.temperature,
                    seed=args.seed)
    dt = time.time() - t0
    return {
        "arch": cfg.name, "mode": "legacy", "batch": args.batch,
        "new_tokens": args.new_tokens, "wall_s": round(dt, 2),
        "tok_per_s": round(args.batch * args.new_tokens / dt, 1),
        "sample": toks[0].tolist()[:8],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=configs.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--page-size", type=int, default=128,
                    help="tokens per KV page; a multiple of 128 on the chip")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--legacy", action="store_true",
                    help="force the contiguous-cache decode path")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = configs.get_config(args.arch, smoke=args.smoke)
    paged = not args.legacy and _supports_paged(cfg)
    params = T.init_params(jax.random.PRNGKey(args.seed), cfg)
    if paged:
        res = _serve_engine(cfg, params, args)
    else:
        res = _serve_legacy(cfg, params, args)
    print(json.dumps(res))
    return 0


def _supports_paged(cfg) -> bool:
    """Whether the paged service covers ``cfg`` (GQA attention only); the
    contiguous-cache path serves every other architecture."""
    try:
        kv_cache.validate_config(cfg)
    except ValueError:
        return False
    return True


if __name__ == "__main__":
    raise SystemExit(main())
