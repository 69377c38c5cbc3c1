"""Model assembly: stages of block supercells, executed with lax.scan.

Three entry points (all pure):

  * ``forward``      — training/prefill logits over a full sequence
                       (``mode="prefill"`` additionally returns caches);
  * ``decode_step``  — one new token against per-layer caches;
  * ``init_params`` / ``init_cache`` — constructors (init_cache is
                       shape-only: usable under ``jax.eval_shape`` for the
                       dry-run's ShapeDtypeStruct inputs).

Layer stacking: a :class:`Stage` repeats a supercell ``repeat`` times; its
parameters (and caches) carry a leading ``repeat`` axis and the supercell
body compiles once (flat compile time in depth — 62-layer Gemma compiles a
6-block body).

Device scopes (:func:`repro.obs.trace.scope`), the same in every mode:
``model.layers`` around each stage, ``block.attention`` / ``block.mlp``
inside an attention block, ``model.head`` around the final norm and the
unembedding.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import BlockSpec, ModelConfig, Stage
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import (dense_init, embed_init, rmsnorm,
                                 rmsnorm_init, swiglu, swiglu_init)
from repro.obs import trace as obs_trace

Array = jax.Array
PyTree = Any


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_block(key, cfg: ModelConfig, spec: BlockSpec, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 6)
    p: dict = {"ln1": rmsnorm_init(cfg.d_model, dtype)}
    if spec.kind in ("attn", "moe_attn"):
        p["attn"] = attn_mod.init_attention(ks[0], cfg, spec.attn, dtype)
        if spec.attn.cross_attn:
            p["ln_x"] = rmsnorm_init(cfg.d_model, dtype)
        p["ln2"] = rmsnorm_init(cfg.d_model, dtype)
        if spec.kind == "moe_attn":
            p["moe"] = moe_mod.init_moe(ks[1], cfg, spec.moe, dtype)
        elif spec.has_mlp and cfg.d_ff > 0:
            p["mlp"] = swiglu_init(ks[1], cfg.d_model, cfg.d_ff, dtype)
    elif spec.kind == "mamba":
        p["mamba"] = ssm_mod.init_mamba(ks[0], cfg, spec.ssm, dtype)
    elif spec.kind == "mlstm":
        p["mlstm"] = xlstm_mod.init_mlstm(ks[0], cfg, spec.xlstm, dtype)
    elif spec.kind == "slstm":
        p["slstm"] = xlstm_mod.init_slstm(ks[0], cfg, spec.xlstm, dtype)
    else:
        raise ValueError(spec.kind)
    return p


def init_stage(key, cfg: ModelConfig, stage: Stage, dtype=jnp.float32) -> dict:
    def one(k):
        kk = jax.random.split(k, len(stage.blocks))
        return {f"b{i}": init_block(kk[i], cfg, sp, dtype)
                for i, sp in enumerate(stage.blocks)}
    if stage.repeat == 1:
        return one(key)
    keys = jax.random.split(key, stage.repeat)
    per = [one(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per)


def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, len(cfg.stages) + 4)
    v_eff = cfg.padded_vocab
    p: dict = {}
    if cfg.n_codebooks > 1:
        p["embed"] = jnp.stack([
            embed_init(k, v_eff, cfg.d_model, dtype)
            for k in jax.random.split(ks[0], cfg.n_codebooks)])
    else:
        p["embed"] = embed_init(ks[0], v_eff, cfg.d_model, dtype)
    if cfg.frontend is not None:
        p["frontend_proj"] = dense_init(ks[1], cfg.frontend.embed_dim,
                                        cfg.d_model, dtype=dtype)
    p["stages"] = {f"s{i}": init_stage(ks[2 + i], cfg, st, dtype)
                   for i, st in enumerate(cfg.stages)}
    p["final_norm"] = rmsnorm_init(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        if cfg.n_codebooks > 1:
            p["lm_head"] = jnp.stack([
                dense_init(k, cfg.d_model, v_eff, dtype=dtype)
                for k in jax.random.split(ks[-1], cfg.n_codebooks)])
        else:
            p["lm_head"] = dense_init(ks[-1], cfg.d_model, v_eff, dtype=dtype)
    return p


# ---------------------------------------------------------------------------
# caches (shape-only constructors)
# ---------------------------------------------------------------------------


def init_block_cache(cfg: ModelConfig, spec: BlockSpec, bsz: int,
                     cache_seq_len: int, dtype) -> Optional[dict]:
    if spec.kind in ("attn", "moe_attn"):
        a = spec.attn
        cl = attn_mod.attn_cache_len(a, cache_seq_len)
        if a.kind == "mla":
            return {
                "c_kv": jnp.zeros((bsz, cl, a.kv_lora_rank), dtype),
                "k_rope": jnp.zeros((bsz, cl, a.qk_rope_head_dim), dtype),
                "pos": jnp.full((bsz, cl), -1, jnp.int32),
            }
        return {
            "k": jnp.zeros((bsz, cl, cfg.n_kv_heads, cfg.hd), dtype),
            "v": jnp.zeros((bsz, cl, cfg.n_kv_heads, cfg.hd), dtype),
            "pos": jnp.full((bsz, cl), -1, jnp.int32),
        }
    if spec.kind == "mamba":
        d_inner = spec.ssm.expand * cfg.d_model
        h = d_inner // spec.ssm.head_dim
        conv_c = d_inner + 2 * spec.ssm.n_groups * spec.ssm.d_state
        return {
            "ssm": jnp.zeros((bsz, h, spec.ssm.d_state, spec.ssm.head_dim),
                             jnp.float32),
            "conv": jnp.zeros((bsz, spec.ssm.d_conv - 1, conv_c), dtype),
        }
    if spec.kind == "mlstm":
        d_inner = int(cfg.d_model * spec.xlstm.proj_factor)
        dk = d_inner // cfg.n_heads
        return {
            "C": jnp.zeros((bsz, cfg.n_heads, dk, dk), jnp.float32),
            "n": jnp.zeros((bsz, cfg.n_heads, dk), jnp.float32),
            "m": jnp.full((bsz, cfg.n_heads), -1e30, jnp.float32),
            "conv": jnp.zeros((bsz, spec.xlstm.conv_window - 1, d_inner), dtype),
        }
    if spec.kind == "slstm":
        z = jnp.zeros((bsz, cfg.d_model), jnp.float32)
        return {"state": (z, jnp.ones_like(z), z,
                          jnp.full((bsz, cfg.d_model), -1e30, jnp.float32))}
    raise ValueError(spec.kind)


def init_cache(cfg: ModelConfig, bsz: int, cache_seq_len: int,
               dtype=jnp.float32) -> dict:
    caches = {}
    for i, st in enumerate(cfg.stages):
        cell = {f"b{j}": init_block_cache(cfg, sp, bsz, cache_seq_len, dtype)
                for j, sp in enumerate(st.blocks)}
        if st.repeat > 1:
            cell = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (st.repeat, *x.shape)), cell)
        caches[f"s{i}"] = cell
    return caches


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------


def apply_block(params: dict, cfg: ModelConfig, spec: BlockSpec, x: Array,
                positions: Array, mode: str, cache: Optional[dict],
                frontend_embeds: Optional[Array],
                cache_len: Optional[int] = None):
    """Returns (x, aux_loss, new_cache).  Attention blocks run in the
    device scopes ``block.attention`` (norm, attention, residual) and
    ``block.mlp`` (norm, MLP or MoE, residual)."""
    aux = jnp.zeros((), jnp.float32)

    if spec.kind in ("attn", "moe_attn"):
        a = spec.attn
        with obs_trace.scope("block.attention"):
            h = rmsnorm(params["ln1"], x, cfg.norm_eps)
            if mode == "decode":
                if a.kind != "mla" and isinstance(cache, dict) \
                        and "k_pages" in cache:
                    # paged serving path: ``positions`` is (position,
                    # block_table)
                    y, cache = attn_mod.gqa_decode_paged(
                        params["attn"], h, cfg, a, positions, cache)
                else:
                    fn = attn_mod.mla_decode if a.kind == "mla" \
                        else attn_mod.gqa_decode
                    y, cache = fn(params["attn"], h, cfg, a, positions,
                                  cache)
            else:
                fn = attn_mod.mla_prefill if a.kind == "mla" \
                    else attn_mod.gqa_prefill
                cl = attn_mod.attn_cache_len(a, cache_len or x.shape[1])
                y, cache = fn(params["attn"], h, cfg, a, positions,
                              make_cache=(mode == "prefill"), cache_len=cl)
            x = x + y
            if a.cross_attn and frontend_embeds is not None:
                hx = rmsnorm(params["ln_x"], x, cfg.norm_eps)
                fkv = attn_mod.make_frontend_kv(params["attn"],
                                                frontend_embeds, cfg)
                x = x + attn_mod.cross_attend(params["attn"], hx, cfg, fkv)
        with obs_trace.scope("block.mlp"):
            h2 = rmsnorm(params["ln2"], x, cfg.norm_eps)
            if spec.kind == "moe_attn":
                y2, aux = moe_mod.apply_moe(params["moe"], h2, spec.moe)
                x = x + y2
            elif "mlp" in params:
                x = x + swiglu(params["mlp"], h2)
        return x, aux, cache

    h = rmsnorm(params["ln1"], x, cfg.norm_eps)
    if spec.kind == "mamba":
        if mode == "decode":
            y, cache = ssm_mod.mamba_decode(params["mamba"], h, cfg, spec.ssm, cache)
        else:
            y, cache = ssm_mod.mamba_prefill(params["mamba"], h, cfg, spec.ssm,
                                             make_cache=(mode == "prefill"))
        return x + y, aux, cache

    if spec.kind == "mlstm":
        if mode == "decode":
            y, cache = xlstm_mod.mlstm_decode(params["mlstm"], h, cfg,
                                              spec.xlstm, cache)
        else:
            y, cache = xlstm_mod.mlstm_prefill(params["mlstm"], h, cfg,
                                               spec.xlstm,
                                               make_cache=(mode == "prefill"))
        return x + y, aux, cache

    if spec.kind == "slstm":
        if mode == "decode":
            y, cache = xlstm_mod.slstm_decode(params["slstm"], h, cfg,
                                              spec.xlstm, cache)
        else:
            y, cache = xlstm_mod.slstm_prefill(params["slstm"], h, cfg,
                                               spec.xlstm,
                                               make_cache=(mode == "prefill"))
        return x + y, aux, cache

    raise ValueError(spec.kind)


def _apply_supercell(cell_params: dict, cfg: ModelConfig, stage: Stage,
                     x: Array, positions: Array, mode: str,
                     cell_cache: Optional[dict],
                     frontend_embeds: Optional[Array],
                     cache_len: Optional[int] = None):
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = {}
    for j, sp in enumerate(stage.blocks):
        bc = None if cell_cache is None else cell_cache.get(f"b{j}")
        x, aux, nc = apply_block(cell_params[f"b{j}"], cfg, sp, x, positions,
                                 mode, bc, frontend_embeds, cache_len)
        aux_total += aux
        new_caches[f"b{j}"] = nc
    return x, aux_total, new_caches


def _holds_pools(stage_cache: Optional[dict]) -> bool:
    """Whether every block cache of a stage is a paged pool
    (``repro.serve.kv_cache``) rather than a per-sequence cache."""
    return bool(stage_cache) and all(
        isinstance(c, dict) and "k_pages" in c for c in stage_cache.values())


def _scan_paged_decode(cell_at, stage_params: dict, x: Array, positions,
                       pools: dict, repeat: int):
    """Decode a scanned stage with its paged pools carried whole.

    ``cell_at(positions)`` gives the stage's cell for the positions (here
    each layer's ``(position, block_table)``).

    Each block's stacked pool ``(R, P, ...)`` is flattened to
    ``(R * P, ...)`` (a bitcast) and rides the scan's carry; layer
    ``i`` addresses its own pages through the block table offset by
    ``i * P`` (``-1`` stays ``-1``: unallocated entries still clamp to one
    page and are masked).  The new token's K/V are written in place into the
    carried buffer and the paged kernel reads layer ``i``'s pages straight
    from it, so no layer's pool is sliced out of the stack or stacked back.
    Inactive slots write into page 0 of the flattened pool, layer 0's dump
    page, from every layer."""
    position, block_table = positions
    n_pages = jax.tree.leaves(pools)[0].shape[1]
    flat = jax.tree.map(lambda l: l.reshape(-1, *l.shape[2:]), pools)

    def body(carry, scanned):
        xx, aux_acc, pp = carry
        p, i = scanned
        bt = jnp.where(block_table >= 0, block_table + i * n_pages, -1)
        xx, aux, pp = cell_at((position, bt))(p, xx, pp)
        return (xx, aux_acc + aux, pp), None

    (x, aux, flat), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), flat),
        (stage_params, jnp.arange(repeat, dtype=jnp.int32)))
    return x, aux, jax.tree.map(lambda f, l: f.reshape(l.shape), flat, pools)


def apply_stage(stage_params: dict, cfg: ModelConfig, stage: Stage, x: Array,
                positions: Array, mode: str, stage_cache: Optional[dict],
                frontend_embeds: Optional[Array],
                cache_len: Optional[int] = None):
    want_cache = mode in ("prefill", "decode")

    def cell_at(pos):
        base = functools.partial(_apply_supercell, cfg=cfg, stage=stage,
                                 positions=pos, mode=mode,
                                 frontend_embeds=frontend_embeds,
                                 cache_len=cache_len)

        def cell(p, xx, cc):
            if cfg.remat and mode == "train":
                ck = jax.checkpoint(
                    lambda pp, xxx: base(pp, x=xxx, cell_cache=None),
                    policy=jax.checkpoint_policies.nothing_saveable)
                return ck(p, xx)
            return base(p, x=xx, cell_cache=cc)
        return cell

    cell = cell_at(positions)

    if stage.repeat == 1:
        x, aux, nc = cell(stage_params, x, stage_cache)
        return x, aux, (nc if want_cache else None)

    if not cfg.use_scan:
        # unrolled execution (dry-run differential cost analysis: while-loop
        # bodies are cost-counted once, so analysis variants unroll)
        aux_total = jnp.zeros((), jnp.float32)
        new_caches = []
        for i in range(stage.repeat):
            p_i = jax.tree.map(lambda l: l[i], stage_params)
            c_i = None if stage_cache is None else \
                jax.tree.map(lambda l: l[i], stage_cache)
            x, aux, nc = cell(p_i, x, c_i)
            aux_total += aux
            new_caches.append(nc)
        if want_cache:
            stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *new_caches)
            return x, aux_total, stacked
        return x, aux_total, None

    if mode == "decode" and _holds_pools(stage_cache):
        return _scan_paged_decode(cell_at, stage_params, x, positions,
                                  stage_cache, stage.repeat)

    def body(carry, scanned):
        xx, aux_acc = carry
        if want_cache:
            p, cc = scanned
        else:
            p, cc = scanned, None
        xx, aux, nc = cell(p, xx, cc)
        return (xx, aux_acc + aux), (nc if want_cache else 0)

    if want_cache:
        (x, aux), caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                        (stage_params, stage_cache))
    else:
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   stage_params)
        caches = None
    return x, aux, caches


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------


def embed_tokens(params: dict, cfg: ModelConfig, tokens: Array) -> Array:
    if cfg.n_codebooks > 1:
        # tokens (B, S, CB): sum of per-codebook embeddings (MusicGen);
        # params["embed"]: (CB, V, d)
        parts = [params["embed"][c][tokens[..., c]]
                 for c in range(cfg.n_codebooks)]
        return sum(parts)
    return params["embed"][tokens]


def unembed(params: dict, cfg: ModelConfig, h: Array) -> Array:
    if cfg.tie_embeddings:
        if cfg.n_codebooks > 1:
            return jnp.einsum("bsd,cvd->bscv", h, params["embed"])
        return h @ params["embed"].T
    if cfg.n_codebooks > 1:
        return jnp.einsum("bsd,cdv->bscv", h, params["lm_head"])
    return h @ params["lm_head"]


def project_frontend(params: dict, cfg: ModelConfig,
                     frontend_embeds: Optional[Array]) -> Optional[Array]:
    if frontend_embeds is None or cfg.frontend is None:
        return None
    return frontend_embeds @ params["frontend_proj"]


def forward(params: dict, cfg: ModelConfig, tokens: Array, *,
            frontend_embeds: Optional[Array] = None, mode: str = "train",
            cache_len: Optional[int] = None, last_logits_only: bool = False):
    """tokens: (B, S) or (B, S, CB).  Returns (logits, aux, caches|None)."""
    b, s = tokens.shape[:2]
    x = embed_tokens(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    fe = project_frontend(params, cfg, frontend_embeds)
    aux_total = jnp.zeros((), jnp.float32)
    caches = {}
    for i, st in enumerate(cfg.stages):
        with obs_trace.scope("model.layers"):
            x, aux, nc = apply_stage(params["stages"][f"s{i}"], cfg, st, x,
                                     positions, mode, None, fe, cache_len)
        aux_total += aux
        if nc is not None:
            caches[f"s{i}"] = nc
    with obs_trace.scope("model.head"):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if last_logits_only:
            x = x[:, -1:]
        logits = unembed(params, cfg, x)
    return logits, aux_total, (caches if mode == "prefill" else None)


def decode_step(params: dict, cfg: ModelConfig, token: Array, position: Array,
                caches: dict, *, frontend_embeds: Optional[Array] = None):
    """token: (B,) or (B, CB); position: (B,) int32.  One-step decode.

    Returns (logits (B, V) or (B, CB, V), new_caches).
    """
    tok = token[:, None] if cfg.n_codebooks == 1 else token[:, None, :]
    x = embed_tokens(params, cfg, tok)
    fe = project_frontend(params, cfg, frontend_embeds)
    new_caches = {}
    for i, st in enumerate(cfg.stages):
        with obs_trace.scope("model.layers"):
            x, _, nc = apply_stage(params["stages"][f"s{i}"], cfg, st, x,
                                   position, "decode", caches[f"s{i}"], fe)
        new_caches[f"s{i}"] = nc
    with obs_trace.scope("model.head"):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = unembed(params, cfg, x)[:, 0]
    return logits, new_caches
