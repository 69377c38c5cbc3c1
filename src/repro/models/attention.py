"""Attention sublayers: GQA (w/ sliding window), MLA (DeepSeek-V2), cross-attn.

Pure functions over param dicts.  The score/softmax/PV core goes through
``repro.kernels.ops.flash_attention`` (Pallas on TPU, blockwise-jnp
elsewhere).  Prefill returns a KV cache; ``decode`` consumes/updates it.

KV caches are ring buffers: slot = position % cache_len, with an explicit
``pos`` array (-1 = empty) used for masking, so sliding-window layers can
allocate ``cache_len == window`` even when the sequence is 500k tokens.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import AttnSpec, ModelConfig
from repro.kernels import ops
from repro.models.layers import apply_rope, dense_init, orthogonal_init

Array = jax.Array


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def init_gqa(key, cfg: ModelConfig, spec: AttnSpec, dtype=jnp.float32):
    hd, h, hkv, d = cfg.hd, cfg.n_heads, cfg.n_kv_heads, cfg.d_model
    ks = jax.random.split(key, 6)
    p = {
        "wq": orthogonal_init(ks[0], d, h * hd, dtype),
        "wk": orthogonal_init(ks[1], d, hkv * hd, dtype),
        "wv": orthogonal_init(ks[2], d, hkv * hd, dtype),
        "wo": orthogonal_init(ks[3], h * hd, d, dtype),
    }
    if spec.cross_attn:
        p["wk_x"] = orthogonal_init(ks[4], d, hkv * hd, dtype)
        p["wv_x"] = orthogonal_init(ks[5], d, hkv * hd, dtype)
    return p


def gqa_prefill(params, x: Array, cfg: ModelConfig, spec: AttnSpec,
                positions: Array, *, make_cache: bool = False,
                cache_len: int = 0):
    """x: (B, S, d).  Returns (y, cache | None)."""
    b, s, d = x.shape
    hd, h, hkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    q = (x @ params["wq"]).reshape(b, s, h, hd)
    k = (x @ params["wk"]).reshape(b, s, hkv, hd)
    v = (x @ params["wv"]).reshape(b, s, hkv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    y = ops.flash_attention(q, k, v, causal=True, window=spec.sliding_window,
                            q_positions=positions, kv_positions=positions)
    out = y.reshape(b, s, h * hd) @ params["wo"]

    cache = None
    if make_cache:
        cl = cache_len or s
        cache = _new_kv_cache(b, cl, hkv, hd, k.dtype)
        cache = _cache_write_many(cache, k, v, positions)
    return out, cache


def gqa_decode(params, x: Array, cfg: ModelConfig, spec: AttnSpec,
               position: Array, cache: dict):
    """One-token decode.  x: (B, 1, d); position: (B,) int32."""
    b, _, d = x.shape
    hd, h, hkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    q = (x @ params["wq"]).reshape(b, 1, h, hd)
    k = (x @ params["wk"]).reshape(b, 1, hkv, hd)
    v = (x @ params["wv"]).reshape(b, 1, hkv, hd)
    pos2 = position[:, None]
    q = apply_rope(q, pos2, cfg.rope_theta)
    k = apply_rope(k, pos2, cfg.rope_theta)

    cache = _cache_write_one(cache, k[:, 0], v[:, 0], position)
    y = ops.flash_attention(q, cache["k"], cache["v"], causal=True,
                            window=spec.sliding_window, q_positions=pos2,
                            kv_positions=cache["pos"])
    return y.reshape(b, 1, h * hd) @ params["wo"], cache


def gqa_decode_paged(params, x: Array, cfg: ModelConfig, spec: AttnSpec,
                     pos_bt, cache: dict):
    """One-token decode against a paged KV pool (``repro.serve.kv_cache``).

    ``pos_bt`` is ``(position, block_table)``: per-slot positions (S,) int32
    of the *incoming* token, and the shared block table (S, M) int32 — they
    ride together through ``decode_step``'s opaque ``position`` argument.
    ``cache`` holds this layer's ``{"k_pages", "v_pages"}`` pools (their
    layout is the kernel layer's, ``ops.paged_write``); the new token's K/V
    are written into the slot's current page (inactive slots land on the
    dump page 0), then attention runs through the block-table gather kernel
    with ``seq_lens = position + 1``."""
    position, block_table = pos_bt
    s, _, _ = x.shape
    hd, h, hkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    q = (x @ params["wq"]).reshape(s, 1, h, hd)
    k = (x @ params["wk"]).reshape(s, 1, hkv, hd)
    v = (x @ params["wv"]).reshape(s, 1, hkv, hd)
    pos2 = position[:, None]
    q = apply_rope(q, pos2, cfg.rope_theta)
    k = apply_rope(k, pos2, cfg.rope_theta)

    kp = ops.paged_write(cache["k_pages"], block_table, position, k[:, 0])
    vp = ops.paged_write(cache["v_pages"], block_table, position, v[:, 0])
    y = ops.paged_decode_attention(q[:, 0], kp, vp, block_table,
                                   position + 1, window=spec.sliding_window)
    return (y.reshape(s, 1, h * hd) @ params["wo"],
            {"k_pages": kp, "v_pages": vp})


def cross_attend(params, x: Array, cfg: ModelConfig, frontend_kv: dict):
    """Cross-attention onto precomputed frontend K/V (not causal)."""
    b, s, d = x.shape
    hd, h = cfg.hd, cfg.n_heads
    q = (x @ params["wq"]).reshape(b, s, h, hd)
    y = ops.flash_attention(q, frontend_kv["k"], frontend_kv["v"],
                            causal=False, q_positions=None, kv_positions=None)
    return y.reshape(b, s, h * hd) @ params["wo"]


def make_frontend_kv(params, embeds: Array, cfg: ModelConfig) -> dict:
    """Project frontend embeddings (B, N, d_model) once into K/V."""
    b, n, _ = embeds.shape
    hd, hkv = cfg.hd, cfg.n_kv_heads
    return {
        "k": (embeds @ params["wk_x"]).reshape(b, n, hkv, hd),
        "v": (embeds @ params["wv_x"]).reshape(b, n, hkv, hd),
    }


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, DeepSeek-V2)
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ModelConfig, spec: AttnSpec, dtype=jnp.float32):
    d, h = cfg.d_model, cfg.n_heads
    dq, dkv = spec.q_lora_rank, spec.kv_lora_rank
    dn, dr, dv = spec.qk_nope_head_dim, spec.qk_rope_head_dim, spec.v_head_dim
    ks = jax.random.split(key, 7)
    p = {
        "w_dkv": dense_init(ks[1], d, dkv + dr, dtype=dtype),        # down: c_kv + k_rope
        "w_uk": orthogonal_init(ks[2], dkv, h * dn, dtype),          # up: K (nope)
        "w_uv": orthogonal_init(ks[3], dkv, h * dv, dtype),          # up: V
        "wo": orthogonal_init(ks[4], h * dv, d, dtype),
    }
    if dq:
        p["w_dq"] = dense_init(ks[0], d, dq, dtype=dtype)
        p["w_uq"] = orthogonal_init(ks[5], dq, h * (dn + dr), dtype)
    else:
        p["w_uq"] = orthogonal_init(ks[5], d, h * (dn + dr), dtype)
    return p


def _mla_qkv(params, x: Array, cfg: ModelConfig, spec: AttnSpec,
             positions: Array):
    b, s, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv = spec.qk_nope_head_dim, spec.qk_rope_head_dim, spec.v_head_dim
    cq = x @ params["w_dq"] if "w_dq" in params else x
    q = (cq @ params["w_uq"]).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    ckv = x @ params["w_dkv"]                                    # (B,S,dkv+dr)
    c_kv, k_rope = ckv[..., :spec.kv_lora_rank], ckv[..., spec.kv_lora_rank:]
    k_rope = apply_rope(k_rope[..., None, :], positions, cfg.rope_theta)[..., 0, :]
    return q, c_kv, k_rope


def _mla_expand_kv(params, c_kv: Array, k_rope: Array, spec: AttnSpec, h: int):
    b, t, _ = c_kv.shape
    dn, dr, dv = spec.qk_nope_head_dim, spec.qk_rope_head_dim, spec.v_head_dim
    k_nope = (c_kv @ params["w_uk"]).reshape(b, t, h, dn)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, t, h, dr))], axis=-1)
    v = (c_kv @ params["w_uv"]).reshape(b, t, h, dv)
    return k, v


def mla_prefill(params, x: Array, cfg: ModelConfig, spec: AttnSpec,
                positions: Array, *, make_cache: bool = False,
                cache_len: int = 0):
    b, s, _ = x.shape
    h = cfg.n_heads
    dv = spec.v_head_dim
    q, c_kv, k_rope = _mla_qkv(params, x, cfg, spec, positions)
    k, v = _mla_expand_kv(params, c_kv, k_rope, spec, h)
    y = ops.flash_attention(q, k, v, causal=True, window=spec.sliding_window,
                            q_positions=positions, kv_positions=positions,
                            softmax_scale=(spec.qk_nope_head_dim
                                           + spec.qk_rope_head_dim) ** -0.5)
    out = y.reshape(b, s, h * dv) @ params["wo"]
    cache = None
    if make_cache:
        cl = cache_len or s
        cache = {
            "c_kv": jnp.zeros((b, cl, spec.kv_lora_rank), c_kv.dtype),
            "k_rope": jnp.zeros((b, cl, spec.qk_rope_head_dim), k_rope.dtype),
            "pos": jnp.full((b, cl), -1, jnp.int32),
        }
        slots = positions % cl
        upd = lambda buf, val: jax.vmap(
            lambda bb, vv, ss: bb.at[ss].set(vv))(buf, val, slots)
        cache = {"c_kv": upd(cache["c_kv"], c_kv),
                 "k_rope": upd(cache["k_rope"], k_rope),
                 "pos": upd(cache["pos"], positions.astype(jnp.int32))}
    return out, cache


def mla_decode(params, x: Array, cfg: ModelConfig, spec: AttnSpec,
               position: Array, cache: dict):
    """Decode with the *compressed* cache (c_kv + shared k_rope) — MLA's
    memory saving; K/V are re-expanded blockwise at attention time."""
    b, _, _ = x.shape
    h = cfg.n_heads
    dv = spec.v_head_dim
    pos2 = position[:, None]
    q, c_kv, k_rope = _mla_qkv(params, x, cfg, spec, pos2)

    slot = position % cache["c_kv"].shape[1]
    cache = {
        "c_kv": jax.vmap(lambda bb, vv, ss: bb.at[ss].set(vv))(
            cache["c_kv"], c_kv[:, 0], slot),
        "k_rope": jax.vmap(lambda bb, vv, ss: bb.at[ss].set(vv))(
            cache["k_rope"], k_rope[:, 0], slot),
        "pos": jax.vmap(lambda bb, vv, ss: bb.at[ss].set(vv))(
            cache["pos"], position.astype(jnp.int32), slot),
    }
    k, v = _mla_expand_kv(params, cache["c_kv"], cache["k_rope"], spec, h)
    y = ops.flash_attention(q, k, v, causal=True, window=spec.sliding_window,
                            q_positions=pos2, kv_positions=cache["pos"],
                            softmax_scale=(spec.qk_nope_head_dim
                                           + spec.qk_rope_head_dim) ** -0.5)
    return y.reshape(b, 1, h * dv) @ params["wo"], cache


# ---------------------------------------------------------------------------
# KV-cache plumbing (ring buffer with explicit positions)
# ---------------------------------------------------------------------------


def _new_kv_cache(b: int, cache_len: int, hkv: int, hd: int, dtype) -> dict:
    return {
        "k": jnp.zeros((b, cache_len, hkv, hd), dtype),
        "v": jnp.zeros((b, cache_len, hkv, hd), dtype),
        "pos": jnp.full((b, cache_len), -1, jnp.int32),
    }


def _cache_write_many(cache: dict, k: Array, v: Array, positions: Array) -> dict:
    cl = cache["k"].shape[1]
    slots = positions % cl
    upd = lambda buf, val: jax.vmap(lambda bb, vv, ss: bb.at[ss].set(vv))(buf, val, slots)
    return {"k": upd(cache["k"], k), "v": upd(cache["v"], v),
            "pos": upd(cache["pos"], positions.astype(jnp.int32))}


def _cache_write_one(cache: dict, k1: Array, v1: Array, position: Array) -> dict:
    cl = cache["k"].shape[1]
    slot = position % cl
    w = lambda buf, val: jax.vmap(lambda bb, vv, ss: bb.at[ss].set(vv))(buf, val, slot)
    return {"k": w(cache["k"], k1), "v": w(cache["v"], v1),
            "pos": w(cache["pos"], position.astype(jnp.int32))}


def attn_cache_len(spec: AttnSpec, seq_len: int) -> int:
    if spec.sliding_window is not None:
        return min(seq_len, spec.sliding_window)
    return seq_len


def init_attention(key, cfg: ModelConfig, spec: AttnSpec, dtype=jnp.float32):
    if spec.kind == "mla":
        return init_mla(key, cfg, spec, dtype)
    return init_gqa(key, cfg, spec, dtype)
