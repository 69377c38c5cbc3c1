"""Plain reference of the decoder the configurations run: pre-norm blocks
of grouped-query attention with rotary positions and a SwiGLU MLP
(the Llama block, which SmolLM-135M publishes and which Granite-3.0 uses
with scalar multipliers that the program's block leaves out; see each
configuration's ``assumed``).

Straight ``jax.numpy``: no kernels, no cache, no batching tricks.  It
imports nothing of the program.  Weights are the tree ``bench/weights.py``
draws.  Matmuls run at the precision that ``Numerics`` names (HIGHEST for
the reference); ``Numerics`` also describes the controls, which compute
the same thing in a lower precision.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

_PREC = {"highest": jax.lax.Precision.HIGHEST,
         "default": jax.lax.Precision.DEFAULT}


@dataclasses.dataclass(frozen=True)
class Numerics:
    """How the reference computes: ``dtype`` of activations and matmul
    operands, matmul ``precision``, and ``fp8``: operands of every weight
    matmul rounded to float8 e4m3 (per-row scales for activations,
    per-column for weights)."""
    dtype: str = "float32"
    precision: str = "highest"
    fp8: bool = False


F32 = Numerics()


def round_e4m3(x):
    """Round to 4 significant bits (float8 e4m3's mantissa), after a scale
    that maps the largest magnitude to 448 (its largest finite value)."""
    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return round_e4m3(x / s) * s


def dot(a, w, num: Numerics):
    """``a @ w`` for a weight matrix ``w`` (in, out)."""
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if num.fp8:
        a, w = _fp8(a, -1), _fp8(w, -2)
    dt = jnp.dtype(num.dtype)
    out = jnp.matmul(a.astype(dt), w.astype(dt),
                     precision=_PREC[num.precision],
                     preferred_element_type=jnp.float32)
    return out.astype(dt)


def rmsnorm(x, scale, eps):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return out.astype(x.dtype) * scale.astype(x.dtype)


def rope(x, positions, theta):
    """Rotary embedding, halves convention: x (B, S, H, hd)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def attention(q, k, v, num: Numerics):
    """Causal softmax attention; q head i reads kv head i // (H / Hkv)."""
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    dt = jnp.dtype(num.dtype)
    prec = _PREC[num.precision]
    qg = q.reshape(b, s, hkv, h // hkv, hd).astype(dt)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k.astype(dt),
                        precision=prec, preferred_element_type=jnp.float32)
    scores = scores * (hd ** -0.5)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", p.astype(dt), v.astype(dt),
                     precision=prec, preferred_element_type=jnp.float32)
    return out.reshape(b, s, h, hd).astype(dt)


def block(x, w, positions, sz: dict, num: Numerics):
    b, s, _ = x.shape
    h, hkv, hd = sz["h"], sz["hkv"], sz["hd"]
    a = w["attn"]
    y = rmsnorm(x, w["ln1"]["scale"], sz["eps"])
    q = rope(dot(y, a["wq"], num).reshape(b, s, h, hd), positions,
             sz["theta"])
    k = rope(dot(y, a["wk"], num).reshape(b, s, hkv, hd), positions,
             sz["theta"])
    v = dot(y, a["wv"], num).reshape(b, s, hkv, hd)
    x = x + dot(attention(q, k, v, num).reshape(b, s, h * hd), a["wo"], num)
    m = w["mlp"]
    y = rmsnorm(x, w["ln2"]["scale"], sz["eps"])
    g = jax.nn.silu(dot(y, m["w_gate"], num).astype(jnp.float32))
    u = dot(y, m["w_up"], num).astype(jnp.float32)
    return x + dot((g * u).astype(x.dtype), m["w_down"], num)


def forward(params, tokens, sz: dict, num: Numerics = F32,
            remat: bool = False):
    """Logits (B, S, V) in float32 for tokens (B, S)."""
    dt = jnp.dtype(num.dtype)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = params["embed"][tokens].astype(dt)

    def body(x, w):
        return block(x, w, positions, sz, num), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["stages"]["s0"]["b0"])
    x = rmsnorm(x, params["final_norm"]["scale"], sz["eps"])
    head = params["embed"].T if sz["tied"] else params["lm_head"]
    return dot(x, head, num).astype(jnp.float32)
