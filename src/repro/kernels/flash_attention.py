"""Flash attention Pallas TPU kernel (online softmax, VMEM-tiled).

Target: TPU v5e — block shapes are MXU-aligned (multiples of 128 on the
matmul dims).  Validated on CPU with ``interpret=True`` against
``ref.attention_naive`` / ``ref.blockwise_attention``.

Layout: q (B, H, S, hd); k/v (B, Hkv, T, hd); GQA handled by the k/v
index_map (kv head = q head // group) — KV is never materialized per q-head.
Supports causal masking with absolute positions (decode: S == 1 with a long
cache) and a static sliding window.

Grid: (B, H, n_q_blocks, n_kv_blocks); the kv loop is the innermost grid
dim, with (acc, m, l) carried in VMEM scratch across kv steps (TPU grid
execution is sequential, so scratch persists).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array
NEG_INF = -1e30

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128


def _flash_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int | None,
                  n_kv_blocks: int):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bkv, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bkv)

    qp = qpos_ref[0]                                      # (bq, 1)  int32
    kp = kpos_ref[0]                                      # (1, bkv) int32
    mask = kp >= 0
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= (qp - kp) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                   # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    # fully-masked rows: keep them zero (m stays NEG_INF => exp underflows OK)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == n_kv_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softmax_scale",
                     "block_q", "block_kv", "interpret"))
def flash_attention_bhsd(q: Array, k: Array, v: Array,
                         q_positions: Array, kv_positions: Array, *,
                         causal: bool = True, window: int | None = None,
                         softmax_scale: float | None = None,
                         block_q: int = DEFAULT_BLOCK_Q,
                         block_kv: int = DEFAULT_BLOCK_KV,
                         interpret: bool = False) -> Array:
    """q: (B, H, S, hd); k/v: (B, Hkv, T, hd); positions (B, S)/(B, T).

    S and T must be multiples of the block sizes (ops.py pads).  On the
    chip every block's last two dims must divide by (8, 128) or span the
    array: ``block_q`` by 8 and ``block_kv`` by 128 unless either covers
    its whole (padded) sequence; hd is always a full dim.  Positions are
    laid out as a (B, S, 1) column and a (B, 1, T) row so their blocks
    obey the same rule and the mask needs no in-kernel transpose.
    """
    b, h, s_len, hd = q.shape
    hkv, t_len = k.shape[1], k.shape[2]
    hdv = v.shape[-1]
    group = h // hkv
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    block_q = min(block_q, s_len)
    block_kv = min(block_kv, t_len)
    n_q = s_len // block_q
    n_kv = t_len // block_kv
    assert s_len % block_q == 0 and t_len % block_kv == 0

    grid = (b, h, n_q, n_kv)
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, n_kv_blocks=n_kv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1), lambda b_, h_, i, j: (b_, i, 0)),
            pl.BlockSpec((1, 1, block_kv), lambda b_, h_, i, j: (b_, 0, j)),
            pl.BlockSpec((1, 1, block_q, hd), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
            pl.BlockSpec((1, 1, block_kv, hdv),
                         lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hdv),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s_len, hdv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hdv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q_positions[:, :, None], kv_positions[:, None, :], q, k, v)
