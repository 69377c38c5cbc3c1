"""Paged-decode flash attention Pallas TPU kernel (block-table gather).

The serving path's KV cache is *paged* (``repro.serve.kv_cache``): each
decode slot owns a row of a block table whose entries index fixed-size
pages ``(Hkv, hd, page_size)`` inside one shared pool.  This kernel runs
one decode step for every slot — q is a single token per slot — attending
over that slot's pages with an online softmax, **gathering pages through
the block table inside the kernel**: the table and the per-slot sequence
lengths ride as scalar-prefetch operands (SMEM), so every k/v BlockSpec
index_map can pick the next physical page while the previous block is
still being computed.

Layout: q ``(S, Hkv, G, hd)`` (S slots, G = n_heads // n_kv_heads query
heads per kv head); pools ``(P, Hkv, hd, page_size)``, token-minor (on the
chip a page size that is a multiple of 128 fills whole lanes, so the pool
holds no padding and XLA keeps it in the row-major layout this kernel
reads, with no copy around the call; smaller pages are relaid out around
it); block table
``(S, M)`` int32 (-1 = unallocated; reads clamp to page 0, the dump page,
and are fully masked); seq_lens ``(S,)`` int32 — valid tokens including
the current query token at position ``seq_lens - 1``.

Grid: ``(S, M // pages_per_block)`` with the page loop innermost — TPU
grid execution is sequential there, so the (acc, m, l) VMEM scratch
persists across page steps exactly like ``flash_attention``'s kv loop.
Each step fetches whole pages (all Hkv heads) and loops over the kv heads
in the kernel body: scores are ``q (G, hd) @ k (hd, ps)``, values enter as
``p (G, ps) · v (hdv, ps)ᵀ``.
``pages_per_block`` fuses several page fetches per grid step (the tuned
knob, see ``kernels/tune.py``) by passing the pool once per fused page
with staggered index_maps.

Validated on CPU with ``interpret=True`` against
``ref.paged_decode_attention_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array
NEG_INF = -1e30

DEFAULT_PAGES_PER_BLOCK = 1


def _paged_kernel(bt_ref, sl_ref, q_ref, *refs, scale: float,
                  window: int | None, n_blocks: int, g_pages: int,
                  page_size: int):
    k_refs = refs[:g_pages]
    v_refs = refs[g_pages:2 * g_pages]
    o_ref = refs[2 * g_pages]
    acc_ref, m_ref, l_ref = refs[2 * g_pages + 1:]
    i = pl.program_id(0)
    j = pl.program_id(1)
    hkv = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    sl = sl_ref[i]                                       # valid tokens
    for r in range(g_pages):     # static: each fetched page in turn
        pos = (j * g_pages + r) * page_size + \
            jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
        mask = pos < sl                                  # (1, ps)
        if window is not None:
            # the query sits at position sl - 1
            mask &= (sl - 1 - pos) < window

        for kh in range(hkv):    # static: every kv head of the fetched page
            q = q_ref[0, kh].astype(jnp.float32) * scale     # (G, hd)
            k = k_refs[r][0, kh].astype(jnp.float32)         # (hd, ps)
            v = v_refs[r][0, kh].astype(jnp.float32)         # (hdv, ps)
            s = jax.lax.dot_general(q, k, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(mask, s, NEG_INF)                  # (G, ps)

            m_prev = m_ref[kh]                               # (G, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            # fully-masked pages (empty slots / dump pages): rows stay 0
            p = jnp.where(mask, p, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[kh] = l_ref[kh] * corr + p.sum(axis=-1, keepdims=True)
            acc_ref[kh] = acc_ref[kh] * corr + jax.lax.dot_general(
                p, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[kh] = m_new

    @pl.when(j == n_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softmax_scale", "pages_per_block",
                     "interpret"))
def paged_decode_shgd(q: Array, k_pages: Array, v_pages: Array,
                      block_table: Array, seq_lens: Array, *,
                      window: int | None = None,
                      softmax_scale: float | None = None,
                      pages_per_block: int = DEFAULT_PAGES_PER_BLOCK,
                      interpret: bool = False) -> Array:
    """q: (S, Hkv, G, hd); pools (P, Hkv, hd/hdv, ps); block_table (S, M)
    int32; seq_lens (S,) int32.  Returns (S, Hkv, G, hdv).

    ``M % pages_per_block == 0`` (ops.py pads the table with -1 columns);
    on the chip the page size should be a multiple of 128 (or the pool's
    whole last dim) and hd divide by 8 (16 for bf16 pools).
    """
    s_slots, hkv, group, hd = q.shape
    n_pages, _, _, ps = k_pages.shape
    hdv = v_pages.shape[2]
    m_pages = block_table.shape[1]
    g = pages_per_block
    assert m_pages % g == 0, (m_pages, g)
    n_blocks = m_pages // g
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5

    grid = (s_slots, n_blocks)

    def page_map(off):
        # scalar-prefetch index_map: clamp -1 (unallocated) to the dump
        # page 0 — those positions are >= seq_len and fully masked anyway
        def index(i, j, bt, sl):
            return (jnp.maximum(bt[i, j * g + off], 0), 0, 0, 0)
        return index

    # every block spans Hkv, hd and the page whole: the chip's tiling rule
    # (last two block dims divisible by (8, 128) or equal to the array's)
    # then holds for any head count, head dim and page size
    in_specs = [pl.BlockSpec((1, hkv, group, hd),
                             lambda i, j, bt, sl: (i, 0, 0, 0))]
    in_specs += [pl.BlockSpec((1, hkv, hd, ps), page_map(off))
                 for off in range(g)]
    in_specs += [pl.BlockSpec((1, hkv, hdv, ps), page_map(off))
                 for off in range(g)]

    kernel = functools.partial(_paged_kernel, scale=scale, window=window,
                               n_blocks=n_blocks, g_pages=g, page_size=ps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, group, hdv),
                               lambda i, j, bt, sl: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, group, hdv), jnp.float32),
            pltpu.VMEM((hkv, group, 1), jnp.float32),
            pltpu.VMEM((hkv, group, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_slots, hkv, group, hdv), q.dtype),
        interpret=interpret,
        name="paged_decode",
    )(block_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q, *([k_pages] * g), *([v_pages] * g))
