"""Pure-jnp oracles for every Pallas kernel.

These are the ground truth for the kernel sweep tests *and* the portable
execution path: on non-TPU backends (this CPU container, the dry-run's
512 fake host devices) ``ops.py`` dispatches here.  ``blockwise_attention``
is written with the same online-softmax streaming structure as the TPU
kernel so its memory profile (never materializes S x T scores) and its
cost_analysis FLOPs match the kernel's.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

Array = jax.Array

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def attention_naive(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: int | None = None,
                    q_positions: Array | None = None,
                    kv_positions: Array | None = None,
                    softmax_scale: float | None = None) -> Array:
    """Reference attention, materializes full scores.  Shapes:
    q (B, S, H, hd); k/v (B, T, Hkv, hd); returns (B, S, H, hd).

    GQA: H must be a multiple of Hkv; kv heads are broadcast.
    ``*_positions``: absolute token positions (B, S) / (B, T); default
    aranges.  Masking: kv_pos <= q_pos (causal) and q_pos - kv_pos < window.
    kv positions < 0 mark empty cache slots (always masked).
    """
    b, s, h, hd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    group = h // hkv
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(t), (b, t))

    hdv = v.shape[-1]
    qg = q.reshape(b, s, hkv, group, hd)
    scores = jnp.einsum("bshgd,bthd->bhgst", qg.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    mask = kv_positions[:, None, :] >= 0
    if causal:
        mask &= kv_positions[:, None, :] <= q_positions[:, :, None]
    if window is not None:
        mask &= (q_positions[:, :, None] - kv_positions[:, None, :]) < window
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgst,bthe->bshge", p, v.astype(jnp.float32))
    return out.reshape(b, s, h, hdv).astype(q.dtype)


def blockwise_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                        window: int | None = None,
                        q_positions: Array | None = None,
                        kv_positions: Array | None = None,
                        softmax_scale: float | None = None,
                        chunk: int = 1024) -> Array:
    """Online-softmax attention streaming over KV chunks (flash-style, pure
    jnp, compiles on any backend).  Same signature/semantics as
    :func:`attention_naive`."""
    b, s, h, hd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    hdv = v.shape[-1]
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    group = h // hkv
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    chunk = min(chunk, t)
    n_chunks = -(-t // chunk)
    pad = n_chunks * chunk - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)),
                               constant_values=-1)

    qf = (q.astype(jnp.float32) * scale).reshape(b, s, hkv, group, hd)
    kc = k.reshape(b, n_chunks, chunk, hkv, hd)
    vc = v.reshape(b, n_chunks, chunk, hkv, hdv)
    pc = kv_positions.reshape(b, n_chunks, chunk)

    def body(carry, inp):
        m, l, acc = carry
        kb, vb, pb = inp                      # (b, chunk, hkv, hd), (b, chunk)
        sc = jnp.einsum("bshgd,bthd->bhgst", qf, kb.astype(jnp.float32))
        mask = pb[:, None, :] >= 0
        if causal:
            mask &= pb[:, None, :] <= q_positions[:, :, None]
        if window is not None:
            mask &= (q_positions[:, :, None] - pb[:, None, :]) < window
        sc = jnp.where(mask[:, None, None, :, :], sc, NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgst,bthe->bhgse", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, group, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, group, s), jnp.float32)
    a0 = jnp.zeros((b, hkv, group, s, hdv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), jnp.moveaxis(pc, 1, 0)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out, 3, 1).reshape(b, s, h, hdv)
    return out.astype(q.dtype)


def paged_decode_attention_ref(q: Array, k_pages: Array, v_pages: Array,
                               block_table: Array, seq_lens: Array, *,
                               window: int | None = None,
                               softmax_scale: float | None = None) -> Array:
    """Oracle for the paged-decode kernel: gather every slot's pages through
    the block table into a contiguous (S, M*ps, Hkv, hd) view, then run the
    streaming attention oracle with positions derived from the page layout.

    q (S, H, hd) — one query token per slot; pools (P, Hkv, hd/hdv, ps);
    block_table (S, M) int32 (-1 = unallocated, clamped to page 0 and fully
    masked); seq_lens (S,) int32 — valid tokens, query at ``seq_lens - 1``.
    A slot with ``seq_lens == 0`` returns exact zeros (all keys masked).
    """
    s_slots = q.shape[0]
    hkv, hd, ps = k_pages.shape[1:]
    hdv = v_pages.shape[2]
    m_pages = block_table.shape[1]
    bt = jnp.maximum(block_table, 0)
    # (S, M, Hkv, hd, ps) token-minor pages -> (S, M * ps, Hkv, hd)
    k = jnp.moveaxis(k_pages[bt], -1, 2).reshape(s_slots, m_pages * ps,
                                                 hkv, hd)
    v = jnp.moveaxis(v_pages[bt], -1, 2).reshape(s_slots, m_pages * ps,
                                                 hkv, hdv)
    pos = jnp.arange(m_pages * ps, dtype=jnp.int32)[None, :]
    kv_pos = jnp.where(pos < seq_lens[:, None], pos, -1)
    q_pos = seq_lens[:, None].astype(jnp.int32) - 1
    out = blockwise_attention(q[:, None], k, v, causal=True, window=window,
                              q_positions=q_pos, kv_positions=kv_pos,
                              softmax_scale=softmax_scale)[:, 0]
    # fully-masked slots: the streaming softmax degenerates to a mean over
    # dump-page values; pin them to the kernel's exact-zero convention
    return jnp.where(seq_lens[:, None, None] > 0, out,
                     jnp.zeros_like(out))


# ---------------------------------------------------------------------------
# Stiefel tangent projection
# ---------------------------------------------------------------------------


def stiefel_project_ref(x: Array, g: Array) -> Array:
    """P_{T_x}(g) = g - x sym(x^T g)  over the last two dims."""
    xtg = jnp.einsum("...dr,...ds->...rs", x, g)
    s = 0.5 * (xtg + jnp.swapaxes(xtg, -1, -2))
    return g - jnp.einsum("...dr,...rs->...ds", x, s)


# ---------------------------------------------------------------------------
# fused polar retraction (tangent project + Gram + NS inverse sqrt + apply)
# ---------------------------------------------------------------------------


def fused_retract_ref(x: Array, g: Array, ns_iters: int = 20) -> Array:
    """R_x(P_x(g)): polar retraction of the tangent-projected AMBIENT
    direction — the fused kernel's semantics, in streaming-free jnp.
    Same math sequence (the geometry layer's coupled Newton--Schulz
    inverse sqrt), so FLOP structure matches."""
    from repro.geometry.stiefel import _invsqrt_newton_schulz

    u = stiefel_project_ref(x, g)
    r = u.shape[-1]
    utu = jnp.einsum("...dr,...ds->...rs", u, u)
    a = jnp.eye(r, dtype=jnp.float32) + utu.astype(jnp.float32)
    inv = _invsqrt_newton_schulz(a, ns_iters)
    return jnp.einsum("...dr,...rs->...ds", (x + u).astype(jnp.float32),
                      inv).astype(x.dtype)


# ---------------------------------------------------------------------------
# ring gossip mix
# ---------------------------------------------------------------------------


def ring_mix_ref(x_self: Array, x_left: Array, x_right: Array,
                 w_self: float, w_side: float) -> Array:
    """One gossip hop's local combine: wc*x + ws*(left + right)."""
    return w_self * x_self + w_side * (x_left + x_right)


# ---------------------------------------------------------------------------
# fused multi-hop ring mix (halo-panel megakernel)
# ---------------------------------------------------------------------------


def _panel_hop(z: Array, w_self: float, w_side: float) -> Array:
    """One ring combine on the *interior* rows of a halo panel: row ``i``'s
    neighbours are rows ``i-1`` / ``i+1``, and the result drops the two
    boundary rows (they have no valid neighbour on one side).  Per-element
    this is the same ``wc*x + ws*(l + r)`` expression as ``ring_mix_ref``;
    the shrinking "pyramid" does only the row work that can still reach the
    center — exactly ``halo >= hops`` wide — instead of combining garbage
    panel ends that get sliced away anyway."""
    return w_self * z[1:-1] + w_side * (z[:-2] + z[2:])


def _panel_hop_dq(q: Array, s: Array, w_self: float, w_side: float) -> Array:
    """One ring combine on quantized panel values with per-row scales,
    dequantizing each shifted operand separately — the same dataflow as
    ``quant_mix_ref`` and the ``multi_hop_mix_quant_flat`` kernel, so the
    oracle and the megakernel agree bitwise under jit (cross-backend
    results agree to FMA rounding of the combines)."""
    def shift_down(z):
        return jnp.concatenate([jnp.zeros_like(z[:1]), z[:-1]], axis=0)

    def shift_up(z):
        return jnp.concatenate([z[1:], jnp.zeros_like(z[:1])], axis=0)

    return (w_self * (q * s)
            + w_side * (shift_down(q) * shift_down(s)
                        + shift_up(q) * shift_up(s)))


def multi_hop_mix_ref(panel: Array, *, hops: int, out_rows: int, halo: int,
                      w_self: float, w_side: float) -> Array:
    """``hops`` fused ring combines over a ``(halo + b + halo, F)`` panel;
    returns the exact center ``(out_rows, F)`` rows (``halo >= hops``).
    Each hop shrinks the live window by one row per side, so the center
    starts at ``halo - hops`` in the final window."""
    z = panel.astype(jnp.float32)
    for _ in range(hops):
        z = _panel_hop(z, w_self, w_side)
    lo = halo - hops
    return z[lo:lo + out_rows].astype(panel.dtype)


def multi_hop_mix_quant_ref(q_panel: Array, s_panel: Array, *, hops: int,
                            w_self: float, w_side: float) -> Array:
    """All-hop compressed schedule on an int8 halo panel: hop 0 fuses
    dequantize + combine, every later hop requantizes deterministically
    (round-to-nearest, per-row max-abs/127 scale, 1e-12 floor — mirrors
    ``comms.compress.quantize_det``) before combining.  Returns the full
    evolved f32 panel (callers slice the center rows), matching
    ``multi_hop_mix_quant_flat``."""
    z = _panel_hop_dq(q_panel.astype(jnp.float32),
                      s_panel.astype(jnp.float32), w_self, w_side)
    for _ in range(1, hops):
        amax = jnp.max(jnp.abs(z), axis=1, keepdims=True)
        scale = jnp.maximum(amax / 127.0, 1e-12)
        q = jnp.clip(jnp.round(z / scale), -127.0, 127.0)
        z = _panel_hop_dq(q, scale, w_self, w_side)
    return z


# ---------------------------------------------------------------------------
# fused dequantize + ring combine
# ---------------------------------------------------------------------------


def quant_mix_ref(q_self: Array, q_left: Array, q_right: Array,
                  s_self: Array, s_left: Array, s_right: Array,
                  w_self: float, w_side: float,
                  out_dtype=jnp.float32) -> Array:
    """Compressed gossip hop's combine on int8 payloads with per-row scales:
    out = wc * dq(qc) + ws * (dq(ql) + dq(qr)), dq(q) = q * scale."""
    def dq(q, s):
        return q.astype(jnp.float32) * s.astype(jnp.float32)

    return (w_self * dq(q_self, s_self)
            + w_side * (dq(q_left, s_left) + dq(q_right, s_right))
            ).astype(out_dtype)
