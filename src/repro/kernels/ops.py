"""jit'd public wrappers for the Pallas kernels, with backend dispatch.

Dispatch policy (per-call overridable with ``impl=``, process-wide with
``REPRO_KERNEL_IMPL=ref|pallas|pallas_interpret``):

  * ``tpu`` backend            -> Pallas kernel (compiled)
  * anything else (CPU here)   -> pure-jnp oracle from ``ref.py`` — identical
    semantics and matching FLOP structure, so the dry-run's cost_analysis is
    representative.
  * ``impl="pallas_interpret"``-> Pallas kernel body interpreted in Python
    (the CPU validation path used by the kernel tests).

Launch configs (block shapes, NS iteration counts) resolve through
``kernels/tune.py``: a tuned config cached for this exact
(kernel, shape, dtype) key wins, the hand-picked defaults otherwise
(``REPRO_TUNE=off`` skips the cache entirely).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import multi_hop_mix as _mh
from repro.kernels import paged_decode as _pd
from repro.kernels import quant_mix as _qm
from repro.kernels import ref
from repro.kernels import retract as _rt
from repro.kernels import ring_mix as _rm
from repro.kernels import stiefel_project as _sp
from repro.kernels import tune as _tune
from repro.obs import estimates as _est

Array = jax.Array


def _default_impl() -> str:
    env = os.environ.get("REPRO_KERNEL_IMPL")
    if env:
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _itemsize(x: Array) -> int:
    return jnp.dtype(x.dtype).itemsize


# ---------------------------------------------------------------------------
# flash attention — public layout (B, S, H, hd) to match the model code
# ---------------------------------------------------------------------------


def _pad_to(x: Array, axis: int, mult: int, value=0) -> tuple[Array, int]:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), pad


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: int | None = None,
                    q_positions: Array | None = None,
                    kv_positions: Array | None = None,
                    softmax_scale: float | None = None,
                    impl: str | None = None,
                    block_q: int | None = None,
                    block_kv: int | None = None) -> Array:
    """Attention over (B, S, H, hd) q and (B, T, Hkv, hd) k/v.

    ``block_q`` / ``block_kv`` default to the tuned config for this
    (B, S, T, H, hd, dtype) key when one is cached (see ``kernels/tune.py``;
    on the ref path the tuned ``block_kv`` drives the streaming chunk), else
    the hand-picked module defaults; explicit values always win.

    Differentiable on every path: the Pallas paths carry a ``custom_vjp``
    whose backward is the VJP of ``ref.blockwise_attention``.
    """
    impl = impl or _default_impl()
    tuned = {}
    if block_q is None or block_kv is None:
        tuned = _tune.lookup(
            "flash_attention",
            (q.shape[0], q.shape[1], k.shape[1], q.shape[2], q.shape[3]),
            str(q.dtype)) or {}
    if block_q is None:
        block_q = tuned.get("block_q", _fa.DEFAULT_BLOCK_Q)
    if block_kv is None:
        block_kv = tuned.get("block_kv")          # None => ref default chunk
    _est.record("flash_attention", _est.flash_attention_est(
        q.shape[0], q.shape[1], k.shape[1], q.shape[2], q.shape[3],
        causal=causal, window=window, block_q=block_q,
        itemsize=_itemsize(q)))
    if impl == "ref":
        kw = {} if block_kv is None else {"chunk": block_kv}
        return ref.blockwise_attention(
            q, k, v, causal=causal, window=window, q_positions=q_positions,
            kv_positions=kv_positions, softmax_scale=softmax_scale, **kw)
    if block_kv is None:
        block_kv = _fa.DEFAULT_BLOCK_KV
    if impl == "ref_naive":
        return ref.attention_naive(
            q, k, v, causal=causal, window=window, q_positions=q_positions,
            kv_positions=kv_positions, softmax_scale=softmax_scale)

    b, s, h, hd = q.shape
    t = k.shape[1]
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    return _flash_pallas(q, k, v, q_positions.astype(jnp.int32),
                         kv_positions.astype(jnp.int32), causal, window,
                         softmax_scale, block_q, block_kv,
                         impl == "pallas_interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_pallas(q, k, v, q_positions, kv_positions, causal, window,
                  softmax_scale, block_q, block_kv, interpret):
    """The Pallas forward at the public (B, S, H, hd) layout.

    Its backward is the VJP of ``ref.blockwise_attention`` (the streaming
    jnp oracle, same semantics) evaluated at the saved q/k/v: XLA compiles
    it on every backend, so ``jax.grad`` never differentiates the
    ``pallas_call`` itself.
    """
    s, t = q.shape[1], k.shape[1]
    qt = jnp.swapaxes(q, 1, 2)           # (B, H, S, hd)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    qt, pad_q = _pad_to(qt, 2, min(block_q, max(s, 1)))
    kt, pad_kv = _pad_to(kt, 2, min(block_kv, max(t, 1)))
    vt, _ = _pad_to(vt, 2, min(block_kv, max(t, 1)))
    qp = jnp.pad(q_positions, ((0, 0), (0, qt.shape[2] - s)),
                 constant_values=0)
    kp = jnp.pad(kv_positions, ((0, 0), (0, kt.shape[2] - t)),
                 constant_values=-1)

    out = _fa.flash_attention_bhsd(
        qt, kt, vt, qp, kp, causal=causal, window=window,
        softmax_scale=softmax_scale,
        block_q=min(block_q, qt.shape[2]), block_kv=min(block_kv, kt.shape[2]),
        interpret=interpret)
    out = jnp.swapaxes(out, 1, 2)
    return out[:, :s]


def _flash_pallas_fwd(q, k, v, q_positions, kv_positions, causal, window,
                      softmax_scale, block_q, block_kv, interpret):
    out = _flash_pallas(q, k, v, q_positions, kv_positions, causal, window,
                        softmax_scale, block_q, block_kv, interpret)
    return out, (q, k, v, q_positions, kv_positions)


def _flash_pallas_bwd(causal, window, softmax_scale, block_q, block_kv,
                      interpret, res, g):
    q, k, v, q_positions, kv_positions = res
    _, vjp = jax.vjp(
        lambda q, k, v: ref.blockwise_attention(
            q, k, v, causal=causal, window=window, q_positions=q_positions,
            kv_positions=kv_positions, softmax_scale=softmax_scale),
        q, k, v)
    return (*vjp(g), None, None)


_flash_pallas.defvjp(_flash_pallas_fwd, _flash_pallas_bwd)


# ---------------------------------------------------------------------------
# paged-decode attention — the serving path's block-table gather kernel
# ---------------------------------------------------------------------------


def paged_decode_attention(q: Array, k_pages: Array, v_pages: Array,
                           block_table: Array, seq_lens: Array, *,
                           window: int | None = None,
                           softmax_scale: float | None = None,
                           impl: str | None = None,
                           pages_per_block: int | None = None) -> Array:
    """One decode step for S slots over a paged KV pool.

    q (S, H, hd); pools (P, Hkv, hd/hdv, page_size); block_table (S, M)
    int32 (-1 = unallocated); seq_lens (S,) int32 (valid tokens, the query
    sits at ``seq_lens - 1``).  Returns (S, H, hdv).

    ``pages_per_block`` (pages fused per kernel grid step) defaults to the
    tuned config for this (S, M, page_size, hd, dtype) key when one is
    cached, else 1; the block table is padded with -1 columns so the knob
    always tiles.
    """
    impl = impl or _default_impl()
    s_slots, h, hd = q.shape
    hkv, ps = k_pages.shape[1], k_pages.shape[3]
    m_pages = block_table.shape[1]
    _est.record("paged_decode", _est.paged_decode_est(
        s_slots, h, hkv, hd, m_pages, ps, itemsize=_itemsize(q)))
    if impl in ("ref", "ref_naive"):
        return ref.paged_decode_attention_ref(
            q, k_pages, v_pages, block_table, seq_lens, window=window,
            softmax_scale=softmax_scale)

    if pages_per_block is None:
        tuned = _tune.lookup("paged_decode", (s_slots, m_pages, ps, hd),
                             str(q.dtype)) or {}
        pages_per_block = tuned.get("pages_per_block",
                                    _pd.DEFAULT_PAGES_PER_BLOCK)
    bt, _ = _pad_to(block_table, 1, max(pages_per_block, 1), value=-1)
    group = h // hkv
    qg = q.reshape(s_slots, hkv, group, hd)
    out = _pd.paged_decode_shgd(
        qg, k_pages, v_pages, bt, seq_lens, window=window,
        softmax_scale=softmax_scale, pages_per_block=pages_per_block,
        interpret=(impl == "pallas_interpret"))
    return out.reshape(s_slots, h, v_pages.shape[2])


def paged_write(pool: Array, block_table: Array, position: Array,
                rows: Array) -> Array:
    """Write one token per slot into a paged pool.

    pool (P, Hkv, hd, page_size); block_table (S, M) int32; position (S,)
    int32; rows (S, Hkv, hd) land at ``position`` of each slot's pages
    (inactive slots, whose block-table rows are all -1, on the dump page 0).

    Written as whole pages, gathered, merged at the token's lane and
    scattered back: a scatter of one token's (Hkv, hd) would want those dims
    minor in the pool's layout, and the chip's compiler would then copy the
    whole pool into that layout around every write.  Slots sharing a page
    (inactive ones, all on the dump page) write it in any order.
    """
    ps = pool.shape[-1]
    page = jnp.maximum(
        block_table[jnp.arange(rows.shape[0]), position // ps], 0)
    lane = jnp.arange(ps) == (position % ps)[:, None]          # (S, ps)
    pages = jnp.where(lane[:, None, None, :], rows[..., None], pool[page])
    return pool.at[page].set(pages)


# ---------------------------------------------------------------------------
# stiefel tangent projection
# ---------------------------------------------------------------------------


def stiefel_project(x: Array, g: Array, *, impl: str | None = None,
                    block_d: int = _sp.DEFAULT_BLOCK_D) -> Array:
    """P_{T_x}(g) over the last two dims; leading dims are vmapped."""
    impl = impl or _default_impl()
    d, r = x.shape[-2:]
    _est.record("stiefel_project", _est.stiefel_project_est(
        d, r, lead=max(1, x.size // (d * r)), itemsize=_itemsize(x)))
    if impl == "ref":
        return ref.stiefel_project_ref(x, g)

    interpret = impl == "pallas_interpret"

    def one(xi: Array, gi: Array) -> Array:
        d, r = xi.shape
        # pad r to the 128-lane boundary, d to a multiple of the block size
        pr = (-r) % 128
        pd = (-d) % 128
        d_p = d + pd
        block = block_d if d_p % block_d == 0 else 128
        xi_p = jnp.pad(xi, ((0, pd), (0, pr)))
        gi_p = jnp.pad(gi, ((0, pd), (0, pr)))
        out = _sp.stiefel_project_2d(xi_p, gi_p, block_d=min(block, d_p),
                                     interpret=interpret)
        return out[:d, :r]

    if x.ndim == 2:
        return one(x, g)
    lead = x.shape[:-2]
    xf = x.reshape((-1,) + x.shape[-2:])
    gf = g.reshape((-1,) + g.shape[-2:])
    out = jax.vmap(one)(xf, gf)
    return out.reshape(lead + x.shape[-2:])


# ---------------------------------------------------------------------------
# fused polar retraction
# ---------------------------------------------------------------------------


def fused_retract(x: Array, g: Array, *, ns_iters: int | None = None,
                  impl: str | None = None,
                  block_d: int | None = None) -> Array:
    """R_x(P_{T_x}(g)) over the last two dims; leading dims (the node-stacked
    axis) are vmapped.  ``g`` is the AMBIENT update direction — tangent
    projection happens inside the kernel (GDAHyper.retraction="polar_fused").

    ``ns_iters`` / ``block_d`` default to the tuned config for this
    (d, r, dtype) when one is cached (see ``kernels/tune.py``), else the
    hand-picked defaults; explicit values always win.

    The Pallas paths hold the whole (r, r) algebra in VMEM, so they raise
    ``ValueError`` where ``retract.vmem_bytes`` exceeds the chip's scoped
    limit (r past 512 after lane padding, e.g. 576 x 576).
    """
    impl = impl or _default_impl()
    d, r = x.shape[-2:]
    if ns_iters is None or block_d is None:
        cfg = _tune.lookup("fused_retract", (d, r), str(x.dtype)) or {}
        if ns_iters is None:
            ns_iters = cfg.get("ns_iters", _rt.DEFAULT_NS_ITERS)
        if block_d is None:
            block_d = cfg.get("block_d", _rt.DEFAULT_BLOCK_D)
    _est.record("fused_retract", _est.fused_retract_est(
        d, r, ns_iters=ns_iters, lead=max(1, x.size // (d * r)),
        itemsize=_itemsize(x)))
    if impl == "ref":
        return ref.fused_retract_ref(x, g, ns_iters=ns_iters)

    interpret = impl == "pallas_interpret"
    d_p = d + (-d) % 128
    block = min(block_d if d_p % block_d == 0 else 128, d_p)
    need = _rt.vmem_bytes(r, block)
    if need > _rt.VMEM_LIMIT_BYTES:
        raise ValueError(
            f"fused_retract at (d, r) = ({d}, {r}) needs about "
            f"{need / 2**20:.1f} MiB of VMEM, over the "
            f"{_rt.VMEM_LIMIT_BYTES / 2**20:.0f} MiB limit; use the unfused "
            "retraction='polar' for this leaf")

    def one(xi: Array, gi: Array) -> Array:
        # pad r to the 128-lane boundary, d to a multiple of the block size;
        # zero padding is exact (see kernels/retract.py docstring)
        pr = (-r) % 128
        pd = d_p - d
        xi_p = jnp.pad(xi, ((0, pd), (0, pr)))
        gi_p = jnp.pad(gi, ((0, pd), (0, pr)))
        out = _rt.fused_retract_2d(xi_p, gi_p, block_d=block,
                                   ns_iters=ns_iters, interpret=interpret)
        return out[:d, :r]

    if x.ndim == 2:
        return one(x, g)
    lead = x.shape[:-2]
    xf = x.reshape((-1,) + x.shape[-2:])
    gf = g.reshape((-1,) + g.shape[-2:])
    out = jax.vmap(one)(xf, gf)
    return out.reshape(lead + x.shape[-2:])


# ---------------------------------------------------------------------------
# ring mix
# ---------------------------------------------------------------------------


def ring_mix(x_self: Array, x_left: Array, x_right: Array, *,
             w_self: float, w_side: float, impl: str | None = None) -> Array:
    """Local gossip combine for arbitrary leaf sizes.

    Data is flattened to (rows, LANE) VMEM panels; BOTH the lane tail and
    the row tail are zero-padded (and sliced back) so the kernel's
    ``rows % block_rows == 0`` tiling contract always holds — a prime-sized
    leaf no longer degenerates to block_rows=1 (or trips the assert), it
    costs at most 7 padded rows.
    """
    impl = impl or _default_impl()
    _est.record("ring_mix",
                _est.ring_mix_est(x_self.size, itemsize=_itemsize(x_self)))
    if impl == "ref":
        return ref.ring_mix_ref(x_self, x_left, x_right, w_self, w_side)

    shape = x_self.shape
    n = x_self.size
    lane = _rm.LANE
    pad = (-n) % lane
    rows = (n + pad) // lane
    # pad rows to the 8-sublane boundary, then pick the largest block that
    # tiles the padded panel exactly
    pad_rows = (-rows) % 8
    rows_p = rows + pad_rows
    tuned = _tune.lookup("ring_mix", (rows_p, lane), str(x_self.dtype)) or {}
    cands = ([tuned["block_rows"]] if "block_rows" in tuned else []) \
        + [_rm.DEFAULT_BLOCK_ROWS, 128, 64, 32, 16, 8]
    block = rows_p
    for cand in cands:
        if rows_p % cand == 0:
            block = cand
            break

    def flat(a):
        af = a.reshape(-1)
        if pad:
            af = jnp.pad(af, (0, pad))
        af = af.reshape(-1, lane)
        if pad_rows:
            af = jnp.pad(af, ((0, pad_rows), (0, 0)))
        return af

    out = _rm.ring_mix_flat(flat(x_self), flat(x_left), flat(x_right),
                            w_self=w_self, w_side=w_side, block_rows=block,
                            interpret=(impl == "pallas_interpret"))
    return out.reshape(-1)[:n].reshape(shape)


# ---------------------------------------------------------------------------
# fused dequantize + ring combine (compressed gossip hop)
# ---------------------------------------------------------------------------


def quant_mix(q_self: Array, q_left: Array, q_right: Array,
              s_self: Array, s_left: Array, s_right: Array, *,
              w_self: float, w_side: float, out_dtype=jnp.float32,
              impl: str | None = None) -> Array:
    """Combine three int8 payloads with per-row scales in one pass:
    ``wc * dq(qc) + ws * (dq(ql) + dq(qr))``.

    ``q_*``: int8, shape (rows, ...) — trailing dims are flattened.
    ``s_*``: one f32 scale per row; any shape reshapeable to (rows, 1).
    """
    impl = impl or _default_impl()
    rows = q_self.shape[0]
    _est.record("quant_mix", _est.quant_mix_est(
        rows, q_self.size // rows,
        out_itemsize=jnp.dtype(out_dtype).itemsize))
    scales = [s.reshape(rows, 1) for s in (s_self, s_left, s_right)]
    if impl == "ref":
        out = ref.quant_mix_ref(
            q_self.reshape(rows, -1), q_left.reshape(rows, -1),
            q_right.reshape(rows, -1), *scales,
            w_self=w_self, w_side=w_side, out_dtype=out_dtype)
        return out.reshape(q_self.shape)

    # each row is (cols / 128, 128) lane rows: pad to whole lane rows, and
    # past one int8 tile height (32) to whole (32, 128) tiles; padded
    # elements carry q=0 and contribute 0
    cols = q_self.size // rows
    n = -(-cols // 128)
    if n > 32:
        n += (-n) % 32
    cols_p = 128 * n

    def flat(q):
        return jnp.pad(q.reshape(rows, -1), ((0, 0), (0, cols_p - cols)))

    tuned = _tune.lookup("quant_mix", (rows, cols_p), "int8") or {}
    cands = ([tuned["block_cols"]] if "block_cols" in tuned else []) \
        + [_qm.DEFAULT_BLOCK_COLS, 16384, 8192, 4096]
    block_c = next((c for c in cands if cols_p % c == 0), cols_p)
    # as many whole rows per step as keep the block near TARGET_BLOCK
    block_r = max(d for d in range(1, rows + 1) if rows % d == 0
                  and (d == 1 or d * block_c <= _qm.TARGET_BLOCK))
    out = _qm.quant_mix_2d(flat(q_self), flat(q_left), flat(q_right), *scales,
                           w_self=w_self, w_side=w_side, out_dtype=out_dtype,
                           block_rows=block_r, block_cols=block_c,
                           interpret=(impl == "pallas_interpret"))
    return out[:, :cols].reshape(q_self.shape)


# ---------------------------------------------------------------------------
# fused multi-hop ring mix (halo-panel megakernel)
# ---------------------------------------------------------------------------


def _pick_block_f(kernel: str, rows: int, f_p: int, dtype,
                  hops: int, block_f: int | None, default: int) -> int:
    """Feature-block width: explicit > tuned-for-this-key > the largest
    default candidate dividing the padded lane count (which is a multiple
    of 128, so the 128 fallback always divides)."""
    if block_f is not None:
        return block_f
    tuned = _tune.lookup(kernel, (rows, f_p), str(dtype),
                         extra={"hops": hops}) or {}
    cands = ([tuned["block_f"]] if "block_f" in tuned else []) \
        + [default, 4096, 2048, 1024, 512, 256, 128]
    for cand in cands:
        if f_p % cand == 0:
            return cand
    return f_p


def multi_hop_mix(panel: Array, *, hops: int, out_rows: int, halo: int,
                  w_self: float, w_side: float, impl: str | None = None,
                  block_f: int | None = None) -> Array:
    """``hops`` fused ring combines on a halo panel ``(halo + b + halo, ...)``
    (trailing dims flattened); returns the exact ``(out_rows, ...)`` center
    rows.  Requires ``halo >= hops``.  The feature tail is zero-padded to
    whole (8, 128) f32 tiles; rows are never padded (the kernel keeps them
    as an untiled leading dim, see ``kernels/multi_hop_mix.py``).
    """
    assert halo >= hops, (halo, hops)
    impl = impl or _default_impl()
    rows = panel.shape[0]
    f = panel.size // rows
    _est.record("multi_hop_mix", _est.multi_hop_mix_est(
        rows, f, hops=hops, out_rows=out_rows, itemsize=_itemsize(panel)))
    if impl == "ref":
        out = ref.multi_hop_mix_ref(panel.reshape(rows, -1), hops=hops,
                                    out_rows=out_rows, halo=halo,
                                    w_self=w_self, w_side=w_side)
        return out.reshape((out_rows,) + panel.shape[1:])

    pad_f = (-f) % _mh.F32_TILE
    p2 = jnp.pad(panel.reshape(rows, -1), ((0, 0), (0, pad_f)))
    f_p = f + pad_f
    block = _pick_block_f("multi_hop_mix", rows, f_p, panel.dtype,
                          hops, block_f, _mh.DEFAULT_BLOCK_F)
    out = _mh.multi_hop_mix_flat(p2, hops=hops, out_rows=out_rows, halo=halo,
                                 w_self=w_self, w_side=w_side, block_f=block,
                                 interpret=(impl == "pallas_interpret"))
    return out[:, :f].reshape((out_rows,) + panel.shape[1:])


def multi_hop_mix_quant(q_panel: Array, s_panel: Array, *, hops: int,
                        out_rows: int, halo: int, w_self: float,
                        w_side: float, out_dtype=jnp.float32,
                        impl: str | None = None,
                        block_f: int | None = None) -> Array:
    """All-hop compressed ``hops``-hop schedule on an int8 halo panel with
    per-row f32 scales: hop 0 fuses dequantize + combine, later hops
    requantize deterministically before combining (the values a receiver
    decodes from an int8 wire).  Returns the ``(out_rows, ...)`` center
    rows in ``out_dtype``."""
    assert halo >= hops, (halo, hops)
    impl = impl or _default_impl()
    rows = q_panel.shape[0]
    f = q_panel.size // rows
    _est.record("multi_hop_mix_quant", _est.multi_hop_mix_est(
        rows, f, hops=hops, out_rows=out_rows, quant=True))
    s2 = s_panel.reshape(rows, 1)
    if impl == "ref":
        z = ref.multi_hop_mix_quant_ref(q_panel.reshape(rows, -1), s2,
                                        hops=hops, w_self=w_self,
                                        w_side=w_side)
        return z[halo:halo + out_rows].astype(out_dtype) \
            .reshape((out_rows,) + q_panel.shape[1:])

    # the feature tail pads to whole (32, 128) int8 tiles (zeros dequantize
    # to 0 and never raise a row max); rows are never padded
    pad_f = (-f) % _mh.INT8_TILE
    q2 = jnp.pad(q_panel.reshape(rows, -1), ((0, 0), (0, pad_f)))
    f_p = f + pad_f
    block = _pick_block_f("multi_hop_mix_quant", rows, f_p, "int8",
                          hops, block_f, _mh.DEFAULT_BLOCK_F_QUANT)
    z = _mh.multi_hop_mix_quant_flat(q2, s2, hops=hops, w_self=w_self,
                                     w_side=w_side, block_f=block,
                                     interpret=(impl == "pallas_interpret"))
    return z[halo:halo + out_rows, :f].astype(out_dtype) \
        .reshape((out_rows,) + q_panel.shape[1:])
