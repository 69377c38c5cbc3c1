"""Fused multi-hop ring-gossip megakernel.

One ``pallas_call`` executes the *entire* local work of a k-hop ``W^k``
ring schedule (the int8 variant takes one per hop, see below).  The
hop-by-hop ``ShardMapBackend`` path pays k ppermute launches plus k
combine launches per mix; the bench shows that launch latency — not
bytes — is what loses to the stacked backend (127 vs 998 hops/sec at 64k
params/node).  This kernel collapses the schedule:

halo formulation
  The caller (``ShardMapBackend._gather_halo``) prepends/appends ``halo``
  neighbour rows to the local ``b``-row node block, giving a
  ``(halo + b + halo, F)`` panel in which row ``i``'s ring neighbours are
  simply rows ``i-1`` / ``i+1``.  All ``hops <= halo`` combines then run
  **locally** with zero wire events as a shrinking "pyramid": each hop
  combines only the interior rows,

      z <- wc * z[1:-1] + ws * (z[:-2] + z[2:])

  dropping the two boundary rows (which have no valid neighbour on one
  side).  After ``hops`` hops the window is exactly the rows a valid
  ``hops``-deep dependency cone can produce, and the center rows are
  bit-exact — per-element the expression is the same f32
  ``wc*x + ws*(l + r)`` as ``ring_mix`` / the stacked ``mix_ring`` leaf,
  which is what keeps the cross-backend bit-identity contract of
  ``test_mix_backend_equiv.py`` intact.  (The pyramid also does only the
  row work that can reach the center — no combines on panel-end garbage.)

panel layout
  A node row is a whole flattened model (~1e8 floats) while a panel has
  only ``b + 2*halo`` rows.  Padding those rows to the chip's sublane tile
  (8 f32 / 32 int8 rows) would multiply the panel's memory by up to 8x,
  so both kernels view the ``(rows, F)`` panel as ``(rows, F / 128,
  128)``: rows become an untiled leading dim and each row's features fill
  whole tiles (``ops.py`` pads the feature tail to :data:`F32_TILE` /
  :data:`INT8_TILE` elements).  Hops slice the leading dim.

fp32 variant (``multi_hop_mix_flat``)
  Single-pass grid over feature blocks: the panel's rows all fit one block
  (``b + 2*halo`` is small), so each grid step loads a ``(rows,
  block_f / 128, 128)`` tile, runs every hop in VMEM, and writes only the
  ``out_rows`` center rows — one panel read + one block write total,
  versus 2k HBM round trips for the unfused schedule.

int8 variant (``multi_hop_mix_quant_flat``)
  The all-hop compressed schedule: the panel arrives as int8 payloads with
  one f32 scale per row (only those bytes crossed the wire), hop 0 fuses
  dequantize + combine, and every later hop *re-quantizes* its input
  deterministically (round-to-nearest, per-row max-abs/127 scale — the
  values a receiver would have decoded had that hop's rows been shipped as
  int8).  Per-row maxima need the whole row, and a TPU kernel never reads
  back an output block it has already written, so the schedule is one
  launch per hop over ``(rows, block_f / 128, 128)`` blocks: each launch
  writes its f32 panel and, fused in, the per-block row maxima from which
  the next hop's scales come.  Quantization math is kept expression-
  identical to ``comms.compress.quantize_det`` so the stacked backend's
  hop-by-hop oracle decodes the same int8 values at every hop (results
  agree to FMA rounding of the final combines).

``kernels/ref.py`` holds the jnp oracles; ``ops.multi_hop_mix`` /
``ops.multi_hop_mix_quant`` own dispatch, padding and blocking.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

#: feature elements of one native tile per row: f32 (8, 128), int8 (32, 128)
F32_TILE = 8 * 128
INT8_TILE = 32 * 128
DEFAULT_BLOCK_F = F32_TILE
DEFAULT_BLOCK_F_QUANT = INT8_TILE
_EPS = 1e-12   # same scale floor as comms.compress


def _hop(z: Array, wc: float, ws: float) -> Array:
    """One ring combine on the interior rows of a panel value (row i sees
    rows i-1 / i+1; the two boundary rows drop out) — the shrinking
    "pyramid": only rows that can still influence the center are combined,
    and no zero-padding concats are materialized.  Mirrors ``_panel_hop``
    in ``kernels/ref.py`` so interpret mode stays bitwise with the oracle."""
    return wc * z[1:-1] + ws * (z[:-2] + z[2:])


def _shift_down(z: Array) -> Array:
    """Row i-1's value at row i; zeros shifted in at the top."""
    return jnp.concatenate([jnp.zeros_like(z[:1]), z[:-1]], axis=0)


def _shift_up(z: Array) -> Array:
    """Row i+1's value at row i; zeros shifted in at the bottom."""
    return jnp.concatenate([z[1:], jnp.zeros_like(z[:1])], axis=0)


def _hop_dq(q: Array, s: Array, wc: float, ws: float) -> Array:
    """One ring combine on quantized panel values with per-row scales,
    dequantizing each shifted operand separately —
    ``wc*dq(q_i) + ws*(dq(q_{i-1}) + dq(q_{i+1}))``, the same dataflow as
    ``quant_mix_ref`` / ``multi_hop_mix_quant_ref`` (so kernel and oracle
    agree bitwise under jit; cross-backend results agree to FMA rounding)."""
    dq = q * s
    dq_l = _shift_down(q) * _shift_down(s)
    dq_r = _shift_up(q) * _shift_up(s)
    return wc * dq + ws * (dq_l + dq_r)


# ---------------------------------------------------------------------------
# fp32 megakernel — single pass
# ---------------------------------------------------------------------------


def _mhm_kernel(x_ref, o_ref, *, hops: int, halo: int, w_self: float,
                w_side: float):
    # refs are (rows, nb, 128) / (out_rows, nb, 128): hops slice dim 0
    z = x_ref[...].astype(jnp.float32)
    for _ in range(hops):
        z = _hop(z, w_self, w_side)
    out_rows = o_ref.shape[0]
    lo = halo - hops                 # each hop dropped one row per side
    o_ref[...] = z[lo:lo + out_rows].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("hops", "out_rows", "halo",
                                             "w_self", "w_side", "block_f",
                                             "interpret"))
def multi_hop_mix_flat(panel: Array, *, hops: int, out_rows: int, halo: int,
                       w_self: float, w_side: float,
                       block_f: int = DEFAULT_BLOCK_F,
                       interpret: bool = False) -> Array:
    """``hops`` fused ring combines on a ``(halo + b + halo, F)`` panel;
    returns the ``(out_rows, F)`` center rows.  ``F % block_f == 0`` and
    ``block_f`` is a multiple of 128 (ops.py pads); on the chip
    ``block_f / 128`` must also divide by 8 unless one block spans the
    row.  Requires ``halo >= hops`` for exact output."""
    rows, f = panel.shape
    block_f = min(block_f, f)
    if f % block_f or block_f % 128:
        raise ValueError(f"multi_hop_mix_flat: F={f} not a multiple of "
                         f"block_f={block_f} (a multiple of 128); pad the "
                         f"lane tail (ops.multi_hop_mix does)")
    kernel = functools.partial(_mhm_kernel, hops=hops, halo=halo,
                               w_self=w_self, w_side=w_side)
    nb = block_f // 128
    out = pl.pallas_call(
        kernel,
        grid=(f // block_f,),
        in_specs=[pl.BlockSpec((rows, nb, 128), lambda j: (0, j, 0))],
        out_specs=pl.BlockSpec((out_rows, nb, 128), lambda j: (0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((out_rows, f // 128, 128),
                                       panel.dtype),
        interpret=interpret,
        name="multi_hop_mix",
    )(panel.reshape(rows, f // 128, 128))
    return out.reshape(out_rows, f)


# ---------------------------------------------------------------------------
# int8 all-hop schedule — one launch per hop, row maxima fused in
# ---------------------------------------------------------------------------


def _mhmq_kernel(x_ref, s_ref, z_ref, mx_ref, *, requant: bool,
                 w_self: float, w_side: float):
    """One hop on a ``(rows, nb, 128)`` block: (requantize with the row
    scales ``(rows, 1, 128)`` when ``requant``), dequantize + combine, and
    emit the block's per-row, per-lane ``|z|`` maxima for the next hop's
    scales."""
    x = x_ref[...].astype(jnp.float32)
    s = s_ref[...]
    if requant:
        # rounded values are integers, exact in f32 — no int8 cast needed
        x = jnp.clip(jnp.round(x / s), -127.0, 127.0)
    z = _hop_dq(x, s, w_self, w_side)
    z_ref[...] = z
    mx_ref[0] = jnp.max(jnp.abs(z), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("hops", "w_self", "w_side",
                                             "block_f", "interpret"))
def multi_hop_mix_quant_flat(q_panel: Array, s_panel: Array, *, hops: int,
                             w_self: float, w_side: float,
                             block_f: int = DEFAULT_BLOCK_F_QUANT,
                             interpret: bool = False) -> Array:
    """All-hop compressed schedule on an int8 ``(rows, F)`` halo panel with
    per-row f32 scales ``(rows, 1)``.  Returns the full f32 ``(rows, F)``
    evolved panel (callers slice the center rows).  ``F % block_f == 0``
    and ``block_f`` is a multiple of 128 (ops.py pads); on the chip
    ``block_f / 128`` must also be a multiple of 32 (the int8 tile height)
    unless one block spans the row."""
    rows, f = q_panel.shape
    block_f = min(block_f, f)
    if f % block_f or block_f % 128:
        raise ValueError(f"multi_hop_mix_quant_flat: F={f} not a multiple "
                         f"of block_f={block_f} (a multiple of 128); pad "
                         f"the lane tail (ops.multi_hop_mix_quant does)")
    nb, n_blocks = block_f // 128, f // block_f
    x_spec = pl.BlockSpec((rows, nb, 128), lambda j: (0, j, 0))
    s_spec = pl.BlockSpec((rows, 1, 128), lambda j: (0, 0, 0))
    mx_spec = pl.BlockSpec((1, rows, 1, 128), lambda j: (j, 0, 0, 0))
    out_shape = [jax.ShapeDtypeStruct((rows, f // 128, 128), jnp.float32),
                 jax.ShapeDtypeStruct((n_blocks, rows, 1, 128), jnp.float32)]

    def hop(x, s, requant):
        return pl.pallas_call(
            functools.partial(_mhmq_kernel, requant=requant, w_self=w_self,
                              w_side=w_side),
            grid=(n_blocks,),
            in_specs=[x_spec, s_spec],
            out_specs=[x_spec, mx_spec],
            out_shape=out_shape,
            interpret=interpret,
            name="multi_hop_mix_quant",
        )(x, s)

    def lanes(scale):                        # (rows, 1) -> (rows, 1, 128)
        return jnp.broadcast_to(scale.reshape(rows, 1, 1), (rows, 1, 128))

    z = q_panel.reshape(rows, f // 128, 128)
    s = lanes(s_panel.astype(jnp.float32))
    for h in range(hops):
        z, mx = hop(z, s, requant=h > 0)
        # the row max is exact in any order, so this scale is bitwise the
        # oracle's max-abs / 127 over the whole row
        s = lanes(jnp.maximum(jnp.max(mx, axis=(0, 3)) / 127.0, _EPS))
    return z.reshape(rows, f)
