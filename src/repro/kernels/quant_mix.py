"""Fused dequantize + weighted 3-way combine Pallas kernel.

The compressed-gossip hop receives its two ring neighbours' payloads as int8
panels with one float32 scale per row (row = node / node-shard).  The naive
pipeline dequantizes three buffers to f32 in HBM and then runs the
``ring_mix`` combine — 4 streamed arrays where one suffices.  This kernel
fuses both:

    out[i, :] = w_self * s_c[i] * qc[i, :]
              + w_side * (s_l[i] * ql[i, :] + s_r[i] * qr[i, :])

reading the int8 payloads directly (4x less HBM traffic than pre-dequantized
inputs) and writing the combined f32/bf16 result once.  Like ``ring_mix`` it
is pure-bandwidth elementwise work.

A row is a node's whole flattened leaf (up to ~3e7 values) and there are
only a few rows, so rows are never padded or folded to the int8 (32, 128)
tile (folding rows into sublanes is a relayout whose compile alone takes
minutes at model size).  Each row is viewed as ``(cols / 128, 128)``
instead: rows are an untiled leading dim, blocks are ``(block_rows,
block_cols / 128, 128)``, and the per-row scales ride along as
lane-replicated ``(rows, 1, 128)`` vectors that broadcast over sublanes.

``ref.quant_mix_ref`` is the oracle; ``ops.quant_mix`` dispatches and owns
padding/blocking.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

DEFAULT_BLOCK_COLS = 32768
#: elements per grid step (block_rows * block_cols) the wrapper aims for
TARGET_BLOCK = 65536


def _quant_mix_kernel(qc_ref, ql_ref, qr_ref, sc_ref, sl_ref, sr_ref, o_ref,
                      *, w_self: float, w_side: float):
    def dq(q_ref, s_ref):
        return q_ref[...].astype(jnp.float32) * s_ref[...]

    o_ref[...] = (w_self * dq(qc_ref, sc_ref)
                  + w_side * (dq(ql_ref, sl_ref) + dq(qr_ref, sr_ref))
                  ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("w_self", "w_side", "out_dtype", "block_rows",
                              "block_cols", "interpret"))
def quant_mix_2d(q_self: Array, q_left: Array, q_right: Array,
                 s_self: Array, s_left: Array, s_right: Array, *,
                 w_self: float, w_side: float, out_dtype=jnp.float32,
                 block_rows: int = 1, block_cols: int = DEFAULT_BLOCK_COLS,
                 interpret: bool = False) -> Array:
    """int8 q_* (rows, cols); f32 s_* (rows, 1) — one scale per row.
    ``cols % 128 == 0``, ``rows % block_rows == 0`` and ``cols %
    block_cols == 0``; on the chip ``block_cols / 128`` must also divide
    by 32 (the int8 tile height) unless one block spans the row."""
    rows, cols = q_self.shape
    block_rows = min(block_rows, rows)
    block_cols = min(block_cols, cols)
    assert cols % 128 == 0 and block_cols % 128 == 0, (cols, block_cols)
    assert rows % block_rows == 0 and cols % block_cols == 0
    kernel = functools.partial(_quant_mix_kernel, w_self=w_self, w_side=w_side)
    nb = block_cols // 128
    q_spec = pl.BlockSpec((block_rows, nb, 128), lambda i, j: (i, j, 0))
    s_spec = pl.BlockSpec((block_rows, 1, 128), lambda i, j: (i, 0, 0))

    def lanes(s):
        return jnp.broadcast_to(s.astype(jnp.float32).reshape(rows, 1, 1),
                                (rows, 1, 128))

    out = pl.pallas_call(
        kernel,
        grid=(rows // block_rows, cols // block_cols),
        in_specs=[q_spec, q_spec, q_spec, s_spec, s_spec, s_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((rows, cols // 128, 128), out_dtype),
        interpret=interpret,
        name="quant_mix",
    )(*(q.reshape(rows, cols // 128, 128) for q in (q_self, q_left, q_right)),
      lanes(s_self), lanes(s_left), lanes(s_right))
    return out.reshape(rows, cols)
