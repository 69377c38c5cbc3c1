"""Fused polar-retraction Pallas kernel.

The DRGDA x-update hot spot, one kernel per Stiefel leaf: given the base
point ``x`` and the AMBIENT update direction ``g`` (the optimizer's
``alpha * [W^k x]_i - beta * u_i``), compute

    u   = P_{T_x}(g) = g - x sym(x^T g)          (tangent projection)
    out = (x + u)(I + u^T u)^{-1/2}              (polar retraction, Lemma 1)

in ONE pallas_call.  The unfused path launches four separate XLA ops
(two Gram matmuls + the Newton--Schulz loop + the apply matmul), each
streaming the tall (d, r) operands through HBM again; here the (r, r)
algebra never leaves VMEM scratch and ``x``/``g`` are read exactly twice.

Key identity — because the algorithm keeps ``x`` exactly on St(d, r)
(x^T x = I), every (r, r) statistic of ``u`` is expressible from two
d-accumulated Grams of the INPUTS:

    B = x^T g,   C = g^T g,   S = sym(B)
    u^T u = C - B^T S - S B + S S
    out   = (x + u) inv = x @ [(I - S) inv] + g @ [inv],
    inv   = (I + u^T u)^{-1/2}   (Newton--Schulz, in-kernel)

so the kernel is a two-pass revisiting grid over d-blocks:

  pass 0  accumulate B, C into VMEM scratch; on the last block run the
          (r, r) finalization: S, A = I + u^T u, the coupled NS iteration,
          and the two apply matrices M1 = (I - S) inv, M2 = inv.
  pass 1  stream the same d-blocks again: out_block = x_blk @ M1 + g_blk @ M2.

``r`` is padded to the 128-lane boundary by the ops.py wrapper; zero
padding is exact end to end (padded A is the identity block, whose NS
inverse sqrt is itself, and padded output rows/cols come out zero).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

DEFAULT_BLOCK_D = 256
DEFAULT_NS_ITERS = 20

#: the chip compiler's default scoped-VMEM limit for one kernel (v5e)
VMEM_LIMIT_BYTES = 16 * 2**20


def vmem_bytes(r: int, block_d: int) -> int:
    """Scoped VMEM the compiled kernel needs at lane-padded rank ``r``.

    Ten f32 (r, r) panels — the four scratch accumulators plus about six
    live temporaries of the finalization and the Newton--Schulz loop — and
    seven f32 (block_d, r) panels: x, g and the output, double-buffered,
    plus one in-kernel temporary.  Fit to the v5e compiler's own figures:
    within 0.6 MiB of them for r in 128..640 and block_d in 128..512 (at
    r = 640, block_d = 256 it needs 20.6 MiB; at r = 256, 3.9 MiB).
    """
    r_p = r + (-r) % 128
    return 4 * (10 * r_p * r_p + 7 * block_d * r_p)


def _ns_invsqrt(a: Array, iters: int) -> Array:
    """Coupled Newton--Schulz inverse sqrt on an (r, r) VMEM value — the
    same iteration as geometry.stiefel._invsqrt_newton_schulz."""
    r = a.shape[-1]
    eye = jnp.eye(r, dtype=a.dtype)
    c = jnp.max(jnp.sum(jnp.abs(a), axis=-1), axis=-1)[..., None, None] + 1e-6
    y = a / c
    z = jnp.broadcast_to(eye, a.shape)

    def body(_, yz):
        y, z = yz
        t = 0.5 * (3.0 * eye - jnp.dot(z, y, preferred_element_type=jnp.float32))
        return (jnp.dot(y, t, preferred_element_type=jnp.float32),
                jnp.dot(t, z, preferred_element_type=jnp.float32))

    _, z = jax.lax.fori_loop(0, iters, body, (y, z))
    return z * jax.lax.rsqrt(c)


def _fused_kernel(x_ref, g_ref, o_ref, b_acc, c_acc, m1_ref, m2_ref, *,
                  ns_iters: int):
    p = pl.program_id(0)      # pass: 0 = accumulate/finalize, 1 = apply
    i = pl.program_id(1)      # d-block
    r = b_acc.shape[-1]

    @pl.when((p == 0) & (i == 0))
    def _init():
        b_acc[...] = jnp.zeros_like(b_acc)
        c_acc[...] = jnp.zeros_like(c_acc)

    @pl.when(p == 0)
    def _accumulate():
        x = x_ref[...].astype(jnp.float32)
        g = g_ref[...].astype(jnp.float32)
        b_acc[...] += jax.lax.dot_general(
            x, g, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        c_acc[...] += jax.lax.dot_general(
            g, g, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        @pl.when(i == pl.num_programs(1) - 1)
        def _finalize():
            eye = jnp.eye(r, dtype=jnp.float32)
            b = b_acc[...]
            c = c_acc[...]
            s = 0.5 * (b + b.T)
            # u^T u = C - B^T S - S B + S S   (uses x^T x = I)
            bts = jnp.dot(b.T, s, preferred_element_type=jnp.float32)
            utu = c - bts - bts.T \
                + jnp.dot(s, s, preferred_element_type=jnp.float32)
            inv = _ns_invsqrt(eye + utu, ns_iters)
            m2_ref[...] = inv
            m1_ref[...] = jnp.dot(eye - s, inv,
                                  preferred_element_type=jnp.float32)

    @pl.when(p == 1)
    def _apply():
        x = x_ref[...].astype(jnp.float32)
        g = g_ref[...].astype(jnp.float32)
        out = jax.lax.dot_general(
            x, m1_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        out += jax.lax.dot_general(
            g, m2_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "ns_iters",
                                             "interpret"))
def fused_retract_2d(x: Array, g: Array, *, block_d: int = DEFAULT_BLOCK_D,
                     ns_iters: int = DEFAULT_NS_ITERS,
                     interpret: bool = False) -> Array:
    """R_x(P_x(g)) for a single (d, r) pair; d % block_d == 0 (ops.py pads)."""
    d, r = x.shape
    block_d = min(block_d, d)
    assert d % block_d == 0, (d, block_d)
    n_d = d // block_d

    spec = pl.BlockSpec((block_d, r), lambda p, i: (i, 0))
    return pl.pallas_call(
        functools.partial(_fused_kernel, ns_iters=ns_iters),
        grid=(2, n_d),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((d, r), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((r, r), jnp.float32),   # B = x^T g accumulator
            pltpu.VMEM((r, r), jnp.float32),   # C = g^T g accumulator
            pltpu.VMEM((r, r), jnp.float32),   # M1 = (I - S) inv
            pltpu.VMEM((r, r), jnp.float32),   # M2 = inv
        ],
        interpret=interpret,
        name="fused_polar_retract",
    )(x, g)
