"""Stiefel manifold St(d, r) = {x in R^{d x r} : x^T x = I_r}.

The paper's geometry (Wu, Hu & Huang, AAAI'23), migrated here from
``repro.core.manifolds`` with

  * tangent projection  P_{T_x}(g) = g - x * sym(x^T g)          (Eq. 3)
  * polar retraction    R_x(u)     = (x + u)(I_r + u^T u)^{-1/2}  (Lemma 1)
  * QR retraction       qf(x + u)  with sign fix
  * Cayley retraction   (I - W/2)^{-1}(I + W/2) x with the Wen--Yin skew
    W = W_hat - W_hat^T, W_hat = (I - x x^T/2) u x^T, solved by matmul-only
    CG / Neumann iterations (see :func:`retract_cayley`)
  * induced arithmetic mean (IAM)  x_hat = P_St(mean_i x_i)       (Eq. 9)

All functions operate on arrays whose *last two* dims are (d, r); leading
dims (node axis, batched heads, ...) broadcast.  TPU adaptation: the polar
factors are computed with Newton--Schulz iterations (matmul-only, maps to
the MXU) instead of SVD/eigh; an eigh-based oracle is kept for tests and
for the CPU-exactness path; the fused "polar_fused" retraction dispatches
to the Pallas kernel in :mod:`repro.kernels.retract`.
"""
from __future__ import annotations

import functools
from typing import Literal, Optional

import jax
import jax.numpy as jnp

from repro.geometry.base import Manifold, register

Array = jax.Array

# ---------------------------------------------------------------------------
# basic tangent-space ops
# ---------------------------------------------------------------------------


def mm(spec: str, *operands: Array) -> Array:
    """``jnp.einsum`` at full f32 precision.

    The manifold algebra must hold x^T x = I to f32 accuracy.  At a TPU's
    default matmul precision (one bf16 pass) x^T x alone is off by ~0.1 in
    Frobenius norm at r = 576, so the retraction, the tangent projection
    and the feasibility check all ask for HIGHEST.  These are (r, r) and
    (d, r) products, a small share of a step's matmul work.
    """
    return jnp.einsum(spec, *operands, precision=jax.lax.Precision.HIGHEST)


def sym(a: Array) -> Array:
    """Symmetric part (over the last two dims)."""
    return 0.5 * (a + jnp.swapaxes(a, -1, -2))


def tangent_project(x: Array, g: Array) -> Array:
    """Orthogonal projection of ambient ``g`` onto T_x St(d, r)  (Eq. 3).

    P_{T_x}(g) = g - x sym(x^T g).  Note P_{T_x}(x) = 0.
    """
    xtg = mm("...dr,...ds->...rs", x, g)
    return g - mm("...dr,...rs->...ds", x, sym(xtg))


def is_tangent(x: Array, u: Array, atol: float = 1e-5) -> Array:
    """Check u in T_x M:  x^T u + u^T x = 0."""
    a = mm("...dr,...ds->...rs", x, u)
    return jnp.max(jnp.abs(a + jnp.swapaxes(a, -1, -2))) < atol


def stiefel_error(x: Array) -> Array:
    """|| x^T x - I ||_F  (feasibility residual)."""
    r = x.shape[-1]
    xtx = mm("...dr,...ds->...rs", x, x)
    return jnp.linalg.norm(xtx - jnp.eye(r, dtype=x.dtype), axis=(-2, -1))


# ---------------------------------------------------------------------------
# matrix inverse square root: Newton--Schulz (TPU) and eigh (oracle)
# ---------------------------------------------------------------------------


def _invsqrt_eigh(a: Array) -> Array:
    """Exact (I-free) inverse square root of an SPD matrix via eigh."""
    w, v = jnp.linalg.eigh(a)
    w = jnp.maximum(w, 1e-12)
    return mm("...ir,...r,...jr->...ij", v, jax.lax.rsqrt(w), v)


def _invsqrt_newton_schulz(a: Array, iters: int = 20) -> Array:
    """Inverse square root of SPD ``a`` via the coupled Newton--Schulz
    (Denman--Beavers variant with Y/Z coupling) iteration.

    Matmul-only => maps onto the TPU MXU; converges quadratically provided
    ||I - a/c|| < 1 after the trace-based scaling below.  For the polar
    retraction, ``a = I + u^T u`` is SPD with eigenvalues >= 1, and ``u`` is a
    (step-size-scaled) tangent update, so conditioning is benign.
    """
    r = a.shape[-1]
    eye = jnp.eye(r, dtype=a.dtype)
    # scale so the spectrum lies in (0, 1]: the induced inf-norm (max abs
    # row sum) upper-bounds the spectral radius of the symmetric ``a``;
    # quadratic NS convergence then needs ~log2(log(eps)/log(1-1/cond))
    # iterations — 12 covers cond ~ 1e2 at fp32 accuracy.
    c = jnp.max(jnp.sum(jnp.abs(a), axis=-1), axis=-1)[..., None, None] + 1e-6
    y = a / c
    z = jnp.broadcast_to(eye, a.shape)

    def body(_, yz):
        y, z = yz
        t = 0.5 * (3.0 * eye - mm("...ij,...jk->...ik", z, y))
        return (mm("...ij,...jk->...ik", y, t), mm("...ij,...jk->...ik", t, z))

    y, z = jax.lax.fori_loop(0, iters, body, (y, z))
    # z ~ (a/c)^{-1/2}  =>  a^{-1/2} = z / sqrt(c)
    return z * jax.lax.rsqrt(c)


def invsqrt_spd(a: Array, method: Literal["ns", "eigh"] = "ns") -> Array:
    if method == "eigh":
        return _invsqrt_eigh(a)
    return _invsqrt_newton_schulz(a)


# ---------------------------------------------------------------------------
# retractions
# ---------------------------------------------------------------------------


def retract_polar(x: Array, u: Array, method: Literal["ns", "eigh"] = "ns") -> Array:
    """Polar retraction R_x(u) = (x+u)(I + u^T u)^{-1/2} (Lemma 1).

    Valid for u in T_x M (then (x+u)^T (x+u) = I + u^T u).  Non-expansive
    towards the manifold (Eq. 7), second-order bounded (Eq. 6).
    """
    r = u.shape[-1]
    utu = mm("...dr,...ds->...rs", u, u)
    a = jnp.eye(r, dtype=u.dtype) + utu
    return mm("...dr,...rs->...ds", x + u, invsqrt_spd(a, method))


def retract_qr(x: Array, u: Array) -> Array:
    """QR retraction: qf(x + u) with sign fix so R_x(0) = x."""
    q, rr = jnp.linalg.qr(x + u)
    d = jnp.sign(jnp.diagonal(rr, axis1=-2, axis2=-1))
    d = jnp.where(d == 0, 1.0, d)
    return q * d[..., None, :]


def retract_cayley(x: Array, u: Array, iters: int = 12,
                   solver: Literal["cg", "neumann"] = "cg") -> Array:
    """Cayley retraction (Wen & Yin 2013):

        R_x(u) = (I - W/2)^{-1} (I + W/2) x,
        W = W_hat - W_hat^T,   W_hat = (I - x x^T / 2) u x^T.

    ``W`` is skew-symmetric by construction, so the Cayley factor is exactly
    orthogonal and R_x(u) lands on St(d, r) for ANY ``u``; for tangent ``u``
    the half-projector makes ``W x = u`` exactly (the cross terms cancel via
    x^T u + u^T x = 0), giving true first-order agreement
    R_x(tu) = x + tu + O(t^2).  Instead of forming or factorizing the (d, d)
    system, the solve is iterative with ``W`` applied in its low-rank form
    (rank <= 2r: tall (d, r) matmuls against (r, r) intermediates) — the
    same matmul-only MXU profile as the Newton--Schulz polar path.

    * ``solver="cg"`` (default): CG on the normal equations.  Because
      (I - W/2)^T = I + W/2, they read  (I - W^2/4) z = (I + W + W^2/4) x
      with the SPD operator I - W^2/4 = I + W^T W / 4 (eigenvalues in
      [1, 1 + ||W||^2/4]) — CG converges for ANY step size, and the benign
      conditioning at step-size-scaled ``u`` makes ~12 iterations cover
      fp32 accuracy.
    * ``solver="neumann"``: the plain fixed point  z <- (I + W/2)x + (W/2)z,
      one ``W`` apply per iteration, but geometric convergence requires
      ||W|| < 2 (roughly ||u|| < 1).
    """
    xtu = mm("...dr,...ds->...rs", x, u)

    def wv(v: Array) -> Array:
        # W v = u (x^T v) - x [ u^T v + 0.5 (x^T u)(x^T v)
        #                               - 0.5 (x^T u)^T (x^T v) ]
        xtv = mm("...dr,...ds->...rs", x, v)
        utv = mm("...dr,...ds->...rs", u, v)
        inner = utv + 0.5 * (mm("...rs,...st->...rt", xtu, xtv)
                             - mm("...sr,...st->...rt", xtu, xtv))
        return (mm("...dr,...rs->...ds", u, xtv)
                - mm("...dr,...rs->...ds", x, inner))

    if solver == "neumann":
        b = x + 0.5 * wv(x)

        def body(_, z):
            return b + 0.5 * wv(z)

        return jax.lax.fori_loop(0, iters, body, b)

    def a_op(v: Array) -> Array:               # (I - W^2/4) v, SPD
        return v - 0.25 * wv(wv(v))

    def dot(a: Array, b: Array) -> Array:
        return jnp.sum(a * b, axis=(-2, -1), keepdims=True)

    wx = wv(x)
    rhs = x + wx + 0.25 * wv(wx)               # (I + W + W^2/4) x
    z = x                                      # z ~ x for small steps
    r = rhs - a_op(z)
    p = r
    rr = dot(r, r)

    def body(_, zrp):
        z, r, p, rr = zrp
        ap = a_op(p)
        # guarded divisions: converged (r = 0) batch elements stay fixed
        alpha = rr / jnp.maximum(dot(p, ap), 1e-30)
        z = z + alpha * p
        r = r - alpha * ap
        rr_new = dot(r, r)
        beta = rr_new / jnp.maximum(rr, 1e-30)
        return z, r, r + beta * p, rr_new

    z, _, _, _ = jax.lax.fori_loop(0, iters, body, (z, r, p, rr))
    return z


# ---------------------------------------------------------------------------
# projection onto the manifold (polar factor) + IAM
# ---------------------------------------------------------------------------


def project_stiefel(a: Array, method: Literal["ns", "eigh"] = "ns") -> Array:
    """P_St(a): nearest Stiefel point = polar factor U of a = U P.

    Computed as a (a^T a)^{-1/2}.  ``a`` must have full column rank (true for
    averages of nearby Stiefel points, the only use in the algorithm).
    """
    ata = mm("...dr,...ds->...rs", a, a)
    return mm("...dr,...rs->...ds", a, invsqrt_spd(ata, method))


def induced_arithmetic_mean(xs: Array, method: Literal["ns", "eigh"] = "ns") -> Array:
    """IAM over the leading axis (Eq. 9): P_St( (1/n) sum_i x_i )."""
    return project_stiefel(jnp.mean(xs, axis=0), method)


def consensus_error(xs: Array) -> Array:
    """(1/n) || x - 1 (x_hat) ||^2 style residual (Eq. 10), returned as the
    mean squared distance of the stacked replicas to their IAM."""
    xhat = induced_arithmetic_mean(xs)
    return jnp.mean(jnp.sum((xs - xhat) ** 2, axis=(-2, -1)))


# ---------------------------------------------------------------------------
# random points / misc
# ---------------------------------------------------------------------------


def random_stiefel(key: jax.Array, d: int, r: int, batch: tuple[int, ...] = (),
                   dtype=jnp.float32) -> Array:
    a = jax.random.normal(key, (*batch, d, r), dtype=dtype)
    q, _ = jnp.linalg.qr(a)
    return q


def riemannian_grad(x: Array, egrad: Array) -> Array:
    """Riemannian gradient = tangent projection of the Euclidean gradient."""
    return tangent_project(x, egrad)


# ---------------------------------------------------------------------------
# the registered geometry
# ---------------------------------------------------------------------------


class Stiefel(Manifold):
    """St(d, r) over the last two dims; the paper's default geometry."""

    name = "stiefel"
    retractions = ("polar", "qr", "cayley", "polar_fused")
    default_retraction = "polar"
    fused_retraction = "polar_fused"
    requires_tall = True

    def tangent_project(self, x: Array, g: Array) -> Array:
        return tangent_project(x, g)

    def retract(self, x: Array, u: Array, kind: Optional[str] = None,
                *, method: str = "ns", iters: Optional[int] = None,
                solver: str = "cg", **kw) -> Array:
        kind = kind or self.default_retraction
        if kind == "polar":
            return retract_polar(x, u, method=method)
        if kind == "qr":
            return retract_qr(x, u)
        if kind == "cayley":
            return retract_cayley(x, u, solver=solver,
                                  **({"iters": iters} if iters else {}))
        if kind == "polar_fused":
            # fused Pallas path: ``u`` is the AMBIENT update direction; the
            # kernel performs tangent projection + Gram + NS + apply in one
            # VMEM-resident pass (ref oracle on non-TPU backends).
            from repro.kernels import ops
            return ops.fused_retract(x, u, **kw)
        raise ValueError(f"unknown retraction {kind!r}")

    def project(self, a: Array, method: str = "ns") -> Array:
        return project_stiefel(a, method)

    def dist(self, x: Array, y: Array) -> Array:
        """Extrinsic (embedded-Frobenius) distance — what the paper's
        consensus/metric expressions use."""
        return jnp.linalg.norm(x - y, axis=(-2, -1))

    def rand(self, key: Array, d: int, r: int, batch: tuple[int, ...] = (),
             dtype=jnp.float32) -> Array:
        return random_stiefel(key, d, r, batch, dtype)

    def check(self, x: Array) -> Array:
        return stiefel_error(x)

    def feasible_init(self, x: Array) -> Array:
        # QR orthonormalization: exact feasibility regardless of the raw
        # initializer's conditioning (polar/NS loses digits when x^T x has
        # tiny eigenvalues); the algorithm only needs x0 ON the manifold.
        return retract_qr(jnp.zeros_like(x), x)


STIEFEL = register(Stiefel())


@functools.partial(jax.jit, static_argnames=("kind",))
def rgd_step(x: Array, egrad: Array, lr: float, kind: str = "polar") -> Array:
    """Single-node Riemannian gradient-descent step (Eq. 4) — used by tests
    and by the centralized reference implementations."""
    return STIEFEL.retract(x, -lr * tangent_project(x, egrad), kind)
