"""Grassmann manifold Gr(d, r) — r-dimensional subspaces of R^d.

Points are represented by Stiefel matrices (orthonormal bases); two
representatives spanning the same subspace are the same Grassmann point.
The horizontal space at ``x`` (the tangent space of the quotient) is

    H_x = { u : x^T u = 0 },      P_{H_x}(g) = (I - x x^T) g = g - x (x^T g)

— note NO symmetrization, unlike Stiefel's Eq. 3: vertical rotations
x Ω (Ω skew) move the representative without moving the subspace, and the
horizontal projection removes them entirely.  Retractions re-orthonormalize
``x + u`` (polar / QR), returning a representative of the retracted
subspace; the IAM projects the Euclidean mean of representatives — for
nearby subspaces this is the standard extrinsic (chordal) mean.

Enables subspace workloads — robust PCA minimax
(:mod:`repro.objectives.robust_pca`) — where only span(x), not the basis,
matters.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.geometry.base import Manifold, register
from repro.geometry import stiefel as S

Array = jax.Array


def horizontal_project(x: Array, g: Array) -> Array:
    """P_{H_x}(g) = g - x (x^T g): projection onto the horizontal space."""
    xtg = S.mm("...dr,...ds->...rs", x, g)
    return g - S.mm("...dr,...rs->...ds", x, xtg)


def principal_angles(x: Array, y: Array) -> Array:
    """Principal angles between span(x) and span(y) (ascending, in [0, pi/2])."""
    s = jnp.linalg.svd(S.mm("...dr,...ds->...rs", x, y),
                       compute_uv=False)
    return jnp.arccos(jnp.clip(s, -1.0, 1.0))[..., ::-1]


class Grassmann(Manifold):
    """Gr(d, r) via orthonormal representatives (last two dims)."""

    name = "grassmann"
    retractions = ("polar", "qr")
    default_retraction = "polar"
    requires_tall = True

    def tangent_project(self, x: Array, g: Array) -> Array:
        return horizontal_project(x, g)

    def retract(self, x: Array, u: Array, kind: Optional[str] = None,
                *, method: str = "ns", **kw) -> Array:
        kind = kind or self.default_retraction
        if kind == "polar":
            # (x+u)^T (x+u) = I + u^T u for horizontal u, same polar factor
            # identity as Stiefel's Lemma 1
            return S.retract_polar(x, u, method=method)
        if kind == "qr":
            return S.retract_qr(x, u)
        raise ValueError(f"unknown retraction {kind!r}")

    def project(self, a: Array, method: str = "ns") -> Array:
        # polar factor: an orthonormal basis of the dominant subspace of a
        return S.project_stiefel(a, method)

    def dist(self, x: Array, y: Array) -> Array:
        """Geodesic (arc-length) distance: || principal angles ||_2."""
        return jnp.linalg.norm(principal_angles(x, y), axis=-1)

    def rand(self, key: Array, d: int, r: int, batch: tuple[int, ...] = (),
             dtype=jnp.float32) -> Array:
        return S.random_stiefel(key, d, r, batch, dtype)

    def check(self, x: Array) -> Array:
        # representative feasibility: orthonormal basis
        return S.stiefel_error(x)

    def feasible_init(self, x: Array) -> Array:
        return S.retract_qr(jnp.zeros_like(x), x)


GRASSMANN = register(Grassmann())
