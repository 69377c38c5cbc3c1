"""Plain reference of decentralized Riemannian stochastic GDA (DRSGDA,
Algorithm 2 of Wu, Hu & Huang, AAAI 2023) on the group-DRO language-model
objective, over ``n`` node replicas gossiping on a ring.

For every node i, with ring weights 1/3 (self, left, right) and one gossip
hop per mix:

  x_{t+1} = R_x( alpha P_x([W x]_i) - beta P_x(u_i) )    Stiefel leaves
  x_{t+1} = x + alpha ([W x]_i - x) - beta u_i            other leaves
  y_{t+1} = Proj_simplex( [W y]_i + eta v_i )
  u_{t+1} = [W u]_i + g_x(x_{t+1}, y_{t+1}; B_{t+1}) - g_x(x_t, y_t; B_t)
  v_{t+1} = [W v]_i + g_y(x_{t+1}, y_{t+1}; B_{t+1}) - g_y(x_t, y_t; B_t)

with P_x(g) = g - x sym(x^T g) and the polar retraction
R_x(u) = (x + u)(I + u^T u)^{-1/2}, computed exactly through ``eigh``.
The loss is sum_g y_g L_g - rho ||y - 1/G||^2, L_g the mean per-sequence
cross-entropy of group g's sequences (the batch mean where a group is
absent).  Nothing of the program is imported.

Memory: the per-node gradient runs node after node (``lax.map``) with
each block rematerialized, and each step is one jitted call that donates
the state, so a full-width 4-node reference fits one chip once the
program's own state is freed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import weights
from bench.reference import llama

HI = jax.lax.Precision.HIGHEST


def _mm(spec, *xs):
    return jnp.einsum(spec, *xs, precision=HI)


def path_of(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)


def stiefel_mask(params) -> dict:
    """True on the manifold-constrained leaves: tall or square attention
    projections (the program's policy ``attn/(wq|wk|wv|wo)``)."""
    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: weights.is_orthonormal_leaf(path_of(kp), leaf.shape),
        params)


def tangent(x, g):
    xtg = _mm("...dr,...ds->...rs", x, g)
    return g - _mm("...dr,...rs->...ds", x,
                   0.5 * (xtg + jnp.swapaxes(xtg, -1, -2)))


def retract(x, u):
    r = u.shape[-1]
    a = jnp.eye(r, dtype=jnp.float32) + _mm("...dr,...ds->...rs", u, u)
    w, v = jnp.linalg.eigh(a)
    inv = _mm("...ir,...r,...jr->...ij", v, jax.lax.rsqrt(w), v)
    return _mm("...dr,...rs->...ds", x + u, inv)


def project_simplex(y):
    k = y.shape[-1]
    s = jnp.sort(y, axis=-1)[..., ::-1]
    css = jnp.cumsum(s, axis=-1) - 1.0
    idx = jnp.arange(1, k + 1, dtype=y.dtype)
    rho = jnp.sum(s - css / idx > 0, axis=-1, keepdims=True)
    theta = jnp.take_along_axis(css, rho - 1, axis=-1) / rho.astype(y.dtype)
    return jnp.maximum(y - theta, 0.0)


def ring(x):
    """One gossip hop over the leading node axis, ring weights 1/3."""
    if x.shape[0] == 1:
        return x
    if x.shape[0] == 2:
        return (x + jnp.roll(x, 1, 0)) / 2.0
    return (x + jnp.roll(x, 1, 0) + jnp.roll(x, -1, 0)) / 3.0


def group_dro_loss(params, y, tokens, gids, sz, num, n_groups, rho):
    logits = llama.forward(params, tokens[:, :-1], sz, num, remat=True)
    lp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1)[..., 0]
    per_seq = nll.mean(axis=-1)
    oh = jax.nn.one_hot(gids, n_groups, dtype=jnp.float32)
    counts = oh.sum(0)
    sums = (per_seq[:, None] * oh).sum(0)
    lg = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0),
                   per_seq.mean())
    return jnp.dot(y, lg, precision=HI) - rho * jnp.sum(
        (y - 1.0 / n_groups) ** 2)


@functools.lru_cache(maxsize=None)
def _programs(sz_items, num, hyper_items, n_groups, rho):
    """The reference's jitted programs for one set of sizes and numerics."""
    sz = dict(sz_items)
    hyper = dict(hyper_items)
    store = jnp.dtype(num.dtype)

    def keep(t):            # the precision state is stored in
        return jax.tree.map(lambda a: a.astype(store).astype(jnp.float32), t)

    def grads(x, y, tokens, gids):
        def one(args):
            xi, yi, ti, gi = args
            loss, (gx, gy) = jax.value_and_grad(group_dro_loss, (0, 1))(
                xi, yi, ti, gi, sz, num, n_groups, rho)
            mask = stiefel_mask(xi)
            gx = jax.tree.map(lambda m, a, g: tangent(a, g) if m else g,
                              mask, xi, gx)
            return loss, gx, gy
        return jax.lax.map(one, (x, y, tokens, gids))

    def init(x, y, tokens, gids):
        _, gx, gy = grads(x, y, tokens, gids)
        gx, gy = keep(gx), keep(gy)
        return (x, y, gx, gy, jax.tree.map(jnp.copy, gx), jnp.copy(gy))

    def step(state, tokens, gids):
        x, y, u, v, gx, gy = state
        a, b = hyper["alpha"], hyper["beta"]
        mask = stiefel_mask(x)

        def upd(m, xl, ul):
            mx = ring(xl)
            if m:
                return retract(xl, a * tangent(xl, mx) - b * tangent(xl, ul))
            return xl + a * (mx - xl) - b * ul

        x_new = keep(jax.tree.map(upd, mask, x, u))
        y_new = keep(project_simplex(ring(y) + hyper["eta"] * v))
        loss, g_new, gy_new = grads(x_new, y_new, tokens, gids)
        g_new, gy_new = keep(g_new), keep(gy_new)
        u_new = keep(jax.tree.map(lambda ul, g, gp: ring(ul) + g - gp,
                                  u, g_new, gx))
        v_new = keep(ring(v) + gy_new - gy)
        return (x_new, y_new, u_new, v_new, g_new, gy_new), jnp.mean(loss)

    return (jax.jit(init), jax.jit(step, donate_argnums=(0,)))


@jax.jit
def _norms(tree):
    return jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


def leaf_norms(tree) -> dict:
    """Per-leaf Frobenius norm over all nodes, by path."""
    flat = jax.tree_util.tree_flatten_with_path(_norms(tree))[0]
    return {path_of(kp): float(v) for kp, v in flat}


def run(params0, batches, sz: dict, hyper: dict, nodes: int, n_groups: int,
        rho: float, num: llama.Numerics = llama.F32, steps: int = 3) -> dict:
    """``steps`` DRSGDA steps from ``params0`` (one replica, copied to every
    node) with ``batches[0]`` at init and ``batches[t]`` at step t.

    Returns the mean loss of each step, per-leaf norms of the gradient the
    optimizer holds after step 1 (``grad``, with ``y``), and per-leaf norms
    of the change of x and y after ``steps`` (``change``)."""
    init, step = _programs(tuple(sorted(sz.items())), num,
                           tuple(sorted(hyper.items())), n_groups, rho)
    store = jnp.dtype(num.dtype)
    x0 = jax.tree.map(lambda a: jnp.broadcast_to(
        a.astype(store).astype(jnp.float32)[None], (nodes,) + a.shape),
        params0)
    y0 = jnp.full((nodes, n_groups), 1.0 / n_groups, jnp.float32)
    state = init(x0, y0, *batches[0])
    del x0
    out = {"loss": []}
    for t in range(1, steps + 1):
        state, loss = step(state, *batches[t])
        out["loss"].append(float(loss))
        if t == 1:
            out["grad"] = leaf_norms(state[4])
            out["grad"]["y"] = leaf_norms(state[5])[""]
    x, y = state[0], state[1]
    out["change"] = change_norms(x, y, params0, n_groups)
    return out


@jax.jit
def _change_sq(x, p0):
    return jax.tree.map(lambda a, b: jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)[None])), x, p0)


def change_norms(x, y, params0, n_groups: int) -> dict:
    """Per-leaf norm of x - x0 over all nodes (x0 the same on every node),
    and of y - 1/G."""
    sq = _change_sq(x, params0)
    flat = jax.tree_util.tree_flatten_with_path(sq)[0]
    out = {path_of(kp): float(jnp.sqrt(v)) for kp, v in flat}
    out["y"] = float(jnp.sqrt(jnp.sum(jnp.square(
        y.astype(jnp.float32) - 1.0 / n_groups))))
    return out
