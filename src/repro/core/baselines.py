"""Comparison baselines used in the paper's experiments.

The paper compares against Euclidean decentralized minimax methods, adding a
projection-like retraction so they respect the Stiefel constraint
("Since these methods were not designed for optimization on the Stiefel
manifold, we add the retraction operation"):

* **GT-GDA**   (Zhang et al. 2021)  — deterministic gradient-tracking GDA.
* **GNSD-A**   (motivated by GNSD, Lu et al. 2019) — stochastic
  gradient-tracking descent ascent.
* **DM-HSGD**  (Xian et al. 2021)  — hybrid (STORM) variance-reduced
  decentralized minimax.
* **GT-SRVR**  (Zhang et al. 2021) — SPIDER/SVRG-style recursive variance
  reduction with periodic anchor batches + gradient tracking.

All share the node-stacked pytree layout of :mod:`repro.core.gda`.
Constrained leaves are *projected back* onto their manifold (polar factor on
Stiefel/Grassmann, column normalization on oblique — each geometry's
``project``) after the Euclidean update — i.e. the update direction is NOT
tangent-projected, which is precisely what distinguishes them from
DRGDA/DRSGDA and what the paper's figures show costs them convergence speed.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.comms import layer as comms_layer
from repro.core.gda import (GDAHyper, StepMetrics, _consensus, _copy_tree,
                            _strong,
                            _tree_consensus, _tree_mean_norm,
                            make_obs_step)
from repro.core.gossip import GossipSpec
from repro.core.minimax import MinimaxProblem
from repro.obs import wire as obs_wire

Array = jax.Array
PyTree = Any


def _project_back(manifold_map: PyTree, x: PyTree, method: str = "ns") -> PyTree:
    return jax.tree.map(lambda m, xi: m.project(xi, method=method),
                        manifold_map, x)


def _euclid_grads(opt, x, y, batch):
    """Per-node (loss, (gx, gy)) — *Euclidean* grads (no tangent
    projection) — mapped over the nodes by ``opt``'s mix backend."""
    def one(xi, yi, bi):
        return jax.value_and_grad(opt.problem.loss_fn, argnums=(0, 1))(
            xi, yi, bi)
    return opt.backend.node_map(one)(x, y, batch)


def _metrics(loss, gx, gy, x, y, u) -> StepMetrics:
    return StepMetrics(
        loss=jnp.mean(loss),
        grad_norm_x=_tree_mean_norm(gx),
        grad_norm_y=jnp.mean(jnp.linalg.norm(gy.reshape(gy.shape[0], -1), axis=-1)),
        consensus_x=_tree_consensus(x),
        consensus_y=_consensus(y),
        tracker_norm_u=_tree_mean_norm(u),
    )


# ---------------------------------------------------------------------------
# GT-GDA / GNSD-A : gradient tracking descent ascent (+ projection)
# ---------------------------------------------------------------------------


class GTState(NamedTuple):
    x: PyTree
    y: Array
    u: PyTree
    v: Array
    gx_prev: PyTree
    gy_prev: Array
    step: Array
    comm: Any = None
    obs: Any = None


class GTGDA:
    """Euclidean gradient-tracking GDA with post-hoc Stiefel projection.

    Deterministic when fed full local batches (GT-GDA); the stochastic
    variant fed minibatches is the paper's GNSD-A baseline (see alias).
    """
    name = "gt-gda"
    deterministic = True

    def __init__(self, problem: MinimaxProblem, gossip: GossipSpec,
                 hyper: GDAHyper = GDAHyper(), telemetry=None):
        self.problem, self.gossip, self.hyper = problem, gossip, hyper
        self.backend = comms_layer.resolve_backend(gossip)
        self.engine = comms_layer.maybe_engine(gossip, backend=self.backend)
        if self.engine is not None:
            # elastic join protocol: project rejoined x through the geometry
            self.engine.register_manifolds({"x": problem.manifold_map})
        self.telemetry = telemetry if telemetry is not None \
            and telemetry.enabled else None

    def init(self, x0: PyTree, y0: Array, batch0: Any) -> GTState:
        x0, y0 = _strong(x0), _strong(y0)
        _, (gx, gy) = _euclid_grads(self, x0, y0, batch0)
        comm0 = comms_layer.maybe_init_state(
            self.engine, {"x": x0, "y": y0, "u": gx, "v": gy})
        obs0 = self.telemetry.init_counters() if self.telemetry else None
        return GTState(x0, y0, gx, gy, _copy_tree(gx), jnp.copy(gy),
                       jnp.zeros((), jnp.int32), comm0, obs0)

    def step(self, state: GTState, batch: Any) -> tuple[GTState, StepMetrics]:
        h = self.hyper
        mix, comm_final = comms_layer.make_mixer(
            self.gossip, self.engine, state.comm, state.step,
            backend=self.backend)
        mix, obs_final = obs_wire.wrap_mixer(
            mix, state.obs, self.gossip, self.engine, self.backend,
            state.comm, state.step)
        x_new = jax.tree.map(lambda mx, u: mx - h.beta * u,
                             mix("x", state.x, 1), state.u)
        x_new = _project_back(self.problem.manifold_map, x_new, h.invsqrt)
        y_new = jax.vmap(self.problem.project_y)(
            mix("y", state.y, 1) + h.eta * state.v)

        loss, (gx, gy) = _euclid_grads(self, x_new, y_new, batch)
        u_new = jax.tree.map(lambda mu, g, gp: mu + g - gp,
                             mix("u", state.u, 1), gx, state.gx_prev)
        v_new = mix("v", state.v, 1) + gy - state.gy_prev
        obs_new = obs_final()
        if self.telemetry is not None:
            self.telemetry.flush_counters(obs_new, state.step + 1)
        new = GTState(x_new, y_new, u_new, v_new, gx, gy, state.step + 1,
                      comm_final(), obs_new)
        return new, _metrics(loss, gx, gy, x_new, y_new, u_new)

    def make_step(self, donate: bool = True):
        return make_obs_step(self.step, self.telemetry, donate=donate)


class GNSDA(GTGDA):
    """GNSD-A — GT-GDA's skeleton driven by stochastic minibatches."""
    name = "gnsd-a"
    deterministic = False


# ---------------------------------------------------------------------------
# DM-HSGD : hybrid stochastic gradient descent ascent (STORM estimator)
# ---------------------------------------------------------------------------


class HSGDState(NamedTuple):
    x: PyTree
    y: Array
    x_prev: PyTree
    y_prev: Array
    dx: PyTree     # STORM estimator for grad_x
    dy: Array
    step: Array
    comm: Any = None
    obs: Any = None


@dataclasses.dataclass(frozen=True)
class HSGDHyper:
    beta: float = 0.01
    eta: float = 0.05
    bx: float = 0.1      # STORM momentum for x (paper tunes {0.1, 0.9})
    by: float = 0.1
    invsqrt: str = "ns"


class DMHSGD:
    """DM-HSGD (Xian et al. 2021) + Stiefel projection.

    STORM/hybrid estimator: d_t = g(w_t; B_t) + (1-b)(d_{t-1} - g(w_{t-1}; B_t))
    — both evaluations on the SAME batch B_t (two grad passes per step).
    """
    name = "dm-hsgd"
    deterministic = False

    def __init__(self, problem: MinimaxProblem, gossip: GossipSpec,
                 hyper: HSGDHyper = HSGDHyper(), telemetry=None):
        self.problem, self.gossip, self.hyper = problem, gossip, hyper
        self.backend = comms_layer.resolve_backend(gossip)
        self.engine = comms_layer.maybe_engine(gossip, backend=self.backend)
        if self.engine is not None:
            # elastic join protocol: project rejoined x through the geometry
            self.engine.register_manifolds({"x": problem.manifold_map})
        self.telemetry = telemetry if telemetry is not None \
            and telemetry.enabled else None

    def init(self, x0: PyTree, y0: Array, batch0: Any) -> HSGDState:
        x0, y0 = _strong(x0), _strong(y0)
        _, (gx, gy) = _euclid_grads(self, x0, y0, batch0)
        comm0 = comms_layer.maybe_init_state(
            self.engine, {"x": x0, "y": y0, "u": gx, "v": gy})
        obs0 = self.telemetry.init_counters() if self.telemetry else None
        return HSGDState(x0, y0, _copy_tree(x0), jnp.copy(y0), gx, gy,
                         jnp.zeros((), jnp.int32), comm0, obs0)

    def step(self, state: HSGDState, batch: Any) -> tuple[HSGDState, StepMetrics]:
        h = self.hyper
        mix, comm_final = comms_layer.make_mixer(
            self.gossip, self.engine, state.comm, state.step,
            backend=self.backend)
        mix, obs_final = obs_wire.wrap_mixer(
            mix, state.obs, self.gossip, self.engine, self.backend,
            state.comm, state.step)
        loss, (gx_cur, gy_cur) = _euclid_grads(self, state.x, state.y, batch)
        _, (gx_old, gy_old) = _euclid_grads(self, state.x_prev, state.y_prev, batch)

        dx = jax.tree.map(lambda g, go, d: g + (1.0 - h.bx) * (d - go),
                          gx_cur, gx_old, state.dx)
        dy = gy_cur + (1.0 - h.by) * (state.dy - gy_old)
        dx = mix("u", dx, 1)
        dy = mix("v", dy, 1)

        x_new = jax.tree.map(lambda mx, d: mx - h.beta * d,
                             mix("x", state.x, 1), dx)
        x_new = _project_back(self.problem.manifold_map, x_new, h.invsqrt)
        y_new = jax.vmap(self.problem.project_y)(
            mix("y", state.y, 1) + h.eta * dy)

        obs_new = obs_final()
        if self.telemetry is not None:
            self.telemetry.flush_counters(obs_new, state.step + 1)
        new = HSGDState(x_new, y_new, state.x, state.y, dx, dy, state.step + 1,
                        comm_final(), obs_new)
        return new, _metrics(loss, gx_cur, gy_cur, x_new, y_new, dx)

    def make_step(self, donate: bool = True):
        return make_obs_step(self.step, self.telemetry, donate=donate)


# ---------------------------------------------------------------------------
# GT-SRVR : SPIDER-style recursive variance reduction + gradient tracking
# ---------------------------------------------------------------------------


class SRVRState(NamedTuple):
    x: PyTree
    y: Array
    x_prev: PyTree
    y_prev: Array
    gx_est: PyTree   # recursive estimator
    gy_est: Array
    u: PyTree        # gradient tracker on the estimator
    v: Array
    gx_est_prev: PyTree
    gy_est_prev: Array
    step: Array
    comm: Any = None
    obs: Any = None


@dataclasses.dataclass(frozen=True)
class SRVRHyper:
    beta: float = 0.01
    eta: float = 0.05
    q: int = 16          # anchor period (full/large batch every q steps)
    invsqrt: str = "ns"


class GTSRVR:
    """GT-SRVR (Zhang et al. 2021) + Stiefel projection.

    ``anchor_step`` refreshes the estimator with a large (anchor) batch;
    ``step`` applies the SPIDER recursion with same-batch grad differences.
    The driver alternates: anchor every ``hyper.q`` steps.
    """
    name = "gt-srvr"
    deterministic = False

    def __init__(self, problem: MinimaxProblem, gossip: GossipSpec,
                 hyper: SRVRHyper = SRVRHyper(), telemetry=None):
        self.problem, self.gossip, self.hyper = problem, gossip, hyper
        self.backend = comms_layer.resolve_backend(gossip)
        self.engine = comms_layer.maybe_engine(gossip, backend=self.backend)
        if self.engine is not None:
            # elastic join protocol: project rejoined x through the geometry
            self.engine.register_manifolds({"x": problem.manifold_map})
        self.telemetry = telemetry if telemetry is not None \
            and telemetry.enabled else None

    def init(self, x0: PyTree, y0: Array, anchor_batch: Any) -> SRVRState:
        x0, y0 = _strong(x0), _strong(y0)
        _, (gx, gy) = _euclid_grads(self, x0, y0, anchor_batch)
        cp = _copy_tree
        comm0 = comms_layer.maybe_init_state(
            self.engine, {"x": x0, "y": y0, "u": gx, "v": gy})
        obs0 = self.telemetry.init_counters() if self.telemetry else None
        return SRVRState(x0, y0, cp(x0), jnp.copy(y0), gx, gy,
                         cp(gx), jnp.copy(gy), cp(gx), jnp.copy(gy),
                         jnp.zeros((), jnp.int32), comm0, obs0)

    def _update_params(self, state: SRVRState, gx_est, gy_est):
        h = self.hyper
        mix, comm_final = comms_layer.make_mixer(
            self.gossip, self.engine, state.comm, state.step,
            backend=self.backend)
        mix, obs_final = obs_wire.wrap_mixer(
            mix, state.obs, self.gossip, self.engine, self.backend,
            state.comm, state.step)
        u_new = jax.tree.map(lambda mu, g, gp: mu + g - gp,
                             mix("u", state.u, 1), gx_est, state.gx_est_prev)
        v_new = mix("v", state.v, 1) + gy_est - state.gy_est_prev
        x_new = jax.tree.map(lambda mx, u: mx - h.beta * u,
                             mix("x", state.x, 1), u_new)
        x_new = _project_back(self.problem.manifold_map, x_new, h.invsqrt)
        y_new = jax.vmap(self.problem.project_y)(
            mix("y", state.y, 1) + h.eta * v_new)
        obs_new = obs_final()
        if self.telemetry is not None:
            self.telemetry.flush_counters(obs_new, state.step + 1)
        return x_new, y_new, u_new, v_new, comm_final(), obs_new

    def anchor_step(self, state: SRVRState, anchor_batch: Any):
        loss, (gx, gy) = _euclid_grads(self, state.x, state.y, anchor_batch)
        x_new, y_new, u_new, v_new, comm, obs = self._update_params(state, gx, gy)
        new = SRVRState(x_new, y_new, state.x, state.y, gx, gy, u_new, v_new,
                        gx, gy, state.step + 1, comm, obs)
        return new, _metrics(loss, gx, gy, x_new, y_new, u_new)

    def step(self, state: SRVRState, batch: Any):
        loss, (gx_cur, gy_cur) = _euclid_grads(self, state.x, state.y, batch)
        _, (gx_old, gy_old) = _euclid_grads(self, state.x_prev,
                                            state.y_prev, batch)
        gx_est = jax.tree.map(lambda g, go, e: e + g - go,
                              gx_cur, gx_old, state.gx_est)
        gy_est = state.gy_est + gy_cur - gy_old
        x_new, y_new, u_new, v_new, comm, obs = self._update_params(
            state, gx_est, gy_est)
        new = SRVRState(x_new, y_new, state.x, state.y, gx_est, gy_est,
                        u_new, v_new, gx_est, gy_est, state.step + 1, comm, obs)
        return new, _metrics(loss, gx_cur, gy_cur, x_new, y_new, u_new)

    def make_step(self, donate: bool = True):
        import itertools
        shared = itertools.count(1)   # one flush cadence across both phases
        return (make_obs_step(self.step, self.telemetry, donate=donate,
                              counter=shared),
                make_obs_step(self.anchor_step, self.telemetry, donate=donate,
                              counter=shared))


ALL_BASELINES = {c.name: c for c in (GTGDA, GNSDA, DMHSGD, GTSRVR)}
