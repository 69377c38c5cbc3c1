"""DRGDA / DRSGDA — Algorithms 1 & 2 of Wu, Hu & Huang (AAAI 2023).

One jitted SPMD step implements, for every node i (leading axis of every
state leaf, vmapped / sharded over the mesh ``node`` axis):

  x_{t+1}^i = R_{x_t^i}( P_{T_x}( alpha * [W^k x_t]_i ) - beta * P_{T_x}(u_t^i) )
  y_{t+1}^i = Proj_Y( [W^k y_t]_i + eta * v_t^i )
  u_{t+1}^i = [W^k u_t]_i + grad_x f_i(x_{t+1}, y_{t+1}; B_{t+1})
                          - grad_x f_i(x_t,     y_t;     B_t)
  v_{t+1}^i = [W   v_t]_i + grad_y f_i(x_{t+1}, y_{t+1}; B_{t+1})
                          - grad_y f_i(x_t,     y_t;     B_t)

Deterministic (DRGDA) and stochastic (DRSGDA) share this skeleton — the only
difference is whether ``batch`` is the node's full local dataset every step
(Alg. 1) or a fresh minibatch (Alg. 2).  Both are exposed as named classes so
experiments read like the paper.

Faithfulness notes
------------------
* Trackers ``u`` are mixed with W^k (step 6) but ``v`` with a single W hop
  (step 7) — we follow the algorithm as printed.
* ``grad_x f_i`` entering the tracker is the Riemannian gradient at its own
  base point (tangent-projected once, at evaluation); the tracker itself is
  mixed in ambient coordinates and re-projected only inside the x-update
  (step 4) — exactly the paper's "project only at step 4" remark.
* The x-update is geometry-generic: each leaf's manifold (from
  ``MinimaxProblem.manifold_map``, see :mod:`repro.geometry`) supplies the
  tangent projection, the consensus direction and the retraction.  Stiefel
  leaves reproduce the paper's update exactly; Euclidean leaves collapse to
  the specialization x <- x + alpha([Wx]_i - x) - beta u (GT-GDA's update;
  with alpha = 1 the classic gradient-tracking consensus step); Grassmann /
  oblique / sphere leaves run the same skeleton with their own geometry.
* ``GDAHyper.retraction="polar_fused"`` routes Stiefel leaves through the
  fused Pallas retraction kernel (tangent-project + Gram + Newton--Schulz +
  apply in one VMEM pass): the ambient direction alpha*[W^k x]_i - beta*u
  is handed to the kernel, which projects internally — valid because the
  tangent projection is linear and P_x(x) = 0.
* The y-update adds an explicit projection onto Y (the paper states
  y in Y compact convex; its analysis needs feasible iterates).

Device scopes (:func:`repro.obs.trace.scope`) name the step's phases in
the compiled program: ``gda.grad`` (model forward and backward, tangent
projection of the gradient), ``gda.retract`` (descent direction and
retraction), ``gda.track`` (y ascent and projection, ``u``/``v``
trackers), ``gda.metrics`` (``StepMetrics``) and, innermost wherever a
mix runs, ``gda.mix``.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.comms import layer as comms_layer
from repro.core.gossip import GossipSpec
from repro.core.minimax import MinimaxProblem
from repro.obs import trace as obs_trace
from repro.obs import wire as obs_wire

Array = jax.Array
PyTree = Any


@dataclasses.dataclass(frozen=True)
class GDAHyper:
    """Tuning parameters {alpha, beta, eta} of Algorithms 1/2."""
    alpha: float = 0.5          # consensus step size (<= 1/M, M retraction bound)
    beta: float = 0.01          # descent step size for x
    eta: float = 0.05           # ascent step size for y
    # "polar" (paper default) | "qr" | "cayley" | "polar_fused" (fused
    # Pallas kernel); resolved per leaf — geometries that don't implement
    # the named kind fall back to their own default retraction.
    retraction: str = "polar"
    invsqrt: str = "ns"         # "ns" (TPU, Newton-Schulz) | "eigh" (oracle)
    k_override: Optional[int] = None  # gossip steps; None -> GossipSpec.k


class GDAState(NamedTuple):
    x: PyTree          # node-stacked min parameters (leaf axis 0 = node)
    y: Array           # node-stacked max variable, (n, ...)
    u: PyTree          # gradient tracker for x (ambient coords)
    v: Array           # gradient tracker for y
    gx_prev: PyTree    # last Riemannian grad_x (per node, own batch)
    gy_prev: Array     # last grad_y
    step: Array        # scalar int32
    comm: Any = None   # comms_layer.CommState when GossipSpec.comm is enabled
    obs: Any = None    # packed f32[6] counter leaf when telemetry is enabled


class StepMetrics(NamedTuple):
    loss: Array                # mean local loss at (x_{t+1}, y_{t+1})
    grad_norm_x: Array         # mean ||grad_x f_i||
    grad_norm_y: Array
    consensus_x: Array         # mean_i ||x_i - x_bar||^2 (Euclidean, cheap)
    consensus_y: Array
    tracker_norm_u: Array


class DecentralizedGDA:
    """Shared engine for DRGDA (deterministic) and DRSGDA (stochastic)."""

    #: subclasses override for reporting
    name = "gda"
    deterministic = True

    def __init__(self, problem: MinimaxProblem, gossip: GossipSpec,
                 hyper: GDAHyper = GDAHyper(), telemetry=None):
        from repro.geometry import base as _gbase
        self.problem = problem
        self.gossip = gossip
        self.hyper = hyper
        # typo guard: per-leaf resolution falls back silently (one config
        # string drives mixed pytrees), so reject globally-unknown names here
        _gbase.check_retraction_name(hyper.retraction)
        self.k = hyper.k_override if hyper.k_override is not None else gossip.k
        # how every mix executes (stacked roll/einsum or shard_map ppermute);
        # the optimizer math below never sees the difference
        self.backend = comms_layer.resolve_backend(gossip)
        self.engine = comms_layer.maybe_engine(gossip, backend=self.backend)
        if self.engine is not None:
            # the elastic join protocol projects a rejoining node's
            # consensus-mean x re-init through the problem's geometry
            self.engine.register_manifolds({"x": problem.manifold_map})
        # static config captured by the jitted closure, like the engine;
        # None (or enabled=False) compiles the exact pre-obs program
        self.telemetry = telemetry if telemetry is not None \
            and telemetry.enabled else None

    # -- initialization -----------------------------------------------------
    def init(self, x0: PyTree, y0: Array, batch0: Any) -> GDAState:
        """x0/y0 node-stacked; u_0 = grad_x f_i(x_0, y_0; B_0), v_0 likewise.

        ``u``/``gx_prev`` (and ``v``/``gy_prev``) start equal but must be
        DISTINCT buffers — the jitted step donates the whole state, and XLA
        rejects donating one buffer twice."""
        x0, y0 = _strong(x0), _strong(y0)
        rgx, gy = self.backend.node_map(self.problem.rgrads)(x0, y0, batch0)
        comm0 = comms_layer.maybe_init_state(
            self.engine, {"x": x0, "y": y0, "u": rgx, "v": gy})
        obs0 = self.telemetry.init_counters() if self.telemetry else None
        return GDAState(x=x0, y=y0, u=rgx, v=gy,
                        gx_prev=_copy_tree(rgx), gy_prev=jnp.copy(gy),
                        step=jnp.zeros((), jnp.int32), comm=comm0, obs=obs0)

    # -- one step -----------------------------------------------------------
    def step(self, state: GDAState, batch: Any) -> tuple[GDAState, StepMetrics]:
        h, k = self.hyper, self.k
        mix, comm_final = comms_layer.make_mixer(
            self.gossip, self.engine, state.comm, state.step,
            backend=self.backend)
        mix, obs_final = obs_wire.wrap_mixer(
            mix, state.obs, self.gossip, self.engine, self.backend,
            state.comm, state.step)

        # ---- step 4: Riemannian consensus + tracked descent on x ----------
        mixed_x = mix("x", state.x, k)

        def leaf_update(m, x, mx, u):
            kind = m.resolve_retraction(h.retraction)
            if kind == m.fused_retraction:
                # fused path: hand the AMBIENT direction to the kernel — the
                # tangent projection is linear with P_x(x) = 0, so
                # P(alpha*mx - beta*u) == alpha*P(mx) - beta*P(u).
                return m.retract(x, h.alpha * mx - h.beta * u, kind)
            return m.descent_update(x, mx, u, alpha=h.alpha, beta=h.beta,
                                    kind=kind,
                                    **({"method": h.invsqrt}
                                       if kind == "polar" else {}))

        with obs_trace.scope("gda.retract"):
            x_new = jax.tree.map(leaf_update, self.problem.manifold_map,
                                 state.x, mixed_x, state.u)

        # ---- step 5: Euclidean consensus + tracked ascent on y ------------
        with obs_trace.scope("gda.track"):
            y_new = jax.vmap(self.problem.project_y)(
                mix("y", state.y, k) + h.eta * state.v)

        # ---- steps 6/7: gradient tracking ----------------------------------
        with obs_trace.scope("gda.grad"):
            (loss_new, (rgx_new, gy_new)) = _vmapped_loss_and_rgrads(
                self.problem, x_new, y_new, batch, self.backend.node_map)

        with obs_trace.scope("gda.track"):
            u_new = jax.tree.map(lambda mu, g, gp: mu + g - gp,
                                 mix("u", state.u, k), rgx_new,
                                 state.gx_prev)
            v_new = mix("v", state.v, 1) + gy_new - state.gy_prev

        obs_new = obs_final()
        if self.telemetry is not None:
            self.telemetry.flush_counters(obs_new, state.step + 1)
        new_state = GDAState(x=x_new, y=y_new, u=u_new, v=v_new,
                             gx_prev=rgx_new, gy_prev=gy_new,
                             step=state.step + 1, comm=comm_final(),
                             obs=obs_new)
        with obs_trace.scope("gda.metrics"):
            metrics = StepMetrics(
                loss=jnp.mean(loss_new),
                grad_norm_x=_tree_mean_norm(rgx_new),
                grad_norm_y=jnp.mean(jnp.linalg.norm(
                    gy_new.reshape(gy_new.shape[0], -1), axis=-1)),
                consensus_x=_tree_consensus(x_new),
                consensus_y=_consensus(y_new),
                tracker_norm_u=_tree_mean_norm(u_new),
            )
        return new_state, metrics

    def make_step(self, donate: bool = True) -> Callable:
        """jitted step closure (state, batch) -> (state, metrics)."""
        return make_obs_step(self.step, self.telemetry, donate=donate)


class DRGDA(DecentralizedGDA):
    """Algorithm 1 — deterministic decentralized Riemannian GDA.

    Call :meth:`step` with each node's **full local dataset** every
    iteration.  Gradient complexity O(eps^-2) (Theorem 1).
    """
    name = "drgda"
    deterministic = True


class DRSGDA(DecentralizedGDA):
    """Algorithm 2 — stochastic decentralized Riemannian GDA.

    Call :meth:`step` with a fresh i.i.d. minibatch B_{t+1} per node each
    iteration.  Sample complexity O(eps^-4) (Theorem 2).
    """
    name = "drsgda"
    deterministic = False


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def make_obs_step(step_fn: Callable, telemetry, donate: bool = True,
                  counter=None) -> Callable:
    """jit ``step_fn`` with the telemetry flush hoisted to host cadence.

    A jitted program containing an io_callback loses fast-path dispatch on
    EVERY call, even when a ``lax.cond`` guards the callback — so with
    telemetry on we compile two executables from the same trace: a quiet
    effect-free one (ordinary steps, async dispatch intact) and a flushing
    one routed to every ``flush_every``-th call by a host-side counter.
    Both are fully fused; the math is identical (test-enforced bit
    identity).  ``counter`` shares one cadence across multiple step
    functions (GT-SRVR's step + anchor_step).
    """
    donate_args = (0,) if donate else ()
    if telemetry is None:
        return jax.jit(step_fn, donate_argnums=donate_args)

    def stepper(state, batch, flush: bool):
        with telemetry.flush_mode("always" if flush else "never"):
            return step_fn(state, batch)

    jitted = jax.jit(stepper, static_argnums=(2,), donate_argnums=donate_args)
    counter = counter if counter is not None else itertools.count(1)

    def run(state, batch):
        # flush on the very first call too: it compiles the flushing
        # executable up front (no mid-run compile stall at step flush_every)
        # and doubles as a telemetry-alive record
        n = next(counter)
        return jitted(state, batch,
                      n == 1 or n % telemetry.flush_every == 0)

    return run


def _copy_tree(tree: PyTree) -> PyTree:
    return jax.tree.map(jnp.copy, tree)


def _strong(tree: PyTree) -> PyTree:
    """Strip weak types from user-supplied init leaves (e.g. a
    ``jnp.full(..., 1.0/G)`` y0).  A weak-typed leaf in the init state gives
    the jitted step different input avals on call one vs call two — i.e. a
    silent second compile mid-training."""
    return jax.tree.map(lambda l: jnp.asarray(l).astype(jnp.asarray(l).dtype),
                        tree)


def _vmapped_loss_and_rgrads(problem: MinimaxProblem, x, y, batch,
                             node_map=jax.vmap):
    def one(xi, yi, bi):
        loss, (gx, gy) = jax.value_and_grad(problem.loss_fn, argnums=(0, 1))(xi, yi, bi)
        rgx = jax.tree.map(lambda m, xl, gl: m.tangent_project(xl, gl),
                           problem.manifold_map, xi, gx)
        return loss, (rgx, gy)
    return node_map(one)(x, y, batch)


def _tree_mean_norm(tree: PyTree) -> Array:
    sq = sum(jnp.sum(l.reshape(l.shape[0], -1) ** 2, axis=-1)
             for l in jax.tree.leaves(tree))
    return jnp.mean(jnp.sqrt(sq))


def _consensus(x: Array) -> Array:
    xb = jnp.mean(x, axis=0, keepdims=True)
    return jnp.mean(jnp.sum((x - xb).reshape(x.shape[0], -1) ** 2, axis=-1))


def _tree_consensus(tree: PyTree) -> Array:
    return sum(_consensus(l) for l in jax.tree.leaves(tree))


def broadcast_to_nodes(tree: PyTree, n: int) -> PyTree:
    """Replicate single-node params to the node-stacked layout (common init:
    'initialize local model parameters ... with the same points')."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n, *x.shape)), tree)
