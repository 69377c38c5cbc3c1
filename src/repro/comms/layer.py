"""The comms engine: compressed, fault-tolerant gossip with error feedback.

``CommEngine`` owns everything between an optimizer's ``mix`` call and the
wire.  One compressed gossip round for a slot (``x``/``y``/``u``/``v``) is
the CHOCO scheme:

    q_i      = C(x_i - x_hat_i)          # the only thing transmitted
    x_hat_i += q_i                       # every replica folds the payload
    x_i     += gamma * ([W_t^s x_hat]_i - x_hat_i)

With the identity compressor and ``gamma = 1`` this reduces exactly to
``x <- W^s x``; with a contractive/unbiased compressor the hat memory keeps
the *error feedback* residual in the loop so consensus error still goes to
zero (naive quantized gossip — ``error_feedback=False`` — plateaus at the
compressor's noise floor instead).

The hop itself runs through :class:`repro.comms.channel.ChannelModel`
(drops / stragglers / schedules); a trivial channel takes the exact
``mix_ring`` path.  For int8 payloads on a clean ring the first hop is the
fused Pallas ``quant_mix`` kernel: ``W(hat + dq(q)) = W hat + [dequantize +
3-way combine of the int8 wire buffers]``.

*How* any of these hops execute — stacked roll/einsum over leaf axis 0, or
``shard_map``/``ppermute`` neighbour exchange over the mesh's node axis —
is the engine's :class:`repro.comms.backend.MixBackend`; every wire touch
in this module routes through it, so EF-int8 gossip and the fused hop work
identically under both layouts.

With ``gamma_mode="adaptive"`` the consensus step is derived from the
compressor's tracked contraction delta (see :meth:`CommEngine._gamma`)
instead of the ``CommSpec.gamma`` constant.

Optimizers thread one :class:`CommState` pytree leaf through their jitted
step; :func:`make_mixer` packages the slot-keyed routing so the four
baselines and DRGDA/DRSGDA share the integration shim.
"""
from __future__ import annotations

import zlib
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.comms.backend import MixBackend, resolve_backend
from repro.comms.channel import ChannelModel
from repro.comms.compress import (Int8Stochastic, compress_tree,
                                  make_compressor, tree_bits,
                                  tree_param_count)
from repro.comms.spec import CommSpec
from repro.obs import trace as obs_trace

Array = jax.Array
PyTree = Any


class CommState(NamedTuple):
    """Per-node communication memory, carried as one optimizer-state leaf."""
    hats: dict[str, PyTree]   # CHOCO public copies, one per mixed slot
    key: Array                # base PRNG for quantization + channel faults
    # per-slot EMA of the compressor's empirical contraction delta
    # (E||C(r) - r||^2 <= (1 - delta)||r||^2); only tracked when
    # CommSpec.gamma_mode == "adaptive"
    deltas: Any = None
    # elastic execution-mode membership (repro.comms.elastic.Membership);
    # None outside elastic mode so existing states keep their treedef
    elastic: Any = None


def _salt(slot: str) -> int:
    return zlib.crc32(slot.encode()) & 0x7FFFFFFF


class CommEngine:
    """Static compression + channel machinery for one ``GossipSpec``."""

    def __init__(self, gossip, backend: Optional[MixBackend] = None):
        comm: Optional[CommSpec] = gossip.comm
        assert comm is not None and comm.enabled, \
            "CommEngine requires an enabled GossipSpec.comm"
        self._setup(gossip, comm, backend)

    def _setup(self, gossip, comm: CommSpec,
               backend: Optional[MixBackend]) -> None:
        """Shared constructor body — ``ElasticEngine`` calls this with a
        substitute (disabled) ``CommSpec`` when the gossip spec carries no
        comm config of its own."""
        self.gossip = gossip
        self.comm = comm
        self.compressor = make_compressor(comm)
        self.channel = ChannelModel.for_gossip(gossip, comm)
        # how hops execute: stacked roll/einsum or shard_map ppermute —
        # every wire touch below goes through this strategy object
        self.backend: MixBackend = backend if backend is not None \
            else resolve_backend(gossip)
        # slot -> manifold map, registered by the optimizer so the elastic
        # join protocol can project re-initialized slots; unused here
        self.manifolds: dict[str, Any] = {}

    def register_manifolds(self, maps: dict[str, Any]) -> None:
        """Record per-slot manifold maps (``{"x": problem.manifold_map}``).
        The base engine never reads them; the elastic engine projects a
        rejoining node's consensus-mean re-init through them."""
        self.manifolds.update({k: v for k, v in maps.items() if v is not None})

    # -- state --------------------------------------------------------------

    def init_state(self, slots: dict[str, PyTree]) -> CommState:
        # channel-only configs never read the CHOCO memory — don't carry
        # model-sized dead buffers through every donated optimizer step
        hats = ({name: jax.tree.map(jnp.zeros_like, tree)
                 for name, tree in slots.items()}
                if self.comm.compressed else {})
        deltas = ({name: jnp.ones((), jnp.float32) for name in slots}
                  if self.comm.compressed and self.comm.adaptive_gamma
                  else None)
        return CommState(hats=hats, key=jax.random.PRNGKey(self.comm.seed),
                         deltas=deltas)

    # -- accounting (static, pure Python over shapes) -----------------------

    def bits_per_mix(self, tree: PyTree) -> float:
        return tree_bits(self.compressor, tree)

    def bits_per_param(self, tree: PyTree) -> float:
        return tree_bits(self.compressor, tree) / max(tree_param_count(tree), 1)

    def wire_round_bytes(self, tree: PyTree, steps: int
                         ) -> tuple[float, float]:
        """(wire, raw) bytes for one ``steps``-hop gossip round over a clean
        channel — the telemetry wire counters' static inputs.

        ``raw`` is ``steps`` full-precision hops of the backend's
        ``est_hop_bytes`` oracle.  A compressed round ships the payload
        ``C(x - x_hat)`` to every neighbour once (2 on a ring, n-1 dense)
        plus ``steps - 1`` hat hops — full-precision under
        ``quant_hops="first"``, int8 (+ per-row scales) when the all-hop
        schedule requantizes at every hop — exactly how ``_gossip_hats``
        executes.  wire/raw is the round's realized compression ratio.
        """
        per_hop = self.backend.est_hop_bytes(self.gossip, tree)
        raw = float(steps) * per_hop
        if not self.comm.compressed:
            return raw, raw
        payload = tree_bits(self.compressor, tree) / 8.0
        fanout = 2.0 if self.gossip.topology == "ring" \
            else float(max(self.gossip.n_nodes - 1, 1))
        per_tail = per_hop
        if self.comm.quant_hops == "all" and self._use_fused_hop():
            per_tail = self.backend.est_quant_hop_bytes(self.gossip, tree)
        wire = fanout * payload + float(max(steps - 1, 0)) * per_tail
        return wire, raw

    def _keys(self, state: CommState, slot: str, rnd: Array | int
              ) -> tuple[Array, Array]:
        """(k_quant, k_chan) for one round — the single derivation both the
        mix and the telemetry accounting (``chan_key``) share."""
        key = jax.random.fold_in(
            jax.random.fold_in(state.key, _salt(slot)), rnd)
        return tuple(jax.random.split(key))

    def chan_key(self, state: CommState, slot: str, rnd: Array | int) -> Array:
        return self._keys(state, slot, rnd)[1]

    # -- one compressed gossip round ---------------------------------------

    def mix(self, state: CommState, slot: str, tree: PyTree, *,
            steps: Optional[int] = None, rnd: Array | int = 0
            ) -> tuple[PyTree, CommState]:
        s = self.gossip.k if steps is None else steps
        if self.gossip.n_nodes == 1 or s == 0:
            return tree, state
        k_quant, k_chan = self._keys(state, slot, rnd)

        if not self.comm.compressed:
            # channel-only: full-precision payload over the faulty links
            return (self.backend.mix_channel(self.gossip, self.channel, tree,
                                             rnd, k_chan, steps=s), state)

        hat = state.hats[slot]
        source = (jax.tree.map(lambda x, h: x - h, tree, hat)
                  if self.comm.error_feedback else tree)
        payload, wire = self._compress(k_quant, source)
        hat_new = (jax.tree.map(lambda h, p: h + p, hat, payload)
                   if self.comm.error_feedback else payload)
        mixed_hat = self._gossip_hats(hat_new, hat, wire, s, rnd, k_chan)
        gamma, deltas = self._gamma(state, slot, source, payload)
        mixed = jax.tree.map(lambda x, mh, h: x + gamma * (mh - h),
                             tree, mixed_hat, hat_new)
        new_hats = dict(state.hats)
        new_hats[slot] = hat_new
        return mixed, CommState(hats=new_hats, key=state.key, deltas=deltas)

    def _gamma(self, state: CommState, slot: str, source: PyTree,
               payload: PyTree):
        """Consensus step size on the hats.

        ``fixed``: the hand-tuned ``CommSpec.gamma`` constant.  ``adaptive``:
        track the compressor's empirical contraction
        ``delta = 1 - ||C(r) - r||^2 / ||r||^2`` per slot as an EMA and step
        with it — CHOCO's admissible step scales with delta, so a lossless
        wire recovers gamma -> 1 and an aggressive compressor automatically
        backs off instead of trusting a config constant.
        """
        if not self.comm.adaptive_gamma:
            return self.comm.gamma, state.deltas
        src_sq = sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                     for l in jax.tree.leaves(source))
        err_sq = sum(jnp.sum(jnp.square((p - s).astype(jnp.float32)))
                     for p, s in zip(jax.tree.leaves(payload),
                                     jax.tree.leaves(source)))
        obs = jnp.clip(1.0 - err_sq / (src_sq + 1e-30), 0.0, 1.0)
        ema = self.comm.gamma_ema
        delta = ema * state.deltas[slot] + (1.0 - ema) * obs
        gamma = jnp.clip(delta, self.comm.gamma_min, 1.0)
        deltas = dict(state.deltas)
        deltas[slot] = delta
        return gamma, deltas

    # -- internals ----------------------------------------------------------

    def _compress(self, key: Array, tree: PyTree):
        """Leaf-wise compression; for int8 also returns the raw wire buffers
        (q, scale) so the fused kernel can consume them."""
        comp = self.compressor
        if isinstance(comp, Int8Stochastic):
            # same per-leaf key decorrelation as compress_tree, but keeping
            # the int8 payloads around for the fused quant_mix hop
            leaves, treedef = jax.tree.flatten(tree)
            keys = [jax.random.fold_in(key, i) for i in range(len(leaves))]
            qs, scales = zip(*(comp.quantize(k, l)
                               for k, l in zip(keys, leaves)))
            payload = jax.tree.unflatten(
                treedef, [comp.dequantize(q, sc, l.dtype)
                          for q, sc, l in zip(qs, scales, leaves)])
            return payload, (list(qs), list(scales), treedef)
        return compress_tree(comp, key, tree), None

    def _use_fused_hop(self) -> bool:
        return (self.comm.fuse_kernel and self.channel.trivial
                and self.gossip.topology == "ring"
                and isinstance(self.compressor, Int8Stochastic))

    def _gossip_hats(self, hat_new: PyTree, hat_old: PyTree, wire,
                     s: int, rnd, k_chan: Array) -> PyTree:
        if wire is not None and self._use_fused_hop():
            qs, scales, treedef = wire
            base = self.backend.mix_hop(self.gossip, hat_old) \
                if self.comm.error_feedback else None

            def hop(q: Array, scale: Array, like: Array) -> Array:
                n = q.shape[0]
                out = self.backend.quant_ring_hop(
                    self.gossip, q.reshape(n, -1), scale.reshape(n, 1),
                    out_dtype=like.dtype)
                return out.reshape(like.shape)

            leaves_old = jax.tree.leaves(hat_old)
            wire_mix = jax.tree.unflatten(
                treedef, [hop(q, sc, l)
                          for q, sc, l in zip(qs, scales, leaves_old)])
            first = (jax.tree.map(lambda b, w: b + w, base, wire_mix)
                     if base is not None else wire_mix)
            if s <= 1:
                return first
            if self.comm.quant_hops == "all":
                # tail hops stay on the int8 wire: every hop requantizes
                # deterministically (the shard_map backend fuses the whole
                # chain into one multi_hop_mix_quant launch per leaf)
                return jax.tree.map(
                    lambda l: self.backend.quant_ring_hops(
                        self.gossip, l, s - 1, out_dtype=l.dtype),
                    first)
            return self.backend.mix(self.gossip, first, steps=s - 1)
        return self.backend.mix_channel(self.gossip, self.channel, hat_new,
                                        rnd, k_chan, steps=s)


# ---------------------------------------------------------------------------
# optimizer shims
# ---------------------------------------------------------------------------


def maybe_engine(gossip,
                 backend: Optional[MixBackend] = None) -> Optional[CommEngine]:
    elastic = getattr(gossip, "elastic", None)
    if elastic is not None and elastic.enabled:
        # lazy: elastic.py imports this module at its top level
        from repro.comms.elastic import ElasticEngine
        return ElasticEngine(gossip, backend=backend)
    comm = getattr(gossip, "comm", None)
    if comm is not None and comm.enabled:
        return CommEngine(gossip, backend=backend)
    return None


def maybe_init_state(engine: Optional[CommEngine],
                     slots: dict[str, PyTree]) -> Optional[CommState]:
    return engine.init_state(slots) if engine is not None else None


def make_mixer(gossip, engine: Optional[CommEngine],
               comm_state: Optional[CommState], rnd: Array | int,
               backend: Optional[MixBackend] = None
               ) -> tuple[Callable[[str, PyTree, int], PyTree],
                          Callable[[], Optional[CommState]]]:
    """Slot-keyed mix router for one optimizer step.

    Returns ``(mix, finalize)``: ``mix(slot, tree, steps)`` routes through
    the comms engine when one is configured (threading the CommState) and
    through the exact path otherwise, on every backend inside the device
    scope ``gda.mix``; ``finalize()`` yields the CommState to store in the
    next optimizer state.  ``backend`` overrides how exact hops
    execute (an engine carries its own backend); default is the gossip
    spec's resolved backend.
    """
    box = {"cs": comm_state}
    exact = backend if backend is not None else resolve_backend(gossip)

    def mix(slot: str, tree: PyTree, steps: int) -> PyTree:
        with obs_trace.scope("gda.mix"):
            if engine is None:
                return exact.mix(gossip, tree, steps)
            out, box["cs"] = engine.mix(box["cs"], slot, tree,
                                        steps=steps, rnd=rnd)
            return out

    return mix, lambda: box["cs"]
