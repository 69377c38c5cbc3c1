"""Pluggable mix backends: how one gossip hop actually executes.

Every layer above this module (``GossipSpec.mix``, ``CommEngine``, the
optimizers) describes *what* to mix — ``x_i <- [W x]_i`` over node-stacked
pytrees.  A :class:`MixBackend` decides *how*:

* :class:`StackedBackend` — the node axis is leaf axis 0 of every array on
  every device.  One hop is ``jnp.roll``/dense einsum over that axis, exactly
  the semantics the repo has always had; XLA may or may not lower the roll to
  a ``collective-permute`` depending on sharding.  Default on CPU and in
  tests; bit-exact reference for the others.
* :class:`ShardMapBackend` — the node axis is a *device mesh axis*.  Leaves
  are ``shard_map``-ped over it, so each device holds a contiguous block of
  ``b = n_nodes / axis_size`` node rows, and one ring hop exchanges only the
  two *edge rows* of each block via ``jax.lax.ppermute`` (int8 payloads for
  the fused compressed hop), followed by the local ``ring_mix`` combine —
  the Pallas ``ring_mix_flat`` kernel on TPU, its jnp oracle elsewhere.  The
  k>1 schedule is double-buffered: hop ``t+1``'s edge rows are computed first
  and put on the wire while hop ``t``'s interior rows combine, so the permute
  latency hides behind the elementwise work.  ``ChannelModel`` faults become
  per-link weight vectors (three diagonals of ``W_t``) applied on the shard —
  never a dense ``(n, n)`` einsum against model-sized data.

Per-row arithmetic is kept *expression-identical* between the two backends
(``wc * x_i + ws * (x_{i-1} + x_{i+1})`` for the ring, the same full-shape
einsum for dense topologies), so a clean-channel fp32 mix is bit-identical
across backends — ``tests/test_mix_backend_equiv.py`` asserts exactly that
under 8 forced host devices.

Topology matrices stay in :mod:`repro.core.gossip` as the spectral-gap
oracle; backends only consume ``spec.matrix`` / ``spec.self_weight``.

NOTE: ``repro.core.gossip`` is imported lazily inside methods — the comms
package must stay import-independent of ``repro.core`` (same convention as
``channel.py``).
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.comms import api

Array = jax.Array
PyTree = Any

_FWD = 1   # ring direction conventions: row i's left neighbour is i-1
_BWD = -1


# ---------------------------------------------------------------------------
# protocol
# ---------------------------------------------------------------------------


@runtime_checkable
class MixBackend(Protocol):
    """Strategy interface between the gossip math and the wire.

    All methods take the ``GossipSpec`` explicitly so one backend object
    (which may hold a device mesh) can serve any number of specs.
    """

    name: str

    def mix(self, spec, tree: PyTree, steps: int) -> PyTree:
        """Exact ``x <- W^steps x`` over a node-stacked pytree."""
        ...

    def mix_hop(self, spec, tree: PyTree) -> PyTree:
        """One exact ``W`` hop (``mix`` with ``steps=1``)."""
        ...

    def mix_channel(self, spec, channel, tree: PyTree, rnd, key: Array,
                    steps: int) -> PyTree:
        """``steps`` hops through a :class:`repro.comms.channel.ChannelModel`
        (link drops / stragglers / schedules)."""
        ...

    def mix_wt(self, spec, tree: PyTree, wt: Array, *,
               steps: int = 1) -> PyTree:
        """``steps`` hops of one explicit effective mixing matrix ``wt``
        (n, n) — the elastic engine's realized W_t, shared across the hops
        of a round.  Per-row math must match ``mix_channel``'s faulty-round
        expression so an elastic round degenerates bit-for-bit to the
        channel path when the realized matrices coincide."""
        ...

    def quant_ring_hop(self, spec, q: Array, scale: Array, *,
                       out_dtype=jnp.float32) -> Array:
        """Fused compressed ring hop on an int8 payload ``q`` (n, F) with
        per-node scales (n, 1): ``wc*dq(q_i) + ws*(dq(q_{i-1}) + dq(q_{i+1}))``.
        Only the int8 bytes travel."""
        ...

    def quant_ring_hops(self, spec, x: Array, steps: int, *,
                        out_dtype=None) -> Array:
        """``steps`` ring hops on one node-stacked leaf where EVERY hop is
        int8-compressed: each hop deterministically requantizes its input
        (round-to-nearest, per-node max-abs/127 scale) and combines the
        dequantized values — so only int8 bytes (+ one f32 scale per row)
        ever need to travel.  The requantization is part of the *math*, not
        the layout: every backend decodes identical int8 values at every
        hop, so results agree across backends to float-contraction (FMA)
        rounding of the final combines — a few ulps."""
        ...

    def est_hop_bytes(self, spec, tree: PyTree) -> float:
        """Estimated bytes moved device-to-device by one exact hop."""
        ...

    def est_quant_hop_bytes(self, spec, tree: PyTree) -> float:
        """Estimated bytes moved by one int8-compressed hop of the
        ``quant_ring_hops`` schedule (int8 payload + f32 scale per row)."""
        ...

    def node_map(self, fn):
        """``fn`` over one node's slices, mapped over node-stacked pytrees
        (``jax.vmap`` semantics), run where each device's node rows live."""
        ...


# ---------------------------------------------------------------------------
# stacked (reference) backend
# ---------------------------------------------------------------------------


class StackedBackend:
    """Node axis = leaf axis 0 everywhere; the repo's original exact paths.

    ``mesh`` (optional) is the device mesh the node-stacked state is
    sharded over.  Mixes stay whole-array ops that XLA partitions; only
    node-local work that calls a Pallas kernel runs per device shard.
    """

    name = "stacked"

    def __init__(self, mesh: Optional[Mesh] = None,
                 axis: str | Sequence[str] = "node"):
        self.mesh = mesh
        self.axes: tuple[str, ...] = (axis,) if isinstance(axis, str) \
            else tuple(axis)

    def node_map(self, fn):
        return _per_node_shard(self.mesh, self.axes, jax.vmap(fn))

    def mix(self, spec, tree: PyTree, steps: int) -> PyTree:
        from repro.core import gossip as G
        if spec.n_nodes == 1 or steps == 0:
            return tree
        if spec.topology == "ring":
            return G.mix_ring(tree, steps=steps, self_weight=spec.self_weight)
        # W^s built ONCE per call (in float64 numpy, so it constant-folds
        # under jit), not per leaf inside the tree map.
        ws = dense_power(spec, steps)
        return jax.tree.map(
            lambda x: jnp.einsum("ij,j...->i...", ws.astype(x.dtype), x), tree)

    def mix_hop(self, spec, tree: PyTree) -> PyTree:
        return self.mix(spec, tree, steps=1)

    def mix_channel(self, spec, channel, tree: PyTree, rnd, key: Array,
                    steps: int) -> PyTree:
        return channel.mix(tree, rnd, key, steps=steps)

    def mix_wt(self, spec, tree: PyTree, wt: Array, *,
               steps: int = 1) -> PyTree:
        # the identical einsum expression ChannelModel.mix_hop applies to a
        # faulty round, so elastic W_t application is bit-equal to the
        # channel path whenever the matrices are bit-equal
        for _ in range(max(steps, 0)):
            tree = jax.tree.map(
                lambda x: jnp.einsum("ij,j...->i...", wt.astype(x.dtype), x),
                tree)
        return tree

    def quant_ring_hop(self, spec, q: Array, scale: Array, *,
                       out_dtype=jnp.float32) -> Array:
        from repro.kernels import ops
        wc = spec.self_weight
        ws = (1.0 - wc) / 2.0
        combine = _per_node_shard(self.mesh, self.axes, functools.partial(
            ops.quant_mix, w_self=wc, w_side=ws, out_dtype=out_dtype))
        return combine(
            q, jnp.roll(q, 1, 0), jnp.roll(q, -1, 0),
            scale, jnp.roll(scale, 1, 0), jnp.roll(scale, -1, 0))

    def quant_ring_hops(self, spec, x: Array, steps: int, *,
                        out_dtype=None) -> Array:
        """Every hop requantizes deterministically and combines the decoded
        values — the layout-independent oracle of the all-hop compressed
        ``W^k`` schedule (what the shard_map megakernel fuses)."""
        from repro.comms.compress import quantize_det
        out_dtype = x.dtype if out_dtype is None else out_dtype
        n = x.shape[0]
        z = x
        for _ in range(max(steps, 0)):
            q, s = quantize_det(z)
            z = self.quant_ring_hop(
                spec, q.reshape(n, -1), s.reshape(n, 1),
                out_dtype=jnp.float32).reshape(x.shape)
        return z.astype(out_dtype)

    def est_hop_bytes(self, spec, tree: PyTree) -> float:
        total = _tree_bytes(tree)
        if spec.topology == "ring":
            # roll moves every node row one slot in each direction
            return 2.0 * total
        # dense einsum over a sharded node axis lowers to an all-gather:
        # every node row reaches every other node
        return float(spec.n_nodes - 1) * total

    def est_quant_hop_bytes(self, spec, tree: PyTree) -> float:
        total = _quant_tree_bytes(tree)
        if spec.topology == "ring":
            return 2.0 * total
        return float(spec.n_nodes - 1) * total

    def __repr__(self):
        if self.mesh is None:
            return "StackedBackend()"
        return f"StackedBackend(mesh_axes={self.axes})"


# ---------------------------------------------------------------------------
# shard_map (SPMD) backend
# ---------------------------------------------------------------------------


class ShardMapBackend:
    """Node axis = device mesh axis; neighbour-only ``ppermute`` exchange.

    ``axis`` may be one mesh axis name or a tuple (e.g. ``("pod", "node")``
    for multi-pod rings — ``ppermute``/``axis_index`` accept the tuple and
    linearize it row-major, extending the gossip ring across pods).

    Falls back to the stacked paths when the factored axis has a single
    device or ``n_nodes < 3`` (degenerate rings have their own exact
    special cases which a neighbour exchange cannot reproduce bit-for-bit).
    """

    name = "shard_map"

    def __init__(self, mesh: Mesh, axis: str | Sequence[str] = "node",
                 fuse: str = "auto", fuse_depth: Optional[int] = None):
        if fuse not in ("auto", "on", "off"):
            raise ValueError(f"fuse must be auto|on|off, got {fuse!r}")
        self.mesh = mesh
        self.axes: tuple[str, ...] = (axis,) if isinstance(axis, str) \
            else tuple(axis)
        for a in self.axes:
            if a not in mesh.shape:
                raise ValueError(f"mesh {mesh.shape} has no axis {a!r}")
        self.axis_size = int(np.prod([mesh.shape[a] for a in self.axes]))
        self.fuse = fuse
        # hops per megakernel launch (halo width == depth); None = all hops
        # in one launch.  Bounds the halo so a deep W^k schedule on a small
        # block doesn't drown the panel in halo rows.
        self.fuse_depth = fuse_depth
        # "auto" fuses everywhere launch latency dominates: always on TPU
        # (the kernel's target — k launches collapse to 1), but on the
        # CPU/GPU oracle paths only for small rows, where the 2*halo extra
        # panel rows cost less than the k-1 saved collective rounds
        self._fuse_on_big_rows = any(
            d.platform == "tpu" for d in mesh.devices.flat)
        self._stacked = StackedBackend()

    #: "auto" row-size cutoff on non-TPU backends (bytes per node row);
    #: above this the hop-by-hop schedule's smaller working set wins there
    AUTO_FUSE_MAX_ROW_BYTES = 1 << 20

    # -- helpers ------------------------------------------------------------

    @property
    def _axis_name(self):
        return self.axes if len(self.axes) > 1 else self.axes[0]

    @property
    def _pspec(self) -> P:
        return P(self.axes if len(self.axes) > 1 else self.axes[0])

    def _block(self, spec) -> int:
        n, d = spec.n_nodes, self.axis_size
        if n % d:
            raise ValueError(
                f"n_nodes={n} must divide over the {self.axes} mesh axes "
                f"(size {d}) for the shard_map backend")
        return n // d

    def _use_stacked(self, spec) -> bool:
        return self.axis_size == 1 or spec.n_nodes < 3

    def _shmap(self, fn, tree_specs, out_specs=None):
        return jax.shard_map(fn, mesh=self.mesh, in_specs=tree_specs,
                             out_specs=out_specs if out_specs is not None
                             else self._pspec, check_vma=False)

    def node_map(self, fn):
        return _per_node_shard(self.mesh, self.axes, jax.vmap(fn))

    def _perm(self, direction: int):
        d = self.axis_size
        return [(i, (i + direction) % d) for i in range(d)]

    def _perm_shift(self, j: int):
        """Permutation under which every device receives from device i-j
        (send i -> i+j around the ring); ``_perm(d)`` generalized."""
        d = self.axis_size
        return [(i, (i + j) % d) for i in range(d)]

    def _gather_halo(self, x: Array, halo: int) -> Array:
        """Assemble the ``(halo + b + halo, ...)`` megakernel input panel.

        The halo of width ``halo`` on each side is fetched with
        ``ceil(halo/b)`` *independent* shift-j ppermutes per direction —
        they carry no data dependence on each other, so XLA can put all of
        them on the wire concurrently (vs. the unfused schedule's k strictly
        serialized edge exchanges).  Wire bytes are identical to k unfused
        hops: 2*halo rows per device either way.
        """
        ax = self._axis_name
        b = x.shape[0]
        m = -(-halo // b)                          # ppermute shifts per side
        top, bot = [], []
        for j in range(1, m + 1):
            cnt = min(b, halo - (j - 1) * b)       # rows still needed
            top.append(jax.lax.ppermute(x[-cnt:], ax, self._perm_shift(j)))
            bot.append(jax.lax.ppermute(x[:cnt], ax, self._perm_shift(-j)))
        # top pieces arrive nearest-neighbour first; the panel wants the
        # furthest rows first, so reverse.  Bottom pieces stack in order.
        return jnp.concatenate(top[::-1] + [x] + bot, axis=0)

    # -- exact ring hops ----------------------------------------------------

    def _ring_hops_block(self, x: Array, steps: int, wc: float,
                         ws: float) -> Array:
        """``steps`` ring hops on the local (b, ...) node block: one halo
        exchange + one fused megakernel (the fast path), or the hop-by-hop
        double-buffered schedule when ``fuse='off'`` — or when ``'auto'``
        decides the fusion doesn't pay on this backend/row size."""
        if self.fuse == "off" or steps <= 0:
            return self._ring_hops_block_unfused(x, steps, wc, ws)
        if self.fuse == "auto" and not self._fuse_on_big_rows:
            row_bytes = (x.size // x.shape[0]) * x.dtype.itemsize
            if row_bytes > self.AUTO_FUSE_MAX_ROW_BYTES:
                return self._ring_hops_block_unfused(x, steps, wc, ws)
        return self._ring_hops_block_fused(x, steps, wc, ws)

    def _ring_hops_block_fused(self, x: Array, steps: int, wc: float,
                               ws: float) -> Array:
        """Halo-panel fusion: gather a halo of width k, then ONE Pallas
        launch runs all k combines VMEM-resident.

        Rows beyond the halo see zeros instead of their true ring
        neighbours, so panel-end garbage advances exactly one row per hop —
        the center ``b`` rows are exact as long as ``halo >= hops`` (same
        invariant the kernel asserts).  Per-element math is the identical
        ``wc*z + ws*(l+r)`` expression, hence still bit-equal to the
        stacked path.  ``fuse_depth`` chunks a deep schedule into multiple
        launches of at most that many hops each.
        """
        from repro.kernels import ops
        shape = x.shape
        remaining = steps
        while remaining > 0:
            k = min(self.fuse_depth or remaining, remaining)
            panel = self._gather_halo(x, k)
            x = ops.multi_hop_mix(
                panel.reshape(panel.shape[0], -1), hops=k,
                out_rows=shape[0], halo=k, w_self=wc, w_side=ws,
            ).reshape(shape)
            remaining -= k
        return x

    def _ring_hops_block_unfused(self, x: Array, steps: int, wc: float,
                                 ws: float) -> Array:
        """``steps`` ring hops on the local (b, ...) node block.

        Per-row math is ``wc*x_i + ws*(x_{i-1} + x_{i+1})`` — expression-
        identical to the stacked ``mix_ring`` leaf, so fp32 results are
        bit-equal.  Double buffering: each hop combines its two edge rows
        FIRST and launches their ppermute for hop ``t+1`` before the
        interior combine, so the wire transfer of the next hop overlaps the
        local elementwise work of the current one.
        """
        from repro.kernels import ops
        ax = self._axis_name
        b = x.shape[0]
        # prologue: hop 0's edge exchange
        prev_last = jax.lax.ppermute(x[-1:], ax, self._perm(_FWD))
        next_first = jax.lax.ppermute(x[:1], ax, self._perm(_BWD))
        for t in range(steps):
            if b == 1:
                lo = hi = wc * x + ws * (prev_last + next_first)
            else:
                lo = wc * x[:1] + ws * (prev_last + x[1:2])
                hi = wc * x[-1:] + ws * (x[-2:-1] + next_first)
            if t + 1 < steps:
                # hop t+1's edges hit the wire while the interior combines
                prev_last = jax.lax.ppermute(hi, ax, self._perm(_FWD))
                next_first = jax.lax.ppermute(lo, ax, self._perm(_BWD))
            if b == 1:
                x = lo
            elif b == 2:
                x = jnp.concatenate([lo, hi], axis=0)
            else:
                inner = ops.ring_mix(x[1:-1], x[:-2], x[2:],
                                     w_self=wc, w_side=ws)
                x = jnp.concatenate([lo, inner, hi], axis=0)
        return x

    # -- gathered dense fallback (full / torus / star) ----------------------

    def _dense_block(self, x: Array, w: Array, b: int) -> Array:
        """All-gather the node axis and run the SAME full-shape einsum as the
        stacked path, then slice the local rows — dense topologies genuinely
        need every row, and reusing the identical contraction keeps the
        result bit-equal to :class:`StackedBackend`."""
        ax = self._axis_name
        xg = jax.lax.all_gather(x, ax, axis=0, tiled=True)      # (n, ...)
        mixed = jnp.einsum("ij,j...->i...", w.astype(xg.dtype), xg)
        return jax.lax.dynamic_slice_in_dim(
            mixed, self._linear_index() * b, b, axis=0)

    def _linear_index(self):
        idx = jax.lax.axis_index(self.axes[0])
        for a in self.axes[1:]:
            idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    # -- MixBackend surface -------------------------------------------------

    def mix(self, spec, tree: PyTree, steps: int) -> PyTree:
        if spec.n_nodes == 1 or steps == 0:
            return tree
        if self._use_stacked(spec):
            return self._stacked.mix(spec, tree, steps)
        b = self._block(spec)
        if spec.topology == "ring":
            wc = spec.self_weight
            ws = (1.0 - wc) / 2.0

            def body(t):
                return jax.tree.map(
                    lambda x: self._ring_hops_block(x, steps, wc, ws), t)
        else:
            w = dense_power(spec, steps)

            def body(t):
                return jax.tree.map(lambda x: self._dense_block(x, w, b), t)

        specs = jax.tree.map(lambda _: self._pspec, tree)
        return self._shmap(body, (specs,), out_specs=specs)(tree)

    def mix_hop(self, spec, tree: PyTree) -> PyTree:
        return self.mix(spec, tree, steps=1)

    def mix_channel(self, spec, channel, tree: PyTree, rnd, key: Array,
                    steps: int) -> PyTree:
        if channel.trivial:
            return self.mix(spec, tree, steps)
        if self._use_stacked(spec):
            return self._stacked.mix_channel(spec, channel, tree, rnd, key,
                                             steps)
        if spec.topology != "ring":
            # dense fallback: same W_t sequence, full gathered contraction
            return self._mix_channel_dense(spec, channel, tree, rnd, key,
                                           steps)
        b = self._block(spec)
        x_specs = jax.tree.map(lambda _: self._pspec, tree)
        for h in range(steps):
            # identical W_t sampling schedule to ChannelModel.mix, but the
            # (n, n) matrix is consumed ONLY as its three ring diagonals:
            # per-link ppermute filtering, no dense einsum on model data.
            wd, wl, wr = channel.ring_link_weights(
                rnd * steps + h, jax.random.fold_in(key, h))
            tree = self._shmap(
                functools.partial(self._channel_ring_hop_blocks, b=b),
                (x_specs, self._pspec, self._pspec, self._pspec),
                out_specs=x_specs,
            )(tree, wd, wl, wr)
        return tree

    def _channel_ring_hop_blocks(self, tree, wd, wl, wr, *, b: int):
        ax = self._axis_name

        def one(x):
            prev_last = jax.lax.ppermute(x[-1:], ax, self._perm(_FWD))
            next_first = jax.lax.ppermute(x[:1], ax, self._perm(_BWD))
            if b == 1:
                left, right = prev_last, next_first
            else:
                left = jnp.concatenate([prev_last, x[:-1]], axis=0)
                right = jnp.concatenate([x[1:], next_first], axis=0)
            shape = (b,) + (1,) * (x.ndim - 1)
            wdx = wd.astype(x.dtype).reshape(shape)
            wlx = wl.astype(x.dtype).reshape(shape)
            wrx = wr.astype(x.dtype).reshape(shape)
            return wdx * x + wlx * left + wrx * right

        return jax.tree.map(one, tree)

    def _mix_channel_dense(self, spec, channel, tree, rnd, key, steps):
        b = self._block(spec)
        x_specs = jax.tree.map(lambda _: self._pspec, tree)
        for h in range(steps):
            wt = channel.w_t(rnd * steps + h, jax.random.fold_in(key, h))
            tree = self._shmap(
                lambda t, w: jax.tree.map(
                    lambda x: self._dense_block(x, w, b), t),
                (x_specs, P()), out_specs=x_specs)(tree, wt)
        return tree

    def mix_wt(self, spec, tree: PyTree, wt: Array, *,
               steps: int = 1) -> PyTree:
        """Explicit-W_t hops.  A realized elastic matrix over a ring stays
        ring-banded (it is the base ring matrix with links masked and the
        mass folded into the diagonal), so it is consumed as its three
        diagonals on the same per-link ``ring_link_weights`` path the
        channel model uses — never a dense (n, n) einsum against model
        data.  The fused ``multi_hop_mix`` megakernel path is untouched:
        clean static-membership mixes keep routing through it."""
        if steps <= 0 or spec.n_nodes == 1:
            return tree
        if self._use_stacked(spec):
            return self._stacked.mix_wt(spec, tree, wt, steps=steps)
        b = self._block(spec)
        x_specs = jax.tree.map(lambda _: self._pspec, tree)
        if spec.topology == "ring":
            n = spec.n_nodes
            i = jnp.arange(n)
            wd, wl, wr = wt[i, i], wt[i, (i - 1) % n], wt[i, (i + 1) % n]
            hop = self._shmap(
                functools.partial(self._channel_ring_hop_blocks, b=b),
                (x_specs, self._pspec, self._pspec, self._pspec),
                out_specs=x_specs)
            for _ in range(steps):
                tree = hop(tree, wd, wl, wr)
            return tree
        hop = self._shmap(
            lambda t, w: jax.tree.map(lambda x: self._dense_block(x, w, b), t),
            (x_specs, P()), out_specs=x_specs)
        for _ in range(steps):
            tree = hop(tree, wt)
        return tree

    def quant_ring_hop(self, spec, q: Array, scale: Array, *,
                       out_dtype=jnp.float32) -> Array:
        if self._use_stacked(spec):
            return self._stacked.quant_ring_hop(spec, q, scale,
                                                out_dtype=out_dtype)
        from repro.kernels import ops
        b = self._block(spec)
        wc = spec.self_weight
        ws = (1.0 - wc) / 2.0
        ax = self._axis_name

        def body(qb, sb):
            # only the int8 edge rows (+ one f32 scale each) travel: the
            # wire window is 4x smaller than a full-precision exchange
            ql_e = jax.lax.ppermute(qb[-1:], ax, self._perm(_FWD))
            sl_e = jax.lax.ppermute(sb[-1:], ax, self._perm(_FWD))
            qr_e = jax.lax.ppermute(qb[:1], ax, self._perm(_BWD))
            sr_e = jax.lax.ppermute(sb[:1], ax, self._perm(_BWD))
            if b == 1:
                ql, qr, sl, sr = ql_e, qr_e, sl_e, sr_e
            else:
                ql = jnp.concatenate([ql_e, qb[:-1]], axis=0)
                sl = jnp.concatenate([sl_e, sb[:-1]], axis=0)
                qr = jnp.concatenate([qb[1:], qr_e], axis=0)
                sr = jnp.concatenate([sb[1:], sr_e], axis=0)
            return ops.quant_mix(qb, ql, qr, sb, sl, sr, w_self=wc,
                                 w_side=ws, out_dtype=out_dtype)

        return self._shmap(body, (self._pspec, self._pspec))(q, scale)

    def quant_ring_hops(self, spec, x: Array, steps: int, *,
                        out_dtype=None) -> Array:
        """All-hop compressed ``W^k`` schedule.  Fused path: quantize the
        local block once, halo-exchange the *int8* panel (+ per-row scales),
        then one ``multi_hop_mix_quant`` launch replays every hop's
        dequant -> combine -> requant chain VMEM-resident.  The in-kernel
        requantization is the same deterministic formula the stacked oracle
        applies globally, so both paths decode identical int8 values and
        agree to FMA rounding of the combines."""
        if self._use_stacked(spec):
            return self._stacked.quant_ring_hops(spec, x, steps,
                                                 out_dtype=out_dtype)
        if steps <= 0:
            return x if out_dtype is None else x.astype(out_dtype)
        from repro.comms.compress import quantize_det
        from repro.kernels import ops
        out_dtype = x.dtype if out_dtype is None else out_dtype
        b = self._block(spec)
        wc = spec.self_weight
        ws = (1.0 - wc) / 2.0

        if self.fuse == "off":
            # hop-by-hop: global deterministic quantize, shard compressed hop
            z = x
            n = x.shape[0]
            for _ in range(steps):
                q, s = quantize_det(z)
                z = self.quant_ring_hop(
                    spec, q.reshape(n, -1), s.reshape(n, 1),
                    out_dtype=jnp.float32).reshape(x.shape)
            return z.astype(out_dtype)

        def body(xb):
            shape = xb.shape
            zb = xb
            remaining = steps
            while remaining > 0:
                k = min(self.fuse_depth or remaining, remaining)
                # quantize_det here IS the requant the kernel's next chunk
                # would have applied — chunking preserves the all-hop math
                qb, sb = quantize_det(zb.reshape(b, -1))
                zb = ops.multi_hop_mix_quant(
                    self._gather_halo(qb, k),
                    self._gather_halo(sb, k),
                    hops=k, out_rows=b, halo=k, w_self=wc, w_side=ws,
                    out_dtype=jnp.float32,
                ).reshape(shape)
                remaining -= k
            return zb.astype(out_dtype)

        return self._shmap(body, (self._pspec,))(x)

    def est_hop_bytes(self, spec, tree: PyTree) -> float:
        if self._use_stacked(spec):
            return self._stacked.est_hop_bytes(spec, tree)
        total = _tree_bytes(tree)
        row = total / max(spec.n_nodes, 1)
        if spec.topology == "ring":
            # two edge rows per device, both directions
            return 2.0 * self.axis_size * row
        return float(spec.n_nodes - 1) * total   # all-gather

    def est_quant_hop_bytes(self, spec, tree: PyTree) -> float:
        if self._use_stacked(spec):
            return self._stacked.est_quant_hop_bytes(spec, tree)
        total = _quant_tree_bytes(tree)
        row = total / max(spec.n_nodes, 1)
        if spec.topology == "ring":
            # halo exchange ships the same 2 rows/hop, just int8 + scale
            return 2.0 * self.axis_size * row
        return float(spec.n_nodes - 1) * total

    def __repr__(self):
        return (f"ShardMapBackend(axes={self.axes}, "
                f"axis_size={self.axis_size}, fuse={self.fuse!r})")


# ---------------------------------------------------------------------------
# shared helpers / registry
# ---------------------------------------------------------------------------


def _per_node_shard(mesh: Optional[Mesh], axes: tuple[str, ...], fn):
    """``fn`` — node-local work on node-stacked arguments and results — run
    on each device's own block of node rows.

    XLA cannot partition a Pallas kernel by itself, so node-local work
    that calls one (the model's flash attention, a combine kernel) is
    handed its shard through ``shard_map``.  Without a multi-device node
    axis this is ``fn`` unchanged.
    """
    if mesh is None or int(np.prod([mesh.shape[a] for a in axes])) == 1:
        return fn
    spec = P(axes if len(axes) > 1 else axes[0])

    def run(*args):
        return jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * len(args),
                             out_specs=spec, check_vma=False)(*args)
    return run


def dense_power(spec, steps: int) -> Array:
    """``W^steps`` as an f32 constant (float64 numpy power, so it constant-
    folds under jit) — the one dense-matrix artifact both backends share."""
    m = spec.matrix
    return jnp.asarray(np.linalg.matrix_power(m, steps) if steps > 1 else m,
                       dtype=jnp.float32)


def _tree_bytes(tree: PyTree) -> float:
    return float(sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(tree)))


def _quant_tree_bytes(tree: PyTree) -> float:
    """Bytes of one int8-compressed copy: 1 B/element + one f32 scale per
    node row (leaf axis 0)."""
    return float(sum(l.size * 1 + l.shape[0] * 4
                     for l in jax.tree.leaves(tree)))


def resolve_backend(spec) -> MixBackend:
    """The backend a ``GossipSpec`` routes through (stacked when unset).

    ``spec.backend`` may be a backend instance or a registry name
    (``"stacked" | "shard_map"``, see :data:`repro.comms.api.BACKENDS`)."""
    be = getattr(spec, "backend", None)
    if be is None:
        return _DEFAULT_STACKED
    if isinstance(be, str):
        return make_backend(be)
    return be


def make_backend(kind: str = "auto", *, mesh: Optional[Mesh] = None,
                 axis: str | Sequence[str] = "node", fuse: str = "auto",
                 fuse_depth: Optional[int] = None) -> MixBackend:
    """Config-knob constructor, dispatching through the
    :data:`repro.comms.api.BACKENDS` string registry.

    ``stacked`` — always the stacked backend.
    ``shard_map`` — requires a mesh with the node axis.
    ``auto`` — shard_map when a mesh with a >1-device node axis is given,
    stacked otherwise.
    ``fuse``/``fuse_depth`` configure the shard_map multi-hop megakernel
    (``auto``/``on`` = fused halo panels, ``off`` = hop-by-hop ppermute).
    """
    if kind == "auto":
        if mesh is not None:
            axes = (axis,) if isinstance(axis, str) else tuple(axis)
            if all(a in mesh.shape for a in axes) and \
                    int(np.prod([mesh.shape[a] for a in axes])) > 1:
                kind = "shard_map"
            else:
                kind = "stacked"
        else:
            kind = "stacked"
    factory = api.BACKENDS.get(kind)
    if factory is None:
        raise ValueError(
            f"unknown mix backend {kind!r}; registered: {api.backend_names()}")
    return factory(mesh=mesh, axis=axis, fuse=fuse, fuse_depth=fuse_depth)


_DEFAULT_STACKED = StackedBackend()


def _make_stacked(*, mesh=None, axis="node", fuse="auto",
                  fuse_depth=None) -> MixBackend:
    return _DEFAULT_STACKED if mesh is None else StackedBackend(mesh, axis)


def _make_shard_map(*, mesh=None, axis="node", fuse="auto",
                    fuse_depth=None) -> MixBackend:
    if mesh is None:
        raise ValueError("mix_backend='shard_map' requires a mesh")
    return ShardMapBackend(mesh, axis=axis, fuse=fuse, fuse_depth=fuse_depth)


api.register_backend("stacked", _make_stacked)
api.register_backend("shard_map", _make_shard_map)
