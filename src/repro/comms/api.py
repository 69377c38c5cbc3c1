"""Typed facade of the comms subsystem — Protocols + the backend registry.

This module is deliberately import-light (stdlib ``typing`` only, no jax):
``repro.core`` annotates ``GossipSpec.comm`` / ``GossipSpec.backend`` /
``GossipSpec.elastic`` against these Protocols under ``TYPE_CHECKING``
without importing any comms machinery at runtime, which kills the old
"``comm: object | None``" loose typing while preserving the one-way import
convention (comms never imports core at module scope; core may import
comms).

Three structural types:

* :class:`CommLike`     — the ``CommSpec`` surface the optimizers and the
  engine consume (compression knobs + channel fault rates);
* :class:`ElasticLike`  — the ``ElasticSpec`` surface (churn schedule,
  stale-hop tolerance ``tau``, execution-mode fault rates);
* :class:`MixBackendProtocol` — how gossip hops execute (stacked
  roll/einsum vs shard_map ppermute); ``repro.comms.backend.MixBackend``
  is the runtime-checkable twin with precise jax types.

Plus the **backend string registry**: ``GossipSpec.backend`` and the
``mix_backend`` config knob accept ``"stacked" | "shard_map"`` names;
``resolve_backend`` / ``make_backend`` construct through
:data:`BACKENDS` instead of ad-hoc isinstance/if-else plumbing, and
third-party backends can :func:`register_backend` themselves.
"""
from __future__ import annotations

from typing import Any, Callable, Protocol, runtime_checkable

__all__ = ["CommLike", "ElasticLike", "MixBackendProtocol", "BACKENDS",
           "register_backend", "backend_names"]


@runtime_checkable
class CommLike(Protocol):
    """What a ``GossipSpec.comm`` value must look like (see ``CommSpec``)."""

    compressor: str
    error_feedback: bool
    gamma: float
    drop_rate: float
    straggler_rate: float
    schedule: str
    seed: int

    @property
    def compressed(self) -> bool: ...

    @property
    def channel_active(self) -> bool: ...

    @property
    def enabled(self) -> bool: ...


@runtime_checkable
class ElasticLike(Protocol):
    """What a ``GossipSpec.elastic`` value must look like (see
    ``repro.comms.elastic.ElasticSpec``)."""

    tau: int
    drop_rate: float
    straggler_rate: float
    seed: int

    @property
    def enabled(self) -> bool: ...


@runtime_checkable
class MixBackendProtocol(Protocol):
    """Strategy interface between the gossip math and the wire.

    The jax-typed runtime twin lives in :mod:`repro.comms.backend`
    (``MixBackend``); this copy exists so ``repro.core`` can type-check
    against the surface without importing jax-heavy comms modules.
    """

    name: str

    def mix(self, spec: Any, tree: Any, steps: int) -> Any: ...

    def mix_hop(self, spec: Any, tree: Any) -> Any: ...

    def mix_channel(self, spec: Any, channel: Any, tree: Any, rnd: Any,
                    key: Any, steps: int) -> Any: ...

    def mix_wt(self, spec: Any, tree: Any, wt: Any, *,
               steps: int = 1) -> Any: ...

    def quant_ring_hop(self, spec: Any, q: Any, scale: Any, *,
                       out_dtype: Any = ...) -> Any: ...

    def quant_ring_hops(self, spec: Any, x: Any, steps: int, *,
                        out_dtype: Any = ...) -> Any: ...

    def est_hop_bytes(self, spec: Any, tree: Any) -> float: ...

    def est_quant_hop_bytes(self, spec: Any, tree: Any) -> float: ...

    def node_map(self, fn: Any) -> Any: ...


# ---------------------------------------------------------------------------
# backend string registry
# ---------------------------------------------------------------------------

#: name -> factory(mesh=None, axis="node", fuse="auto", fuse_depth=None).
#: Populated by :mod:`repro.comms.backend` at import time ("stacked",
#: "shard_map"); extensible via :func:`register_backend`.
BACKENDS: dict[str, Callable[..., Any]] = {}


def register_backend(name: str, factory: Callable[..., Any]) -> None:
    """Register a mix-backend factory under a config-string name."""
    BACKENDS[name] = factory


def backend_names() -> list[str]:
    return sorted(BACKENDS)
