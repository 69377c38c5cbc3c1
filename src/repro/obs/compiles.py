"""Process-wide count and seconds of JAX's tracing, lowering and compiling.

One ``jax.monitoring`` duration listener, registered when this module is
first imported, adds up the ``/jax/core/compile/*`` events: each jaxpr
trace (``jaxpr_trace_duration``), each lowering to an MLIR module
(``jaxpr_to_mlir_module_duration``) and each backend compile
(``backend_compile_duration``; a persistent-cache hit counts too, with its
load time).  Read :func:`snapshot` before and after a region and take
:func:`since` to see what compiled inside it::

    before = compiles.snapshot()
    run_steady_state()
    assert compiles.since(before)["compiles"] == 0
"""
from __future__ import annotations

import threading

import jax

#: event -> the key its count and seconds are kept under
EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
          "/jax/core/compile/backend_compile_duration": "compiles"}

_lock = threading.Lock()
_totals = {k: 0 for k in EVENTS.values()}
_totals.update({f"{k}_s": 0.0 for k in EVENTS.values()})


def _listen(event: str, duration_secs: float, **_) -> None:
    key = EVENTS.get(event)
    if key is not None:
        with _lock:
            _totals[key] += 1
            _totals[key + "_s"] += duration_secs


jax.monitoring.register_event_duration_secs_listener(_listen)


def snapshot() -> dict:
    """Counts (``traces``, ``lowerings``, ``compiles``) and seconds (the
    same keys with ``_s``) since the process imported this module."""
    with _lock:
        return dict(_totals)


def since(before: dict) -> dict:
    """What :func:`snapshot` gained after ``before``."""
    now = snapshot()
    return {k: now[k] - before[k] for k in now}
