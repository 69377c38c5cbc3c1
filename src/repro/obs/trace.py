"""Program spans and scopes on the profiler's clock, plus Chrome-trace /
Perfetto export.

Two mechanisms mark where time goes, both read from a JAX profiler trace
(``jax.profiler.start_trace`` / ``stop_trace``):

* :func:`scope` names a region *inside* a jitted program.  It is
  ``jax.named_scope``: the name lands in the ``op_name`` metadata of every
  HLO instruction lowered inside it (``jit(step)/gda.retract/...``) and
  adds no operation, so the device program is the same with or without
  it.  A device op belongs to the innermost scope of a family
  (``gda.``, ``model.``/``block.``) in its ``op_name``.
* :func:`span` marks a *host* region as a ``jax.profiler.TraceAnnotation``
  on the profiler's clock, the clock the device ops are on.  Outside a
  profiler trace it costs one annotation object.

``Trace`` records wall-clock *complete* events ("ph": "X") from its
``span()`` context manager (nesting is reconstructed by Perfetto from the
timestamps) and counter tracks ("ph": "C") from flushed jit counters; each
of its spans is also a program span.  ``to_chrome_trace()`` emits the
standard ``{"traceEvents": [...]}`` JSON that both ``chrome://tracing`` and
https://ui.perfetto.dev open directly; ``from_chrome_trace`` round-trips it
(schema-checked by ``tests/test_obs.py``).

Timestamps are microseconds since the trace epoch (``t0``), per the trace
event format.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Optional

import jax


def scope(name: str):
    """Device scope: ``jax.named_scope(name)`` around traced code."""
    return jax.named_scope(name)


def span(name: str):
    """Host span on the profiler's clock around host code."""
    return jax.profiler.TraceAnnotation(name)


class Trace:
    """An in-memory Chrome-trace event buffer for one run."""

    def __init__(self, run: str = "run", pid: int = 0):
        self.run = run
        self.pid = pid
        self.t0 = time.perf_counter()
        self.events: list[dict] = []
        self._lock = threading.Lock()

    # -- clock --------------------------------------------------------------

    def _now_us(self) -> float:
        return (time.perf_counter() - self.t0) * 1e6

    def _tid(self) -> int:
        return threading.get_ident() % 1_000_000

    def _append(self, ev: dict) -> None:
        with self._lock:
            self.events.append(ev)

    # -- recording ----------------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "host", **args: Any):
        """Wall-clock region: ``with trace.span("step", step=t): ...``;
        also a program span of the same name."""
        t0 = self._now_us()
        try:
            with span(name):
                yield self
        finally:
            t1 = self._now_us()
            self._append({"name": name, "cat": cat, "ph": "X", "ts": t0,
                          "dur": t1 - t0, "pid": self.pid, "tid": self._tid(),
                          "args": args})

    def counter(self, name: str, values: dict[str, float],
                ts: Optional[float] = None) -> None:
        """Counter track (one series per dict key)."""
        self._append({"name": name, "cat": "counters", "ph": "C",
                      "ts": self._now_us() if ts is None else ts,
                      "pid": self.pid,
                      "args": {k: float(v) for k, v in values.items()}})

    # -- export -------------------------------------------------------------

    def spans(self) -> list[dict]:
        return [e for e in self.events if e["ph"] == "X"]

    def to_chrome_trace(self) -> dict:
        return {
            "traceEvents": list(self.events),
            "displayTimeUnit": "ms",
            "otherData": {"run": self.run, "format": "repro.obs/chrome-trace"},
        }

    def save(self, path: str) -> str:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path

    @classmethod
    def from_chrome_trace(cls, payload: dict) -> "Trace":
        """Inverse of :meth:`to_chrome_trace` (round-trip tested)."""
        other = payload.get("otherData", {})
        t = cls(run=other.get("run", "run"))
        t.events = list(payload["traceEvents"])
        return t

    @classmethod
    def load(cls, path: str) -> "Trace":
        with open(path) as f:
            return cls.from_chrome_trace(json.load(f))
