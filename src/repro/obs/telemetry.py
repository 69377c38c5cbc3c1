"""The user-facing telemetry facade.

One ``Telemetry`` object per run ties the three layers together:

* **spans / trace** — a host-side :class:`repro.obs.trace.Trace` whose
  Chrome-trace JSON lands under ``out_dir`` at :meth:`export`; each span
  is also a program span on the profiler's clock
  (:func:`repro.obs.trace.span`);
* **jit counters** — :meth:`init_counters` seeds the packed ``f32[6]``
  counter leaf (``WireCounters`` is its host-side view) and
  :meth:`flush_counters` emits it from *inside* a
  jitted step via ``jax.experimental.io_callback``.  Any program containing
  an io_callback pays a per-call host tax (effects disable the fast
  dispatch path), so the optimizers' ``make_step`` compiles TWO
  executables from the same step function via :meth:`flush_mode`: a quiet
  effect-free one for ordinary steps and a flushing one used every
  ``flush_every``-th call — both fully fused, and the tax lands on one
  call per flush window.  The default ``"cond"`` mode (a ``lax.cond`` on
  ``step % flush_every == 0``) keeps standalone ``opt.step`` jits correct
  without the dual-executable wrapper;
* **event log** — every flush / dashboard / export appends a
  schema-validated line to ``<out_dir>/<run>.events.jsonl``.

The object is static configuration: it is captured by the jitted closure
(like ``CommEngine``), never traced.  Passing ``telemetry=None`` (the
default everywhere) compiles the exact same program as before this module
existed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from typing import Any, Optional

import jax
from jax.experimental import io_callback

from repro.obs import events as obs_events
from repro.obs import wire as obs_wire
from repro.obs.trace import Trace

PyTree = Any


@dataclasses.dataclass
class Telemetry:
    """Static per-run telemetry configuration + host-side sinks."""

    run: str = "run"
    out_dir: str = os.path.join("experiments", "telemetry")
    flush_every: int = 50          # io_callback cadence, in optimizer steps
    enabled: bool = True
    trace: Trace = None            # created in __post_init__ when omitted

    def __post_init__(self):
        if self.trace is None:
            self.trace = Trace(run=self.run)
        self._meta_written = False
        self._flush_mode = "cond"

    # -- paths --------------------------------------------------------------

    @property
    def events_path(self) -> str:
        return os.path.join(self.out_dir, f"{self.run}.events.jsonl")

    @property
    def trace_path(self) -> str:
        return os.path.join(self.out_dir, f"{self.run}.trace.json")

    # -- host-side event emission -------------------------------------------

    def event(self, type_: str, data: dict, step: Optional[int] = None) -> dict:
        self._ensure_meta()
        ev = obs_events.make_event(type_, self.run, data, step=step)
        obs_events.append_jsonl(self.events_path, ev)
        return ev

    def _ensure_meta(self) -> None:
        if self._meta_written:
            return
        self._meta_written = True
        ev = obs_events.make_event(
            "meta", self.run,
            {"backend": jax.default_backend(),
             "device_count": jax.device_count(),
             "flush_every": self.flush_every,
             "started": time.strftime("%Y-%m-%dT%H:%M:%S")})
        obs_events.append_jsonl(self.events_path, ev)

    def span(self, name: str, **args):
        return self.trace.span(name, **args)

    # -- jit-side counters --------------------------------------------------

    def init_counters(self) -> jax.Array:
        return obs_wire.zero_counters()

    def _flush_cb(self, step, vals) -> None:
        # runs on the host; never let telemetry kill a training step
        try:
            data = obs_wire.unpack(vals).as_dict()
            self.event("counters", data, step=int(step))
            self.trace.counter("wire", {"wire_bytes": data["wire_bytes"],
                                        "raw_bytes": data["raw_bytes"],
                                        "hops": data["hops"]})
        except Exception as e:     # pragma: no cover - defensive
            print(f"[obs] counter flush failed: {e!r}", flush=True)

    @contextlib.contextmanager
    def flush_mode(self, mode: str):
        """Trace-time switch for :meth:`flush_counters`: ``"cond"`` (default,
        runtime step check), ``"always"`` (unconditional io_callback — the
        flush executable), ``"never"`` (no effects at all — the quiet
        executable, whose program is free of the effect dispatch tax)."""
        assert mode in ("cond", "always", "never"), mode
        prev = self._flush_mode
        self._flush_mode = mode
        try:
            yield self
        finally:
            self._flush_mode = prev

    def flush_counters(self, counters: Optional[jax.Array], step) -> None:
        """Call inside the jitted step: host flush every ``flush_every``
        steps (unordered io_callback — steps stay fused; see
        :meth:`flush_mode` for how make_step keeps quiet steps effect-free).
        ``counters`` is the packed ``f32[6]`` leaf from
        :meth:`init_counters`.
        """
        if counters is None or self._flush_mode == "never":
            return
        if self._flush_mode == "always":
            io_callback(self._flush_cb, None, step, counters, ordered=False)
            return

        def do(args):
            io_callback(self._flush_cb, None, *args, ordered=False)
            return 0

        jax.lax.cond(step % self.flush_every == 0, do, lambda args: 0,
                     (step, counters))

    # -- convergence dashboard ----------------------------------------------

    def dashboard(self, problem, x_stacked: PyTree, y_stacked, batches,
                  step: int, extra: Optional[dict] = None) -> dict:
        """Stream M_t components + per-geometry feasibility + cross-node
        drift into the event log (host-side, at ``eval_every`` cadence)."""
        from repro.core import metric as core_metric  # lazy: no import cycle

        m = core_metric.convergence_metric(problem, x_stacked, y_stacked,
                                           batches)
        data = {k: float(v) for k, v in m.items()}
        data["drift"] = {
            name: float(v)
            for name, v in core_metric.per_leaf_drift(
                problem, x_stacked).items()}
        if extra:
            data.update(extra)
        return self.event("dashboard", data, step=step)

    # -- export -------------------------------------------------------------

    def export(self) -> dict:
        """Write the Perfetto/Chrome trace; returns the artifact paths."""
        self._ensure_meta()
        path = self.trace.save(self.trace_path)
        return {"trace": path, "events": self.events_path}


def read_counter_series(events_path: str) -> list[dict]:
    """The flushed counter events of a run, in step order."""
    rows = [ev for ev in obs_events.read_jsonl(events_path)
            if ev["type"] == "counters"]
    rows.sort(key=lambda ev: ev.get("step", 0))
    return rows
