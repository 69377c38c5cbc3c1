"""repro.obs — jit-safe telemetry: spans + traces, wire/kernel counters,
and the streaming convergence dashboard.

Three coordinated layers (see each module's docstring):

* :mod:`repro.obs.trace` — device scopes and host spans on the profiler's
  clock, Chrome-trace/Perfetto export;
* :mod:`repro.obs.compiles` — process-wide count and seconds of traces,
  lowerings and compiles;
* :mod:`repro.obs.wire` + :mod:`repro.obs.estimates` — jit-threaded wire
  counters and analytical per-kernel cost estimates;
* :mod:`repro.obs.telemetry` — the ``Telemetry`` facade the optimizers,
  ``launch/train.py`` and ``benchmarks/obs.py`` consume, flushing to the
  schema-validated JSONL event log (:mod:`repro.obs.events`).

This package never imports ``repro.core`` or ``repro.kernels`` at module
scope (the dependency points the other way), so it can sit underneath both.
"""
from repro.obs import compiles, estimates, events, trace, wire  # noqa: F401
from repro.obs.estimates import Estimates  # noqa: F401
from repro.obs.telemetry import Telemetry  # noqa: F401
from repro.obs.trace import Trace  # noqa: F401
from repro.obs.wire import (WireCounters, unpack, wrap_mixer,  # noqa: F401
                            zero_counters)
