"""Trace capture and its reduction to per-layer numbers.

A traced run records the JAX profiler over its measured window.  The
window itself is the host span ``bench.window``; the benchmark's other
host spans (``batch.prepare``, ``step.dispatch``, ``step.wait``,
``serve.admit``, ``serve.step``, ``serve.sched``) mark what the host was
doing.  From the device planes (``/device:TPU:<n>``) the reduction takes
the operations the chip ran, by name, with start and end: the union of
their intervals is the busy time, what the window leaves uncovered the
idle time, and each idle gap is put down to the host span that covers
most of it.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import shutil
import tempfile
from collections import defaultdict
from typing import Iterable, Optional

WINDOW = "bench.window"
HOST_SPANS = ("batch.prepare", "step.dispatch", "step.wait", "serve.admit",
              "serve.step", "serve.sched")
#: device-plane lines that hold one event per executed operation
OP_LINES = ("XLA Ops",)


@dataclasses.dataclass
class Trace:
    ops: dict            # device id -> sorted [(name, start_ns, end_ns)]
    spans: list          # [(name, start_ns, end_ns)] of the host spans
    window: tuple        # (start_ns, end_ns) of ``bench.window``

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def in_window(self, dev: int) -> list:
        a, b = self.window
        return [(n, max(s, a), min(e, b)) for n, s, e in self.ops[dev]
                if e > a and s < b]

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        if not self.ops:
            return 0.0
        tot = sum(_union_ns(self.in_window(d)) for d in self.ops)
        return tot * 1e-9 / len(self.ops)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def op_seconds(self, match) -> tuple[float, int]:
        """Summed device seconds and count of the operations whose name
        satisfies ``match``, over all devices."""
        t, n = 0, 0
        for d in self.ops:
            for name, s, e in self.in_window(d):
                if match(name):
                    t += e - s
                    n += 1
        return t * 1e-9, n

    def span_durations(self, name: str) -> list[float]:
        a, b = self.window
        return [(e - s) * 1e-9 for n, s, e in self.spans
                if n == name and s >= a and e <= b]

    def top_ops(self, k: int = 10) -> list:
        """Device operations by summed self time (per device), largest
        first: an operation that runs inside another (the body of a
        ``while``) is taken out of the outer one's time."""
        acc = defaultdict(int)
        for d in self.ops:
            for name, t in _self_times(self.in_window(d)):
                acc[short_name(name)] += t
        nd = max(1, len(self.ops))
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v * 1e-9 / nd] for n, v in top]

    def idle_gaps(self, k: int = 10) -> list:
        """Idle time of device 0, by the host span that covers most of each
        gap (``none`` where no span does), largest first."""
        if not self.ops:
            return []
        dev = min(self.ops)
        spans = sorted(self.spans, key=lambda x: x[1])
        starts = [s for _, s, _ in spans]
        longest = max((e - s for _, s, e in spans), default=0)
        acc = defaultdict(int)
        for gs, ge in _gaps(self.in_window(dev), self.window):
            best, cover = "none", 0
            lo = bisect.bisect_left(starts, gs - longest)
            hi = bisect.bisect_right(starts, ge)
            for name, s, e in spans[lo:hi]:
                c = min(e, ge) - max(s, gs)
                if c > cover:
                    best, cover = name, c
            acc[best] += ge - gs
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v * 1e-9] for n, v in top]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def short_name(op: str) -> str:
    """``%copy.86 = bf16[40,385]{...} copy(...)`` -> ``copy.86 bf16[40,385]``:
    the instruction's name and the type it produces."""
    head, _, rest = op.partition(" = ")
    return (head.lstrip("%") + " " + rest.split("{", 1)[0].split(" ", 1)[0]
            ).strip()[:120]


def _self_times(intervals) -> list:
    """(name, own time) of each interval, less the intervals nested in it."""
    out, stack = [], []            # stack: [name, start, end, children]
    for name, s, e in sorted(intervals, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            n, a, b, c = stack.pop()
            out.append((n, b - a - c))
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0])
    out.extend((n, b - a - c) for n, a, b, c in stack)
    return out


def _union_ns(intervals: Iterable) -> int:
    tot, end = 0, None
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        if end is None or s > end:
            tot += e - s
            end = e
        elif e > end:
            tot += e - end
            end = e
    return tot


def _gaps(intervals, window) -> list:
    out, cur = [], window[0]
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < window[1]:
        out.append((cur, window[1]))
    return out


def reduce_profile(pd, span_names=HOST_SPANS) -> Trace:
    """A ``jax.profiler.ProfileData`` reduced to device operations and the
    benchmark's host spans."""
    ops, spans, window = {}, [], None
    want = set(span_names) | {WINDOW}
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and name[12:].isdigit():
            evs = []
            for line in plane.lines:
                if line.name in OP_LINES:
                    evs.extend((e.name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns))
                               for e in line.events)
            ops[int(name[12:])] = sorted(evs, key=lambda x: x[1])
        elif name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in want:
                        s = int(e.start_ns)
                        rec = (e.name, s, s + int(e.duration_ns))
                        if e.name == WINDOW:
                            window = rec[1:]
                        else:
                            spans.append(rec)
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    return Trace(ops=ops, spans=spans, window=window)


def load(logdir: str) -> Trace:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return reduce_profile(ProfileData.from_file(files[0]))


class Recorder:
    """Profiler around the measured window, or nothing when ``on`` is false.

    ``span(name)`` is a host span on the profiler's clock (a no-op when
    tracing is off); after :meth:`stop`, ``trace`` holds the reduction."""

    def __init__(self, on: bool):
        self.on = on
        self.trace: Optional[Trace] = None
        self._dir = None

    def span(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start(self):
        if self.on:
            import jax
            self._dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self._dir)

    def stop(self):
        if self.on:
            import jax
            jax.profiler.stop_trace()
            try:
                self.trace = load(self._dir)
            finally:
                shutil.rmtree(self._dir, ignore_errors=True)
