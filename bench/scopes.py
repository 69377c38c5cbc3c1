"""Device time of a traced window by the program's own scopes.

The program names regions of its jitted step and decode wave with
``repro.obs.trace.scope`` (``jax.named_scope``): the name lands in the
``op_name`` metadata of every instruction of the compiled program
(``jit(step)/gda.grad/.../dot_general``).  The profiler's device ops carry
only the instruction (``%fusion.12 = f32[4,8]{1,0} fusion(...)``), so the
reduction reads the scopes from the compiled program's HLO text: an op of
the window belongs to the program where the program has an instruction of
its name and result shape, and its own time (less the ops nested in it,
the body of a ``while``) goes to the innermost scope of a family
(``gda.``; ``model.``/``block.``) in that instruction's ``op_name``, or to
``other`` where it has none.

The HLO text comes from lowering the cell's program again, as the job's
``aot`` does but with no explicit placement, which lowers the same module
the window ran: its compile is a persistent-cache hit where the cache is
on.  A program without scopes (one that predates them) gives every op to
``other``; the readers then report nothing.
"""
from __future__ import annotations

import json
import re
import sys
import time
from collections import defaultdict

from bench import harness
from bench.trace import _self_times, short_name

#: the program each job's ``aot`` hands over whose scopes are read
PROGRAM = {"train": "step", "serve": "decode wave"}
#: scope families: the DRSGDA step's phases; the model's layers and head
GDA = ("gda.",)
MODEL = ("model.", "block.")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _split(text: str):
    """``%name = <type> <opcode>(...`` -> (name, type without layouts);
    None for a line that defines no instruction."""
    head, sep, rest = text.strip().removeprefix("ROOT ").partition(" = ")
    if not sep or not head.startswith("%"):
        return None
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        typ = rest[:i + 1]
    else:
        typ = rest.split(" ", 1)[0]
    return head[1:], re.sub(r"\{[^{}]*\}", "", typ)


def hlo_op_names(text: str) -> dict:
    """Compiled HLO text -> ``{(instruction, result type): op_name}`` (the
    type without layouts; ``""`` where the instruction has no op_name)."""
    out = {}
    for line in text.splitlines():
        key = _split(line)
        if key is not None:
            m = _OP_NAME.search(line)
            out[key] = m.group(1) if m else ""
    return out


def innermost(op_name: str, family: tuple) -> str:
    """The last scope of ``op_name`` whose name starts with one of the
    prefixes in ``family``, or ``other``."""
    best = "other"
    for part in re.split(r"[/()]", op_name):
        if part.startswith(family):
            best = part
    return best


def scope_op_seconds(trace, hlo: dict, family: tuple) -> dict:
    """Own device seconds of the window's ops, ``{scope: {op: s}}`` by
    innermost scope of ``family`` (``other`` for the program's ops with
    none; ``not_program`` for ops the program does not hold) and op
    (``trace.short_name``), averaged over the devices."""
    acc = defaultdict(lambda: defaultdict(int))
    for d in trace.ops:
        for name, t in _self_times(trace.in_window(d)):
            key = _split(name)
            op_name = hlo.get(key) if key is not None else None
            scope = "not_program" if op_name is None \
                else innermost(op_name, family)
            acc[scope][short_name(name)] += t
    nd = max(1, len(trace.ops))
    return {k: {op: v * 1e-9 / nd for op, v in ops.items()}
            for k, ops in acc.items()}


def scope_seconds(trace, hlo: dict, family: tuple) -> dict:
    """Own device seconds of the window's ops by scope
    (``scope_op_seconds`` summed over ops)."""
    return {k: sum(ops.values())
            for k, ops in scope_op_seconds(trace, hlo, family).items()}


def _program_text(cell) -> str:
    """Compiled HLO text of the cell's timed program (``PROGRAM``)."""
    want, found = PROGRAM[cell.job], []

    class Done(Exception):
        pass

    def report(name, lowered):
        if name == want:
            found.append(lowered.compile().as_text())
            raise Done

    try:
        harness.load_job(cell).aot(cell, None, report)
    except Done:
        pass
    return found[0]


def cell_scopes(ctx, family: tuple):
    """Seconds by scope of the cell's timed program over the traced
    window (``scope_seconds``), computed once per run and family (kept on
    the readers' ``ctx``) and printed on standard error with each scope's
    largest ops and the seconds spent lowering the program again; None
    where the trace holds no device op or the program has no scope of
    ``family``."""
    if not ctx.trace.ops:
        return None
    memo = vars(ctx).setdefault("scopes", {})
    if family not in memo:
        t = time.perf_counter()
        hlo = hlo_op_names(_program_text(ctx.cell))
        lower_s = time.perf_counter() - t
        ops = scope_op_seconds(ctx.trace, hlo, family)
        secs = {k: sum(v.values()) for k, v in ops.items()}
        scoped = sum(v for k, v in secs.items()
                     if k not in ("other", "not_program"))
        memo[family] = secs if scoped > 0 else None
        top = {k: sorted(v.items(), key=lambda kv: -kv[1])[:8]
               for k, v in ops.items()}
        print("scopes " + json.dumps({
            "family": list(family), "seconds": secs, "top_ops": top,
            "busy_s": ctx.trace.busy_s(), "program_s": lower_s}),
            file=sys.stderr, flush=True)
    return memo[family]
