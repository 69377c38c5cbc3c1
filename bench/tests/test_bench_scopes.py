"""Device time by program scope (``bench/scopes.py``), the program spans on
the profiler's clock, and compiles inside the window, on the CPU: the
tiny cells' programs with and without their scopes, a small trace laid out
as the profiler lays out a chip's ops, a trace recorded on a TPU v5e with
the compiled HLO text of its program, and a CPU profiler trace of the
tiny serving cell."""
import contextlib
import glob
import re
import time
import types
from pathlib import Path

import jax
import pytest

from bench import harness, scopes
from bench import trace as bench_trace
from bench.jobs import serve as serve_job

from repro.obs import compiles
from repro.obs import trace as obs_trace

CELLS = ("tiny-lm.train.tiny", "tiny-gqa.serve.tiny")
DATA = Path(__file__).parent / "data"


def canonical_hlo(text: str) -> str:
    """Compiled HLO text without metadata or source tables, instruction
    names numbered in the order they first appear (XLA derives the names'
    numbers from the ops' source locations, which scopes change)."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    lines = text.splitlines()
    first = next(i for i, l in enumerate(lines)
                 if l.startswith(("%", "ENTRY")))
    text = "\n".join(lines[:1] + lines[first:])
    names: dict = {}
    return re.sub(r"%([\w\-.]+)",
                  lambda m: "%" + names.setdefault(m.group(1),
                                                   f"v{len(names)}"), text)


@pytest.mark.parametrize("name", CELLS)
def test_scopes_add_no_op(tiny_root, monkeypatch, name):
    """The step and the wave compile to the same program with ``scope``
    patched to a null context, metadata and name numbers aside."""
    cell = harness.load_cell(name, tiny_root)
    family = scopes.GDA if cell.job == "train" else scopes.MODEL
    real = scopes._program_text(cell)
    found = {scopes.innermost(n, family)
             for n in scopes.hlo_op_names(real).values()}
    want = {"gda.grad", "gda.retract", "gda.track", "gda.mix",
            "gda.metrics"} if cell.job == "train" else \
        {"model.layers", "block.attention", "block.mlp", "model.head"}
    assert want <= found
    monkeypatch.setattr(obs_trace, "scope",
                        lambda n: contextlib.nullcontext())
    null = scopes._program_text(cell)
    assert canonical_hlo(real) == canonical_hlo(null)


HLO = """HloModule jit_probe, entry_computation_layout={(f32[8,8]{1,0})->f32[]}

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  ROOT %fusion.3 = f32[8,8]{1,0:T(8,128)} fusion(%p), kind=kOutput, calls=%f3, metadata={op_name="jit(probe)/gda.track/while/body/closed_call/gda.mix/dot_general" stack_frame_id=4}
}

ENTRY %main.9 (x.1: f32[8,8]) -> f32[] {
  %x.1 = f32[8,8]{1,0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[8,8]{1,0:T(8,128)} fusion(%x.1), kind=kLoop, calls=%f1, metadata={op_name="jit(probe)/transpose(jvp(gda.grad))/tanh"}
  %while.2 = (s32[], f32[8,8]{1,0}) while(%t), condition=%c, body=%body, metadata={op_name="jit(probe)/gda.track/while"}
  %copy-start = (f32[8,8]{1,0:T(8,128)S(1)}, f32[8,8]{1,0}, u32[]{:S(2)}) copy-start(%x.1)
  ROOT %fusion.4 = f32[]{:T(128)} fusion(%while.2), kind=kOutput, calls=%f4, metadata={op_name="jit(probe)/reduce_sum"}
}
"""


def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def synthetic(devices=1):
    """Device ops named as the profiler names them (operand shapes in):
    gda.grad 100 ns, the loop's own 100 ns in gda.track, two bodies of 100
    ns in gda.mix, 50 ns in no scope, and an op of another program (the
    same name, another shape) half inside the window."""
    ops = [_ev("%fusion.1 = f32[8,8]{1,0:T(8,128)} fusion(f32[8,8]{1,0} "
               "%x.1), kind=kLoop, calls=%f1", 0, 100),
           _ev("%while.2 = (s32[]{:T(128)}, f32[8,8]{1,0}) while((s32[], "
               "f32[8,8]{1,0}) %t), condition=%c, body=%body", 100, 300),
           _ev("%fusion.3 = f32[8,8]{1,0:T(8,128)} fusion(...)", 150, 100),
           _ev("%fusion.3 = f32[8,8]{1,0:T(8,128)} fusion(...)", 260, 100),
           _ev("%copy-start = (f32[8,8]{1,0:T(8,128)S(1)}, f32[8,8]{1,0}, "
               "u32[]{:S(2)}) copy-start(f32[8,8]{1,0} %x.1)", 400, 10),
           _ev("%fusion.4 = f32[]{:T(128)} fusion(...)", 410, 40),
           _ev("%fusion.1 = f32[16]{0} fusion(f32[16]{0} %a)", 500, 100)]
    planes = [types.SimpleNamespace(name=f"/device:TPU:{d}", lines=[
        types.SimpleNamespace(name="XLA Ops", events=ops)])
        for d in range(devices)]
    planes.append(types.SimpleNamespace(name="/host:CPU", lines=[
        types.SimpleNamespace(name="python3",
                              events=[_ev(bench_trace.WINDOW, 0, 550)])]))
    return bench_trace.reduce_profile(types.SimpleNamespace(planes=planes))


@pytest.mark.parametrize("devices", [1, 2])
def test_scope_seconds_on_a_small_trace(devices):
    secs = scopes.scope_seconds(synthetic(devices), scopes.hlo_op_names(HLO),
                                scopes.GDA)
    assert secs == pytest.approx({"gda.grad": 100e-9, "gda.track": 100e-9,
                                  "gda.mix": 200e-9, "other": 50e-9,
                                  "not_program": 50e-9})


def test_hlo_op_names_keys_and_innermost():
    hlo = scopes.hlo_op_names(HLO)
    assert hlo[("copy-start", "(f32[8,8], f32[8,8], u32[])")] == ""
    assert ("x.1", "f32[8,8]") in hlo
    assert scopes.innermost(hlo[("fusion.3", "f32[8,8]")],
                            scopes.GDA) == "gda.mix"
    assert scopes.innermost(hlo[("fusion.1", "f32[8,8]")],
                            scopes.GDA) == "gda.grad"
    assert scopes.innermost("jit(f)/model.layers/while/body/closed_call/"
                            "block.attention/dot_general",
                            scopes.MODEL) == "block.attention"
    assert scopes.innermost("jit(f)/model.layers/while/dynamic_slice",
                            scopes.MODEL) == "model.layers"


def _ctx(text, monkeypatch, **kw):
    monkeypatch.setattr(scopes, "_program_text", lambda cell: text)
    return types.SimpleNamespace(trace=synthetic(), cell=None, **kw)


def test_phase_readers(monkeypatch, capsys):
    ctx = _ctx(HLO, monkeypatch, steps=2)
    got = {m: harness.load_reader(m).read(ctx) for m in (
        "grad_ms.train", "retract_ms.train", "mix_ms.train",
        "tracking_ms.train")}
    assert got == pytest.approx({"grad_ms.train": 50e-6,
                                 "retract_ms.train": 0.0,
                                 "mix_ms.train": 100e-6,
                                 "tracking_ms.train": 50e-6})
    assert capsys.readouterr().err.count("scopes ") == 1


def test_pool_carry_reader(monkeypatch):
    text = HLO.replace("gda.grad", "block.mlp").replace("gda.mix",
                                                        "block.attention")
    text = text.replace("gda.track", "model.layers")
    ctx = _ctx(text, monkeypatch, waves=[(1, 8)] * 5)
    # the loop's own 100 ns and the 50 ns in no scope, over 5 waves
    assert harness.load_reader("wave_pool_carry_ms.serve").read(ctx) == \
        pytest.approx(150e-9 * 1e3 / 5)


def test_readers_report_nothing_without_scopes(monkeypatch):
    """A program that predates the scopes (or a trace with no device op)
    gives no reading, and raises nothing."""
    text = re.sub(r"(gda|model|block)\.\w+", "f", HLO)
    ctx = _ctx(text, monkeypatch, steps=2, waves=[(1, 8)])
    for m in ("grad_ms.train", "wave_pool_carry_ms.serve"):
        assert harness.load_reader(m).read(ctx) is None
    ctx.trace.ops.clear()
    assert harness.load_reader("mix_ms.train").read(ctx) is None


def test_recorded_v5e_scope_probe():
    """``record_scope_probe.py`` on one TPU v5 lite: the probe program
    (``gda.grad`` matmul, a 4-step scan in ``gda.track`` whose body is in
    ``gda.mix``, a reduction and XLA's copies in no scope) and another
    program (``not_program``), twice each, in a ``bench.window`` span;
    every op maps to its instruction in the probe's compiled HLO text or
    to the other program, and the scopes sum to the busy time."""
    from jax.profiler import ProfileData

    t = bench_trace.reduce_profile(ProfileData.from_file(
        str(DATA / "v5e_scope_probe.xplane.pb")))
    hlo = scopes.hlo_op_names((DATA / "v5e_scope_probe.hlo.txt").read_text())
    assert len(t.ops[0]) == 34
    secs = scopes.scope_seconds(t, hlo, scopes.GDA)
    assert secs == pytest.approx({"gda.grad": 3.006e-6, "gda.mix": 57.137e-6,
                                  "gda.track": 0.153e-6, "other": 12.277e-6,
                                  "not_program": 16.491e-6})
    assert sum(secs.values()) == pytest.approx(t.busy_s())
    ops = scopes.scope_op_seconds(t, hlo, scopes.GDA)
    assert list(ops["gda.mix"]) == ["convolution_sine_fusion.2 f32[512,512]"]
    assert list(ops["gda.track"]) == ["while (s32[]"]


def test_engine_spans_nest_in_the_benchmark_serve_step(tiny_root, tmp_path):
    """A CPU profiler trace of the tiny serving cell's window: every
    ``engine.wave.*`` span lies inside one of the benchmark's
    ``serve.step`` spans, each prefill inside a ``serve.admit``."""
    from jax.profiler import ProfileData

    cell = harness.load_cell("tiny-gqa.serve.tiny", tiny_root)
    rec = bench_trace.Recorder(True)
    seed = 2_913_000_777
    sz, params, engine, Sched = serve_job.build(cell, seed, 1.0, rec)
    jax.profiler.start_trace(str(tmp_path))
    serve_job.window(engine, Sched, cell.traffic, 1.0, seed, sz["vocab"],
                     rec)
    jax.profiler.stop_trace()
    (pb,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for p in ProfileData.from_file(pb).planes
           if p.name.startswith("/host:") for ln in p.lines
           for e in ln.events]

    def inside(name, outer):
        inner = [e for e in evs if e[0] == name]
        outs = [e for e in evs if e[0] == outer]
        assert inner and outs
        return all(any(a <= s and e <= b for _, a, b in outs)
                   for _, s, e in inner)

    for n in ("engine.wave.inputs", "engine.wave.launch",
              "engine.wave.fetch"):
        assert inside(n, "serve.step"), n
    assert inside("engine.prefill", "serve.admit")
    assert inside("serve.sched", "loop.sched")
    assert len([e for e in evs if e[0] == "engine.wave.launch"]) == \
        len(engine.waves)


class CountingRecorder:
    """A recorder that counts the compiles between ``start`` and
    ``stop`` (the window) and records no trace."""
    on = False

    def span(self, name):
        return contextlib.nullcontext()

    def start(self):
        self.before = compiles.snapshot()

    def stop(self):
        self.got = compiles.since(self.before)


@pytest.mark.parametrize("name", CELLS)
def test_no_compiles_in_window(tiny_root, name):
    cell = harness.load_cell(name, tiny_root)
    rec = CountingRecorder()
    out = harness.load_job(cell).run(cell, 2_913_000_555, 1.0, rec,
                                     time.perf_counter(), jax.devices()[:1])
    assert out["attempted"] > 0
    assert rec.got["compiles"] == 0 and rec.got["lowerings"] == 0, rec.got
