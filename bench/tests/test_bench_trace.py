"""The reduction from a profiler trace to busy time, idle gaps by host
span, and kernel time, on a small trace laid out as the profiler lays out
one chip's run (device plane ``/device:TPU:0`` with its ``XLA Ops`` line,
host plane with the benchmark's spans)."""
import types

import pytest

from bench import trace


def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


def recorded():
    ops = [_ev("fusion.1", 100, 200), _ev("fusion.2", 300, 100),
           _ev("copy.3", 600, 100), _ev("flash_attention.4", 800, 100),
           _ev("fusion.1", 950, 150), _ev("fusion.1", 1200, 100)]
    host = [_ev(trace.WINDOW, 0, 1000), _ev("step.dispatch", 0, 120),
            _ev("step.wait", 400, 200), _ev("batch.prepare", 700, 90),
            _ev("unrelated", 0, 1000)]
    return types.SimpleNamespace(planes=[
        _plane("/host:metadata", []),
        _plane("/device:TPU:0", [_line("XLA Modules", [_ev("m", 0, 1000)]),
                                 _line("XLA Ops", ops)]),
        _plane("/device:TPU:0 SparseCore", [_line("XLA Ops", ops)]),
        _plane("/host:CPU", [_line("python3", host)])])


def test_busy_idle_and_window_clip():
    t = trace.reduce_profile(recorded())
    assert list(t.ops) == [0] and t.window == (0, 1000)
    # 100-400, 600-700, 800-900 and 950-1000 (clipped); 1200- is outside
    assert t.busy_s() == pytest.approx(550e-9)
    assert t.idle_share() == pytest.approx(0.45)
    assert t.window_s == pytest.approx(1e-6)


def test_idle_gaps_by_host_span():
    gaps = dict(trace.reduce_profile(recorded()).idle_gaps())
    assert gaps == pytest.approx({"step.wait": 200e-9,
                                  "step.dispatch": 100e-9,
                                  "batch.prepare": 100e-9,
                                  "none": 50e-9})


def test_kernel_time_and_top_ops():
    t = trace.reduce_profile(recorded())
    secs, n = t.op_seconds(lambda name: "flash_attention" in name)
    assert (secs, n) == (pytest.approx(100e-9), 1)
    top = t.top_ops()
    assert top[0] == ["fusion.1", pytest.approx(250e-9)]
    assert {n for n, _ in top} == {"fusion.1", "fusion.2", "copy.3",
                                   "flash_attention.4"}
    assert t.span_durations("step.wait") == [pytest.approx(200e-9)]


def test_nested_ops_count_their_own_time():
    ops = [_ev("%while.5 = (s32[]) while(...)", 0, 100),
           _ev("%fusion.1 = f32[4,8]{1,0} fusion(...)", 10, 30),
           _ev("%paged_decode.2 = bf16[2]{0} custom-call(...)", 50, 40)]
    host = [_ev(trace.WINDOW, 0, 100)]
    pd = types.SimpleNamespace(planes=[
        _plane("/device:TPU:0", [_line("XLA Ops", ops)]),
        _plane("/host:CPU", [_line("python3", host)])])
    t = trace.reduce_profile(pd)
    assert dict(t.top_ops()) == pytest.approx({
        "while.5 (s32[])": 30e-9, "fusion.1 f32[4,8]": 30e-9,
        "paged_decode.2 bf16[2]": 40e-9})
    assert t.busy_s() == pytest.approx(100e-9)


def test_missing_window_is_an_error():
    pd = recorded()
    pd.planes[-1].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        trace.reduce_profile(pd)


def test_recorded_v5e_trace():
    """A trace recorded on one TPU v5 lite: a jitted flash attention call and
    a small matmul scan, twice, inside a ``bench.window`` span."""
    from pathlib import Path

    from jax.profiler import ProfileData

    path = Path(__file__).parent / "data" / "v5e_flash_probe.xplane.pb"
    t = trace.reduce_profile(ProfileData.from_file(str(path)))
    assert list(t.ops) == [0] and len(t.ops[0]) == 40
    assert t.window_s == pytest.approx(3.31979e-3)
    secs, n = t.op_seconds(lambda name: "flash_attention" in name)
    assert n == 1 and secs == pytest.approx(73.667e-6)
    assert t.top_ops()[0] == ["flash_attention.1 f32[1,9,512,64]",
                              pytest.approx(73.667e-6)]
    assert t.busy_s() == pytest.approx(104.9e-6)
    assert [n for n, _ in t.idle_gaps()] == ["step.dispatch", "none"]
