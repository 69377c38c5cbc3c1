"""Observability subsystem tests.

Coverage, per the subsystem's contracts:

* obs on/off trajectories are **bit-identical** (counters never feed back
  into the math), including runs where the io_callback flush fires;
* the threaded wire counters agree with the analytical byte oracle
  (``backend.est_hop_bytes`` / ``CommEngine.wire_round_bytes``) within 1%;
* the JSONL event log validates against the checked-in schema, and
  malformed events are rejected;
* the Chrome-trace/Perfetto export round-trips;
* ``kernels/ops.py`` dispatch records analytical Estimates;
* ``launch/roofline.py`` hardware models resolve via env/explicit name and
  ``place()`` classifies compute- vs memory-bound correctly;
* importing ``launch/perf.py`` never clobbers ``XLA_FLAGS`` (satellite
  regression test);
* ``benchmarks/run.py`` summary records append with parsed metrics.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comms import CommSpec
from repro.core import manifolds as M
from repro.core.gda import DRGDA, DRSGDA, GDAHyper, broadcast_to_nodes
from repro.core.gossip import GossipSpec
from repro.core.minimax import MinimaxProblem, project_simplex
from repro.obs import (Telemetry, WireCounters, estimates as obs_est,
                       events as obs_events, unpack)
from repro.obs.telemetry import read_counter_series
from repro.obs.trace import Trace

D, R, G, N_NODES = 10, 2, 3, 6
RHO = 1.0
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_problem(seed=0):
    a = np.stack([np.random.RandomState(seed + i).randn(D, D)
                  for i in range(G)])
    a = jnp.asarray((a + np.swapaxes(a, 1, 2)) / 2, jnp.float32)

    def loss_fn(x, y, batch):
        ag = a + batch
        lg = -jnp.einsum("dr,gde,er->g", x["w"], ag, x["w"])
        return jnp.dot(y, lg) - RHO * jnp.sum((y - 1.0 / G) ** 2)

    return MinimaxProblem(loss_fn=loss_fn, project_y=project_simplex,
                          manifold_map={"w": "stiefel"})


def _init(seed=5):
    x0 = broadcast_to_nodes(
        {"w": M.random_stiefel(jax.random.PRNGKey(seed), D, R)}, N_NODES)
    y0 = jnp.full((N_NODES, G), 1.0 / G)
    return x0, y0


def _batches(seed=6, scale=0.1):
    return scale * jax.random.normal(jax.random.PRNGKey(seed),
                                     (N_NODES, G, D, D))


def _run(opt, steps=6):
    x0, y0 = _init()
    batches = _batches()
    state = opt.init(x0, y0, batches)
    step = opt.make_step(donate=False)
    for _ in range(steps):
        state, m = step(state, batches)
    jax.block_until_ready(m.loss)
    return state


# ---------------------------------------------------------------------------
# bit-identity + flush cadence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cls", [DRGDA, DRSGDA])
def test_trajectory_bit_identical_obs_on_off(cls, tmp_path):
    """Jitted trajectories with telemetry on (flushes firing) and off agree
    bit for bit — the counters never touch the update math."""
    prob = _make_problem()
    spec = GossipSpec(topology="ring", n_nodes=N_NODES)
    tel = Telemetry(run="bit", out_dir=str(tmp_path), flush_every=3)
    s_off = _run(cls(prob, spec, GDAHyper()))
    s_on = _run(cls(prob, spec, GDAHyper(), telemetry=tel))
    for leaf_on, leaf_off in zip(
            jax.tree.leaves((s_on.x, s_on.y, s_on.u, s_on.v)),
            jax.tree.leaves((s_off.x, s_off.y, s_off.u, s_off.v))):
        assert bool((leaf_on == leaf_off).all())
    # flushes really fired: call 1 plus every 3rd call
    steps_flushed = [ev["step"] for ev in read_counter_series(tel.events_path)]
    assert steps_flushed == [1, 3, 6]


def test_counters_cumulative_and_monotone(tmp_path):
    prob = _make_problem()
    spec = GossipSpec(topology="ring", n_nodes=N_NODES)
    tel = Telemetry(run="mono", out_dir=str(tmp_path), flush_every=2)
    _run(DRGDA(prob, spec, GDAHyper()), steps=6)  # obs-off runs stay clean
    _run(DRGDA(prob, spec, GDAHyper(), telemetry=tel), steps=6)
    rows = read_counter_series(tel.events_path)
    assert [r["step"] for r in rows] == [1, 2, 4, 6]
    for key in WireCounters._fields:
        series = [r["data"][key] for r in rows]
        assert series == sorted(series), key


# ---------------------------------------------------------------------------
# wire accounting vs the analytical oracle
# ---------------------------------------------------------------------------


def test_wire_counters_match_hop_oracle(tmp_path):
    """bytes/hop from the threaded counters == the hop-weighted mean of the
    backend's est_hop_bytes over DRGDA's four mixed slots, within 1%."""
    prob = _make_problem()
    spec = GossipSpec(topology="ring", n_nodes=N_NODES)
    tel = Telemetry(run="oracle", out_dir=str(tmp_path), flush_every=100)
    opt = DRGDA(prob, spec, GDAHyper(), telemetry=tel)
    steps = 4
    state = _run(opt, steps=steps)
    obs = unpack(state.obs)
    x0, y0 = _init()
    k = opt.k
    assert obs.rounds == steps * 4               # x, y, u, v per step
    assert obs.hops == steps * (3 * k + 1)       # x/y/u at k hops, v at 1
    assert obs.dropped_links == 0.0
    per_slot = {s: opt.backend.est_hop_bytes(spec, t) for s, t in
                (("x", x0), ("y", y0), ("u", x0), ("v", y0))}
    hops = {"x": k, "y": k, "u": k, "v": 1}
    expect = sum(per_slot[s] * hops[s] for s in hops) / sum(hops.values())
    got = obs.wire_bytes / obs.hops
    assert abs(got - expect) / expect < 0.01
    assert obs.wire_bytes == obs.raw_bytes       # engine-less: no compression


def test_wire_counters_compressed_engine(tmp_path):
    """Under an int8 CommEngine the wire bytes track wire_round_bytes —
    strictly below raw, and matching the engine's own accounting within 1%."""
    prob = _make_problem()
    comm = CommSpec(compressor="int8", gamma=0.9)
    spec = GossipSpec(topology="ring", n_nodes=N_NODES, comm=comm)
    tel = Telemetry(run="comp", out_dir=str(tmp_path), flush_every=100)
    opt = DRGDA(prob, spec, GDAHyper(), telemetry=tel)
    steps = 3
    state = _run(opt, steps=steps)
    obs = unpack(state.obs)
    x0, y0 = _init()
    k = opt.k
    expect_wire = expect_raw = 0.0
    for tree, hops in ((x0, k), (y0, k), (x0, k), (y0, 1)):   # x, y, u, v
        w, r = opt.engine.wire_round_bytes(tree, hops)
        expect_wire += float(w)
        expect_raw += float(r)
    assert abs(obs.wire_bytes - steps * expect_wire) / (steps * expect_wire) \
        < 0.01
    assert abs(obs.raw_bytes - steps * expect_raw) / (steps * expect_raw) \
        < 0.01
    # compression strictly helps, modestly here: multi-hop rounds still ship
    # k-1 full-precision hat hops (exactly what _gossip_hats executes)
    assert obs.wire_bytes < obs.raw_bytes


def test_wire_counters_quant_all_hops(tmp_path):
    """quant_hops="all": the k-1 tail hops ship int8 payloads (+ one f32
    scale per row), so the counters must follow est_quant_hop_bytes for the
    tail — strictly below the quant_hops="first" wire, still matching the
    engine's own accounting within 1%."""
    prob = _make_problem()
    comm = CommSpec(compressor="int8", gamma=0.9, quant_hops="all")
    spec = GossipSpec(topology="ring", n_nodes=N_NODES, comm=comm)
    tel = Telemetry(run="allhop", out_dir=str(tmp_path), flush_every=100)
    opt = DRGDA(prob, spec, GDAHyper(), telemetry=tel)
    steps = 3
    state = _run(opt, steps=steps)
    obs = unpack(state.obs)
    x0, y0 = _init()
    k = opt.k
    assert k > 1, "multi-hop gossip required to exercise the tail hops"
    eng_first = DRGDA(prob, GossipSpec(topology="ring", n_nodes=N_NODES,
                                       comm=CommSpec(compressor="int8",
                                                     gamma=0.9)),
                      GDAHyper()).engine
    expect_wire = expect_first = 0.0
    for tree, hops in ((x0, k), (y0, k), (x0, k), (y0, 1)):   # x, y, u, v
        w, _ = opt.engine.wire_round_bytes(tree, hops)
        wf, _ = eng_first.wire_round_bytes(tree, hops)
        expect_wire += float(w)
        expect_first += float(wf)
        # the tail accounting really is the int8 oracle
        if hops > 1:
            per_tail = opt.engine.backend.est_quant_hop_bytes(
                opt.engine.gossip, tree)
            per_fp32 = opt.engine.backend.est_hop_bytes(
                opt.engine.gossip, tree)
            assert per_tail < per_fp32
            assert abs((w - wf) - (hops - 1) * (per_tail - per_fp32)) < 1e-6
    assert expect_wire < expect_first
    assert abs(obs.wire_bytes - steps * expect_wire) / (steps * expect_wire) \
        < 0.01
    assert obs.wire_bytes < obs.raw_bytes


# ---------------------------------------------------------------------------
# event log + schema
# ---------------------------------------------------------------------------


def test_event_log_validates_and_rejects_malformed(tmp_path):
    tel = Telemetry(run="ev", out_dir=str(tmp_path))
    tel.event("dashboard", {"M_t": 1.0}, step=10)
    n = obs_events.validate_log(tel.events_path)
    assert n == 2                                 # meta + dashboard
    with pytest.raises(ValueError):               # unknown type enum
        obs_events.make_event("bogus_type", "ev", {})
    with pytest.raises(ValueError):               # data must be an object
        obs_events.validate_event(
            {"type": "counters", "ts": 0.0, "run": "ev", "data": 3})
    with pytest.raises(ValueError):               # missing required field
        obs_events.validate_event({"type": "counters", "ts": 0.0, "run": "ev"})
    bad = tmp_path / "bad.events.jsonl"
    bad.write_text(json.dumps({"type": "span", "run": "ev", "data": {}})
                   + "\n")                        # no ts
    with pytest.raises(ValueError):
        obs_events.validate_log(str(bad))


def test_dashboard_streams_metric_components(tmp_path):
    prob = _make_problem()
    tel = Telemetry(run="dash", out_dir=str(tmp_path))
    x0, y0 = _init()
    ev = tel.dashboard(prob, x0, y0, _batches(), step=7, extra={"loss": 1.5})
    data = ev["data"]
    for key in ("M_t", "grad_norm", "consensus_x", "loss"):
        assert key in data, key
    assert "w" in data["drift"]                   # per-leaf cross-node drift
    assert obs_events.validate_log(tel.events_path) == 2


# ---------------------------------------------------------------------------
# trace round-trip
# ---------------------------------------------------------------------------


def test_trace_perfetto_roundtrip(tmp_path):
    tr = Trace(run="rt")
    with tr.span("outer", step=1):
        with tr.span("inner"):
            pass
    tr.counter("wire", {"wire_bytes": 123.0})
    payload = tr.to_chrome_trace()
    assert payload["otherData"]["run"] == "rt"
    phases = sorted(e["ph"] for e in payload["traceEvents"])
    assert phases == ["C", "X", "X"]
    spans = {e["name"]: e for e in payload["traceEvents"] if e["ph"] == "X"}
    assert spans["inner"]["dur"] <= spans["outer"]["dur"]
    path = tr.save(str(tmp_path / "t.trace.json"))
    rt = Trace.load(path)
    assert rt.run == "rt"
    assert rt.events == tr.events


# ---------------------------------------------------------------------------
# kernel estimates
# ---------------------------------------------------------------------------


def test_ops_dispatch_records_estimates():
    from repro.kernels import ops

    x = M.random_stiefel(jax.random.PRNGKey(0), 32, 4)
    g = jax.random.normal(jax.random.PRNGKey(1), (32, 4))
    with obs_est.collect() as c:
        jax.block_until_ready(ops.stiefel_project(x, g))
        jax.block_until_ready(ops.fused_retract(x, 0.1 * g))
    snap = c.snapshot()
    assert set(snap) == {"stiefel_project", "fused_retract"}
    expect = obs_est.stiefel_project_est(32, 4)
    rec = snap["stiefel_project"]
    assert rec["calls"] == 1
    assert rec["ops"] == expect.ops
    assert rec["mem"] == expect.mem
    assert rec["intensity"] == pytest.approx(expect.intensity)
    # under jit the wrapper records once per trace, not per execution
    f = jax.jit(lambda a, b: ops.stiefel_project(a, b))
    with obs_est.collect() as c2:
        for _ in range(5):
            jax.block_until_ready(f(x, g))
    assert c2.snapshot()["stiefel_project"]["calls"] == 1


def test_estimates_algebra():
    e = obs_est.Estimates(ops=100.0, lds=20.0, mem=10.0)
    assert (e + e).ops == 200.0
    assert e.scaled(3).mem == 30.0
    assert e.intensity == 10.0
    assert set(obs_est.KERNELS) == {"flash_attention", "stiefel_project",
                                    "fused_retract", "ring_mix", "quant_mix",
                                    "multi_hop_mix", "multi_hop_mix_quant",
                                    "paged_decode"}


# ---------------------------------------------------------------------------
# hardware models + roofline placement
# ---------------------------------------------------------------------------


def test_hardware_model_selection(monkeypatch):
    from repro.launch import roofline

    monkeypatch.delenv("REPRO_HW", raising=False)
    assert roofline.get_hardware().name == "tpu_v5e"
    monkeypatch.setenv("REPRO_HW", "tpu_v4")
    assert roofline.get_hardware().name == "tpu_v4"
    assert roofline.get_hardware("tpu_v5p").name == "tpu_v5p"  # explicit wins
    with pytest.raises(ValueError):
        roofline.get_hardware("tpu_v9000")
    hw = roofline.HARDWARE["tpu_v5e"]
    assert roofline.PEAK_FLOPS == hw.peak_flops    # legacy constants track


def test_hardware_model_from_device_kind(monkeypatch):
    """With a TPU attached the chip's own model is used, looked up by its
    ``device_kind``; an unknown kind, or a name for another chip, is an
    error rather than a default."""
    import types

    from repro.launch import roofline

    assert roofline.hardware_for_kind("TPU v5 lite").name == "tpu_v5e"
    assert roofline.hardware_for_kind("TPU v4").name == "tpu_v4"
    with pytest.raises(ValueError, match="device_kind"):
        roofline.hardware_for_kind("TPU v99")

    def attach(kind):
        dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
        monkeypatch.setattr(jax, "devices", lambda *a, **k: [dev])

    monkeypatch.delenv("REPRO_HW", raising=False)
    attach("TPU v5 lite")
    assert roofline.get_hardware().name == "tpu_v5e"
    assert roofline.get_hardware("tpu_v5e").name == "tpu_v5e"
    with pytest.raises(ValueError, match="attached chip"):
        roofline.get_hardware("tpu_v4")
    monkeypatch.setenv("REPRO_HW", "tpu_v5p")
    with pytest.raises(ValueError, match="attached chip"):
        roofline.get_hardware()
    monkeypatch.delenv("REPRO_HW")
    attach("TPU v99")
    with pytest.raises(ValueError, match="device_kind"):
        roofline.get_hardware()


def test_roofline_place_classifies_bound():
    from repro.launch import roofline

    hw = roofline.get_hardware("tpu_v5e")
    hot = obs_est.Estimates(ops=1e12, lds=1e6, mem=1e6)     # high intensity
    cold = obs_est.Estimates(ops=1e6, lds=1e9, mem=1e9)     # streaming
    assert roofline.place(hot, hw)["bound"] == "compute"
    assert roofline.place(cold, hw)["bound"] == "memory"
    p = roofline.place(cold, hw)
    assert p["attainable_flops"] == pytest.approx(hw.hbm_bw * cold.intensity)
    assert p["time_s"] == pytest.approx(cold.ops / p["attainable_flops"])


# ---------------------------------------------------------------------------
# satellites: perf.py XLA_FLAGS + BENCH_summary
# ---------------------------------------------------------------------------


def test_perf_import_does_not_clobber_xla_flags():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = os.path.join(_REPO_ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c",
         "import os, repro.launch.perf; print(repr(os.environ.get('XLA_FLAGS')))"],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "None"


def test_perf_dryrun_flags_respect_user_env(monkeypatch):
    from repro.launch import perf

    monkeypatch.setenv("XLA_FLAGS", "--user_set=1")
    assert perf._set_dryrun_xla_flags() == "--user_set=1"
    monkeypatch.delenv("XLA_FLAGS")
    monkeypatch.setenv("REPRO_DRYRUN_XLA_FLAGS", "--custom=2")
    assert perf._set_dryrun_xla_flags() == "--custom=2"
    monkeypatch.delenv("XLA_FLAGS")
    monkeypatch.delenv("REPRO_DRYRUN_XLA_FLAGS")
    assert perf._set_dryrun_xla_flags() == perf.DEFAULT_DRYRUN_XLA_FLAGS


def test_bench_summary_append(tmp_path, monkeypatch):
    from benchmarks import run as bench_run

    path = tmp_path / "BENCH_summary.json"
    monkeypatch.setattr(bench_run, "SUMMARY_PATH", str(path))
    bench_run.append_summary("obs", 123.4,
                             "overhead_pct=3.21;bit_identical=True", rev="abc")
    bench_run.append_summary("mix", 9.9, "hps=100.5", rev="abc")
    rows = json.loads(path.read_text())
    assert [r["name"] for r in rows] == ["obs", "mix"]
    assert rows[0]["metrics"]["overhead_pct"] == 3.21
    assert rows[0]["metrics"]["bit_identical"] == "True"
    assert rows[0]["git_rev"] == "abc"
    assert rows[0]["us_per_call"] == 123.4
    assert "timestamp" in rows[0]


def test_bench_harness_fails_on_entry_error(monkeypatch, capsys):
    """Every entry runs and is reported; any failure fails the run."""
    from benchmarks import run as bench_run

    def boom():
        raise RuntimeError("broken entry")

    ran = []
    monkeypatch.setattr(bench_run, "ALL", {"boom": boom,
                                           "ok": lambda: ran.append(1)
                                           or (1.0, "x=1")})
    monkeypatch.setattr(bench_run, "append_summary", lambda *a, **k: None)
    monkeypatch.setattr(sys, "argv", ["run.py", "boom", "ok"])
    assert bench_run.main() == 1
    assert ran == [1]
    assert "boom,nan,ERROR:RuntimeError:broken entry" in capsys.readouterr().out
    monkeypatch.setattr(sys, "argv", ["run.py", "ok"])
    assert bench_run.main() == 0


def test_mix_bench_refuses_tpu_instead_of_spawning(monkeypatch):
    from benchmarks import mix_backend

    def no_spawn(*a, **k):
        raise AssertionError("spawned a worker process")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mix_backend.subprocess, "run", no_spawn)
    with pytest.raises(RuntimeError, match="holds a TPU"):
        mix_backend.run()
