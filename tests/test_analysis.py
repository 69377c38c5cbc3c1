"""repro.analysis: lint rules, kernel checker, contracts, sentinels.

Covers the engine itself (every rule fires on a seeded bad fixture and
stays quiet on a good one), the satellite regressions (optimizer/serve
weak-type sweeps, the ServeEngine prefill-bucket recompile sentinel, the
doubly-stochastic channel sweep), and the CLI selftest.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (Finding, LintTarget, RecompileError,
                            RecompileSentinel, RULES, contracts,
                            kernel_check, lint)
from repro.analysis import entrypoints
from repro.launch import roofline


# ---------------------------------------------------------------------------
# jaxpr lint rules
# ---------------------------------------------------------------------------


def test_weak_type_leak_fires_and_passes():
    bad = {"y": jnp.full((4, 3), 0.5), "x": jnp.zeros((4, 2))}
    findings = RULES["weak-type-leak"](LintTarget(name="t", state=bad))
    assert len(findings) == 1 and "'y'" in findings[0].where
    good = jax.tree.map(lambda l: l.astype(l.dtype), bad)
    assert not RULES["weak-type-leak"](LintTarget(name="t", state=good))


def test_weak_type_dtype_whitelist():
    state = {"q": jnp.zeros((2,), jnp.int8), "s": jnp.zeros((2,))}
    assert not RULES["weak-type-leak"](
        LintTarget(name="t", state=state), allowed_dtypes=("int8", "float32"))
    findings = RULES["weak-type-leak"](
        LintTarget(name="t", state=state), allowed_dtypes=("float32",))
    assert len(findings) == 1 and "int8" in findings[0].message


def test_effect_in_quiet_path_fires(assert_jaxpr_rule):
    from jax.experimental import io_callback

    def noisy(x):
        io_callback(lambda a: None, None, x)
        return x + 1

    with pytest.raises(AssertionError, match="effect"):
        assert_jaxpr_rule("effect-in-quiet-path", fn=noisy,
                          args=(jnp.zeros((2,)),))
    assert_jaxpr_rule("effect-in-quiet-path", fn=lambda x: x + 1,
                      args=(jnp.zeros((2,)),))


def test_donation_miss_fires_on_collapsed_buffers(assert_jaxpr_rule):
    # two donated leaves, one output of that aval: one donation must miss
    def collapse(state):
        return state["a"] + state["b"]

    args = ({"a": jnp.zeros((4, 4)), "b": jnp.zeros((4, 4))},)
    with pytest.raises(AssertionError, match="donation-miss"):
        assert_jaxpr_rule("donation-miss", fn=collapse, args=args,
                          donate_argnums=(0,))

    # carried-state shape: every donated leaf reappears as an output
    def carry(state):
        return {"a": state["a"] * 2, "b": state["b"] + 1}

    assert_jaxpr_rule("donation-miss", fn=carry, args=args,
                      donate_argnums=(0,))


def test_comm_schedule_counts(assert_jaxpr_rule):
    # a plain matmul trips the forbidden-primitive check ...
    f = lambda a: a @ a
    args = (jnp.zeros((4, 4)),)
    with pytest.raises(AssertionError, match="dot_general"):
        assert_jaxpr_rule("comm-schedule", fn=f, args=args,
                          forbid_primitives=("dot_general",))
    # ... and elementwise code passes it
    assert_jaxpr_rule("comm-schedule", fn=lambda a: a + a, args=args,
                      forbid_primitives=("dot_general",))


def test_iter_eqns_descends_into_scan():
    from repro.analysis import count_primitive

    def f(x):
        return jax.lax.scan(lambda c, _: (jnp.sin(c), None), x, None,
                            length=3)[0]

    cj = jax.make_jaxpr(f)(jnp.zeros((2,)))
    assert count_primitive(cj, "sin") == 1     # inside the scan body


def test_lint_multi_rule_dispatch():
    target = LintTarget(name="t", state={"y": jnp.full((2,), 0.5)},
                        jaxpr=jax.make_jaxpr(lambda x: x + 1)(jnp.zeros(2)))
    findings = lint(target, ["weak-type-leak", "effect-in-quiet-path"])
    assert [f.rule for f in findings] == ["weak-type-leak"]


# ---------------------------------------------------------------------------
# RecompileSentinel
# ---------------------------------------------------------------------------


def test_sentinel_wrap_counts_traces():
    s = RecompileSentinel()
    f = s.wrap(lambda x: x * 2, label="double")
    f(jnp.zeros((2,)))
    f(jnp.ones((2,)))          # same shape: cached, no retrace
    s.check(max_traces=1)
    f(jnp.zeros((3,)))         # new shape: retrace
    assert s.traces("double") == 2
    with pytest.raises(RecompileError, match="double"):
        s.check(max_traces=1)


def test_sentinel_watch_existing_jitted():
    s = RecompileSentinel()
    g = jax.jit(lambda x: x + 1)
    g(jnp.zeros((2,)))
    s.watch("g", g)            # baseline snapshot: 1 compile already done
    g(jnp.ones((2,)))
    s.check(max_traces=0)      # no growth since the snapshot
    g(jnp.zeros((5,)))
    with pytest.raises(RecompileError):
        s.check(max_traces=0)


def test_sentinel_watch_rejects_plain_functions():
    with pytest.raises(TypeError):
        RecompileSentinel().watch("f", lambda x: x)


# ---------------------------------------------------------------------------
# kernel checker
# ---------------------------------------------------------------------------


def test_vmem_budget_clean_on_registered_configs():
    assert kernel_check.check_vmem(roofline.get_hardware("tpu_v5e")) == []


def test_vmem_budget_fires_on_oversized_block():
    findings = kernel_check.vmem_findings(
        "multi_hop_mix", {"block_f": 1 << 21},
        dims={"rows": 64, "out_rows": 32})
    assert findings and findings[0].rule == "vmem-budget"
    assert "exceeds" in findings[0].message


@pytest.mark.parametrize("r,over", [(576, True), (192, False), (512, False)])
def test_vmem_fused_retract_matches_chip_compiler(r, over):
    """At d = 576 the v5e compiler refuses r = 576 (padded to 640 lanes)
    and compiles r = 192; the model, the kernel's own budget check and the
    wrapper's refusal all agree."""
    hw = roofline.get_hardware("tpu_v5e")
    findings = kernel_check.vmem_findings("fused_retract", {"block_d": 256},
                                          dims={"r": r}, hw=hw)
    assert bool(findings) == over
    from repro.kernels import retract
    assert (retract.vmem_bytes(r, 256) > retract.VMEM_LIMIT_BYTES) == over


def test_vmem_footprint_scales_with_config():
    small = kernel_check.vmem_footprint("ring_mix", {}, {"block_rows": 8})
    big = kernel_check.vmem_footprint("ring_mix", {}, {"block_rows": 512})
    assert big == 64 * small


def test_vmem_footprint_unknown_kernel():
    with pytest.raises(KeyError, match="no footprint model"):
        kernel_check.vmem_footprint("nope", {}, {})


def test_tiling_contracts_clean():
    assert kernel_check.check_tiling() == []


def test_oracle_coverage_clean():
    assert kernel_check.check_oracle_coverage() == []


def test_oracle_coverage_fires_on_missing_oracle(tmp_path):
    # a dispatched kernel with estimates but no ref.* call and no tune entry
    bad = tmp_path / "ops.py"
    bad.write_text(
        "def rogue_kernel(x):\n"
        "    _est.record('rogue', None)\n"
        "    _tune.lookup('rogue', (1,), 'float32')\n"
        "    return x\n")
    findings = kernel_check.check_oracle_coverage(bad)
    msgs = "\n".join(f.message for f in findings)
    assert "no ref.py oracle" in msgs
    assert "estimates.KERNELS" in msgs
    assert "tune.DEFAULTS" in msgs


# ---------------------------------------------------------------------------
# numerical contracts
# ---------------------------------------------------------------------------


def test_matrix_findings_fire_on_substochastic():
    from repro.core.gossip import ring_matrix
    w = np.asarray(ring_matrix(6)) * 0.9
    findings = contracts.matrix_findings(w, where="scaled")
    assert any("row sums" in f.message for f in findings)
    assert not contracts.matrix_findings(np.asarray(ring_matrix(6)))


def test_matrix_findings_fire_on_asymmetry():
    w = np.asarray([[0.6, 0.4], [0.3, 0.7]])
    findings = contracts.matrix_findings(w)
    assert any("asymmetric" in f.message for f in findings)


@pytest.mark.parametrize("schedule", ["static", "round_robin", "matching"])
@pytest.mark.parametrize("drop,straggle", [(0.3, 0.0), (0.0, 0.3),
                                           (0.25, 0.25)])
def test_faulty_channels_stay_doubly_stochastic(schedule, drop, straggle):
    """Satellite: every ChannelModel edge schedule keeps effective W_t
    doubly stochastic across 100 seeded rounds."""
    from repro.comms.channel import ChannelModel
    from repro.core.gossip import ring_matrix
    ch = ChannelModel(np.asarray(ring_matrix(8), np.float32),
                      schedule=schedule, drop_rate=drop,
                      straggler_rate=straggle)
    assert contracts.doubly_stochastic_findings(ch, rounds=100) == []


def test_channel_sweep_clean():
    assert contracts.channel_sweep_findings(rounds=5) == []


def test_doubly_stochastic_fires_on_leaky_channel():
    class Leaky:
        def w_t(self, rnd, key):
            from repro.core.gossip import ring_matrix
            return jnp.asarray(ring_matrix(4), jnp.float32) * 0.95

    findings = contracts.doubly_stochastic_findings(Leaky(), rounds=2)
    assert findings and findings[0].rule == "doubly-stochastic"


def test_manifold_feasibility_clean():
    assert contracts.manifold_findings() == []


# ---------------------------------------------------------------------------
# entry points + satellites
# ---------------------------------------------------------------------------


def test_all_optimizer_inits_strongly_typed():
    """Satellite: weak-type-leak over all five optimizer families' inits."""
    assert entrypoints.pass_optimizer_state(None) == []


def test_optimizer_donations_alias():
    assert entrypoints.pass_optimizer_donation(None) == []


def test_quiet_paths_effect_free():
    assert entrypoints.pass_quiet_path(None) == []


def test_replica_group_strong_even_from_weak_params():
    """Satellite regression: ReplicaGroup must strong-cast while stacking —
    jnp.stack preserves weak_type from user-supplied params."""
    from repro.serve.replica import ReplicaGroup
    weak = {"embed": jnp.full((4, 8), 0.5),
            "scale": jnp.float32(2.0) * jnp.ones((3,))}
    assert any(l.weak_type for l in jax.tree.leaves(weak))   # fixture is bad
    rg = ReplicaGroup(weak, n_replicas=2)
    assert not RULES["weak-type-leak"](
        LintTarget(name="replica", state=rg.params))
    assert not RULES["weak-type-leak"](
        LintTarget(name="replica.comm", state=rg.state))


def test_selftest_catches_all_fixtures():
    assert entrypoints.selftest() == []


def test_cli_exits_clean_and_writes_summary(tmp_path):
    from repro.analysis.__main__ import main
    out = tmp_path / "analysis.json"
    # restrict to the cheap self-contained passes: kernel + contract checks
    rc = main(["--rules", "vmem-budget", "tiling", "oracle-coverage",
               "doubly-stochastic", "manifold-feasibility",
               "--json", str(out)])
    assert rc == 0
    import json
    summary = json.loads(out.read_text())
    assert summary["n_findings"] == 0
    assert set(summary["passes"]) == {"kernels", "contracts"}


# ---------------------------------------------------------------------------
# serve prefill-bucket sentinel (satellite)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_model():
    from repro import configs
    from repro.models import transformer as T
    cfg = configs.get_config("smollm-135m", smoke=True)
    return cfg, T.init_params(jax.random.PRNGKey(0), cfg)


def test_prefill_compiles_once_per_bucket(smoke_model):
    """Satellite: the page-bucketed prefill jit cache compiles exactly once
    per page bucket, never per request."""
    from repro.serve import PagedKVSpec, ServeEngine
    cfg, params = smoke_model
    spec = PagedKVSpec(page_size=8, n_pages=32, max_pages_per_slot=4)
    eng = ServeEngine(cfg, params, kv_spec=spec, n_slots=2)
    sentinel = RecompileSentinel()

    rng = np.random.default_rng(0)
    buckets_seen = set()
    # prompt lengths spanning two buckets (<=8 -> 1 page, 9..16 -> 2 pages),
    # several requests per bucket
    for i, length in enumerate([3, 8, 5, 9, 16, 12, 2, 11]):
        prompt = rng.integers(1, cfg.vocab_size, size=length).tolist()
        npg = spec.pages_for(length)
        pages = list(range(1 + 4 * (i % 2), 1 + 4 * (i % 2) + npg))
        eng.admit(i % 2, prompt, pages)
        buckets_seen.add(npg * spec.page_size)
        eng.step()
        eng.release(i % 2)

    assert buckets_seen == {8, 16}
    assert set(eng._prefill_fns) == buckets_seen       # one fn per bucket
    sentinel.watch("decode_step", eng._step)    # compiled once by now
    for cache_len, fn in eng._prefill_fns.items():
        sentinel.watch(f"prefill[{cache_len}]", fn)
        assert fn._cache_size() == 1, (cache_len, fn._cache_size())
    # replay the same workload: nothing may recompile
    for i, length in enumerate([6, 10, 8, 15]):
        prompt = rng.integers(1, cfg.vocab_size, size=length).tolist()
        npg = spec.pages_for(length)
        eng.admit(i % 2, prompt, list(range(1, 1 + npg)))
        eng.step()
        eng.release(i % 2)
    sentinel.check(max_traces=0)


# ---------------------------------------------------------------------------
# Finding plumbing
# ---------------------------------------------------------------------------


def test_finding_str_and_json():
    f = Finding("r", "w", "m")
    assert str(f) == "[r] w: m"
    assert f.to_json() == {"rule": "r", "where": "w", "message": "m"}
