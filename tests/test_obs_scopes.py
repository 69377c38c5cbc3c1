"""Device scopes, program spans and the compile counter of ``repro.obs``.

* every phase of the DRGDA step has its device scope in the compiled
  program's ``op_name`` metadata (``bench/tests/test_bench_scopes.py``
  shows that the scopes add no op);
* ``span`` is a profiler ``TraceAnnotation``; ``Trace.span`` and
  ``Telemetry.span`` enter one as well as keeping their Chrome-trace
  record;
* the serving engine and loop mark their host work with the ``engine.*`` /
  ``loop.*`` spans, in order, and none shares a name with a span of the
  benchmark;
* the compile counter counts a new program once and a cached one not at
  all.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import manifolds as M
from repro.core.gda import DRGDA, GDAHyper, broadcast_to_nodes
from repro.core.gossip import GossipSpec
from repro.core.minimax import MinimaxProblem, project_simplex
from repro.models import transformer as T
from repro.obs import Telemetry, compiles
from repro.obs import trace as obs_trace
from repro.serve import (ContinuousBatchingScheduler, PagedKVSpec, Request,
                         ServeEngine, serve_requests)

D, R, G, N = 10, 2, 3, 4
STEP_SCOPES = ("gda.grad", "gda.retract", "gda.track", "gda.mix",
               "gda.metrics")
#: the benchmark's own host spans (``bench/trace.HOST_SPANS``), which no
#: program span may share
BENCH_SPANS = ("bench.window", "batch.prepare", "step.dispatch", "step.wait",
               "serve.admit", "serve.step", "serve.sched")


def _toy_step_text():
    a = jnp.asarray(np.random.RandomState(0).randn(G, D, D), jnp.float32)

    def loss_fn(x, y, batch):
        lg = -jnp.einsum("dr,gde,er->g", x["w"], a + batch, x["w"])
        return jnp.dot(y, lg) - jnp.sum((y - 1.0 / G) ** 2)

    prob = MinimaxProblem(loss_fn=loss_fn, project_y=project_simplex,
                          manifold_map={"w": "stiefel"})
    opt = DRGDA(prob, GossipSpec(topology="ring", n_nodes=N), GDAHyper())
    x0 = broadcast_to_nodes(
        {"w": M.random_stiefel(jax.random.PRNGKey(1), D, R)}, N)
    y0 = jnp.full((N, G), 1.0 / G)
    batch = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (N, G, D, D))
    state = opt.init(x0, y0, batch)
    return opt.make_step(donate=False).lower(state, batch).compile() \
        .as_text()


def test_step_phases_have_scopes():
    names = set(re.findall(r'op_name="([^"]*)"', _toy_step_text()))
    for s in STEP_SCOPES:
        assert any(f"/{s}/" in n for n in names), s


def test_span_is_a_profiler_annotation():
    assert isinstance(obs_trace.span("engine.wave.launch"),
                      jax.profiler.TraceAnnotation)


@pytest.fixture
def span_names(monkeypatch):
    names = []

    @contextlib.contextmanager
    def record(name):
        names.append(name)
        yield

    monkeypatch.setattr(obs_trace, "span", record)
    return names


def test_trace_spans_are_program_spans(span_names, tmp_path):
    tel = Telemetry(run="spans", out_dir=str(tmp_path))
    with tel.span("train", steps=3):
        with tel.trace.span("eval", step=1):
            pass
    assert span_names == ["train", "eval"]
    assert [e["name"] for e in tel.trace.spans()] == ["eval", "train"]
    assert tel.trace.spans()[1]["args"] == {"steps": 3}


def test_serve_loop_spans(span_names):
    cfg = configs.get_config("smollm-135m", smoke=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    spec = PagedKVSpec(page_size=4, n_pages=17, max_pages_per_slot=4)
    engine = ServeEngine(cfg, params, kv_spec=spec, n_slots=2)
    sched = ContinuousBatchingScheduler(2, spec)
    ticks = iter(np.arange(0.0, 1e3, 0.05))  # compile time moves no arrival
    fin = serve_requests(engine, sched, [
        Request(prompt=[1, 2, 3], max_new_tokens=3, arrival=0.0),
        Request(prompt=[4, 5, 6, 7, 8], max_new_tokens=2, arrival=5.0)],
        clock=lambda: next(ticks))
    assert len(fin) == 2
    assert set(span_names) == {
        "engine.prefill", "engine.scatter", "engine.wave.inputs",
        "engine.wave.launch", "engine.wave.fetch", "loop.sched",
        "loop.tokens", "loop.wait"}
    assert not set(span_names) & set(BENCH_SPANS)
    wave = [n for n in span_names if n.startswith("engine.wave.")]
    assert wave == ["engine.wave.inputs", "engine.wave.launch",
                    "engine.wave.fetch"] * engine.steps_run
    first = span_names.index("engine.prefill")
    assert span_names[first - 1:first + 3] == [
        "loop.sched", "engine.prefill", "engine.scatter", "loop.tokens"]


def test_compile_counter_counts_new_programs_once():
    f = jax.jit(lambda x: jnp.tanh(x) * 3.0 + 1.0)
    x = jnp.ones((7, 5), jnp.float32)
    before = compiles.snapshot()
    f(x).block_until_ready()
    got = compiles.since(before)
    assert got["compiles"] == 1 and got["lowerings"] == 1
    assert got["compiles_s"] > 0
    y = x + 1.0
    before = compiles.snapshot()
    f(y).block_until_ready()
    assert compiles.since(before)["compiles"] == 0
