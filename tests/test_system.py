"""End-to-end system tests: real multi-step decentralized minimax training
on CPU (reduced configs), serving loop, and the launchers' CLIs."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.metric import convergence_metric
from repro.data.synthetic import TokenStream
from repro.launch.serve import generate
from repro.launch.steps import build_trainer, init_train_state
from repro.models import transformer as T


def test_end_to_end_decentralized_lm_training_loss_decreases():
    """Train the reduced smollm with DRSGDA for 30 steps: loss must drop,
    consensus must hold, Stiefel leaves must stay feasible."""
    cfg = configs.get_config("smollm-135m", smoke=True)
    n_nodes, bpn, seq = 4, 4, 32
    opt, problem = build_trainer(cfg, n_nodes, optimizer="drsgda")
    stream = TokenStream(n_nodes, bpn, seq, cfg.vocab_size,
                         n_groups=cfg.n_groups, seed=0)

    def to_jax(b):
        return {k: jnp.asarray(v) for k, v in b.items()}

    batch0 = to_jax(stream.batch(0))
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt, n_nodes, batch0)
    step = opt.make_step(donate=True)
    losses = []
    for t in range(30):
        state, metrics = step(state, to_jax(stream.batch(t + 1)))
        losses.append(float(metrics.loss))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5])
    m = convergence_metric(problem, state.x, state.y,
                           to_jax(stream.batch(99)))
    assert float(m["stiefel_residual"]) < 1e-3
    # adversary moved off uniform (groups genuinely differ)
    y_bar = np.asarray(state.y).mean(0)
    assert np.abs(y_bar - 1.0 / cfg.n_groups).max() > 1e-4


def test_drgda_vs_baseline_on_lm_smoke():
    """Both DRGDA and GT-GDA improve the deterministic objective; DRGDA
    keeps feasibility without re-projection."""
    cfg = configs.get_config("granite-3-2b", smoke=True)
    n_nodes = 2
    stream = TokenStream(n_nodes, 4, 32, cfg.vocab_size,
                         n_groups=cfg.n_groups, seed=1)
    full = {k: jnp.asarray(v) for k, v in stream.batch(0).items()}

    results = {}
    for name in ("drgda", "gt-gda"):
        opt, problem = build_trainer(cfg, n_nodes, optimizer=name)
        state = init_train_state(jax.random.PRNGKey(0), cfg, opt, n_nodes,
                                 full)
        step = opt.make_step(donate=False)
        first = last = None
        for t in range(15):
            state, metrics = step(state, full)
            if first is None:
                first = float(metrics.loss)
            last = float(metrics.loss)
        results[name] = (first, last)
    for name, (first, last) in results.items():
        assert last < first, (name, first, last)


def test_generate_loop_all_token_kinds():
    for arch in ("smollm-135m", "musicgen-large", "llama-3.2-vision-11b",
                 "xlstm-1.3b"):
        cfg = configs.get_config(arch, smoke=True)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        shape = (2, 8) if cfg.n_codebooks == 1 else (2, 8, cfg.n_codebooks)
        prompt = jax.random.randint(jax.random.PRNGKey(1), shape, 0,
                                    cfg.vocab_size)
        fe = None
        if cfg.frontend is not None:
            fe = 0.1 * jax.random.normal(
                jax.random.PRNGKey(2),
                (2, cfg.frontend.n_tokens, cfg.frontend.embed_dim))
        toks = generate(cfg, params, prompt, 4, frontend_embeds=fe,
                        temperature=0.0)
        assert toks.shape[:2] == (2, 4)
        assert int(toks.max()) < cfg.vocab_size


def test_train_cli_smoke(capsys):
    from repro.launch import train as train_cli
    rc = train_cli.main([
        "--arch", "smollm-135m", "--smoke", "--steps", "6", "--nodes", "2",
        "--batch-per-node", "2", "--seq-len", "32", "--eval-every", "3"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    rows = [json.loads(l) for l in out if l.startswith("{")]
    assert rows and np.isfinite(rows[-1]["loss"])


def test_serve_cli_smoke(capsys):
    from repro.launch import serve as serve_cli
    rc = serve_cli.main(["--arch", "smollm-135m", "--smoke", "--batch", "2",
                         "--prompt-len", "8", "--new-tokens", "4"])
    assert rc == 0


def test_serve_cli_takes_paged_path_for_gqa(capsys):
    from repro.launch import serve as serve_cli
    assert serve_cli._supports_paged(
        configs.get_config("smollm-135m", smoke=True))
    assert not serve_cli._supports_paged(
        configs.get_config("xlstm-1.3b", smoke=True))
    rc = serve_cli.main(["--arch", "smollm-135m", "--smoke", "--batch", "2",
                         "--prompt-len", "8", "--new-tokens", "4"])
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["mode"] == "paged" and res["tokens"] == 2 * 4


def test_serve_cli_paged_failure_propagates(monkeypatch):
    """A ValueError inside the paged run (as a kernel the chip's compiler
    refuses raises) is an error, not a cue to switch decode paths."""
    from repro.launch import serve as serve_cli

    def broken(*a, **k):
        raise ValueError("kernel refused")

    def legacy(*a, **k):
        raise AssertionError("fell back to the contiguous-cache path")

    monkeypatch.setattr(serve_cli, "_serve_engine", broken)
    monkeypatch.setattr(serve_cli, "_serve_legacy", legacy)
    with pytest.raises(ValueError, match="kernel refused"):
        serve_cli.main(["--arch", "smollm-135m", "--smoke", "--batch", "2",
                        "--prompt-len", "8", "--new-tokens", "4"])


def test_compile_cache_dir(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and is left to JAX; otherwise the
    cache sits at one fixed path in the checkout."""
    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        assert got == str(compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.DEFAULT_DIR.parent == \
            Path(__file__).resolve().parents[1]
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
