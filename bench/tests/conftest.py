"""Fixtures: a small benchmark tree that the harness runs on the CPU.

``tiny_root`` is a directory laid out like the repository's root (a
``BENCHMARK.json`` and a ``bench/`` with configurations, traffic, limits,
jobs and metric readers), holding two small cells: ``tiny-lm.train.tiny``
(the training job on SmolLM's reduced configuration, 4 nodes, 64-token
sequences) and ``tiny-gqa.serve.tiny`` (the serving job on Granite's
reduced configuration, 4 slots).  Each takes the limits of the full-size
cell whose job it runs, so the CPU tests hold the same limits the chip
runs do.
"""
from __future__ import annotations

import json
import shutil
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
TRAIN_CELL = "smollm-135m.train.n4-s2048"
SERVE_CELL = "granite-3-2b.serve.chat"

TINY_LM = {"hidden_size": 96, "intermediate_size": 256,
           "num_hidden_layers": 2, "num_attention_heads": 3,
           "num_key_value_heads": 1, "head_dim": 32, "vocab_size": 256,
           "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
           "tie_word_embeddings": True, "dtype": "float32",
           "repo_config": "smollm-135m", "repo_variant": "smoke",
           "program_changes": {"tie_embeddings": True}}
TINY_GQA = {"hidden_size": 128, "intermediate_size": 256,
            "num_hidden_layers": 2, "num_attention_heads": 8,
            "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
            "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
            "tie_word_embeddings": True, "dtype": "bfloat16",
            "repo_config": "granite-3-2b", "repo_variant": "smoke",
            "program_changes": {"tie_embeddings": True}}


def _load(path: Path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path: Path) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_root(root: Path) -> Path:
    bench = root / "bench"
    for d in ("jobs", "metrics"):
        shutil.copytree(REPO / "bench" / d, bench / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "bench" / "peaks.json", bench / "peaks.json")
    _dump(TINY_LM, bench / "configs" / "tiny-lm.json")
    _dump(TINY_GQA, bench / "configs" / "tiny-gqa.json")
    train = _load(REPO / "bench" / "traffic" / "train.n4-s2048.json")
    _dump(dict(train, seq_len=64), bench / "traffic" / "train.tiny.json")
    serve = _load(REPO / "bench" / "traffic" / "serve.chat.json")
    serve.update(rate_per_s=6.0, page_size=16, slots=4, max_context=160,
                 pool_pages=40, drain_cap_s=30,
                 prompt={"median": 24, "sigma": 0.9, "min": 4, "max": 96},
                 output={"median": 8, "sigma": 0.8, "min": 2, "max": 48},
                 sample={"min_tokens": 40, "max_requests": 4})
    _dump(serve, bench / "traffic" / "serve.tiny.json")
    (bench / "limits").mkdir()
    shutil.copy(REPO / "bench" / "limits" / f"{TRAIN_CELL}.json",
                bench / "limits" / "tiny-lm.train.tiny.json")
    shutil.copy(REPO / "bench" / "limits" / f"{SERVE_CELL}.json",
                bench / "limits" / "tiny-gqa.serve.tiny.json")
    spec = _load(REPO / "BENCHMARK.json")
    rename = {TRAIN_CELL: "tiny-lm.train.tiny",
              SERVE_CELL: "tiny-gqa.serve.tiny"}
    spec["workloads"] = [
        {"name": "tiny-lm.train.tiny", "config": "tiny-lm",
         "traffic": "train.tiny", "chips": 1, "why": "CPU test"},
        {"name": "tiny-gqa.serve.tiny", "config": "tiny-gqa",
         "traffic": "serve.tiny", "chips": 1, "why": "CPU test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]]
    _dump(spec, root / "BENCHMARK.json")
    return root


@pytest.fixture
def tiny_root(tmp_path) -> Path:
    return make_root(tmp_path)


CPU_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def run_tiny(root: Path, cell: str, seed: int = 2_200_000_123,
             seconds: float = 0.5, trace: bool = False) -> dict:
    """A whole run of a tiny cell past the harness's look for a chip."""
    from bench import harness
    return harness.run_cell(harness.load_cell(cell, root), seed, seconds,
                            trace, CPU_DEVICE, time.perf_counter())
