"""Cells, configurations and metric readers are found by name, and new ones
are picked up from new files alone."""
import json
import types

import pytest

from bench import harness
from bench.tests.conftest import TRAIN_CELL, SERVE_CELL


def test_every_cell_of_the_benchmark_loads():
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.chips == w["chips"] and cell.config and cell.traffic
        assert harness.load_job(cell).run
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert harness.load_reader(m["name"]).read
            assert m["moves"] in {e["name"] for e in cell.end_to_end}
        assert set(harness.load_job(cell).READINGS) == set(cell.limits)


def test_configuration_files_match_the_program():
    for cell in (TRAIN_CELL, SERVE_CELL):
        c = harness.load_cell(cell)
        extra = {"n_groups": c.traffic["n_groups"],
                 "rho": c.traffic["rho"]} if c.job == "train" else {}
        cfg = harness.program_config(c, **extra)
        assert cfg.tie_embeddings is True
    bad = harness.load_cell(SERVE_CELL)
    bad.config = dict(bad.config, hidden_size=1024)
    with pytest.raises(ValueError, match="hidden_size"):
        harness.program_config(bad)


def test_unknown_names_are_errors(tiny_root):
    with pytest.raises(KeyError):
        harness.load_cell("no-such.cell", tiny_root)
    with pytest.raises(FileNotFoundError):
        harness.load_reader("no_such_metric", tiny_root)
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v99")


def test_new_files_are_picked_up_without_edits(tiny_root):
    """A new configuration, traffic mix, cell and per-layer metric are new
    files plus entries in BENCHMARK.json; no harness file changes."""
    bench = tiny_root / "bench"
    cfg = json.loads((bench / "configs" / "tiny-lm.json").read_text())
    cfg["rope_theta"] = 500000.0
    cfg["program_changes"] = dict(cfg["program_changes"], rope_theta=500000.0)
    (bench / "configs" / "tiny-lm-b.json").write_text(json.dumps(cfg))
    tr = json.loads((bench / "traffic" / "train.tiny.json").read_text())
    (bench / "traffic" / "train.tiny-b.json").write_text(
        json.dumps(dict(tr, seq_len=32)))
    (bench / "limits" / "tiny-lm-b.train.tiny-b.json").write_text(
        (bench / "limits" / "tiny-lm.train.tiny.json").read_text())
    (bench / "metrics" / "steps.train.py").write_text(
        "def read(ctx):\n    return ctx.steps\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny-lm-b.train.tiny-b",
                              "config": "tiny-lm-b",
                              "traffic": "train.tiny-b", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "steps.train", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "train_tokens_per_s",
                              "workloads": ["tiny-lm-b.train.tiny-b"]})
    for m in spec["end_to_end"]:
        if "workloads" in m and "tiny-lm.train.tiny" in m["workloads"]:
            m["workloads"].append("tiny-lm-b.train.tiny-b")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("tiny-lm-b.train.tiny-b", tiny_root)
    assert cell.config["rope_theta"] == 500000.0
    assert cell.traffic["seq_len"] == 32
    assert "steps.train" in [m["name"] for m in cell.per_layer]
    assert "steps.train" not in [
        m["name"] for m in harness.load_cell("tiny-lm.train.tiny",
                                             tiny_root).per_layer]
    reader = harness.load_reader("steps.train", tiny_root)
    assert reader.read(types.SimpleNamespace(steps=7)) == 7
    assert harness.program_config(cell, n_groups=8, rho=1.0).rope_theta \
        == 500000.0


def test_checks_and_result_line():
    checks = harness.checks_from({"a": 0.5, "b": 2.0},
                                 {"a": {"limit": 1.0}, "b": {"limit": 1.0}})
    line = harness.result_line(checks=checks, attempted=3, failed=0,
                               metrics={}, device={"platform": "tpu"})
    assert line["correct"] is False and list(line)[-1] == "checks"
    assert line["checks"]["b"] == {"value": 2.0, "limit": 1.0}
    with pytest.raises(KeyError):
        harness.checks_from({"c": 1.0}, {"a": {"limit": 1.0}})
    nan = harness.checks_from({"a": float("nan")}, {"a": {"limit": 1.0}})
    assert not nan[0].ok
