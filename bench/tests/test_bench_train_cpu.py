"""A whole training run of a small cell on the CPU, past the look for a
chip: sound, it is correct; with the timed path broken underneath, or
with the reference in bfloat16 in the program's place, it is not."""
import pytest

from bench import faults, harness
from bench.jobs import train as job
from bench.reference import llama
from bench.tests.conftest import run_tiny

CELL = "tiny-lm.train.tiny"


def test_sound_run_is_correct(tiny_root):
    r = run_tiny(tiny_root, CELL)
    line = r["line"]
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == set(job.READINGS)


@pytest.mark.parametrize("fault", sorted(faults.TRAIN))
def test_fault_is_not_correct(tiny_root, fault):
    with faults.TRAIN[fault]():
        r = run_tiny(tiny_root, CELL)
    assert r["line"]["correct"] is False, (fault, r["line"]["checks"])


def test_bfloat16_control_is_not_correct(tiny_root):
    cell = harness.load_cell(CELL, tiny_root)
    tr, seed = cell.traffic, 2_200_000_321
    sz, step, feed, key, state = job.build(cell, seed)
    del state, step
    want = job.reference(feed, key, sz, tr)
    ctl = job.reference(feed, key, sz, tr, num=llama.Numerics(
        dtype="bfloat16", precision="default"))
    checks = harness.checks_from(job.readings(ctl, want), cell.limits)
    assert not all(c.ok for c in checks), checks
