"""A whole serving run of a small cell on the CPU, past the look for a
chip: sound, it is correct; with a token altered where it is produced, or
with float8 operands in the program's place, it is not."""
from bench import faults, harness
from bench.jobs import serve as job
from bench.reference import llama
from bench.tests.conftest import run_tiny

CELL = "tiny-gqa.serve.tiny"


def test_sound_run_is_correct(tiny_root):
    r = run_tiny(tiny_root, CELL, seconds=2.0)
    line = r["line"]
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] == round(6.0 * 2.0) and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_itl_p95_ms",
                                   "serve_output_tokens_per_s", "setup_s"}
    assert r["out"]["detail"]["checked_tokens"] >= 40


def test_altered_token_is_not_correct(tiny_root):
    with faults.token_altered():
        r = run_tiny(tiny_root, CELL, seconds=2.0)
    assert r["line"]["correct"] is False


def test_float8_control_is_not_correct(tiny_root):
    from bench.trace import Recorder
    cell = harness.load_cell(CELL, tiny_root)
    tr, seed = cell.traffic, 2_200_000_654
    rec = Recorder(False)
    sz, params, engine, Sched = job.build(cell, seed, 2.0, rec)
    w = job.window(engine, Sched, tr, 2.0, seed, sz["vocab"], rec)
    chk = job.sample(w["requests"], w["failed"], seed, tr)
    gap = job.served_gaps(params, chk, sz, tr, num=llama.Numerics(fp8=True))
    checks = harness.checks_from({"served_gap": gap["control"]}, cell.limits)
    assert not all(c.ok for c in checks), checks
