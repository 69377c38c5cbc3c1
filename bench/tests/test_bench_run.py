"""``bench/run.py`` prints no result and exits non-zero without a TPU, and
in a tree that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

from bench import harness
from bench.tests.conftest import TRAIN_CELL

ARGS = ["--workload", TRAIN_CELL, "--seed", "2200000001", "--seconds", "1",
        "--trace", "0"]


def _run(root, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("REPRO_KERNEL_IMPL", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, str(root / "bench" / "run.py"),
                           *ARGS], capture_output=True, text=True, env=env,
                          cwd=root, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _run(harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_kernel_override_is_refused():
    p = _run(harness.ROOT, {"REPRO_KERNEL_IMPL": "ref"})
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
