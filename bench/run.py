"""Run one benchmark cell on the chip and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the cell's chips: it loads the program, warms up every
shape the cell uses (``setup_s``), measures for ``--seconds``, checks what
the timed path produced against the plain reference, and prints one JSON
line last.  With ``--trace 0`` its metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a profiler
trace of the window.  There is no CPU fallback: without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result.  The numbers compared with the reference go last on standard
error and last in the result line, each with its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_KERNEL_IMPL"):
        print("bench: REPRO_KERNEL_IMPL is set; it would route the kernels "
              "under test to other implementations", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.load_cell(args.workload)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    try:
        device = harness.device_info(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), device, T_START)
    harness.print_checks(result["checks_list"])
    print(json.dumps(result["line"]), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
