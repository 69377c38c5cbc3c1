"""Find a serving cell's knee: the highest offered rate it sustains.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates <r> ...

One process, one engine; per rate one window of the cell's mix at that
rate (``sweep`` of the cell's job), printed as a JSON line: requests,
failures, the backlog of due requests when the window closed, TTFT and
inter-token p95, and output tokens per second.  A rate above the knee
leaves a backlog that grows with the window.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = harness.load_cell(args.workload)
    try:
        harness.device_info(cell.chips)
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    harness.load_job(cell).sweep(
        cell, args.rates, args.seconds, args.seed,
        lambda **row: print(json.dumps(row), flush=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
