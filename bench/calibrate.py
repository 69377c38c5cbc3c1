"""Readings from which a cell's limits are set, in one process on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds <n> [<n> ...]
        [--control] [--faults <name> ...] [--seconds <s>]

For each seed it prints, as one JSON line, the numbers the cell compares
for the program (the lower readings), and with ``--control`` for the
control: the plain reference computed one precision step below the one
the configuration states (bfloat16 for float32 training, float8 e4m3
operands for bfloat16 serving).  Each job's ``calibrate`` does the
work.  ``--faults`` plants faults of
``bench/faults.py`` in the program and reads them too.  Training needs no
window: the readings come from the first steps.  Serving runs a window of
``--seconds`` at the cell's own load for every seed, with the engine's
weights redrawn from each seed.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _emit(**row) -> None:
    print(json.dumps(row), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = harness.load_cell(args.workload)
    try:
        harness.device_info(cell.chips)
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    harness.load_job(cell).calibrate(cell, args.seeds, args.control,
                                     args.faults, args.seconds, _emit)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
