"""Compile a cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 bench/aot_check.py <cell> [<cell> ...]

For each program the cell's window and its check run (each job's
``aot``: training compiles the jitted init, the step and the reference's
init and step; serving the decode wave and the largest prefill) it
prints the compile time and ``memory_analysis()``: what the chip's
compiler refuses here costs no chip time.  The kernels compile as
Pallas kernels (``REPRO_KERNEL_IMPL=pallas``, set by this script only).
"""
from __future__ import annotations

import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["REPRO_KERNEL_IMPL"] = "pallas"

import jax  # noqa: E402

from bench import harness  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)


def _report(name: str, lowered) -> None:
    t = time.perf_counter()
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    print(f"{name}: compile {time.perf_counter() - t:.1f} s; arguments "
          f"{m.argument_size_in_bytes} B, outputs {m.output_size_in_bytes} B, "
          f"temporaries {m.temp_size_in_bytes} B, aliased "
          f"{m.alias_size_in_bytes} B", flush=True)


def main(argv) -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    for name in argv:
        cell = harness.load_cell(name)
        print(f"== {name}", flush=True)
        harness.load_job(cell).aot(cell, chip, _report)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
