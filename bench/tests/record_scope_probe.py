"""Record the scope probe on a chip: ``python3 bench/tests/record_scope_probe.py <out>``.

A small jitted function with device scopes (``gda.grad`` around a matmul,
``gda.track`` around a four-step scan whose body runs in ``gda.mix``, a
reduction in none) and a second jitted function with none run twice each
inside a ``bench.window`` span under the profiler.  Writes
``<out>/v5e_scope_probe.xplane.pb`` and the probe's compiled HLO text,
``<out>/v5e_scope_probe.hlo.txt``, which ``test_bench_scopes.py`` reads.
"""
import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.obs.trace import scope  # noqa: E402


def probe(x, w):
    with scope("gda.grad"):
        y = jnp.tanh(x @ w)

    def body(c, _):
        with scope("gda.mix"):
            return jnp.sin(c @ w), None

    with scope("gda.track"):
        y, _ = jax.lax.scan(body, y, None, length=4)
    return jnp.sum(y * y)


def main(out: str) -> int:
    jax.config.update("jax_enable_compilation_cache", False)
    x = jax.random.normal(jax.random.PRNGKey(0), (512, 512), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (512, 512), jnp.float32)
    jp = jax.jit(probe)
    other = jax.jit(lambda a: jnp.cos(a) * 2.0)
    jax.block_until_ready((jp(x, w), other(x)))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        time.sleep(0.02)    # margin for the host and device clocks
        for _ in range(2):
            jax.block_until_ready((jp(x, w), other(x)))
        time.sleep(0.02)
    jax.profiler.stop_trace()
    os.makedirs(out, exist_ok=True)
    (pb,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    shutil.copy(pb, os.path.join(out, "v5e_scope_probe.xplane.pb"))
    with open(os.path.join(out, "v5e_scope_probe.hlo.txt"), "w") as f:
        # source paths relative to the checkout
        f.write(jp.lower(x, w).compile().as_text().replace(f"{ROOT}/", ""))
    print(jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
