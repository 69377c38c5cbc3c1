"""JAX persistent compilation cache: one fixed place per checkout.

The cache key includes the directory, so a path that moves between runs
(a temp dir, a pid or a timestamp in it) never hits.  Call
:func:`enable_compile_cache` before the process compiles anything.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Where compiled programs are cached, setting it if nobody has.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and is left to JAX to
    read; otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
