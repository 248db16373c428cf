"""Where JAX keeps its persistent compilation cache.

Entry points (`chip_smoke.py`, `python -m repro.serve`, the examples and
the benchmarks) call `use_compile_cache` once before their first compile,
so a second run of the same program loads its kernels and jitted steps
instead of compiling them again.  Tests do not call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# fixed and inside the checkout: the directory is part of what a cached
# entry is found by, so a path built from a temp name, pid or time never
# hits twice
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and no
    other directory is set here; otherwise the cache goes to
    ``<checkout>/.jax_cache``.  Every compile is cached, however short:
    the p-bit kernels compile in about a second each, under JAX's default
    one-second floor.
    """
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
