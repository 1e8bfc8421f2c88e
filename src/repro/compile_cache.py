"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``serve_sim``,
the tuning CLI, the examples) call :func:`use_compile_cache` once at
start-up, so a second process reuses every kernel the first compiled.
It never runs at import time: tests keep JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache — a fixed path, because the directory is part of
# what a later process must find again.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
    directory and no other is set here; otherwise the cache goes to
    :data:`DEFAULT_DIR`. Either way the minimum compile time an entry
    needs is lowered to zero: most Pallas stencil kernels compile in
    under JAX's default threshold of one second and would otherwise
    never be cached.
    """
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
