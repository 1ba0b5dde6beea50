"""JAX's persistent compilation cache, configured by the entry points.

Importing ``repro`` never touches the cache; ``chip_smoke.py``, the
``benchmarks`` mains and the ``examples`` call :func:`configure_compile_cache`
first thing.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here.  Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache``: the path is part of what a later run looks up, so
it is never built from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# the checkout holding src/repro (this file is src/repro/jax_cache.py)
CHECKOUT = Path(__file__).resolve().parents[2]


def configure_compile_cache() -> str:
    """Point the persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` if set,
    else at ``<checkout>/.jax_cache``.  Returns the directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_entries(path: str) -> int:
    """Number of compiled programs stored under ``path`` (0 if absent)."""
    try:
        return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
    except FileNotFoundError:
        return 0
