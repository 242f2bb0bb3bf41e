"""JAX persistent compilation cache: one place decides where it lives.

Entry points (``chip_smoke.py``, ``benchmarks/*``, ``examples/*``) call
``enable_compile_cache()`` before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing else
is set here; otherwise the cache goes to ``<checkout>/.jax_cache`` — a fixed
path (the path is part of a cache entry's key, so a directory named from a
temp name, a pid or the time would never hit), git-ignored.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout-local default, used when ``JAX_COMPILATION_CACHE_DIR`` is unset.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory.

    Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
