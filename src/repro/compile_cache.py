"""Where JAX keeps its persistent compilation cache for this checkout.

The device loop at fleet size compiles for seconds; a run that starts from
a warm cache skips that. Entry points that compile the served path (the
chip smoke check, the benchmark harness) call :func:`enable_compile_cache`
once, before anything compiles. Tests never call it.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed
#: path inside the checkout (listed in .gitignore), so every run of the same
#: checkout finds the entries the previous run wrote
CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and this
    sets nothing. Otherwise the cache goes to :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
