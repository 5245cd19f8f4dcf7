"""JAX's persistent compilation cache, kept at one fixed path.

A cached executable is found again only under the same directory, so
the path must not move between runs: it is never built from a temp
name, a pid or the time.  Entry points (``chip_smoke.py``,
``benchmarks/run.py``, ``examples/quickstart.py``,
``python -m repro.program``) call :func:`enable_compile_cache` before
their first compile; the library never calls it on import.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

# <checkout>/.jax_cache — listed in .gitignore.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.  Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
