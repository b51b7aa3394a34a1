"""JAX's persistent compilation cache, for the program entry points.

The entry points (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, ``benchmarks/run.py``) call :func:`enable_compile_cache`
once at start-up; library imports and tests never do.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it.  Otherwise the cache lives at one fixed path inside the
checkout (``.jax_cache/``, git-ignored): the directory is part of what a
later run must find, so it is never derived from a temporary name, a
process id or the time.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path."""
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
