"""The persistent XLA compile cache every entry point shares.

``chip_smoke.py``, ``benchmarks/common.py``, the solver-serve launcher
and the examples call :func:`enable` once, before they compile anything.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing.  Otherwise the cache lives at the fixed
``<checkout>/.jax_cache``: the path is part of what makes a second run of
the same program find the first run's executables, so it is never a
temporary or per-process directory.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable", "DEFAULT_DIR"]

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compile cache at its one directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
