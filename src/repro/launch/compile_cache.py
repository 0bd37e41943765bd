"""JAX's persistent compilation cache at a fixed place.

Entry points that run on the chip (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``) call :func:`enable_compile_cache` before their first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing here overrides it; otherwise the cache lives in ``.jax_cache``
at the root of the checkout.  The path must not move between runs — it
is part of the cache's key — so it is never temporary, per-process or
time-stamped.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    cache_dir = os.environ.get(ENV, "").strip()
    if not cache_dir:
        cache_dir = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
