"""Persistent XLA compilation cache for the entry points.

The entry points (``launch/serve.py``, ``launch/train.py``,
``chip_smoke.py``) call :func:`use_compile_cache` at start-up; importing
any module never turns the cache on.  ``JAX_COMPILATION_CACHE_DIR``,
when set, is JAX's own setting and is left alone.  Otherwise the cache
lives at one fixed directory inside the checkout (``.jax_cache/``,
git-ignored): the path is part of what makes an entry findable again,
so it is never built from a temporary name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
