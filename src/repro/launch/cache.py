"""The persistent compilation cache for the command-line entry points.

Called from each CLI's ``main()`` and from ``chip_smoke.py`` — never on
import, so a library user keeps whatever cache setting they chose.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

# src/repro/launch/cache.py -> the checkout root
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it itself) and nothing else is set.  Otherwise the cache is the fixed
    ``.jax_cache/`` at the checkout root: the directory is part of each
    entry's key, so it must not move between runs."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
