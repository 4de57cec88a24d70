"""Where JAX keeps its persistent compilation cache.

The launchers and ``chip_smoke.py`` call :func:`enable_compile_cache` before
their first compile.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here.  Otherwise the cache lives at one fixed path
inside the checkout (git-ignored): a directory that moves between runs never
hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
