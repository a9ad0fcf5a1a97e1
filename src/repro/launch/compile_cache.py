"""JAX's persistent compilation cache, placed from outside.

Entry points (``chip_smoke.py`` and :func:`repro.launch.discover.main`)
call :func:`enable_compile_cache` once at start-up; importing
:mod:`repro` never does, so compiles for a described TPU in the tests do
not write cache entries that no later run could read.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no directory.  Otherwise the cache lives in ``.jax_cache/`` at
the checkout root: a fixed path, because the path is part of what makes a
later run find the entries.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
