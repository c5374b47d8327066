"""Where JAX keeps its persistent compilation cache.

Entry points that compile full model steps (``chip_smoke.py``,
``launch/train.py``) call :func:`setup_compile_cache` once, before their
first compile.  The cache key includes the directory, so the path must
not move between runs: it is either what ``JAX_COMPILATION_CACHE_DIR``
names (JAX reads that variable itself; nothing is overridden) or the
fixed ``.jax_cache`` directory at the root of this checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def setup_compile_cache() -> str:
    """Point JAX's persistent compile cache at its one fixed place and
    return that directory.  Touches no backend."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
