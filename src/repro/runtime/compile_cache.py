"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points (``launch/serve.py``, ``launch/train.py``, the benchmark
scripts, ``chip_smoke.py``) call :func:`enable_compile_cache` before they
compile anything.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing here overrides it.  Otherwise the cache lives in
``.jax_cache/`` at the root of the checkout: a fixed path, because the path
is part of what JAX matches entries against, so a directory named after a
process, a temporary file or the time would never be hit again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/runtime/``).
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
