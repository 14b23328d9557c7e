"""JAX's persistent compilation cache, at a place a caller can choose.

Entry points that compile at chip scale (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once at start-up;
``import repro`` does not. The cache key includes the directory, so the
default is one fixed path inside the checkout, never a temporary name.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (git-ignored): the directory used when
#: ``JAX_COMPILATION_CACHE_DIR`` is not set.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set (and no other), else
    :data:`DEFAULT_DIR`."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
