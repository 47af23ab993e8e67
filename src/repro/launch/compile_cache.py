"""Where JAX keeps its persistent compilation cache.

Called by the entry points (``launch/serve.py``, ``launch/train.py``,
``chip_smoke.py``), never at import. Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it itself and no other directory is set here. Otherwise the
cache is the fixed ``.jax_cache/`` at the checkout root: the path is part of
the cache key, so it never depends on a temp dir, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache(root: Path = CHECKOUT_ROOT) -> str:
    """Turn the persistent cache on and return its directory."""
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return env_dir
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
