"""JAX's persistent compilation cache for the entry points.

``enable_compile_cache()`` is called by the entry points (``chip_smoke.py``,
``launch/train.py``), never at import.  The cache directory is part of the
cache's key, so it must not move between runs:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing else is
  set.
- otherwise: ``<checkout>/.jax_cache/`` (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the cache lives in: the env var if set, else the fixed
    in-checkout default."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    and return that directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
