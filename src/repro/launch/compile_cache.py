"""JAX's persistent compilation cache, in one fixed place.

The cache key includes the directory, so a directory that moves never
hits: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
no other directory is set here; otherwise the cache lives at
``<repo>/.jax_cache`` (git ignores it).  Entry points call
``enable_compile_cache()`` before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory and return it."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
