"""JAX persistent compilation cache, placed from outside or at a fixed path.

A cache entry's key includes the directory, so the directory must not
move between runs: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads
it itself, and nothing here overrides it), else ``<repo>/.jax_cache``.
Call :func:`enable_compile_cache` before the first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    return os.environ.get(ENV_VAR) or str(REPO_ROOT / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the cache on; returns its directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
