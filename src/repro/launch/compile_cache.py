"""Where JAX keeps its persistent compilation cache.

The cache is keyed partly by its own path, so it lives at one fixed
place: the directory ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that
variable itself), else ``<repo root>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point the persistent compile cache at its fixed directory and
    return that directory.  Call it from a ``main()`` before the first
    compile, never at import."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return jax.config.jax_compilation_cache_dir
