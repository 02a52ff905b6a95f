"""JAX's persistent compilation cache, at a directory that does not move.

The cache key includes the directory, so a path derived from a temp name,
a pid or the time never hits.  Every entry point calls
:func:`configure_compile_cache` before its first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def configure_compile_cache() -> str:
    """Return the cache directory in use.  When ``JAX_COMPILATION_CACHE_DIR``
    is set, JAX reads it itself and nothing here overrides it; otherwise
    the cache goes to :data:`DEFAULT_DIR`."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
