"""JAX's persistent compilation cache for the entry points.

Call ``enable_compile_cache()`` first thing in an entry point's ``main``,
never at import: importing a module must not change process-wide JAX
configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (git-ignored): a fixed path, because a cache
# directory that moves between runs never hits
_DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.
    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (JAX reads
    the variable itself) and nothing is overridden; otherwise the cache
    lives in ``.jax_cache`` at the root of the checkout."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
