"""Where JAX keeps its persistent compile cache.

The directory must not move from one run to the next: a run that names a
fresh path (a temporary name, a pid, the time) finds nothing to reuse.
"""
from __future__ import annotations

import os

import jax

# <repo>/.jax_cache (gitignored): this file is <repo>/src/repro/launch/...
REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def place_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here; otherwise the cache is ``<repo>/.jax_cache``.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
