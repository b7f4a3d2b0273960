"""Where the entry points keep JAX's persistent compilation cache.

The cache key includes the directory, so the directory must not move between
runs: ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself),
else the fixed ``<repo>/.jax_cache``. Entry points call this once at start;
tests do not.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Sets nothing
    when ``JAX_COMPILATION_CACHE_DIR`` is set."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
