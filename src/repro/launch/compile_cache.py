"""JAX's persistent compilation cache at one fixed place.

A cached executable is found again only under the same directory, so the
directory must not move between runs: it never depends on a temporary
name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (listed in .gitignore)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.
    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
    nothing is set here; otherwise the cache is `<checkout>/.jax_cache`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
