"""Process-level JAX setup shared by every entry point: the persistent
compilation cache."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache: a fixed path, because the cache key includes it — a
# directory that moved between runs would never hit
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone; otherwise the cache lives at ``<repo>/.jax_cache``. Call at
    the start of an entry point, before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
