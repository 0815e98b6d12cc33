"""Where JAX's persistent compilation cache lives.

``chip_smoke.py`` uses ``JAX_COMPILATION_CACHE_DIR`` when the environment
sets it — JAX reads that variable itself, so no other directory is set
in code — and otherwise the fixed ``<repo>/.jax_cache``.  A fixed path
matters: a cache directory named from a temp dir, a pid or the time
never hits again.
"""

from __future__ import annotations

import os

__all__ = ["enable_compile_cache", "REPO_CACHE_DIR"]

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on (every program, however small or
    quick to compile) and return its directory.  Call before the first
    compile: JAX fixes the cache when it first uses it."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir
