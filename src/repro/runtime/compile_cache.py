"""JAX's persistent compilation cache, kept at one fixed place.

A compiled executable is keyed, among other things, by the cache
directory's path, so a directory that moves between runs never hits.
``JAX_COMPILATION_CACHE_DIR``, where it is set, is read by JAX itself and
left alone here; otherwise the cache lives in ``.jax_cache/`` at the root
of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process and return its
    directory.  Call before the first compile: JAX opens the cache once."""
    import jax
    # Kernel compiles take well under JAX's default 1 s threshold; keep
    # them too.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
