"""JAX's persistent compilation cache, kept at one fixed place.

A cache hit needs the same path every time (the path is part of the
cache key), so the cache never goes to a temporary, per-process or
time-stamped directory.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_DIR", "enable_compile_cache"]

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
