"""JAX's persistent compilation cache for the entry points.

A cold process on the chip compiles every fused KVI region and every
model step; the cache lets later processes on the same disk reuse them.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (the path is part of every cache
#: key, so it is fixed: never a temporary name, a process id or a time)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself), else
    ``<checkout>/.jax_cache``.

    Every compile is kept, however short (a Mosaic kernel compiles in
    about 0.1 s), unless the cache has a size limit
    (``JAX_COMPILATION_CACHE_MAX_SIZE``): JAX then scans the whole
    directory on every write, which on a TPU v5e host with ~1800 entries
    cost ~0.9 s a write, more than the compile it saves, so only JAX's
    default of compiles over a second is kept."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    if jax.config.jax_compilation_cache_max_size == -1:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
