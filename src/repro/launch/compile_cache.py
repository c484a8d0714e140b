"""JAX's persistent compilation cache for the command-line entry points.

Every shape the rate-resolution kernels see compiles once; the cache keeps
those programs across processes.  The entry points (``sweep``, ``schedd``,
``report`` and the root ``chip_smoke.py``) call :func:`enable_compile_cache`
before their first compile; library modules never do.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the fallback directory: fixed, because the path is part of each entry's
#: key, so a cache that moves never hits
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set; otherwise the cache
    lives in ``<checkout>/.jax_cache``.  Programs are cached however fast
    they compiled: the kernels compile in well under JAX's default 1 s
    threshold and would otherwise never be kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
