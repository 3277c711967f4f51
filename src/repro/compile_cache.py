"""JAX's persistent compilation cache, kept at one fixed place.

Entry points call :func:`enable` before they compile anything, so that a
second run of the same program finds its kernels and steps already
compiled.  The cache directory is part of what a cached entry is found by,
so the path is fixed: it never holds a temporary name, a process id or a
time.
"""

from __future__ import annotations

import os
import pathlib

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``)
CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing; otherwise the cache lives in :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
