"""JAX's persistent compilation cache, placed for the repository's scripts.

Entry points (``chip_smoke.py``, the benchmarks, ``launch/serve.py``) call
:func:`enable_compile_cache` once at start-up; importing the library never
does, so a caller's own cache setting is left alone.
"""

from __future__ import annotations

import os
from pathlib import Path

#: ``<repo>/.jax_cache``.  Fixed, never derived from a temporary name, a pid
#: or the clock: the directory is part of what a later run must find again.
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, so no
    other directory is set.  Otherwise the cache goes to
    :data:`REPO_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
