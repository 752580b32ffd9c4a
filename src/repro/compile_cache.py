"""Placement of JAX's persistent compilation cache.

Entry points call ``enable_compile_cache()`` from their ``main()`` (never
at import).  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here.  Otherwise the cache lives at
``<checkout>/.jax_cache``: a fixed path, because the directory is part of
what a later process must find again (a per-run temp or pid path would
never hit).  The directory is listed in ``.gitignore``.

The served path compiles a few hundred small programs (one per bucket
shape), each in well under JAX's default one-second threshold for caching
a program, so the threshold is lowered to zero unless
``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache for this process and
    return the directory it uses."""
    import jax
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
