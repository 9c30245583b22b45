"""JAX's persistent compilation cache for the command-line entry points.

Call :func:`enable_compile_cache` at the top of a CLI's ``main`` (never
on import).  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it
already and that directory stands.  Otherwise the cache goes to
``<checkout>/.jax_cache``: a fixed path (the path is part of what the
cache is keyed on, so a directory that moves never hits), git-ignored.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn the persistent cache on and return its directory.

    The minimum compile time is lowered to zero so the window kernels,
    which compile in about a second each, are cached too.  On the CPU
    the cache stays off (returns None): CPU compiles are cheap, and
    XLA:CPU warns about machine features on every cached load.
    """
    if jax.default_backend() == "cpu":
        return None
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
