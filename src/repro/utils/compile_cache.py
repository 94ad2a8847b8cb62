"""JAX's persistent compilation cache for the entry points.

Called from an entry point's ``main``, never at import time.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
set here.  Otherwise the cache lives at one fixed path inside the
checkout (``<repo>/.jax_cache``, git-ignored): the path is part of the
cache key, so it must not move between runs.
"""
from __future__ import annotations

import os
import pathlib

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
