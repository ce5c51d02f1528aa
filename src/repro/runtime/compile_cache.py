"""JAX's persistent compilation cache, for entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it at import and this
module sets nothing.  Otherwise the cache goes to a fixed directory inside
the checkout, ``<repo>/.jax_cache`` (git-ignored).  The path is part of
the cache key, so it is never built from a temporary name, a PID or the
time.  Entry points call ``enable_compile_cache()`` from ``main``; library
code and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get(_ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_DIR))
    return str(_DEFAULT_DIR)
