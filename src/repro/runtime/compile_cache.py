"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points call :func:`enable_compile_cache` once at start-up (never at
import).  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and nothing else is configured.  Otherwise the cache lives in
``<checkout>/.jax_cache`` (git-ignored): a fixed path, since the path is
part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    import jax

    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
