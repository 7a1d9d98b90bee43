"""JAX persistent compilation cache, placed from outside or at a fixed path.

Entry points call :func:`enable_compile_cache` from their ``main()`` (never
at import).  ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory
and nothing else is set in code.  Otherwise the cache lives at
``<checkout>/.jax_cache``: a fixed path, because the directory is part of
what a later process must find again (a temporary or time-stamped path
would never hit).
"""
from __future__ import annotations

import os

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "ENV_VAR", "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR) or CHECKOUT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
