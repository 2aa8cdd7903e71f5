"""Where JAX's persistent compilation cache lives.

Entry points that compile for a device call :func:`enable_compile_cache`
once, before their first compile. With ``JAX_COMPILATION_CACHE_DIR`` set,
JAX reads the variable itself and nothing is set here. Otherwise the cache
goes to ``.jax_cache/`` at the root of the checkout: a fixed path, because
the path is part of what a later run has to find again (never a temporary,
per-process or time-stamped directory).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
