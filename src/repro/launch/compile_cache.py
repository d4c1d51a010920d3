"""Where JAX keeps its persistent compilation cache.

`enable()` is called by the launchers before their first compile. Where
JAX_COMPILATION_CACHE_DIR is set, JAX has read it itself and `enable()`
leaves it alone. Otherwise the cache goes to one fixed directory inside
the checkout, `<repo>/.jax_cache` (listed in .gitignore). The directory is
part of every entry's key, so it is never built from a temp name, a PID or
the time: a path that moved would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_DIR))
    return str(CHECKOUT_DIR)
