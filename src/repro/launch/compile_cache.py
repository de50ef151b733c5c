"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` before their first compile.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the cache goes to ``<checkout>/.jax_cache``:
a fixed path, because the path is part of what a later run has to find —
one built from a temp name, a pid or the time would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def cache_dir() -> Path:
    """The directory the persistent compilation cache lives in."""
    env = os.environ.get(ENV_VAR)
    return Path(env) if env else CHECKOUT / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn the persistent cache on at :func:`cache_dir` and return it."""
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
