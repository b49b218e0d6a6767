"""JAX's persistent compile cache, placed by the entry points only.

Importing ``repro`` sets nothing; ``launch/train.py`` and ``chip_smoke.py``
call ``setup_compile_cache`` before their first compile.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["setup_compile_cache"]

# <checkout>/src/repro/launch/cache.py -> <checkout>
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def setup_compile_cache() -> str:
    """Return the cache directory in use.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and left
    alone. Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed
    path, because the path is part of what a later run must find again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
