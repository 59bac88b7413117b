"""JAX's persistent compilation cache, placed from outside the code.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself reads it as
``jax_compilation_cache_dir`` and nothing here overrides it. Otherwise
the cache lives at :data:`DEFAULT_DIR`, one fixed directory inside the
checkout (listed in ``.gitignore``): a path that never moves is what
lets a later run find the entries again.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call before the first compile."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
