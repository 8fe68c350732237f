"""JAX's persistent compilation cache for the programs' entry points.

A full-width run compiles the model's decode step and every kernel
variant; with the cache on, a second run on the same tree reads them back
instead.  The directory is fixed — JAX keys entries by program, and a
directory that moves between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

#: The checkout's own cache directory (listed in ``.gitignore``).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX reads it
    and nothing is changed.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
