"""Where JAX's persistent compilation cache lives — decided here only.

If ``JAX_COMPILATION_CACHE_DIR`` is in the environment, JAX reads it itself
and nothing is set in code, so the cache can be placed from outside. Else
the cache goes to one fixed path inside the checkout: the directory is part
of the cache's key, so a path that moves (a temp name, a pid, a hash of the
host) never hits.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(  # tpudist/utils/cache.py -> three levels up
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one home and return
    the directory in use. Call before the first compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
