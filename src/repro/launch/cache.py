"""JAX's persistent compilation cache for the launchers and chip_smoke.py.

A cold chip run of the train step compiles for about a minute; the cache
lets a later run in the same checkout skip that.
"""
from __future__ import annotations

import os

CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Returns the cache directory. ``JAX_COMPILATION_CACHE_DIR``, when
    set, is left to JAX and nothing is set here; otherwise the cache
    goes to the fixed ``.jax_cache/`` in the checkout root, never to a
    path built from a temp name, a pid or the time, so that a later run
    in the same checkout finds it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(CHECKOUT_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
