"""Persistent compilation cache for the entry points.

Called by the launchers, never on import: tests keep the cache off, since a
compile for a described (not attached) chip is written to the cache but can
never be read back.
"""
from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache"]

# <checkout>/.jax_cache: fixed, so every run from this checkout hits it
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache"
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing is changed here. Otherwise the cache lives in ``.jax_cache`` at
    the root of the checkout.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.normpath(_DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
