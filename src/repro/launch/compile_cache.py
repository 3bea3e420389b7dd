"""JAX's persistent compilation cache, kept at one fixed place.

A compiled program is found again only under the same cache directory, so
the directory must not move between runs: where ``JAX_COMPILATION_CACHE_DIR``
is set JAX reads it itself and nothing is set here; otherwise the cache
lives in ``.jax_cache`` at the root of this checkout.
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
