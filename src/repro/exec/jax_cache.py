"""Placement of JAX's persistent compilation cache for the entry points.

``chip_smoke.py`` and the ``benchmarks/`` and ``examples/`` scripts call
``use_persistent_cache()`` under their ``__main__`` guard.  The library
never calls it, neither at import nor under pytest.

Placement rule:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing
    here sets another path;
  * otherwise: one fixed directory inside the checkout,
    ``<repo>/.jax_cache`` (git-ignored).  It is never built from a temp
    name, a pid or the time: a cache directory that moves between runs
    is never found again.
"""
from __future__ import annotations

import os

import jax

#: The fixed in-checkout cache directory (used when the env var is unset).
CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, ".jax_cache"))


def use_persistent_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
