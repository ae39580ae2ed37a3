"""JAX persistent compilation cache for the repo's entry points.

A graph-sized jit (the scale-22 PageRank fixpoint, the frontier steps) costs
seconds to compile, and every fresh process pays it again.  Entry points
(``chip_smoke.py``, ``python -m repro.serve.server``, the ``benchmarks/``
mains) call :func:`enable` first thing; library imports never do, so tests
and embedding callers keep whatever cache setting they already have.

Placement: when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here overrides it.  Otherwise the cache lives in ``.jax_cache/`` at
the root of this checkout.  The path is part of every cache key, so it is
fixed: never a temporary name, a process id or a time.
"""

from __future__ import annotations

import os

import jax

__all__ = ["enable", "CHECKOUT_CACHE_DIR"]

#: ``<checkout>/.jax_cache`` — this file lives at ``<checkout>/src/repro/``
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
