"""Persistent compile cache for the entry points.

``enable_compile_cache()`` is called at start-up by the programs that run
on the chip (``chip_smoke.py``, ``python -m repro.launch.train``,
``benchmarks/run.py``); importing the library never calls it. The path is
part of the cache key, so it is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when
that is set (JAX reads it itself, and nothing is set in code), else
``.jax_cache/`` at the root of the checkout, which git ignores.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache"]

_CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its fixed directory and
    return that directory.

    >>> enable_compile_cache()
    '/path/to/checkout/.jax_cache'
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE))
    return str(_CHECKOUT_CACHE)
