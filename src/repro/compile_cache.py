"""JAX persistent compilation cache at a fixed place.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples)
call :func:`enable_compile_cache` before their first compile; importing
the library never does, so tests run with no cache. The directory is
part of the cache's key, so it is fixed: ``$JAX_COMPILATION_CACHE_DIR``
when set (JAX reads that variable itself and nothing here overrides it),
otherwise ``.jax_cache/`` at the checkout root (git-ignored).
"""
from __future__ import annotations

import os

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
