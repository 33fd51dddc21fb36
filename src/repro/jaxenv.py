"""Process-wide JAX settings shared by the library and its entry points.

* :func:`x64` — float64 inside a ``with`` block, the previous setting
  restored on exit (the dense float64 reference steps, the interpreted
  float64 kernels, and the jax arc-load engine run under it).
* :func:`enable_compile_cache` — JAX's persistent compilation cache,
  placed from outside: ``JAX_COMPILATION_CACHE_DIR`` when set, otherwise
  a fixed ``.jax_cache/`` at the root of the checkout.  The directory is
  part of each entry's key, so it never moves between runs.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["x64", "enable_compile_cache", "CACHE_ENV", "CHECKOUT_CACHE"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/jaxenv.py -> <checkout>/.jax_cache
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def x64():
    """Context manager enabling float64 for the enclosed block."""
    import jax

    return jax.enable_x64(True)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so when it is set
    nothing is overridden.  Call before the first compile: JAX fixes the
    cache location when it first consults it."""
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
