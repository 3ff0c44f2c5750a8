"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, the ``launch`` CLIs, the benchmark mains)
call :func:`enable_compile_cache` first thing in ``main``; nothing calls it
at import or from tests.  The cache key includes the directory, so the
directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets
it (JAX reads that variable itself, and nothing here overrides it), else
``.jax_cache/`` at the root of the checkout -- never a temp, pid- or
time-derived path.
"""

from __future__ import annotations

import os

import jax

__all__ = ["CACHE_ENV", "default_cache_dir", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` (this file is ``src/repro/utils/...``)."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.abspath(os.path.join(here, "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
