"""Where JAX keeps its persistent compilation cache.

Entry points that compile for the chip (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` before their first
compile, so a second run of the same program loads its executables instead
of compiling them again.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# a fixed directory inside the checkout (listed in .gitignore): the cache is
# found again only by a run that looks in the same place
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.  Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`.
    """
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
