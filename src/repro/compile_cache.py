"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples)
call ``use_compile_cache`` once, before any JAX computation. Importing
the library does not, and neither do the tests.
"""
from __future__ import annotations

import os

import jax


def use_compile_cache(checkout: str) -> str:
    """Point JAX's persistent compilation cache at one fixed directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and no
    other directory is set here. Otherwise the cache goes to
    ``<checkout>/.jax_cache``: a fixed path, never derived from a
    temporary name, a pid or the time, because the path is part of what
    makes a later run find the entries again. Returns the directory.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
