"""JAX's persistent compilation cache, switched on by entry points.

Scripts (``chip_smoke.py``, ``benchmarks/run.py``, the examples) call
:func:`enable_compile_cache` once at start-up; importing this module does
nothing. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and no directory is set here. Otherwise the cache lives at ``.jax_cache``
in the repository checkout (git-ignored). The path is fixed because it is
part of the cache key: a directory that moves never hits.
"""
from __future__ import annotations

import contextlib
import os

import jax
from jax.experimental.compilation_cache import compilation_cache

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # the default threshold skips sub-second compiles; the engine's small
    # shape buckets are exactly those, so cache everything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # a compile that ran before this call latched the cache off; re-check
    compilation_cache.reset_cache()
    return path


@contextlib.contextmanager
def cache_off():
    """The persistent cache off for the compiles inside: ahead-of-time
    compiles for a described, unattached device, whose entries cannot be
    read back."""
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()
