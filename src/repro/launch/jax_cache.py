"""JAX's persistent compilation cache, kept at one fixed place.

A mine compiles one kernel per (strategy, bucket dims, sweep grid,
chunk width): a portfolio over a real graph traces well over a hundred
of them, so a process that starts cold pays every compile again.  The
entry points (``chip_smoke.py``, :mod:`repro.launch.mine`,
:mod:`repro.launch.serve`, ``benchmarks/run.py``) call
:func:`enable_compile_cache` before their first compile, so a second
run finds the first one's executables.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache
and no other is set in code.  Otherwise the cache lives in the checkout
at ``.jax_cache`` (ignored by git): a fixed path, because the path is
part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["compile_cache_dir", "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else ``<checkout>/.jax_cache``."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at :func:`compile_cache_dir` and
    cache every executable, however quick its compile (the mining
    kernels are many and small).  Returns the directory."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
