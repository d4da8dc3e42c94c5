"""Persistent JAX compilation cache for the program's entry points.

Compiling the cover round loop and the Pallas kernels for every shape class
a run meets takes seconds each; a persistent cache lets the next process
in the same checkout load them instead.  Entry points (``chip_smoke.py``,
benchmarks) call `enable_compile_cache` once at start-up; the library never
does, so importing it, and the tests, leave JAX's cache settings alone.

Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` when the environment
sets it (JAX reads it at import, so no directory is set in code), else one
fixed directory inside the checkout.  Never a temporary, per-process or
time-stamped path: a cache that moves is never hit again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory `enable_compile_cache` puts the cache in."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_DIR)


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on for this process and
    return its directory.  Every program is cached, not only those that
    compile for longer than JAX's default one-second floor."""
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return compile_cache_dir()
