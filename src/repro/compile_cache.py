"""JAX's persistent compilation cache, kept at one fixed path.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples) call
``enable_compile_cache()`` before they compile anything, so a second run of
the same checkout loads its compiled programs instead of compiling them
again.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that variable
itself and this module sets nothing.  Otherwise the cache is
``<checkout>/.jax_cache``, resolved from this file's own location: never a
temporary name, a process id or the time, since a directory that moves
between runs is never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
