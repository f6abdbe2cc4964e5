"""Persistent compilation cache placement for the entry points.

``chip_smoke.py``, ``repro.launch.serve`` and ``benchmarks.run`` call
:func:`enable_compile_cache` before anything compiles. A cache directory
given from outside through ``JAX_COMPILATION_CACHE_DIR`` (which JAX reads
itself) is left alone; otherwise the cache lives at a fixed directory
inside the checkout, never one named from a temp directory, a process
id or the time, so a later run from the same checkout finds it again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
