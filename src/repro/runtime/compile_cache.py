"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, the benchmark
mains) call ``enable_compile_cache()`` before their first compile; a
plain ``import repro`` never does.  The cache key includes the
directory, so it is a fixed path: ``JAX_COMPILATION_CACHE_DIR`` when set
(JAX reads it itself and nothing here overrides it), else ``.jax_cache``
at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
