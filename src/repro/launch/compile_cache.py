"""Persistent XLA compilation cache for the entry points.

Only entry points call :func:`use_compile_cache` (``chip_smoke.py``,
``pgserve.main``, ``benchmarks/run.py``); importing the library changes no
JAX setting.  The cache directory is part of what a later run must find
again, so it is a fixed path: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself), otherwise
``<checkout>/.jax_cache``, which git ignores.
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py → the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> None:
    """Point JAX's persistent compilation cache at its fixed directory,
    unless ``JAX_COMPILATION_CACHE_DIR`` already places it."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT / ".jax_cache"))
