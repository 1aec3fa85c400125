"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.sim_campaign``,
``benchmarks.run``) call ``configure()`` once at start; tests do not.  The
directory is part of the cache key, so it is a fixed path: the one
``JAX_COMPILATION_CACHE_DIR`` names when that is set (JAX reads the variable
itself and nothing here overrides it), else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def cache_dir() -> str:
    """The directory ``configure`` puts the cache in."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(CHECKOUT / ".jax_cache"))


def configure() -> str:
    """Point JAX's persistent compilation cache at ``cache_dir()``."""
    path = cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
