"""Where JAX's persistent compilation cache lives for the launchers.

The cache is keyed by its directory, so it sits at a fixed path: the one
``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself), or
else ``<repo root>/.jax_cache``.  Entry points call :func:`use_compile_cache`
before their first compile; library modules never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
