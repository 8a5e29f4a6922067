"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.stream``,
``benchmarks/run.py``) call :func:`use_compile_cache` before their first
compile, so a second process with the same programs loads them instead
of compiling again.  ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's
own setting and is left alone.  Otherwise the cache lives at the fixed
``<repo>/.jax_cache``: the directory is part of what a later process
looks up, so it never depends on a temp dir, a pid or the time.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
