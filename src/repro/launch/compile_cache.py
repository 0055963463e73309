"""Where the entry points keep JAX's persistent compilation cache.

Each entry point (``chip_smoke.py``, ``launch.serve``, ``launch.train``,
``benchmarks/run.py``) calls :func:`enable_compile_cache` before its first
compile. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
nothing here overrides it. Otherwise the cache goes to ``<repo>/.jax_cache``:
a fixed path, so a second run from the same checkout finds what the first
one compiled (the directory is part of the cache key's lookup, so a path
built from a temporary name, a process id or the time would never hit).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
