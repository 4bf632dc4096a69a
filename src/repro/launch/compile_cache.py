"""JAX's persistent compilation cache for the repo's entry points.

The cache key includes the cache directory, so the directory must not
move between runs: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing is set here; otherwise the cache lives at the
fixed path ``<repo>/.jax_cache`` (listed in ``.gitignore``).  Entry
points (``chip_smoke.py``, ``benchmarks/``, ``examples/``) call
:func:`enable_compile_cache` before their first compile; tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
