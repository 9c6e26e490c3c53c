"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call :func:`setup_compile_cache` once at start-up,
never at import.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins and is
left to JAX; otherwise the cache lives at a fixed path inside the
checkout (``.jax_cache/``, gitignored).  The path is part of the cache
key, so it never depends on a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
