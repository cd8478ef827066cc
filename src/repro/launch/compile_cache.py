"""Where JAX keeps its persistent compilation cache.

Entry points (`chip_smoke.py`, `examples/selection_service.py`,
`benchmarks/run.py`) call `enable_compile_cache()` once from their main;
importing a library module never sets a cache.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
is set here. Otherwise the cache goes to ``.jax_cache/`` at the root of the
checkout (listed in ``.gitignore``). The directory is fixed: one that moved
between runs — a temp name, a pid, a time — would never be hit again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
