"""JAX persistent compilation cache, placed from outside or at a fixed path.

Every entry point that compiles the served path (``chip_smoke.py``, the
``examples/`` scripts, the gated benches) calls
:func:`enable_compile_cache` before its first compile, so a second run
on the same machine reuses the per-tier fleet-step and wire-decoder
programs instead of compiling them from cold.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# The cache key covers the directory, so the fallback is one fixed path
# at the repository root (listed in .gitignore).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here. Otherwise the cache goes to
    :data:`DEFAULT_DIR`, keeping every program however fast it compiled
    (the small per-occupancy wire decoders included).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(DEFAULT_DIR)
