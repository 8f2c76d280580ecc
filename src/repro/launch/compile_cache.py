"""Persistent JAX compilation cache for the program entry points."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (git-ignored): src/repro/launch/ -> checkout root
_IN_CHECKOUT = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Keep compiled programs across runs and return the directory in use.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself); otherwise the cache lives at the fixed ``.jax_cache`` of this
    checkout — a fixed path, since the directory is part of the cache key.
    Called once where a program starts (``__main__`` blocks, the benchmark
    runner, ``chip_smoke.py``); never from library code or tests.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(_IN_CHECKOUT))
    return jax.config.jax_compilation_cache_dir
