"""JAX's persistent compilation cache, placed for the entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
``enable_compile_cache()`` once, before their first compile; importing this
module changes nothing.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and this code sets no directory.  Otherwise the cache lives
at the fixed path ``<repo>/.jax_cache``: the path is part of what a later
run must find again, so it is never built from a temporary name, a process
id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Every program is kept, however short its compile: most of the parser's
    bucket programs compile in about a second, and JAX's default of
    keeping only compiles of 1 s or more left a second run on the chip
    recompiling about half of them."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
