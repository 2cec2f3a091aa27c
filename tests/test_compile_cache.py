"""Placement of JAX's persistent compilation cache (launch/compile_cache.py).

Each case runs in a fresh interpreter: the cache directory is read once per
process, and the parent test process must keep its own cache untouched.
"""

import os
import subprocess
import sys
from pathlib import Path

from repro.launch.compile_cache import DEFAULT_CACHE_DIR

REPO = Path(__file__).resolve().parents[1]

PROBE = r"""
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print("DIR", enable_compile_cache())
print("CONFIG", jax.config.jax_compilation_cache_dir)
print("MIN_COMPILE_S", jax.config.jax_persistent_cache_min_compile_time_secs)
if COMPILE:
    # a compile far under a second: kept only because the minimum is 0
    jax.jit(lambda x: jnp.sin(x) * 3.0).lower(jnp.ones(7)).compile()
"""


def _probe(env_dir, compile_=False):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", f"COMPILE = {compile_}\n" + PROBE],
        env=env, capture_output=True, text=True, timeout=120, check=True,
    ).stdout
    return dict(line.split(" ", 1) for line in out.splitlines())


def test_default_is_the_fixed_repo_path():
    got = _probe(None)
    assert got["DIR"] == got["CONFIG"] == str(DEFAULT_CACHE_DIR)
    assert DEFAULT_CACHE_DIR == REPO / ".jax_cache"


def test_env_dir_is_left_to_jax_and_receives_the_entries(tmp_path):
    before = set(DEFAULT_CACHE_DIR.iterdir()) if DEFAULT_CACHE_DIR.is_dir() else set()
    got = _probe(tmp_path / "cache", compile_=True)
    assert got["DIR"] == got["CONFIG"] == str(tmp_path / "cache")
    assert float(got["MIN_COMPILE_S"]) == 0
    assert any((tmp_path / "cache").iterdir())
    after = set(DEFAULT_CACHE_DIR.iterdir()) if DEFAULT_CACHE_DIR.is_dir() else set()
    assert after == before
