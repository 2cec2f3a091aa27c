"""Checkpoint manager: atomicity, keep-k GC, bf16 round-trip, elastic load."""

import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train.checkpoint import CheckpointManager


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "w": jax.random.normal(k, (8, 4), jnp.float32),
        "b16": jax.random.normal(k, (4,), jnp.bfloat16),
        "nested": {"step": jnp.int32(7)},
    }


def test_save_restore_roundtrip(tmp_path):
    m = CheckpointManager(tmp_path, keep=2)
    t = _tree()
    m.save(10, t, extra={"loss": 1.5})
    step, got, extra = m.restore(t)
    assert step == 10 and extra["loss"] == 1.5
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(got)):
        assert str(a.dtype) == str(np.asarray(b).dtype) or np.asarray(b).dtype == a.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_keep_k_gc(tmp_path):
    m = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        m.save(s, _tree(s))
    assert m.all_steps() == [3, 4]


def test_atomic_publish_ignores_tmp(tmp_path):
    m = CheckpointManager(tmp_path, keep=3)
    m.save(5, _tree())
    # simulate a crash mid-write: stray tmp dir must be invisible to restore
    (tmp_path / "step_0000000009.tmp").mkdir()
    (tmp_path / "step_0000000009.tmp" / "garbage").write_text("x")
    assert m.latest_step() == 5
    step, _, _ = m.restore(_tree())
    assert step == 5


def test_restore_shape_mismatch_raises(tmp_path):
    m = CheckpointManager(tmp_path)
    m.save(1, {"w": jnp.zeros((4, 4))})
    with pytest.raises(ValueError):
        m.restore({"w": jnp.zeros((8, 4))})


def test_async_save(tmp_path):
    m = CheckpointManager(tmp_path, keep=2)
    m.async_save(3, _tree())
    m.wait()
    assert m.latest_step() == 3


def test_elastic_restore_onto_sharding(tmp_path):
    """Checkpoints are full arrays: restoring onto a (1-device) NamedSharding
    works regardless of the mesh that wrote them."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    m = CheckpointManager(tmp_path)
    t = _tree()
    m.save(2, t)
    from jax.sharding import AxisType

    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), t)
    step, got, _ = m.restore(t, shardings=sh)
    assert step == 2
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


@pytest.mark.slow
def test_elastic_remesh_subprocess(tmp_path):
    """Fault-tolerance requirement: a checkpoint written on a (2,4) mesh
    restores onto a (4,2) mesh AND onto a 2-device subset mesh with identical
    values — elastic scaling across restarts (separate process: device count
    is locked at jax init)."""
    import subprocess, sys, os
    from pathlib import Path

    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train.checkpoint import CheckpointManager

tree = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
        "emb": jnp.arange(32, dtype=jnp.bfloat16).reshape(16, 2)}}
from jax.sharding import AxisType
AUTO2 = (AxisType.Auto,) * 2
mesh_a = jax.make_mesh((2, 4), ("data", "model"), axis_types=AUTO2)
sh_a = {{"w": NamedSharding(mesh_a, P("data", "model")),
        "emb": NamedSharding(mesh_a, P("data", None))}}
placed = jax.tree.map(lambda t, s: jax.device_put(t, s), tree, sh_a)
m = CheckpointManager(r"{tmp_path}", keep=2)
m.save(1, placed)

# restore on a different topology
mesh_b = jax.make_mesh((4, 2), ("data", "model"), axis_types=AUTO2)
sh_b = {{"w": NamedSharding(mesh_b, P("model", "data")),
        "emb": NamedSharding(mesh_b, P(None, "model"))}}
step, got, _ = m.restore(tree, shardings=sh_b)
for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
    np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))

# restore on a smaller world (2 devices) — node-loss scenario
mesh_c = jax.make_mesh(
    (2,), ("data",), axis_types=(AxisType.Auto,), devices=jax.devices()[:2]
)
sh_c = jax.tree.map(lambda _: NamedSharding(mesh_c, P("data")), tree)
step, got2, _ = m.restore(tree, shardings=sh_c)
for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got2)):
    np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
print("ELASTIC-OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).parents[1] / "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ELASTIC-OK" in out.stdout
