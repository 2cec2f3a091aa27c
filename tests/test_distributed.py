"""Mesh-native distributed parse runtime (core/distributed.py).

Two tiers:
  * 1-device-mesh tests — the full shard_map routes (chunk-sharded parse,
    batch × chunk parse_batch, sharded streaming join) run degenerately on
    whatever single device the plain suite has; bit-identity always checked.
  * 8-device tests — require a host mesh with real collectives; they run
    in-process when the interpreter was launched with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (scripts/ci.sh
    does), and otherwise via the slow subprocess test at the bottom.
"""

import subprocess
import sys
from pathlib import Path

import jax
from jax.sharding import AxisType
import numpy as np
import pytest

import repro.core.engine as engine_mod
from repro.core.distributed import DistributedEngine
from repro.core.engine import ParserEngine
from repro.core.reference import ParallelArtifacts
from repro.core.serial import parse_serial_matrix
from repro.core.stream import StreamingParser
from repro.launch.mesh import make_parse_mesh
from repro.serve.parse_service import ParseService

AMBIG = "(a|b|ab)+"
# mixed-length, empty, and ambiguous inputs (acceptance criteria set)
TEXTS = ["abab", "", "b", "ab" * 13, "a" * 17, "ba" * 3, "aabb" * 5, "x"]

multi = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8",
)


@pytest.fixture(scope="module")
def art():
    return ParallelArtifacts.generate(AMBIG)


@pytest.fixture(scope="module")
def ref_engine(art):
    return ParserEngine(art.matrices)


def _mesh_8():
    return jax.make_mesh(
        (2, 4), ("pod", "data"), axis_types=(AxisType.Auto,) * 2
    )


# ------------------------------------------------------------ legacy gone


def test_legacy_sharded_path_is_gone():
    """One distribution-aware runtime: the pre-phases path no longer exists."""
    assert not hasattr(engine_mod, "make_sharded_parser")
    assert not hasattr(engine_mod, "sharded_parse_step")


# --------------------------------------------------- 1-device mesh routes


def test_mesh_route_parse_batch_matches_engine(art, ref_engine):
    eng = ParserEngine(art.matrices, mesh=make_parse_mesh())
    got = eng.parse_batch(TEXTS)
    base = ref_engine.parse_batch(TEXTS)
    for t, g, b in zip(TEXTS, got, base):
        srl = parse_serial_matrix(art.matrices, t)
        assert np.array_equal(g.columns, srl.columns), t
        assert np.array_equal(g.pack(), b.pack()), t
        assert g.count_trees() == b.count_trees(), t


def test_mesh_route_single_parse_matches_engine(art, ref_engine):
    eng = ParserEngine(art.matrices, mesh=make_parse_mesh())
    for t in TEXTS:
        got = eng.parse(t)
        assert np.array_equal(
            got.columns, parse_serial_matrix(art.matrices, t).columns
        ), t
        assert got.count_trees() == ref_engine.parse(t).count_trees(), t


def test_mesh_route_pallas_backend(art, ref_engine):
    eng = ParserEngine(art.matrices, mesh=make_parse_mesh(), backend="pallas")
    for t in ["abab", "ba"]:
        assert np.array_equal(
            eng.parse_batch([t])[0].columns, ref_engine.parse(t).columns
        ), t


def test_streaming_on_mesh_engine(art, ref_engine):
    """Sharded streaming: every incremental state bit-identical to cold."""
    eng = ParserEngine(art.matrices, mesh=make_parse_mesh())
    sp = StreamingParser(eng, first_seal_len=4)
    prefix = ""
    for piece in ["ab", "ab", "", "abab", "ba", "ab" * 8, "x"]:
        sp.append(piece)
        prefix += piece
        cold = ref_engine.parse(prefix)
        assert np.array_equal(sp.current_slpf().pack(), cold.pack()), piece


def test_stream_edit_on_mesh_engine(art, ref_engine):
    """Mid-text splices on a mesh engine: the segment tree's flattened leaf
    frontier stays the all-gather payload, so post-edit queries route
    through the same sharded join — bit-identical to cold."""
    eng = ParserEngine(art.matrices, mesh=make_parse_mesh())
    sp = StreamingParser(eng, first_seal_len=4, max_seal_len=8)
    text = "ab" * 14
    sp.append(text)
    for lo, hi, repl in [(5, 9, "ba"), (0, 2, ""), (10, 10, "abab")]:
        text = text[:lo] + repl + text[hi:]
        assert sp.edit(lo, hi, repl) == len(text)
        cold = ref_engine.parse(text)
        assert np.array_equal(sp.current_slpf().pack(), cold.pack()), (lo, hi)
        assert sp.accepted == cold.accepted, (lo, hi)


def test_standalone_distributed_engine(art, ref_engine):
    dist = DistributedEngine(art.matrices, make_parse_mesh())
    got = dist.parse_batch(TEXTS[:4])
    for t, g in zip(TEXTS[:4], got):
        assert np.array_equal(g.columns, ref_engine.parse(t).columns), t


def test_prebuilt_engine_rejects_mesh_kwarg(art, ref_engine):
    with pytest.raises(ValueError):
        ParseService(ref_engine, mesh=make_parse_mesh())


# ------------------------------------------------------- 8-device routes


@multi
def test_chunk_sharded_parse_8dev(art, ref_engine):
    eng = ParserEngine(art.matrices, mesh=_mesh_8())
    assert eng.dist.chunk_axes == ("pod", "data")
    for t in TEXTS:
        got = eng.parse(t)
        assert np.array_equal(
            got.columns, parse_serial_matrix(art.matrices, t).columns
        ), t
        assert got.count_trees() == ref_engine.parse(t).count_trees(), t


@multi
def test_batch_times_chunk_sharded_parse_batch_8dev(art, ref_engine):
    eng = ParserEngine(art.matrices, mesh=_mesh_8())
    assert eng.dist.batch_axes == ("data",)
    assert eng.dist.batch_chunk_axes == ("pod",)
    got = eng.parse_batch(TEXTS)
    base = ref_engine.parse_batch(TEXTS)
    for t, g, b in zip(TEXTS, got, base):
        assert np.array_equal(g.pack(), b.pack()), t
        assert np.array_equal(
            g.columns, parse_serial_matrix(art.matrices, t).columns
        ), t
        assert g.count_trees() == b.count_trees(), t


@multi
def test_sharded_streaming_append_8dev(art, ref_engine):
    eng = ParserEngine(art.matrices, mesh=_mesh_8())
    sp = StreamingParser(eng, first_seal_len=4)
    prefix = ""
    for piece in ["ab", "ab", "abab", "ba", "ab" * 10, ""]:
        sp.append(piece)
        prefix += piece
        cold = ref_engine.parse(prefix)
        assert np.array_equal(sp.current_slpf().pack(), cold.pack()), piece
        assert sp.accepted == cold.accepted, piece


@multi
def test_parse_service_serves_sharded_batched_8dev(art, ref_engine):
    svc = ParseService(art.matrices, mesh=_mesh_8(), max_batch=8, n_chunks=4)
    rids = [svc.submit(t) for t in TEXTS]
    done = {r.rid: r for r in svc.run()}
    for rid, t in zip(rids, TEXTS):
        assert np.array_equal(
            done[rid].slpf.columns, parse_serial_matrix(art.matrices, t).columns
        ), t


@multi
def test_batched_program_collective_footprint_8dev(art):
    """The batched route's only collective is the product-stack all-gather."""
    import re
    from collections import Counter

    eng = ParserEngine(art.matrices, mesh=_mesh_8())
    t = eng.tables
    hlo = (
        eng.dist.batched_program.lower(
            t.N, t.I, t.F, jax.ShapeDtypeStruct((8, 8, 16), np.int32)
        )
        .compile()
        .as_text()
    )
    c = Counter(re.findall(r"(all-gather|all-reduce|all-to-all|reduce-scatter)", hlo))
    assert c["all-gather"] >= 1, c
    assert c["all-to-all"] == 0 and c["reduce-scatter"] == 0, c


# ------------------------------------------------------- subprocess cover


@pytest.mark.slow
def test_distributed_multidevice_subprocess():
    """8-device coverage for plain single-device suite runs (device count is
    locked at jax init, so a fresh process sets the flag first)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro.core.reference import ParallelArtifacts
from repro.core.serial import parse_serial_matrix
from repro.core.engine import ParserEngine
from repro.core.stream import StreamingParser
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
art = ParallelArtifacts.generate("(a|b|ab)+")
ref = ParserEngine(art.matrices)
eng = ParserEngine(art.matrices, mesh=mesh)
texts = ["abab", "", "b", "ab"*13, "a"*17, "x"]
for t, g in zip(texts, eng.parse_batch(texts)):
    assert np.array_equal(g.columns, parse_serial_matrix(art.matrices, t).columns), t
    assert np.array_equal(g.pack(), ref.parse(t).pack()), t
assert np.array_equal(eng.parse("ab"*17).columns, ref.parse("ab"*17).columns)
sp = StreamingParser(eng, first_seal_len=4)
prefix = ""
for piece in ["ab", "abab", "ba"*4]:
    sp.append(piece); prefix += piece
    cold = ref.parse(prefix)
    assert np.array_equal(sp.current_slpf().pack(), cold.pack()), piece
    assert sp.accepted == cold.accepted
print("DISTRIBUTED-OK")
"""
    env = {"PYTHONPATH": str(Path(__file__).parents[1] / "src"), "PATH": "/usr/bin:/bin"}
    import os

    env.update({k: v for k, v in os.environ.items() if k not in env})
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=600
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "DISTRIBUTED-OK" in out.stdout
