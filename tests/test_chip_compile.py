"""Compile rehearsal for a described TPU v5e — no chip attached.

The TPU compiler that ships with jax compiles for a chip that is described
(``topologies.get_topology_desc``) but not present.  These tests compile the
parser's Pallas kernels with ``interpret=False`` at real widths, the fused
batch programs that carry them, and the four-chip mesh programs, so a
BlockSpec the chip's tiling rules refuse, a slice Mosaic cannot lower, or a
program past the scoped VMEM or the 16 GB of HBM fails here.  Nothing runs:
a compile that passes is not a chip run.

The topology is described only inside the module fixture (one process holds
the TPU library, so never at import, in ``skipif`` or in ``parametrize``).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro import Parser, ParserConfig
from repro.core.engine import ParserEngine
from repro.core.segments import compute_segments
from repro.kernels import build, packed_reach, reach

HBM_BYTES = 16 * 10**9          # one v5e chip
N_CLASSES = 8                   # A+1 transition matrices
K = 4096                        # chunk length
TRAFFIC_RE = r"((GET|POST|PUT) /([a-z0-9]|/)* ([0-9]{3}) (ok|err|-)\n)+"
E125 = "(a|b)*a(a|b){125}"      # ℓ = 257: the dense regime


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the persistent
    # cache without one; keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _fits(compiled):
    m = compiled.memory_analysis()
    used = m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes
    assert used < HBM_BYTES, used


@pytest.mark.parametrize("ell_pad", [128, 384, reach.MAX_ELL_PAD])
def test_reach_kernel_compiles(one_chip, ell_pad):
    shapes = [((N_CLASSES, ell_pad, ell_pad), jnp.float32), ((K,), jnp.int32)]
    fn = lambda N, ids: reach.reach_chunk_product(N, ids, interpret=False)
    _assert_mosaic(_compile(fn, shapes, one_chip))


@pytest.mark.parametrize("ell_pad", [128, 384, build.MAX_ELL_PAD])
def test_build_merge_kernel_compiles(one_chip, ell_pad):
    shapes = [
        ((N_CLASSES, ell_pad, ell_pad), jnp.float32),
        ((K,), jnp.int32),
        ((ell_pad,), jnp.float32),
        ((ell_pad,), jnp.float32),
    ]
    fn = lambda N, ids, ef, eb: build.build_merge_chunk(N, ids, ef, eb, interpret=False)
    _assert_mosaic(_compile(fn, shapes, one_chip))


@pytest.mark.parametrize(
    "ell_pad,rows",
    [
        (288, 288),                                      # packed: W = 9
        (288, 16),                                       # sparse: S = 16
        (packed_reach.MAX_ELL_PAD, packed_reach.MAX_ELL_PAD),
    ],
    ids=["packed-288", "sparse-288-S16", "packed-max"],
)
def test_packed_fold_kernel_compiles(one_chip, ell_pad, rows):
    W = ell_pad // 32
    shapes = [
        ((N_CLASSES, ell_pad, W), jnp.uint32),
        ((K,), jnp.int32),
        ((rows, W), jnp.uint32),
    ]
    fn = lambda Np, ids, R0: packed_reach.packed_fold_rows(Np, ids, R0, interpret=False)
    _assert_mosaic(_compile(fn, shapes, one_chip))


@pytest.mark.parametrize("backend", ["auto", "packed", "jnp"])
def test_traffic_fused_batch_program_fits_one_chip(one_chip, backend):
    """2 MB of TRAFFIC in 8 chunks, one program: the default served route
    (auto resolves to sparse) and the two XLA routes beside it."""
    p = Parser(ParserConfig(regex=TRAFFIC_RE, backend=backend))
    assert p.backend_name == {"auto": "sparse"}.get(backend, backend)
    _fits(p.engine.lower_batch(1, 8, 262_144, sharding=one_chip).compile())


@pytest.fixture(scope="module")
def e125():
    return Parser.from_matrices(
        compute_segments(E125), ParserConfig(regex="<e125>", analyze="off")
    ).matrices


@pytest.mark.parametrize(
    "backend,kernel", [("pallas", False), ("packed", True), ("sparse", True)]
)
def test_fused_kernel_program_compiles(one_chip, e125, backend, kernel):
    """Each kernel backend's whole batch program at ℓ = 257, 256 KB."""
    p = Parser.from_matrices(
        e125,
        ParserConfig(regex="<e125>", backend=backend, kernel=kernel, analyze="off"),
    )
    # the engine resolves interpret mode from the CPU it sees; the described
    # chip needs the Mosaic lowering
    p.engine.backend.interpret = False
    compiled = p.engine.lower_batch(1, 8, 32_768, sharding=one_chip).compile()
    _assert_mosaic(compiled)
    _fits(compiled)


def test_mesh_programs_compile_on_four_chips(topo):
    """mesh="host" on a (2, 2) pod×data mesh: the chunk-sharded long text
    and the data×pod batch, each with its one product all-gather."""
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("pod", "data"))
    eng = ParserEngine(compute_segments(TRAFFIC_RE), backend="sparse", mesh=mesh)
    t, dist = eng.tables, eng.dist
    rep = NamedSharding(mesh, PartitionSpec())
    tables = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep) for a in (t.N, t.I, t.F)]
    programs = [
        (dist.chunk_program, (8, 262_144)),
        (dist.batched_program, (8, 8, 4096)),
    ]
    for prog, shape in programs:
        compiled = prog.lower(*tables, jax.ShapeDtypeStruct(shape, jnp.int32)).compile()
        assert "all-gather" in compiled.as_text()
        assert len(compiled.input_shardings[0][3].device_set) == 4
        _fits(compiled)


def test_mesh_phase_split_programs_compile_on_four_chips(topo):
    """The traced mesh route at the four-chip cell's bucket (32 chunks of
    262,144 over a (2, 2) mesh): reach and build&merge shard-local, the
    join program the only one with the product all-gather."""
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("pod", "data"))
    eng = ParserEngine(compute_segments(TRAFFIC_RE), backend="sparse", mesh=mesh)
    t, dist = eng.tables, eng.dist
    rep = NamedSharding(mesh, PartitionSpec())
    rows = NamedSharding(mesh, PartitionSpec(("pod", "data")))

    def spec(a, sharding):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    N, I, F = (spec(a, rep) for a in (t.N, t.I, t.F))
    chunks = jax.ShapeDtypeStruct((32, 262_144), jnp.int32, sharding=rows)
    P = spec(jax.eval_shape(dist.reach_program, N, chunks), rows)
    Jf, Jb, _ = (spec(a, rep) for a in jax.eval_shape(dist.join_program, P, I, F))
    programs = [
        (dist.reach_program, (N, chunks), False),
        (dist.join_program, (P, I, F), True),
        (dist.build_merge_program, (N, chunks, Jf, Jb), False),
    ]
    for prog, args, gathers in programs:
        compiled = prog.lower(*args).compile()
        assert ("all-gather" in compiled.as_text()) == gathers
        assert len(compiled.input_shardings[0][0].device_set) == 4
        _fits(compiled)
