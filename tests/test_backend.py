"""Backend switch (jnp / pallas-interpret) + batched bucketed front-end."""

import numpy as np
import pytest

from repro.core.backend import (
    JnpBackend,
    PackedBackend,
    PallasBackend,
    SparseBackend,
    get_backend,
    join_entries,
)
from repro.core.engine import ParserEngine, _entries_from_products
from repro.core.reference import ParallelArtifacts, parse_parallel_reference
from repro.core.serial import parse_serial_matrix

BACKENDS = ["jnp", "pallas", "packed", "sparse"]

TEXTS = ["", "b", "ba", "abab", "ababab", "a" * 23, "ab" * 40]


@pytest.fixture(scope="module")
def art():
    return ParallelArtifacts.generate("(a|b|ab)+")


@pytest.fixture(scope="module", params=BACKENDS)
def engine(art, request):
    return ParserEngine(art.matrices, backend=request.param)


def test_get_backend_resolution():
    assert isinstance(get_backend("jnp"), JnpBackend)
    assert isinstance(get_backend("pallas"), PallasBackend)
    assert isinstance(get_backend("packed"), PackedBackend)
    assert isinstance(get_backend("sparse"), SparseBackend)
    b = PallasBackend(interpret=True)
    assert get_backend(b) is b
    with pytest.raises(ValueError, match="unknown parse backend"):
        get_backend("cuda")


def test_join_is_the_engine_join():
    """The engine's join phase IS the shared scan-based implementation."""
    assert _entries_from_products is join_entries


@pytest.mark.parametrize("c", [1, 2, 4, 8])
def test_backend_equivalence_vs_reference(art, engine, c):
    """Identical SLPF columns vs both oracles (core/reference + core/serial)."""
    for text in TEXTS:
        got = engine.parse(text, n_chunks=c)
        ref = parse_serial_matrix(art.matrices, text)
        assert np.array_equal(ref.columns, got.columns), (engine.backend.name, text, c)
        paper = parse_parallel_reference(art, text, c=min(c, max(1, len(text))))
        assert np.array_equal(paper.columns, got.columns), (engine.backend.name, text, c)


def test_backends_agree_bit_exactly(art):
    engines = [ParserEngine(art.matrices, backend=b) for b in BACKENDS]
    for text in TEXTS:
        outs = [e.parse(text, n_chunks=4) for e in engines]
        for e, got in zip(engines[1:], outs[1:]):
            assert np.array_equal(outs[0].columns, got.columns), (
                e.backend.name, text,
            )


def test_parse_batch_matches_per_text_parse(art, engine):
    """Mixed-length batch output is exactly the per-text parse output."""
    got = engine.parse_batch(TEXTS, n_chunks=4)
    assert len(got) == len(TEXTS)
    for text, slpf in zip(TEXTS, got):
        ref = parse_serial_matrix(art.matrices, text)
        assert np.array_equal(ref.columns, slpf.columns), (engine.backend.name, text)
        single = engine.parse(text, n_chunks=4)
        assert np.array_equal(single.columns, slpf.columns), (engine.backend.name, text)


@pytest.mark.parametrize("backend", BACKENDS)
def test_parse_batch_compiles_one_program_per_bucket(art, backend):
    """Mixed lengths hit a handful of static shapes, not one jit per length."""
    eng = ParserEngine(art.matrices, backend=backend)
    texts = ["a" * n for n in (0, 1, 2, 5, 9, 17, 23, 31)]  # one (c=4, k=8) bucket
    eng.parse_batch(texts, n_chunks=4)
    assert eng.compile_count == 1
    # Same bucket + same padded batch-slot count → zero recompilation.
    eng.parse_batch(["ab" * 3, "b" * 30] * 4, n_chunks=4)
    assert eng.compile_count == 1
    # A genuinely new bucket (k=16) compiles exactly one more program.
    eng.parse_batch(["a" * 60], n_chunks=4)
    assert eng.compile_count == 2


def test_single_parse_reuses_bucketed_program(art):
    """parse() no longer re-jits per text length inside a bucket."""
    eng = ParserEngine(art.matrices)
    for n in (1, 3, 7, 12, 20, 31):
        eng.parse("a" * n, n_chunks=4)
    assert eng.compile_count == 1


def test_empty_text_routes_through_bucketed_path(art, engine):
    """Zero-length requests use the same padded/jitted program (no special
    case) and pin the seed's SLPF output: the single column I ∧ F."""
    slpf = engine.parse("", n_chunks=8)
    expected = (art.matrices.I & art.matrices.F)[None, :]
    assert np.array_equal(slpf.columns, expected)
    assert slpf.classes.shape == (0,)
    ref = parse_serial_matrix(art.matrices, "")
    assert np.array_equal(slpf.columns, ref.columns)
    # and through the batch front-end, mixed with non-empty texts
    outs = engine.parse_batch(["", "abab", ""], n_chunks=8)
    assert np.array_equal(outs[0].columns, expected)
    assert np.array_equal(outs[2].columns, expected)


def test_pallas_engine_reaches_kernels(art, monkeypatch):
    """ParserEngine(backend="pallas") actually invokes kernels/reach.py and
    kernels/build.py (not the jnp fallback)."""
    import repro.kernels.build as kbuild
    import repro.kernels.reach as kreach

    calls = []
    real_reach = kreach.reach_chunk_product
    real_build = kbuild.build_merge_chunk
    monkeypatch.setattr(
        kreach, "reach_chunk_product",
        lambda *a, **k: calls.append("reach") or real_reach(*a, **k),
    )
    monkeypatch.setattr(
        kbuild, "build_merge_chunk",
        lambda *a, **k: calls.append("build") or real_build(*a, **k),
    )
    eng = ParserEngine(art.matrices, backend="pallas")
    got = eng.parse("abab", n_chunks=2)
    ref = parse_serial_matrix(art.matrices, "abab")
    assert np.array_equal(ref.columns, got.columns)
    assert "reach" in calls and "build" in calls


def test_pallas_lane_pad_floor(art):
    """The pallas backend forces the kernels' 128-lane MXU alignment."""
    eng = ParserEngine(art.matrices, backend="pallas", lane_pad=32)
    assert eng.tables.ell_pad % 128 == 0


@pytest.mark.parametrize(
    "backend",
    [PallasBackend(), PackedBackend(kernel=True), SparseBackend(kernel=True)],
    ids=["pallas", "packed+kernel", "sparse+kernel"],
)
def test_kernel_backend_refuses_wider_than_its_kernels(backend):
    """A kernel backend checks ℓp against what its kernels compile for."""
    limit = backend.max_ell_pad
    backend.check_ell_pad(limit)
    with pytest.raises(ValueError, match=f"ℓp ≤ {limit}"):
        backend.check_ell_pad(limit + 128)


@pytest.mark.parametrize("backend", ["jnp", "packed", "sparse"])
def test_xla_backends_have_no_kernel_width_limit(backend):
    b = get_backend(backend)
    assert b.max_ell_pad is None
    b.check_ell_pad(1 << 16)


def test_wide_automaton_refused_when_the_parser_is_built():
    """e(445) has ℓ = 897 > the reach kernel's 896: construction raises
    rather than falling back to another path."""
    from repro import Parser, ParserConfig
    from repro.core.segments import compute_segments

    table = compute_segments("(a|b)*a(a|b){445}")
    with pytest.raises(ValueError, match="pallas"):
        Parser.from_matrices(
            table, ParserConfig(regex="<e445>", backend="pallas", analyze="off")
        )
    p = Parser.from_matrices(
        table, ParserConfig(regex="<e445>", backend="packed", kernel=True, analyze="off")
    )
    assert p.engine.tables.ell_pad == 928
