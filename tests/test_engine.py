"""JAX parallel engine (core/engine.py) vs serial oracle.

The distributed (mesh) path lives in tests/test_distributed.py."""

import numpy as np
import pytest

from repro.core.engine import EngineTables, ParserEngine
from repro.core.reference import ParallelArtifacts
from repro.core.serial import parse_serial_matrix
from repro.data.regen import random_regex, sample_string


@pytest.fixture(scope="module")
def art():
    return ParallelArtifacts.generate("(a|b|ab)+")


@pytest.fixture(scope="module", params=["jnp", "pallas"])
def engine(art, request):
    return ParserEngine(art.matrices, backend=request.param)


@pytest.mark.parametrize("text,c", [
    ("abab", 1), ("abab", 2), ("abab", 4), ("ababab", 3),
    ("", 2), ("b", 1), ("ba", 2), ("a" * 23, 5),
])
def test_engine_matches_serial(art, engine, text, c):
    ref = parse_serial_matrix(art.matrices, text)
    got = engine.parse(text, n_chunks=c)
    assert np.array_equal(ref.columns, got.columns), (text, c)


def test_identity_padding_is_noop(art, engine):
    """PAD-class chunks (identity matrices) never change the SLPF."""
    text = "ababa"
    a = engine.parse(text, n_chunks=2)   # k=3, 1 pad char
    b = engine.parse(text, n_chunks=5)   # k=1, no pad
    c = engine.parse(text, n_chunks=4)   # k=2, 3 pads
    assert np.array_equal(a.columns, b.columns)
    assert np.array_equal(a.columns, c.columns)


def test_lane_padding_invariance(art):
    """Padding ℓ to 128 lanes (kernel alignment) is semantics-free."""
    e32 = ParserEngine(art.matrices, lane_pad=32)
    e128 = ParserEngine(art.matrices, lane_pad=128)
    for text in ["abab", "ba", "aabba"]:
        assert np.array_equal(
            e32.parse(text, 3).columns, e128.parse(text, 3).columns
        )


def test_property_engine_equals_serial():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    from repro.core.numbering import number_regex
    from repro.core.segments import compute_segments

    @hyp.given(st.integers(0, 5_000), st.integers(3, 8), st.integers(1, 5))
    @hyp.settings(max_examples=20, deadline=None)
    def run(seed, size, c):
        rng = np.random.Generator(np.random.Philox(seed))
        ast = random_regex(size, rng)
        art = ParallelArtifacts.generate(compute_segments(number_regex(ast)))
        eng = ParserEngine(art.matrices)
        text = sample_string(ast, rng)[:10]
        ref = parse_serial_matrix(art.matrices, text)
        got = eng.parse(text, n_chunks=c)
        assert np.array_equal(ref.columns, got.columns)

    run()


def test_assemble_matches_wordwise_formula():
    """The engine seam: ``_assemble`` of a 2-chunk TRAFFIC text's packed
    columns equals the word-wise shift-and-mask formula over the same
    concatenated words, and the serial oracle."""
    from repro.core.segments import compute_segments

    eng = ParserEngine(
        compute_segments(r"((GET|POST|PUT) /([a-z0-9]|/)* ([0-9]{3}) (ok|err|-)\n)+"),
        backend="sparse",
    )
    text = "GET /a/b1 200 ok\nPOST /x 404 err\nPUT // 500 -\n"
    classes = eng.classes_of_text(text)
    c, k = eng.bucket_shape(len(classes), 2)
    assert c == 2
    t = eng.tables
    col0s, colss = eng._jit_batched(t.N, t.I, t.F, eng._pad_to(classes, c, k)[None])
    col0, cols = np.asarray(col0s[0]), np.asarray(colss[0])
    got = eng._assemble(col0, cols, classes)

    W = cols.shape[-1]
    packed = np.concatenate([col0[None], cols.reshape(-1, W)[: len(classes)]])
    bits = (packed[..., :, None] >> np.arange(32, dtype=np.uint32)) & np.uint32(1)
    want = bits.reshape(packed.shape[0], -1)[:, : t.ell].astype(bool)
    assert got.columns.shape == (len(classes) + 1, t.ell)
    assert np.array_equal(got.columns, want)
    assert np.array_equal(got.columns, parse_serial_matrix(eng.matrices, text).columns)
