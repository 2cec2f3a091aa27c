"""Packed uint32 semiring layer: round-trips, OR-AND word ops, reach kernel.

Property tests (hypothesis when installed, a fixed seed sweep always) for the
host-side packers in ``core/matrices.py`` — including the n % 32 != 0 padding
edge — and for the jnp-side packed ops the "packed" backend is built from,
each checked against the dense boolean oracles (``boolean_matmul`` /
``boolean_matvec``).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.matrices import (
    boolean_matmul,
    boolean_matvec,
    pack_bits,
    pack_bits_jnp,
    pack_transition_table,
    pack_transition_table_jnp,
    packed_identity,
    packed_matvec,
    packed_matvec_T,
    packed_matvec_T_words,
    packed_matvec_words,
    packed_semiring_matmul,
    unpack_bits,
    unpack_bits_jnp,
)

SEEDS = list(range(8))


def _rand_mats(seed, n, density=0.2):
    rng = np.random.default_rng(seed)
    A = rng.random((n, n)) < density
    B = rng.random((n, n)) < density
    v = rng.random(n) < 0.35
    return A, B, v


# ------------------------------------------------------- host-side packers


def _check_roundtrip(seed: int, n: int, axis: int) -> None:
    rng = np.random.default_rng(seed)
    shape = [3, 4, 5]
    shape[axis] = n                      # n sits on the packed axis
    mat = rng.random(tuple(shape)) < 0.3
    packed = pack_bits(mat, axis=axis)
    assert packed.dtype == np.uint32
    assert packed.shape[axis] == -(-n // 32)
    assert np.array_equal(unpack_bits(packed, n, axis=axis), mat)


@pytest.mark.parametrize("axis", [-1, 0, 1])
# 1, 31, 33, 63: every n % 32 != 0 shape class around the word boundary
@pytest.mark.parametrize("n", [1, 31, 32, 33, 63, 64, 96])
def test_pack_unpack_roundtrip_any_width(n, axis):
    for seed in SEEDS:
        _check_roundtrip(seed, n, axis)


def _unpack_wordwise(packed: np.ndarray, n: int) -> np.ndarray:
    """Word-wise shift-and-mask reference of unpack_bits along the last axis."""
    bits = (packed[..., :, None] >> np.arange(32, dtype=np.uint32)) & np.uint32(1)
    flat = bits.reshape(packed.shape[:-1] + (32 * packed.shape[-1],))
    return flat[..., :n].astype(bool)


@pytest.mark.parametrize("layout", ["contiguous", "strided", "zero_rows"])
@pytest.mark.parametrize("axis", [-1, 0])
@pytest.mark.parametrize("n", [1, 31, 32, 33, 37, 64, 257])
def test_unpack_bits_matches_wordwise_golden(n, axis, layout):
    """Full random words, so every padding bit above n is set and must be
    dropped; a strided view and a zero-row input take the same path."""
    rng = np.random.default_rng(n)
    W = -(-n // 32)
    rows = 0 if layout == "zero_rows" else 5
    words = rng.integers(0, 1 << 32, size=(2 * rows, W + 1), dtype=np.uint64)
    words = words.astype(np.uint32)
    if layout == "strided":
        packed = words[::2, :W]              # every other row, last word dropped
        assert not packed.flags.c_contiguous
    else:
        packed = np.ascontiguousarray(words[:rows, :W])
    want = _unpack_wordwise(packed, n)
    if axis == 0:
        got = unpack_bits(packed.T, n, axis=0)
        assert got.dtype == bool and got.shape == (n, rows)
        assert np.array_equal(got, want.T)
        return
    got = unpack_bits(packed, n, axis=axis)
    assert got.dtype == bool and got.shape == (rows, n)
    assert got.flags.c_contiguous and got.flags.writeable
    assert np.array_equal(got, want)


def test_pack_unpack_roundtrip_property():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.given(st.integers(0, 10_000), st.integers(1, 130), st.sampled_from([-1, 0, 1]))
    @hyp.settings(max_examples=40, deadline=None)
    def run(seed, n, axis):
        _check_roundtrip(seed, n, axis)

    run()


@pytest.mark.parametrize("n", [24, 32, 40, 64])
def test_pack_transition_table_orientation(n):
    """N_packed[c, col] is the packed target set of source col — bit row of
    column col — including the n % 32 != 0 tail-padding edge."""
    rng = np.random.default_rng(n)
    N = rng.random((3, n, n)) < 0.25
    packed = pack_transition_table(N)
    W = -(-n // 32)
    assert packed.shape == (3, n, W)
    for c in range(3):
        for col in range(n):
            assert np.array_equal(
                unpack_bits(packed[c, col], n), N[c, :, col]
            ), (c, col)


# --------------------------------------------------------- jnp-side packers


@pytest.mark.parametrize("n", [32, 64, 96])
def test_jnp_packers_match_numpy(n):
    for seed in SEEDS:
        A, _, v = _rand_mats(seed, n)
        Nf = A.astype(np.float32)[None]
        assert np.array_equal(
            np.asarray(pack_transition_table_jnp(jnp.asarray(Nf))),
            pack_transition_table(A[None]),
        )
        assert np.array_equal(
            np.asarray(pack_bits_jnp(jnp.asarray(v.astype(np.float32)))),
            pack_bits(v),
        )
        packed = pack_bits(A)
        assert np.array_equal(
            np.asarray(unpack_bits_jnp(jnp.asarray(packed), n)),
            A.astype(np.float32),
        )


def test_packed_identity_is_packed_eye():
    for n in (32, 64, 128):
        assert np.array_equal(
            np.asarray(packed_identity(n)),
            pack_transition_table(np.eye(n, dtype=bool)[None])[0],
        )


# ------------------------------------------------- packed OR-AND vs oracle


def _check_packed_ops(seed: int, n: int, density: float) -> None:
    A, B, v = _rand_mats(seed, n, density)
    Qa = jnp.asarray(pack_transition_table(A[None])[0])
    Qb = jnp.asarray(pack_transition_table(B[None])[0])
    vf = jnp.asarray(v.astype(np.float32))
    vp = jnp.asarray(pack_bits(v))
    # matmul: packed product of packed operands == packed dense product
    C = pack_transition_table(boolean_matmul(A, B)[None])[0]
    assert np.array_equal(np.asarray(packed_semiring_matmul(Qa, Qb)), C)
    # matvec (f32 entries) and its free transpose
    assert np.array_equal(
        np.asarray(packed_matvec(Qa, vf)), boolean_matvec(A, v).astype(np.float32)
    )
    assert np.array_equal(
        np.asarray(packed_matvec_T(Qa, vf)),
        boolean_matvec(A.T, v).astype(np.float32),
    )
    # word-resident matvecs (the build&merge inner loop)
    assert np.array_equal(
        np.asarray(packed_matvec_words(Qa, vp)), pack_bits(boolean_matvec(A, v))
    )
    assert np.array_equal(
        np.asarray(packed_matvec_T_words(Qa, vp)),
        pack_bits(boolean_matvec(A.T, v)),
    )
    # identity is a two-sided no-op
    eye = packed_identity(n)
    assert np.array_equal(np.asarray(packed_semiring_matmul(eye, Qa)), np.asarray(Qa))
    assert np.array_equal(np.asarray(packed_semiring_matmul(Qa, eye)), np.asarray(Qa))


@pytest.mark.parametrize("n", [32, 64, 96, 160])
@pytest.mark.parametrize("density", [0.0, 0.05, 0.3, 1.0])
def test_packed_ops_match_boolean_oracle(n, density):
    for seed in SEEDS[:4]:
        _check_packed_ops(seed, n, density)


def test_packed_ops_property():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.given(
        st.integers(0, 10_000),
        st.sampled_from([32, 64, 96]),
        st.floats(0.0, 1.0),
    )
    @hyp.settings(max_examples=30, deadline=None)
    def run(seed, n, density):
        _check_packed_ops(seed, n, density)

    run()


def test_packed_matmul_batched_leading_dims():
    """associative_scan calls the combine on stacked blocks — leading batch
    dims must broadcast like matmul."""
    rng = np.random.default_rng(3)
    mats = rng.random((5, 64, 64)) < 0.2
    Q = jnp.asarray(pack_transition_table(mats))
    got = np.asarray(packed_semiring_matmul(Q[:4], Q[1:]))
    for i in range(4):
        want = pack_transition_table(boolean_matmul(mats[i], mats[i + 1])[None])[0]
        assert np.array_equal(got[i], want), i


# ------------------------------------------------------------ reach kernel


@pytest.mark.parametrize("k", [1, 3, 8])
def test_packed_reach_kernel_matches_fold(k):
    """kernels/packed_reach.py (interpret mode) == the jnp packed fold =="""
    from repro.kernels.ops import packed_reach_chunk_product

    rng = np.random.default_rng(k)
    n, A = 64, 4
    N = rng.random((A + 1, n, n)) < 0.2
    N[A] = np.eye(n, dtype=bool)
    ids = rng.integers(0, A + 1, size=k).astype(np.int32)
    Np = jnp.asarray(pack_transition_table(N))
    got = np.asarray(packed_reach_chunk_product(Np, jnp.asarray(ids)))
    # dense oracle: P = N[x_k] ⊗ … ⊗ N[x_1]
    P = np.eye(n, dtype=bool)
    for cls in ids:
        P = boolean_matmul(N[cls], P)
    assert np.array_equal(got, pack_transition_table(P[None])[0])


def test_packed_kernel_backend_bit_identical():
    """PackedBackend(kernel=True) routes reach through the Pallas kernel and
    stays bit-identical to the XLA word-op path on a real parse."""
    from repro.core.backend import PackedBackend
    from repro.core.engine import ParserEngine
    from repro.core.reference import ParallelArtifacts

    art = ParallelArtifacts.generate("(a|b|ab)+")
    ek = ParserEngine(art.matrices, backend=PackedBackend(kernel=True))
    ej = ParserEngine(art.matrices, backend="packed")
    for text in ["", "ba", "abab", "ab" * 17]:
        a = ek.parse(text, n_chunks=4)
        b = ej.parse(text, n_chunks=4)
        assert np.array_equal(a.columns, b.columns), text
