"""Boolean connection matrices of the parser NFA (paper Sect. 2.4).

For each character class ``c`` (App. A alphabet partition) the matrix ``N_c`` has
``N_c[row, col] = 1`` iff the NFA has an arc labeled ``c`` from segment ``col`` to
segment ``row`` — i.e. ``row ∈ FolSeg(col)`` and ``col``'s end-letter reads ``c``.

Layout: ``N`` is a dense ``(n_classes + 1, ℓ, ℓ)`` array.  Index ``n_classes`` is the
synthetic PAD class whose matrix is the identity: padding a text with PAD characters
is a semantic no-op for both the column recurrence and chunk products, which lets the
parallel engine use statically-shaped equal chunks (the TPU replacement for the
paper's load-balancing fragments).

Bit-packing: segments are packed 32-per-lane into uint32 words.  ``N_packed`` has
shape ``(n_classes + 1, ℓ, W)`` with ``W = ceil(ℓ/32)``; row-major packing along the
*target* dimension so the Boolean mat-vec ``out = OR_col v[col] & N[col]`` becomes a
masked OR-reduction — the VPU-friendly form used by the bit-packed kernels.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import numpy as np

from .segments import SegmentTable


@dataclass
class ParserMatrices:
    table: SegmentTable
    N: np.ndarray          # (A+1, ℓ, ℓ) bool;  N[A] = I (PAD class)
    I: np.ndarray          # (ℓ,) bool — initial segments
    F: np.ndarray          # (ℓ,) bool — final segments
    byte_to_class: np.ndarray  # (256,) int32

    @property
    def n_segments(self) -> int:
        return self.N.shape[1]

    @property
    def n_classes(self) -> int:  # including DEAD, excluding PAD
        return self.N.shape[0] - 1

    @property
    def pad_class(self) -> int:
        return self.N.shape[0] - 1

    def classes_of_text(self, text: bytes | str) -> np.ndarray:
        if isinstance(text, str):
            text = text.encode("utf-8")
        return self.byte_to_class[np.frombuffer(text, dtype=np.uint8)]


def build_matrices(table: SegmentTable) -> ParserMatrices:
    ell = table.n
    A = table.numbered.n_classes
    N = np.zeros((A + 1, ell, ell), dtype=bool)
    for col in range(ell):
        succs = table.folseg[col]
        if not succs:
            continue
        for cls in table.seg_classes[col]:
            for row in succs:
                N[cls, row, col] = True
    N[A] = np.eye(ell, dtype=bool)  # PAD class = identity
    return ParserMatrices(
        table=table,
        N=N,
        I=table.initial.copy(),
        F=table.final.copy(),
        byte_to_class=np.asarray(table.numbered.byte_to_class, dtype=np.int32),
    )


def pad_matrices_bundle(
    m: ParserMatrices, *, ell_pad: int, n_classes: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad one automaton's (N, I, F) to a shared fleet-bucket table shape.

    Returns float32 ``N (n_classes, ell_pad, ell_pad)``, ``I (ell_pad,)``,
    ``F (ell_pad,)`` — the multi-tenant generalization of
    ``EngineTables.from_matrices``'s lane padding, so automata of different
    sizes stack on a leading tenant axis and share ONE compiled program:

      * state axes zero-pad ℓ → ell_pad: padded states have no incoming or
        outgoing arcs and I/F zero there, so they are unreachable — products
        and entry vectors restricted to the first ℓ rows are bit-identical
        to the unpadded automaton's;
      * the tenant's real classes keep indices 0..A-1 (``byte_to_class`` is
        unchanged); every index from A through n_classes-1 — the relocated
        PAD class (now uniformly ``n_classes - 1`` across the bucket) and
        any unused padding classes below it — is the identity over the
        padded space, a semantic no-op in any chunk position.

    Padding is semantics-free for every backend: dense/packed consume the
    f32 layout directly (packing happens in-jit), and the sparse feasible
    width of an identity class is its diagonal — bounded by the bucket's
    shared width bucket S, which the fleet binds to the member maximum.
    """
    ell = m.n_segments
    A1 = m.N.shape[0]                       # tenant classes incl. its PAD
    if ell_pad < ell:
        raise ValueError(f"ell_pad {ell_pad} < automaton segments {ell}")
    if n_classes < A1:
        raise ValueError(f"n_classes {n_classes} < automaton classes {A1}")
    N = np.zeros((n_classes, ell_pad, ell_pad), dtype=np.float32)
    N[: A1 - 1, :ell, :ell] = m.N[:-1].astype(np.float32)
    N[A1 - 1 :] = np.eye(ell_pad, dtype=np.float32)  # PAD + unused = identity
    I = np.zeros(ell_pad, dtype=np.float32)
    I[:ell] = m.I
    F = np.zeros(ell_pad, dtype=np.float32)
    F[:ell] = m.F
    return N, I, F


def feasible_width_bound(m: ParserMatrices) -> int:
    """Worst-case single-character feasible-start width of one automaton.

    max over REAL classes (PAD and identity padding excluded — their
    "width" is ℓ by construction and would force the dense fallback) of
    nnz-cols(N[a]): the depth-1 bound every deeper feasible set respects.
    This is the host-side quantity the fleet maxes over an ℓp-bucket's
    members to pick the bucket's shared sparse width S.
    """
    N = np.asarray(m.N[:-1]) > 0
    widths = N.any(axis=1).sum(axis=1)
    return int(widths.max()) if widths.size else 1


def pack_bits(mat: np.ndarray, axis: int = -1) -> np.ndarray:
    """Pack a boolean array along ``axis`` into uint32 words (little-endian bits)."""
    mat = np.moveaxis(np.asarray(mat, dtype=bool), axis, -1)
    n = mat.shape[-1]
    W = (n + 31) // 32
    padded = np.zeros(mat.shape[:-1] + (W * 32,), dtype=bool)
    padded[..., :n] = mat
    r = padded.reshape(mat.shape[:-1] + (W, 32))
    weights = (np.uint64(1) << np.arange(32, dtype=np.uint64)).astype(np.uint64)
    packed = (r.astype(np.uint64) * weights).sum(axis=-1).astype(np.uint32)
    return np.moveaxis(packed, -1, axis if axis >= 0 else len(packed.shape) + axis)


def unpack_bits(packed: np.ndarray, n: int, axis: int = -1) -> np.ndarray:
    """Inverse of :func:`pack_bits`.

    Unpacks byte-wise: bit ``b`` of little-endian word ``w`` is bit
    ``b % 8`` of byte ``4w + b // 8``, so one ``np.unpackbits`` pass over
    the uint8 view writes exactly the ``n`` wanted columns and allocates
    nothing but the output.
    """
    packed = np.asarray(packed)
    last = axis == -1 or axis == packed.ndim - 1
    if not last:
        packed = np.moveaxis(packed, axis, -1)
    words = np.ascontiguousarray(packed, dtype="<u4")
    flat = np.unpackbits(
        words.view(np.uint8), axis=-1, count=n, bitorder="little"
    ).view(bool)
    if last:
        return flat
    return np.moveaxis(flat, -1, axis if axis >= 0 else len(flat.shape) + axis)


def pack_transition_table(N: np.ndarray) -> np.ndarray:
    """``(A, ℓ, ℓ)`` bool → ``(A, ℓ, W)`` uint32 packed along the *row* (target) dim.

    ``N_packed[c, col]`` is the packed target set of source segment ``col`` — the
    transposed orientation needed by the OR-AND mat-vec (out = OR of rows of packed
    selected by the source vector's set bits).
    """
    return pack_bits(np.swapaxes(N, -1, -2), axis=-1)


def boolean_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Boolean-semiring product of (…, m, k) @ (…, k, n) boolean arrays."""
    return np.matmul(a.astype(np.uint8), b.astype(np.uint8)) > 0


def boolean_matvec(mat: np.ndarray, vec: np.ndarray) -> np.ndarray:
    return (mat.astype(np.uint8) @ vec.astype(np.uint8)) > 0


# ------------------------------------------------- jnp-side packed semiring
#
# Device-side (jit-traceable) counterparts of pack_bits/unpack_bits plus the
# Boolean OR-AND semiring evaluated directly on uint32 words — the compute
# layer of the "packed" ParserBackend (core/backend.py).
#
# Packed-matrix representation (the pack_transition_table orientation): a
# {0,1} matrix M (ℓp, ℓp) is stored as Q (ℓp, W) uint32 with W = ℓp/32 and
# bit b of Q[col, w] equal to M[32·w + b, col] — row ``col`` of Q is the
# packed *target* set of source segment ``col`` (little-endian bits along
# the row/target dim).  Every op below is pure word arithmetic (AND / OR /
# shift): a packed matmul is ℓp²·W word ops vs ℓp³ f32 MACs, and a packed
# product moves ℓp·W·4 = ℓp²/8 bytes vs ℓp²·4 — the 32× bandwidth cut on
# the SLPF path.

import jax
import jax.numpy as jnp

_WORD = 32
_SHIFTS = np.arange(_WORD, dtype=np.uint32)


def _or_reduce(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Bitwise-OR reduction along ``axis`` (uint32)."""
    axis = axis % x.ndim
    return jax.lax.reduce(x, jnp.uint32(0), jax.lax.bitwise_or, (axis,))


def pack_bits_jnp(bits: jnp.ndarray) -> jnp.ndarray:
    """(…, ℓp) {0,1} numeric → (…, ℓp/32) uint32 along the last axis.

    Device-side twin of :func:`pack_bits` (last axis only, ℓp % 32 == 0);
    bit-identical to the numpy packer and to ``backend.pack_columns_u32``.
    """
    n = bits.shape[-1]
    assert n % _WORD == 0, f"packed dim {n} must be a multiple of 32"
    r = bits.reshape(bits.shape[:-1] + (n // _WORD, _WORD)).astype(jnp.uint32)
    return _or_reduce(r << jnp.asarray(_SHIFTS), axis=-1)


def unpack_bits_jnp(packed: jnp.ndarray, n: int) -> jnp.ndarray:
    """(…, W) uint32 → (…, n) f32 {0,1} along the last axis (inverse pack)."""
    bits = (packed[..., :, None] >> jnp.asarray(_SHIFTS)) & jnp.uint32(1)
    flat = bits.reshape(packed.shape[:-1] + (-1,))
    return flat[..., :n].astype(jnp.float32)


def pack_transition_table_jnp(N: jnp.ndarray) -> jnp.ndarray:
    """(…, ℓp, ℓp) {0,1} → (…, ℓp, W) uint32 packed along the row (target) dim.

    Device-side twin of :func:`pack_transition_table`: ``out[…, col]`` is the
    packed target set of source ``col`` — the packed-matrix representation of
    each leading-dim matrix.
    """
    return pack_bits_jnp(jnp.swapaxes(N, -1, -2))


def packed_identity(ell_pad: int) -> jnp.ndarray:
    """Packed identity matrix (ℓp, W): bit ``j`` set in row ``j``."""
    assert ell_pad % _WORD == 0
    j = jax.lax.broadcasted_iota(jnp.uint32, (ell_pad, ell_pad // _WORD), 0)
    w = jax.lax.broadcasted_iota(jnp.uint32, (ell_pad, ell_pad // _WORD), 1)
    return jnp.where(j // _WORD == w, jnp.uint32(1) << (j % _WORD), jnp.uint32(0))


def packed_semiring_matmul(later: jnp.ndarray, earlier: jnp.ndarray) -> jnp.ndarray:
    """OR-AND product ``later ⊗ earlier`` of packed matrices (…, ℓp, W).

    Column j of the result is the OR of ``later``'s rows selected by the set
    bits of ``earlier``'s column j:  Qc[j] = OR_k bit_k(Qe[j]) · Ql[k].  The
    contraction runs as a scan over 32-bit word blocks of k, so the live
    intermediate is (…, ℓp, 32, W) words = one f32 matrix's worth, never ℓp³.
    Leading batch dims broadcast like ``matmul`` (``associative_scan`` calls
    its combine on stacked blocks).
    """
    lp, W = later.shape[-2:]
    later, earlier = jnp.broadcast_arrays(later, earlier)
    batch = later.shape[:-2]
    blocks = later.reshape(batch + (W, _WORD, W))     # rows, k-word-grouped
    # scan over the k word-blocks: put that axis first
    words_seq = jnp.moveaxis(earlier, -1, 0)          # (W, …, ℓp)
    blocks_seq = jnp.moveaxis(blocks, -3, 0)          # (W, …, 32, W)

    def body(acc, xs):
        words, block = xs                             # (…, ℓp) · (…, 32, W)
        bits = (words[..., None] >> jnp.asarray(_SHIFTS)) & jnp.uint32(1)
        mask = jnp.uint32(0) - bits                   # {0, 0xFFFFFFFF}
        sel = mask[..., :, None] & block[..., None, :, :]   # (…, ℓp, 32, W)
        return acc | _or_reduce(sel, axis=-2), None

    acc0 = jnp.zeros(batch + (lp, W), jnp.uint32)
    acc, _ = jax.lax.scan(body, acc0, (words_seq, blocks_seq))
    return acc


def _select_or(Q: jnp.ndarray, bits: jnp.ndarray) -> jnp.ndarray:
    """OR of ``Q``'s rows (ℓp, W) selected by ``bits`` (ℓp,) {0,1} → (W,)."""
    mask = jnp.uint32(0) - bits.astype(jnp.uint32)
    return _or_reduce(mask[:, None] & Q, axis=0)


def packed_matvec(Q: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """``M v`` with packed M: {0,1} f32 v (ℓp,) → {0,1} f32 (ℓp,).

    out = OR of the packed target rows whose source bit is set in v — the
    masked OR-reduction form of ``boolean_matvec`` (module docstring).
    """
    return unpack_bits_jnp(_select_or(Q, v > 0.5), Q.shape[0])


def packed_matvec_words(Q: jnp.ndarray, vp: jnp.ndarray) -> jnp.ndarray:
    """``M v`` staying packed: words vp (W,) → words (W,)."""
    bits = ((vp[:, None] >> jnp.asarray(_SHIFTS)) & jnp.uint32(1)).reshape(-1)
    return _select_or(Q, bits)


def packed_matvec_T(Q: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """``Mᵀ v`` with packed M: out[col] = 1 iff v hits any target of col.

    One AND + OR-reduce per row: out[col] = any(Q[col] & pack(v)) — the
    transposed mat-vec is *free* on the packed layout (no transpose pass).
    """
    vp = pack_bits_jnp(v)
    hits = _or_reduce(Q & vp[None, :], axis=1) != 0      # (ℓp,) bool
    return hits.astype(jnp.float32)


def packed_matvec_T_words(Q: jnp.ndarray, vp: jnp.ndarray) -> jnp.ndarray:
    """``Mᵀ v`` staying packed: words vp (W,) → words (W,)."""
    hits = _or_reduce(Q & vp[None, :], axis=1) != 0      # (ℓp,) bool
    return pack_bits_jnp(hits)


# --------------------------------------------- sparse feasible-start products
#
# The speculation-width-reduced product representation of the "sparse"
# ParserBackend (core/backend.py).  A chunk product only has nonzero packed
# rows at the *feasible start states* — the states surviving the chunk's
# leading character(s) (PaREM §III) — so it is carried as an (S, 1+W) uint32
# array of gathered rows instead of the dense (ℓp, W) packed matrix:
#
#   P[j, 0]  = source-state index of listed row j, or SPARSE_EMPTY for an
#              unused slot (a zero row);
#   P[j, 1:] = that row's packed target-set words (the packed-semiring layout
#              above — bit b of word w ⇔ target 32·w + b reachable).
#
# S is a static power-of-two bucket ≥ the automaton's max per-class feasible
# width (chosen host-side at engine build; dense fallback S = ℓp when the
# bound does not shrink).  The monoid identity cannot list its ℓp nonzero
# rows inside S slots, so it is encoded by a flag: P[0, 0] == SPARSE_IDENT
# marks the whole product as the identity (every other slot ignored).  All
# ops below honour the flag with `where`, so identity pad slots in join
# stacks stay semantic no-ops exactly as in the dense representations.

SPARSE_EMPTY = np.uint32(0x7FFFFFFF)   # unused slot (zero row)
SPARSE_IDENT = np.uint32(0x7FFFFFFE)   # in slot [0, 0]: product = identity


def sparse_identity(rows: int, W: int) -> jnp.ndarray:
    """The identity product in the sparse layout: flag set, no listed rows."""
    P = jnp.full((rows, 1 + W), SPARSE_EMPTY, dtype=jnp.uint32)
    P = P.at[:, 1:].set(jnp.uint32(0))
    return P.at[0, 0].set(SPARSE_IDENT)


def sparse_is_identity(P: jnp.ndarray) -> jnp.ndarray:
    """Scalar (or batched) bool: is this sparse product the flagged identity?"""
    return P[..., 0, 0] == SPARSE_IDENT


def sparse_init_rows(idx: jnp.ndarray, ell_pad: int) -> jnp.ndarray:
    """Packed identity rows e_idx: (S,) indices → (S, W) words.

    Row j holds the single bit ``idx[j]``; sentinel indices (≥ ℓp) give zero
    rows — the reach fold's start state (partial product after 0 characters).
    """
    W = ell_pad // _WORD
    S = idx.shape[0]
    w = jax.lax.broadcasted_iota(jnp.uint32, (S, W), 1)
    i = idx.astype(jnp.uint32)[:, None]
    return jnp.where(
        (i < ell_pad) & (i // _WORD == w),
        jnp.uint32(1) << (i % _WORD),
        jnp.uint32(0),
    )


def sparse_to_packed(P: jnp.ndarray, ell_pad: int) -> jnp.ndarray:
    """Sparse (S, 1+W) → dense packed (ℓp, W): scatter listed rows, zeros
    elsewhere; the flagged identity densifies to ``packed_identity``."""
    idx = P[:, 0].astype(jnp.int32)
    W = P.shape[-1] - 1
    dense = (
        jnp.zeros((ell_pad, W), jnp.uint32).at[idx].set(P[:, 1:], mode="drop")
    )
    return jnp.where(sparse_is_identity(P), packed_identity(ell_pad), dense)


def _sparse_compose_one(later: jnp.ndarray, earlier: jnp.ndarray) -> jnp.ndarray:
    """``later ⊗ earlier`` of two (S, 1+W) sparse products.

    The composition's feasible rows are (a subset of) ``earlier``'s listed
    rows — a start state dead by ``earlier``'s leading characters stays dead —
    so the output keeps ``earlier``'s index column and rewrites each listed
    row through ``later``: out[s] = OR of ``later``'s rows selected by the
    target bits of ``earlier[s]`` (S·ℓp·W word ops vs the dense ℓp²·W).
    Identity flags short-circuit either side.
    """
    W = later.shape[-1] - 1
    ell_pad = W * _WORD
    D = sparse_to_packed(later, ell_pad)                     # (ℓp, W)
    out_words = jax.vmap(lambda vp: packed_matvec_words(D, vp))(earlier[:, 1:])
    composed = jnp.concatenate([earlier[:, :1], out_words], axis=1)
    out = jnp.where(sparse_is_identity(later), earlier, composed)
    return jnp.where(sparse_is_identity(earlier), later, out)


def sparse_compose(later: jnp.ndarray, earlier: jnp.ndarray) -> jnp.ndarray:
    """Batched-leading-dims ``later ⊗ earlier`` (``associative_scan`` calls
    its combine on stacked blocks, so leading dims must broadcast)."""
    return jnp.vectorize(
        _sparse_compose_one, signature="(s,v),(s,v)->(s,v)"
    )(later, earlier)


def sparse_matvec(P: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """``M v`` with sparse M: {0,1} f32 v (ℓp,) → {0,1} f32 (ℓp,).

    Gathers v at the listed source indices, ORs the selected rows' words —
    S word-selects instead of ℓp.
    """
    W = P.shape[-1] - 1
    ell_pad = W * _WORD
    idx = P[:, 0].astype(jnp.int32)
    vi = jnp.where(idx < ell_pad, v[jnp.clip(idx, 0, ell_pad - 1)], 0.0)
    mask = jnp.uint32(0) - (vi > 0.5).astype(jnp.uint32)
    words = _or_reduce(mask[:, None] & P[:, 1:], axis=0)     # (W,)
    return jnp.where(sparse_is_identity(P), v, unpack_bits_jnp(words, ell_pad))


def sparse_matvec_T(P: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """``Mᵀ v`` with sparse M: out is nonzero only at listed source states
    whose target set intersects v — one AND + OR-reduce per listed row."""
    W = P.shape[-1] - 1
    ell_pad = W * _WORD
    vp = pack_bits_jnp(v)
    hits = (_or_reduce(P[:, 1:] & vp[None, :], axis=1) != 0).astype(jnp.float32)
    idx = P[:, 0].astype(jnp.int32)
    out = jnp.zeros(ell_pad, jnp.float32).at[idx].set(hits, mode="drop")
    return jnp.where(sparse_is_identity(P), v, out)


def feasible_start_widths(
    N: np.ndarray, chunks: np.ndarray, depth: int = 1
) -> np.ndarray:
    """Host-side observed speculation widths: per-chunk feasible-set sizes.

    For each (k,) chunk row of ``chunks``, the number of start states whose
    column of ``N[y_d] ⊗ … ⊗ N[y_1]`` is nonzero — the states a chunk
    processor actually needs to speculate on, vs the paper's ℓp.  Chunks
    starting with the PAD class (all-PAD padding) report -1: their product is
    the identity and they carry no speculation.  Pure numpy (stats path).
    """
    N = np.asarray(N) > 0
    chunks = np.asarray(chunks).reshape(-1, np.asarray(chunks).shape[-1])
    pad = N.shape[0] - 1
    out = np.empty(chunks.shape[0], dtype=np.int64)
    for i, chunk in enumerate(chunks):
        if chunk[0] == pad:
            out[i] = -1
            continue
        u = np.ones(N.shape[-1], dtype=bool)
        for j in range(min(depth, len(chunk)) - 1, -1, -1):
            u = (N[chunk[j]] & u[:, None]).any(axis=0)
        out[i] = int(u.sum())
    return out
