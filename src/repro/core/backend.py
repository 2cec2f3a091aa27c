"""Pluggable three-phase parse backends (reach / join / build&merge).

The paper's decomposition (Sect. 3.2) exists in this repo at three levels:
the pure-jnp engine, the generic monoid-scan primitive (``core/scan.py``),
and the Pallas TPU kernels (``repro/kernels``).  This module collapses them
into ONE runtime schema with swappable phase implementations:

  reach        (c, k) class chunks → (c, ℓp, ℓp) chunk products
  join         chunk products → forward/backward entry states, expressed as
               ``core/scan.py``'s ``exclusive_entries`` over the Boolean
               OR-AND matrix monoid — the SAME scan the Mamba-2 SSD state
               passing uses, so there is exactly one join implementation.
  build&merge  (chunks, entries) → clean SLPF columns (Fig. 14, fused)

Backends:
  * ``JnpBackend``    — pure ``jax.numpy`` phase bodies (vmap over chunks and
    over the batch axis); the reference device program, runs anywhere.
  * ``PallasBackend`` — the ``kernels/reach.py`` + ``kernels/build.py``
    Mosaic kernels, scalar-prefetch DMA pipelining on TPU; on CPU the same
    calls run with ``interpret=True`` so tests exercise the real BlockSpecs.
    Chunks and batch rows are driven by ``lax.map`` (the kernels own the
    intra-chunk grid).
  * ``PackedBackend`` — chunk products as uint32 bit-words (32 segments per
    lane word); reach / compose / join-combine / build&merge run as OR-AND
    word ops (``core/matrices.py`` packed semiring) — a 32× bandwidth cut on
    the SLPF path for large automata.
  * ``SparseBackend`` — speculation-width reduction on top of the packed
    words: products carry only the *feasible-start rows* (the states that
    survive the chunk's leading character(s), PaREM's boundary set), so the
    product path pays |feasible| ≤ S rows instead of ℓp.

``ParserEngine(backend=...)`` selects by name; ``register_backend`` adds new
ones (GPU, …) without touching the engine.

The product-representation contract
-----------------------------------

A *chunk product* is an opaque, backend-owned device array; callers
(``ParserEngine.phases``, ``core/stream.py``'s prefix cache,
``core/distributed.py``'s all-gather join) may only assume:

  * axis 0 of ``reach``'s output indexes chunks; slicing / restacking /
    concatenating along it (``P[i]``, ``jnp.stack``, all-gather) is legal,
    as is measuring ``size * dtype.itemsize`` for cache accounting;
  * ``compose(later, earlier)`` and ``identity_product(ℓp)`` stay inside the
    representation (monoid closure); identity products are semantic no-ops
    in every position of a join stack;
  * dtype/shape beyond that are backend-private — f32 (ℓp, ℓp) matrices for
    ``jnp``/``pallas``, uint32 (ℓp, W = ℓp/32) packed target-set rows for
    ``packed``, and a *reduced* uint32 (S, 1+W) row-subset layout for
    ``sparse``.  Nothing outside the backend may arithmetic on a product.
  * backends whose representation depends on the concrete automaton hook
    ``bind_tables(tables)``, called once by ``ParserEngine.__init__`` before
    any phase is traced; the default is a no-op.

The sparse reduced representation: a chunk's product columns can only be
nonzero at start states that survive the chunk's first character(s) — the
feasible start-state set F(chunk).  ``sparse`` therefore stores, per chunk,
an (S, 1+W) uint32 array of gathered rows (slot = [source index | packed
target words]; see ``core/matrices.py``), where S is a static power-of-two
bucket of the automaton's worst-case single-character feasible width
max_a nnz-cols(N[a]) — a bound every depth-d set respects, so compiled
shapes stay fixed while the payload tracks the automaton, not ℓp.  The
monoid identity (which is not row-sparse) is carried as a flagged sentinel
product; all-PAD padding chunks produce exactly it, keeping identity slots
semantic no-ops in join stacks.  *Dense-fallback rule*: when the pow2
bucket reaches ℓp (an automaton whose first characters admit ~all states),
S = ℓp — the representation degenerates to dense packed rows plus an index
column and every op stays correct, just without the reduction.

Long chunks as sub-chains: a chunk's reach and build&merge are chains of k
dependent steps, so on the chip step latency, not work, sets their time.
The packed and sparse backends cut every chunk of static length
k ≥ ``SPLIT_MIN_LEN`` into m = k / ``SUB_CHAIN_LEN`` contiguous sub-chains
(``sub_chains(k)``).  ``reach`` folds all c·m sub-chains in one scan of
k/m steps, then composes each chunk's m sub-products in a sequential
m-step scan; ``build_merge_packed`` recomputes the sub-products, carries
each chunk's entry pair across its sub-chains (a forward scan of ``act``
and a reverse scan of ``act_T``, m steps each), and builds every sub-chain
as a chunk of its own.  Every step is one ``lax.scan``, so the program
has the same loops whatever m is; the outputs, and so the join's c
products and the mesh's all-gather, are bit-identical to the whole-chunk
programs, which shorter chunks keep unchanged.

The non-product boundaries are fixed across backends: ``join`` consumes a
(c, …) product stack and returns f32 (c, ℓp) entry vectors {0,1};
``start_column`` returns the f32 (ℓp,) text-start column; and
``build_merge_packed`` emits the engine-boundary output format — uint32
bit-packed SLPF columns (c, k, W), bit-identical across backends.  Those
fixed f32/u32 seams are what let every route (fused, phase-split,
streaming, mesh) swap backends without conversion code.
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple, Type, Union

import numpy as np

import jax
import jax.numpy as jnp

from .matrices import (
    SPARSE_EMPTY,
    pack_bits_jnp,
    pack_transition_table_jnp,
    packed_identity,
    packed_matvec,
    packed_matvec_T,
    packed_matvec_T_words,
    packed_matvec_words,
    packed_semiring_matmul,
    sparse_compose,
    sparse_identity,
    sparse_init_rows,
    sparse_matvec,
    sparse_matvec_T,
)
from .scan import exclusive_entries


def _next_pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())


#: Chunks at least this long run reach and build&merge as sub-chains of
#: ``SUB_CHAIN_LEN`` steps (``PackedBackend.sub_chains``); shorter chunks
#: keep the whole-chunk program.
SPLIT_MIN_LEN = 1 << 14
#: Steps of one sub-chain of a split chunk.
SUB_CHAIN_LEN = 1 << 9


def _resolve_interpret(interpret: Union[bool, None]) -> bool:
    """A kernel backend's ``interpret`` toggle; None = interpret only on
    the CPU platform (``kernels.ops.use_interpret``)."""
    if interpret is None:
        from ..kernels.ops import use_interpret

        return use_interpret()
    return interpret


# ----------------------------------------------------------- semiring ops


def semiring_matmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Boolean OR-AND product on {0,1} floats: clamp(a @ b)."""
    return jnp.minimum(jnp.matmul(a, b, precision=jax.lax.Precision.DEFAULT), 1.0)


def semiring_matvec(m: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    return jnp.minimum(m @ v, 1.0)


def pack_columns_u32(cols: jnp.ndarray) -> jnp.ndarray:
    """(…, ℓp) {0,1} floats → (…, ℓp/32) uint32, little-endian bits.

    Engine-boundary alias of the packed semiring's packer — ONE device-side
    bit layout repo-wide (``core/matrices.py``).
    """
    return pack_bits_jnp(cols)


# ------------------------------------------------------ jnp phase bodies


def reach_chunk(N: jnp.ndarray, chunk: jnp.ndarray) -> jnp.ndarray:
    """Chunk product P = N[y_k] ⊗ … ⊗ N[y_1] — the reach phase (Eq. 6)."""
    lp = N.shape[-1]

    def step(P, cls):
        return semiring_matmul(N[cls], P), None

    P, _ = jax.lax.scan(step, jnp.eye(lp, dtype=N.dtype), chunk)
    return P


def build_merge_chunk(
    N: jnp.ndarray, chunk: jnp.ndarray, entry_f: jnp.ndarray, entry_b: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fig. 14 fused builder&merger for one chunk.

    Returns (M, beta0): M (k, ℓp) clean columns at positions 1..k of the chunk;
    beta0 (ℓp,) the backward state at the chunk start (used for global C_0).
    """

    def fstep(v, cls):
        nv = semiring_matvec(N[cls], v)
        return nv, nv

    _, fwd = jax.lax.scan(fstep, entry_f, chunk)            # fwd[t] = B_{t+1}

    def bstep(v, cls):
        nv = semiring_matvec(N[cls].T, v)
        return nv, nv

    _, bwd_rev = jax.lax.scan(bstep, entry_b, chunk[::-1])  # β_{k-1} … β_0
    bwd = bwd_rev[::-1]                                     # β_0 … β_{k-1}
    beta0 = bwd[0]
    # merge: M[t] = fwd[t] ∧ β_{t+1};  β_k = entry_b
    bwd_for_merge = jnp.concatenate([bwd[1:], entry_b[None]], axis=0)
    return fwd * bwd_for_merge, beta0


# ------------------------------------------------------- shared join phase


def join_entries(
    P: jnp.ndarray, I: jnp.ndarray, F: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Join phase (Eq. 7) from stacked chunk products P (c, ℓp, ℓp).

    Forward entry of chunk i:  J_i = (P_{i-1} ⊗ … ⊗ P_0) I.
    Backward entry of chunk i: Ĵ_i = (P_{c-1} ⊗ … ⊗ P_{i+1})ᵀ F — the
    transposed-suffix form that makes the backward reach free (DESIGN §2).

    Both directions are instances of ``core/scan.exclusive_entries`` over the
    Boolean matrix monoid — the identical scan the Mamba-2 SSD chunked state
    passing uses, so the parser and the model share one join implementation.
    """
    Jf = exclusive_entries(
        combine=semiring_matmul,                     # (later, earlier) → later ⊗ earlier
        act=semiring_matvec,
        summaries=P,
        init=I,
    )
    # Backward: scan the reversed products with flipped composition, acting by
    # the transpose; index j of the reversed scan is chunk c-1-j.
    Jb_rev = exclusive_entries(
        combine=lambda later, earlier: semiring_matmul(earlier, later),
        act=lambda m, v: semiring_matvec(m.T, v),
        summaries=P[::-1],
        init=F,
    )
    return Jf, Jb_rev[::-1]


# --------------------------------------------------------------- backends


class ParserBackend:
    """Swappable implementations of the three phases over EngineTables arrays.

    Table inputs use the engine's padded layout — N (A+1, ℓp, ℓp) f32, chunks
    (c, k) int32 — while chunk *products* are backend-owned opaque arrays (see
    the module docstring's product-representation contract).  Entries stay
    f32 (c, ℓp) and SLPF columns leave ``build_merge_packed`` as uint32 words
    in every backend.  ``join`` is shared (scan-based); subclasses provide
    ``reach`` and ``build_merge`` plus a batching strategy, and override the
    product-touching ops together when they change the representation.
    """

    name: str = "abstract"
    min_lane_pad: int = 32   # segment-dim alignment this backend requires

    @property
    def max_ell_pad(self) -> Union[int, None]:
        """Largest ℓp this backend's Pallas kernels compile for on a TPU
        v5e core (its scoped VMEM), or None when it runs no kernel."""
        return None

    def check_ell_pad(self, ell_pad: int) -> None:
        """Refuse an automaton wider than the kernels compile for — at
        construction, never by falling back to another path."""
        limit = self.max_ell_pad
        if limit is not None and ell_pad > limit:
            raise ValueError(
                f"backend {self.name!r} runs Pallas kernels that compile for "
                f"ℓp ≤ {limit} on a TPU v5e (scoped VMEM); this automaton "
                f"has ℓp={ell_pad}"
            )

    def bind_tables(self, tables) -> None:
        """One-time hook: the concrete ``EngineTables`` this backend will run.

        Called by ``ParserEngine.__init__`` before any phase program is
        traced.  Backends whose product representation depends on the
        automaton (the sparse width bucket S) derive their static shapes
        here; the default only checks ℓp against the kernels' limit.
        """
        self.check_ell_pad(int(tables.ell_pad))

    def sub_chains(self, k: int) -> int:
        """Sub-chains m that ``reach`` and ``build_merge_packed`` cut each
        chunk of static length ``k`` into (1: the chunk runs whole)."""
        return 1

    def reach(self, N: jnp.ndarray, chunks: jnp.ndarray) -> jnp.ndarray:
        """(c, k) chunks → stacked chunk products (axis 0 = chunk)."""
        raise NotImplementedError

    def compose(self, later: jnp.ndarray, earlier: jnp.ndarray) -> jnp.ndarray:
        """Monoid composition of two chunk products: ``later ⊗ earlier``.

        The single-step form of the reach fold — the streaming prefix cache
        extends its tail product with this instead of re-folding the whole
        tail.  Backends with a different product representation (bit-packed
        uint32 words, …) override it together with ``reach``.
        """
        return semiring_matmul(later, earlier)

    def identity_product(self, ell_pad: int, dtype=jnp.float32) -> jnp.ndarray:
        """The monoid identity in this backend's product representation.

        Used by the streaming tail (empty-product init) and as the semantic
        no-op pad slot of every join stack (``core/stream.py``,
        ``core/distributed.py``).
        """
        return jnp.eye(ell_pad, dtype=dtype)

    def join(
        self, P: jnp.ndarray, I: jnp.ndarray, F: jnp.ndarray
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Product stack (c, …) + I/F (ℓp,) f32 → f32 (c, ℓp) entries ×2."""
        return join_entries(P, I, F)

    def start_column(
        self, P: jnp.ndarray, I: jnp.ndarray, Jb0: jnp.ndarray
    ) -> jnp.ndarray:
        """Text-start column C₀ = I ∧ (P₀ᵀ Ĵ₀) as an f32 (ℓp,) vector.

        The backward state at text start is recovered from the first chunk's
        reach product — the only place outside the backend that would
        otherwise need product arithmetic, so it lives on the contract.
        """
        return I * semiring_matvec(P[0].T, Jb0)

    def build_merge(
        self, N: jnp.ndarray, chunks: jnp.ndarray, Jf: jnp.ndarray, Jb: jnp.ndarray
    ) -> jnp.ndarray:
        """(c, k) chunks + entries → (c, k, ℓp) clean columns."""
        raise NotImplementedError

    def build_merge_packed(
        self, N: jnp.ndarray, chunks: jnp.ndarray, Jf: jnp.ndarray, Jb: jnp.ndarray
    ) -> jnp.ndarray:
        """(c, k) chunks + entries → (c, k, W) uint32 bit-packed clean columns.

        The engine-boundary output format (identical across backends);
        word-native backends override it to emit packed columns directly.
        """
        return pack_columns_u32(self.build_merge(N, chunks, Jf, Jb))

    def batch_core(self, core: Callable) -> Callable:
        """Lift ``core(N, I, F, (c,k) chunks)`` to a (B, c, k) batch axis."""
        raise NotImplementedError

    def lift_batch(self, fn: Callable) -> Callable:
        """Lift a single phase body over a leading batch axis (all args).

        The per-phase analogue of ``batch_core``, used where the phases run
        as separate programs with a batch dim — the distributed batched route
        maps phase bodies per batch row *inside* ``shard_map``.  vmap by
        default; backends whose kernels own the device grid override with a
        sequential ``lax.map``.
        """
        return jax.vmap(fn)


class JnpBackend(ParserBackend):
    """Pure-jnp phase bodies — vmap everywhere; the reference device program."""

    name = "jnp"
    min_lane_pad = 32

    def reach(self, N, chunks):
        return jax.vmap(lambda ch: reach_chunk(N, ch))(chunks)

    def build_merge(self, N, chunks, Jf, Jb):
        M, _ = jax.vmap(lambda ch, ef, eb: build_merge_chunk(N, ch, ef, eb))(
            chunks, Jf, Jb
        )
        return M

    def batch_core(self, core):
        return jax.vmap(core, in_axes=(None, None, None, 0))


class PallasBackend(ParserBackend):
    """Mosaic kernels for the two hot loops (scalar-prefetch DMA pipelining).

    ``interpret=None`` auto-selects: interpret mode on the CPU (kernel bodies
    run under the Pallas interpreter, validating BlockSpecs and index maps —
    the CI-checkable form of the TPU program), real Mosaic everywhere else.  Chunk and batch
    axes run under ``lax.map``: the kernels own the intra-chunk grid, and the
    sequential outer loop keeps a single (ℓp, ℓp) VMEM working set live.
    """

    name = "pallas"
    min_lane_pad = 128   # MXU tile alignment required by the kernels

    def __init__(self, interpret: Union[bool, None] = None):
        self.interpret = interpret

    @property
    def max_ell_pad(self) -> int:
        from ..kernels import build, reach

        return min(reach.MAX_ELL_PAD, build.MAX_ELL_PAD)

    def _interp(self) -> bool:
        return _resolve_interpret(self.interpret)

    def reach(self, N, chunks):
        from ..kernels.reach import reach_chunk_product

        interp = self._interp()
        return jax.lax.map(
            lambda ch: reach_chunk_product(N, ch, interpret=interp), chunks
        )

    def build_merge(self, N, chunks, Jf, Jb):
        from ..kernels.build import build_merge_chunk as kernel_build_merge

        interp = self._interp()
        return jax.lax.map(
            lambda args: kernel_build_merge(N, *args, interpret=interp),
            (chunks, Jf, Jb),
        )

    def batch_core(self, core):
        return lambda N, I, F, batch: jax.lax.map(
            lambda ch: core(N, I, F, ch), batch
        )

    def lift_batch(self, fn):
        # sequential over batch rows: the kernels own the intra-chunk grid and
        # a vmapped pallas_call would multiply the live VMEM working set
        return lambda *args: jax.lax.map(lambda a: fn(*a), args)


class PackedBackend(ParserBackend):
    """Bit-packed uint32 phase bodies — OR-AND word ops on the VPU path.

    Chunk products are (ℓp, W = ℓp/32) uint32 packed target-set rows (the
    ``pack_transition_table`` orientation; see ``core/matrices.py``'s packed
    semiring).  Reach, compose, and the join's scan combine run as word
    AND/OR/shift — ℓp³/32 word ops and ℓp²/8 product bytes vs the f32
    layout's ℓp³ MACs and 4ℓp² bytes — and build&merge scans packed state
    words end-to-end, emitting the packed SLPF columns with no unpack pass.
    The padded f32 tables (N, I, F) are packed *inside* the jitted phase
    bodies, so every entry point keeps the engine's table layout; entries
    crossing phase boundaries stay f32 per the module contract.  The in-jit
    table packing costs O((A+1)·ℓp²) bit-gathers per call — ≤ ~(A+1)/k of
    the reach work, bounded because chunk buckets floor at
    ``ParserEngine.min_chunk_len`` (8) — the price of keeping one table
    layout at every boundary; a table-resident packed N belongs to the
    real-TPU tuning item (ROADMAP).

    ``kernel=True`` routes reach through the Pallas packed OR-AND kernel
    (``kernels/packed_reach.py``; interpret mode on the CPU) instead of the
    XLA word ops — the TPU-experiment path, bit-identical by test.
    """

    name = "packed"
    min_lane_pad = 32   # exact uint32 word packing needs ℓp % 32 == 0

    def __init__(self, kernel: bool = False, interpret: Union[bool, None] = None):
        self.kernel = kernel
        self.interpret = interpret

    @property
    def max_ell_pad(self) -> Union[int, None]:
        if not self.kernel:
            return None
        from ..kernels.packed_reach import MAX_ELL_PAD

        return MAX_ELL_PAD

    def sub_chains(self, k: int) -> int:
        """k / ``SUB_CHAIN_LEN`` once a chunk reaches ``SPLIT_MIN_LEN``
        steps, else 1.  A kernel keeps whole chunks: it owns the
        intra-chunk grid."""
        if self.kernel or k < SPLIT_MIN_LEN or k % SUB_CHAIN_LEN:
            return 1
        return k // SUB_CHAIN_LEN

    def chain_products(self, N, Np, chains):
        """(n, L) chains → n products, each folded from the chain's start:
        the whole-chunk reach body, also run over sub-chains."""
        if self.kernel:
            from ..kernels.packed_reach import packed_reach_chunk_product

            interp = _resolve_interpret(self.interpret)
            return jax.lax.map(
                lambda ch: packed_reach_chunk_product(Np, ch, interpret=interp),
                chains,
            )
        eye = packed_identity(N.shape[-1])

        def one(chunk):
            def step(Q, cls):
                return packed_semiring_matmul(Np[cls], Q), None

            Q, _ = jax.lax.scan(step, eye, chunk)
            return Q

        return jax.vmap(one)(chains)

    def _sub_products(self, N, Np, chunks, m):
        """(c, k) chunks → (c, m, …) products of their m contiguous
        sub-chains, all c·m folded in one ``L = k/m``-step scan."""
        c, k = chunks.shape
        P = self.chain_products(N, Np, chunks.reshape(c * m, k // m))
        return P.reshape((c, m) + P.shape[1:])

    def reach(self, N, chunks):
        Np = pack_transition_table_jnp(N)            # (A+1, ℓp, W)
        m = self.sub_chains(chunks.shape[-1])
        if m == 1:
            return self.chain_products(N, Np, chunks)

        def fold(subs):                              # (m, …) → chunk product
            def step(acc, p):
                return self.compose(p, acc), None

            acc, _ = jax.lax.scan(step, subs[0], subs[1:])
            return acc

        return jax.vmap(fold)(self._sub_products(N, Np, chunks, m))

    def compose(self, later, earlier):
        return packed_semiring_matmul(later, earlier)

    def act(self, P, v):
        """``P v`` on f32 {0,1} state vectors (the join's forward act)."""
        return packed_matvec(P, v)

    def act_T(self, P, v):
        """``Pᵀ v`` on f32 {0,1} state vectors (the backward act)."""
        return packed_matvec_T(P, v)                 # transpose is free packed

    def identity_product(self, ell_pad, dtype=jnp.float32):
        return packed_identity(ell_pad)

    def join(self, P, I, F):
        Jf = exclusive_entries(
            combine=self.compose, act=self.act, summaries=P, init=I
        )
        Jb_rev = exclusive_entries(
            combine=lambda later, earlier: self.compose(earlier, later),
            act=self.act_T,
            summaries=P[::-1],
            init=F,
        )
        return Jf, Jb_rev[::-1]

    def start_column(self, P, I, Jb0):
        return I * self.act_T(P[0], Jb0)

    def _sub_entries(self, subs, ef, eb):
        """One chunk's (m, …) sub-products and entry pair → the m sub-chains'
        forward and backward entries, (m, ℓp) each: sub-chain j enters with
        ``subs[j-1] ⊗ … ⊗ subs[0]`` applied to ``ef`` and with
        ``(subs[m-1] ⊗ … ⊗ subs[j+1])ᵀ`` applied to ``eb``."""

        def fstep(v, p):
            return self.act(p, v), v

        def bstep(v, p):
            return self.act_T(p, v), v

        _, ef_subs = jax.lax.scan(fstep, ef, subs)
        _, eb_subs = jax.lax.scan(bstep, eb, subs, reverse=True)
        return ef_subs, eb_subs

    def build_merge_packed(self, N, chunks, Jf, Jb):
        Np = pack_transition_table_jnp(N)
        c, k = chunks.shape
        m = self.sub_chains(k)
        if m > 1:
            # recompute the sub-products, carry each chunk's entries across
            # its sub-chains, then build every sub-chain as a chunk of its own
            subs = self._sub_products(N, Np, chunks, m)
            Jf, Jb = jax.vmap(self._sub_entries)(subs, Jf, Jb)
            chunks = chunks.reshape(c * m, k // m)
            Jf, Jb = Jf.reshape(c * m, -1), Jb.reshape(c * m, -1)

        def one(chunk, ef, eb):
            def fstep(vp, cls):
                nvp = packed_matvec_words(Np[cls], vp)
                return nvp, nvp

            _, fwd = jax.lax.scan(fstep, pack_bits_jnp(ef), chunk)

            # backward pass fused with the merge, visiting t = k-1 … 0 with
            # β_{t+1} carried (β_k = entry_b): M[t] = fwd[t] ∧ β_{t+1}, one
            # word-AND, then β_t = N[x_t]ᵀ β_{t+1}.  No reversed or shifted
            # copy of the (k, W) state stack is made: at k = 2¹⁸ the TPU
            # compiler's program for that form returned wrong columns.
            def bstep(vp, xs):
                cls, f = xs
                return packed_matvec_T_words(Np[cls], vp), f & vp

            _, M = jax.lax.scan(bstep, pack_bits_jnp(eb), (chunk, fwd), reverse=True)
            return M                                 # (k, W) packed columns

        # sub-chains are contiguous: (c·m, L, W) is (c, k, W) row for row
        return jax.vmap(one)(chunks, Jf, Jb).reshape(c, k, -1)

    def build_merge(self, N, chunks, Jf, Jb):
        from .matrices import unpack_bits_jnp

        return unpack_bits_jnp(
            self.build_merge_packed(N, chunks, Jf, Jb), N.shape[-1]
        )

    def batch_core(self, core):
        return jax.vmap(core, in_axes=(None, None, None, 0))


class SparseBackend(PackedBackend):
    """Feasible-start sparse products — the speculation-width reduction.

    The paper pays ℓp speculative start states per chunk; PaREM's observation
    is that boundary information prunes that to the *feasible start-state
    set*: only states with an outgoing transition on the chunk's first
    character(s) can have a nonzero product column.  This backend computes
    that set per chunk inside the jitted reach body (a depth-``d`` backward
    Boolean mat-vec over the chunk's leading classes), gathers the surviving
    rows, and folds ONLY those through the packed OR-AND word ops — S·ℓp·W
    word ops and S·(1+W)·4 product bytes per chunk vs the dense packed
    ℓp²·W ops and ℓp·W·4 bytes.

    Products are uint32 (S, 1+W) gathered-row arrays (module contract /
    ``core/matrices.py``): slot = [source index | packed target words],
    ``SPARSE_EMPTY`` index = unused slot, identity carried as the
    ``SPARSE_IDENT`` flag (all-PAD padding chunks emit exactly it).  S is
    static per automaton — ``bind_tables`` buckets the worst-case
    single-character feasible width max_a nnz-cols(N[a]) to the next pow2
    (floor ``min_width``), with the dense-fallback rule S = ℓp when the
    bucket reaches ℓp.  Every depth-d feasible set is a subset of the
    depth-1 set of the chunk's first class, so S slots always suffice and
    compiled shapes never depend on the text.

    The reduced representation flows end-to-end: the join scan composes
    (S, 1+W) summaries, ``StreamingParser``'s sealed cache stores them
    (``size·itemsize`` accounting sees the cut), and ``DistributedEngine``'s
    all-gather moves them across the mesh.  Entries, start column, and
    build&merge keep the contract's fixed f32/u32 seams (build&merge is
    entry-driven and inherits the packed word path unchanged).

    ``kernel=True`` routes the gathered-row fold through the packed Pallas
    kernel (``kernels/packed_reach.py``; interpret mode on the CPU).  ``depth`` is the
    feasible-prefix depth: characters of the chunk consulted when pruning
    (``ParserConfig.feasible_depth``); deeper prunes harder at the cost of
    d sequential mat-vecs before the fold.
    """

    name = "sparse"
    min_lane_pad = 32

    def __init__(
        self,
        kernel: bool = False,
        interpret: Union[bool, None] = None,
        depth: int = 1,
        min_width: int = 8,
    ):
        super().__init__(kernel=kernel, interpret=interpret)
        if depth < 1:
            raise ValueError(f"feasible-prefix depth must be ≥ 1, got {depth}")
        self.depth = int(depth)
        self.min_width = int(min_width)
        self._width: Union[int, None] = None      # S: static product rows
        self._ell_pad: Union[int, None] = None
        self.class_widths: Union[np.ndarray, None] = None

    # -------------------------------------------------- static width bucket

    def bind_tables(self, tables) -> None:
        N = np.asarray(tables.N) > 0
        lp = int(N.shape[-1])
        # per real class (PAD excluded): nnz columns of N[a] = states with an
        # outgoing transition on a = the depth-1 feasible width upper bound
        widths = N[:-1].any(axis=1).sum(axis=1).astype(np.int64)
        w_static = int(widths.max()) if widths.size else 1
        self.class_widths = widths
        self.bind_shape(lp, w_static)

    def bind_shape(self, ell_pad: int, raw_width: int) -> None:
        """Bind static product shapes from an ℓp and a raw feasible-width bound.

        The fleet path calls this directly: one SparseBackend instance serves
        every tenant of an (Ab, ℓp) automaton bucket, bound at the bucket's
        worst-case width (max over member tenants) — a width ≥ any member's
        own bound keeps every gather correct, the extra slots just carry
        ``SPARSE_EMPTY``.  Applies the same pow2 bucketing + dense-fallback
        rule as ``bind_tables``.
        """
        lp = int(ell_pad)
        self.check_ell_pad(lp)
        S = _next_pow2(max(self.min_width, int(raw_width), 1))
        # dense-fallback rule: no reduction to be had → carry every row
        self._width = lp if S >= lp else S
        self._ell_pad = lp

    def _require_bound(self, lp: int) -> int:
        if self._width is None:
            raise RuntimeError(
                "sparse backend is unbound — ParserEngine.__init__ calls "
                "bind_tables(tables) before tracing; standalone use must too"
            )
        if lp != self._ell_pad:
            raise ValueError(
                f"sparse backend bound to ℓp={self._ell_pad}, got ℓp={lp}; "
                "one SparseBackend instance serves one automaton"
            )
        return self._width

    # ------------------------------------------------------------ phase ops

    def chain_products(self, N, Np, chains):
        lp = N.shape[-1]
        S = self._require_bound(lp)
        pad_cls = N.shape[0] - 1
        depth = min(self.depth, chains.shape[-1])
        ident = sparse_identity(S, lp // 32)
        if self.kernel:
            from ..kernels.packed_reach import packed_fold_rows

            interp = _resolve_interpret(self.interpret)

        def feasible_idx(chunk):
            u = jnp.ones((lp,), N.dtype)
            for j in range(depth - 1, -1, -1):
                u = jnp.minimum(N[chunk[j]].T @ u, 1.0)
            return jnp.sort(
                jnp.where(
                    u > 0.5,
                    jnp.arange(lp, dtype=jnp.int32),
                    jnp.int32(SPARSE_EMPTY),
                )
            )[:S]

        def one(chunk):
            idx = feasible_idx(chunk)
            R0 = sparse_init_rows(idx, lp)           # (S, W) packed e_idx rows
            if self.kernel:
                R = packed_fold_rows(Np, chunk, R0, interpret=interp)
            else:
                def step(R, cls):
                    return (
                        jax.vmap(lambda vp: packed_matvec_words(Np[cls], vp))(R),
                        None,
                    )

                R, _ = jax.lax.scan(step, R0, chunk)
            body = jnp.concatenate([idx.astype(jnp.uint32)[:, None], R], axis=1)
            # a chain that starts with PAD is all PAD (PAD only pads the
            # tail) ⇒ its product is exactly the identity → flagged encoding
            return jnp.where(chunk[0] == pad_cls, ident, body)

        if self.kernel:
            # sequential over chunks: the kernel owns the intra-chunk grid
            return jax.lax.map(one, chains)
        return jax.vmap(one)(chains)

    def compose(self, later, earlier):
        return sparse_compose(later, earlier)

    def act(self, P, v):
        return sparse_matvec(P, v)

    def act_T(self, P, v):
        return sparse_matvec_T(P, v)                 # transpose free on rows

    def identity_product(self, ell_pad, dtype=jnp.float32):
        S = self._require_bound(ell_pad)
        return sparse_identity(S, ell_pad // 32)


_BACKENDS: Dict[str, Type[ParserBackend]] = {}


def register_backend(cls: Type[ParserBackend]) -> Type[ParserBackend]:
    _BACKENDS[cls.name] = cls
    return cls


register_backend(JnpBackend)
register_backend(PallasBackend)
register_backend(PackedBackend)
register_backend(SparseBackend)


def list_backends() -> list:
    """Sorted names of every registered parse backend."""
    return sorted(_BACKENDS)


def get_backend(backend: Union[str, ParserBackend]) -> ParserBackend:
    """Resolve a backend name (or pass an instance through)."""
    if isinstance(backend, ParserBackend):
        return backend
    try:
        return _BACKENDS[backend]()
    except KeyError:
        raise ValueError(
            f"unknown parse backend {backend!r}; known: {sorted(_BACKENDS)}"
        ) from None
