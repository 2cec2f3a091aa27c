"""Layered parse runtime: backend-pluggable three-phase engine, batched front-end.

The runtime is organised in three layers (bottom-up):

  phase backends   ``core/backend.py`` — swappable implementations of the
                   paper's reach / join / build&merge phases over the padded
                   table layout.  ``jnp`` is the pure-``jax.numpy`` reference
                   device program; ``pallas`` wires in the Mosaic kernels of
                   ``repro/kernels`` (scalar-prefetch DMA pipelining on TPU,
                   interpret mode on CPU so CI exercises the real BlockSpecs).
                   The join phase is shared by every backend: it is
                   ``core/scan.py``'s ``exclusive_entries`` over the Boolean
                   OR-AND matrix monoid — the same scan primitive the Mamba-2
                   SSD chunked state passing uses.

  engine           ``ParserEngine(backend=...)`` compiles ONE program per
                   static chunk shape (c, k) and runs texts through it.
                   Texts pad to equal static chunks with the PAD class, whose
                   matrix is the identity — a semantic no-op replacing the
                   paper's load-balancing fragments (Sect. 4.3) with
                   SPMD-exact balance.  Chunk lengths are *bucketed* to a
                   small set of power-of-two shapes so arbitrary text lengths
                   hit a handful of compiled programs instead of re-jitting
                   per length (``compile_count`` exposes the trace count).
                   Zero-length texts flow through the same bucketed path.

  batched front-end ``parse_batch(texts)`` groups mixed-length requests by
                   shape bucket, pads each group to power-of-two batch slots,
                   and executes one batched device program per bucket —
                   request-level serving on top lives in
                   ``serve/parse_service.py`` (slot pattern of the LM
                   scheduler).

  phase programs   ``ParserEngine.phases`` — the same three phases as
                   separately-jitted programs whose boundaries (the stacked
                   chunk products P_i — backend-owned representation — and
                   the join entries) are first-class, cacheable arrays
                   instead of fused intermediates.  This is the seam the
                   streaming layer caches across calls.

  stream layer     ``core/stream.py``'s ``StreamingParser`` — a persistent
                   prefix cache of sealed chunk products + a mutable tail;
                   ``append`` re-runs only the appended piece's reach and the
                   O(log c) join over cached summaries.  Session-level
                   serving lives in ``serve/stream_service.py`` (bucket-
                   batched tail execution across sessions, bytes-budget
                   eviction).

  distribution     ``core/distributed.py``'s ``DistributedEngine`` — the
                   same phase bodies placed over a device mesh: reach and
                   build&merge shard-local, ONE all-gather of the stacked
                   chunk products, replicated join.  ``ParserEngine(mesh=...)``
                   builds it lazily and routes ``parse`` (chunks over every
                   'chunk' axis) and ``parse_batch`` (batch over 'data' ×
                   chunks over 'pod') through it; specs resolve via
                   ``parallel/sharding.py``'s ``MeshRules``.

Mapping from the paper's phases (all validated against ``core/reference.py``,
the paper-faithful oracle):

  reach   Per chunk, the Boolean-semiring matrix chain product
          ``P_i = N_{y_k} ⊗ … ⊗ N_{y_1}`` (ℓ×ℓ).  Column j of ``P_i`` equals
          ``R_{i,j}`` (Eq. 6): all ℓ speculative ME-DFA entries are evaluated
          *simultaneously* as matrix columns on the MXU.

  join    Eq. (7) becomes an exclusive monoid scan over the chunk products.
          Cross-device: one all_gather of the (c, ℓ, ℓ) summaries + a
          replicated log-depth local scan — O(c·ℓ²) bytes of collective
          traffic, independent of the text length.

  build & Fig. 14's fused builder&merger.  Beyond the paper: the backward
  merge   *reach* phase is free — reverse chunk summaries are the transposes
          ``P_iᵀ`` (Eq. 5 + product reversal), so only one reach pass is ever
          computed (paper runs both).

Numeric form: {0,1} float32 matrices; ``⊗`` = matmul + min(·,1) (exact in f32
up to 2²⁴ ≫ ℓ).  SLPF columns are emitted bit-packed (uint32, 32 segments per
word, App. C encoding).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import ObsHandle
from ..obs.metrics import Counter
from .backend import (
    ParserBackend,
    get_backend,
    join_entries,
    pack_columns_u32,
)
from .matrices import ParserMatrices, build_matrices, unpack_bits
from .segments import SegmentTable
from .slpf import SLPF

# Back-compat alias: the join phase now lives in core/backend.py on top of
# core/scan.py's exclusive_entries (one scan implementation repo-wide).
_entries_from_products = join_entries


# ---------------------------------------------------------------- tables


@dataclass
class EngineTables:
    """Device-resident parser tables for one RE."""

    N: jnp.ndarray            # (A+1, ℓp, ℓp) f32 — PAD class (index A) = identity
    I: jnp.ndarray            # (ℓp,) f32
    F: jnp.ndarray            # (ℓp,) f32
    byte_to_class: jnp.ndarray  # (256,) int32
    ell: int                  # true segment count
    ell_pad: int              # padded to a multiple of ``lane_pad``
    pad_class: int

    @classmethod
    def from_matrices(cls, m: ParserMatrices, lane_pad: int = 32) -> "EngineTables":
        ell = m.n_segments
        lp = max(lane_pad, ((ell + lane_pad - 1) // lane_pad) * lane_pad)
        A1 = m.N.shape[0]
        N = np.zeros((A1, lp, lp), dtype=np.float32)
        N[:, :ell, :ell] = m.N.astype(np.float32)
        N[-1] = np.eye(lp, dtype=np.float32)  # PAD = identity over the padded space
        I = np.zeros(lp, dtype=np.float32)
        I[:ell] = m.I
        F = np.zeros(lp, dtype=np.float32)
        F[:ell] = m.F
        return cls(
            N=jnp.asarray(N),
            I=jnp.asarray(I),
            F=jnp.asarray(F),
            byte_to_class=jnp.asarray(m.byte_to_class),
            ell=ell,
            ell_pad=lp,
            pad_class=m.pad_class,
        )


# ------------------------------------------------------------- parse core


def join_with_col0(backend: ParserBackend, P, I, F):
    """Join phase over stacked products, plus the packed text-start column.

    C_0 = I ∧ β_0 with β_0 = P_0ᵀ Ĵ_0 — the backward state at text start,
    recovered from the reach products (no extra backward pass).  ``P`` is the
    backend's opaque product stack; the product arithmetic lives behind
    ``backend.start_column`` so representations (f32 matrices, packed words)
    never leak here.
    """
    Jf, Jb = backend.join(P, I, F)                       # (c, ℓp) f32 each
    col0 = backend.start_column(P, I, Jb[0])
    return Jf, Jb, pack_columns_u32(col0)


def make_parse_core(backend: ParserBackend):
    """Single-text three-phase program over one (c, k) chunk grid.

    Returns ``core(N, I, F, chunks) -> (packed col0 (W,), packed cols (c,k,W))``.
    This is the *fused* composition of the phase bodies; ``PhasePrograms``
    exposes the identical phases as separate programs with cacheable
    boundaries.
    """

    def parse_core(N, I, F, chunks):
        P = backend.reach(N, chunks)                     # (c, …) products
        Jf, Jb, col0p = join_with_col0(backend, P, I, F)
        return col0p, backend.build_merge_packed(N, chunks, Jf, Jb)  # (c, k, W)

    return parse_core


class PhasePrograms:
    """The three phases as separately-jitted, shape-bucketed device programs.

    Where ``make_parse_core`` fuses reach → join → build&merge into one
    program (best for cold batch parsing), these programs expose every phase
    boundary as a first-class array contract:

      reach        (N, (c, k) chunks)        → (c, …) chunk products P_i
      compose      (later P, earlier P)      → later ⊗ earlier (one product)
      join         (P (c, …), I, F)          → (Jf, Jb, packed C_0)
      build_merge  (N, chunks, Jf, Jb)       → (c, k, W) packed clean columns

    The products crossing these seams are *backend-owned opaque* device
    arrays (f32 (ℓp, ℓp) matrices for jnp/pallas, uint32 (ℓp, W) words for
    packed — see ``core/backend.py``'s contract); callers may cache, slice
    along axis 0, restack, and feed them back in, never arithmetic on them.
    Entries and packed columns are fixed f32/u32 layouts.  This is the
    contract the streaming prefix cache (``core/stream.py``) is built on,
    and the same seam sharded-batched execution plugs into.  Each program
    re-traces once per input shape, so callers that bucket their shapes
    (power-of-two chunk lengths / product counts) keep the compiled set
    bounded exactly like the fused path.
    """

    def __init__(self, backend: ParserBackend, on_trace: Optional[Callable] = None):
        notify = on_trace or (lambda: None)

        def _reach(N, chunks):
            notify()
            return backend.reach(N, chunks)

        def _compose(later, earlier):
            notify()
            return backend.compose(later, earlier)

        def _join(P, I, F):
            notify()
            return join_with_col0(backend, P, I, F)

        def _build_merge(N, chunks, Jf, Jb):
            notify()
            return backend.build_merge_packed(N, chunks, Jf, Jb)

        self.backend = backend
        self.reach = jax.jit(_reach)
        self.compose = jax.jit(_compose)
        self.join = jax.jit(_join)
        self.build_merge = jax.jit(_build_merge)


def _next_pow2(x: int) -> int:
    return 1 << max(0, x - 1).bit_length()


# ---------------------------------------------------------------- engine


class ParserEngine:
    """Single-host engine: backend-pluggable, shape-bucketed, batch-capable."""

    def __init__(
        self,
        matrices_or_table,
        *,
        lane_pad: int = 32,
        backend: Union[str, ParserBackend] = "jnp",
        min_chunk_len: int = 8,
        mesh=None,
        mesh_rules=None,
        obs: Optional[ObsHandle] = None,
    ):
        if isinstance(matrices_or_table, SegmentTable):
            matrices = build_matrices(matrices_or_table)
        else:
            matrices = matrices_or_table
        self.matrices = matrices
        self.table = matrices.table
        self.backend = get_backend(backend)
        lane_pad = max(lane_pad, self.backend.min_lane_pad)
        self.tables = EngineTables.from_matrices(matrices, lane_pad=lane_pad)
        # table-dependent backends (sparse width bucket) fix their static
        # product shapes here, before any phase program is traced
        self.backend.bind_tables(self.tables)
        self.min_chunk_len = max(1, min_chunk_len)
        self.mesh = mesh
        self.mesh_rules = mesh_rules
        # the observability seam every layer over this engine records into
        # (core/stream.py, core/distributed.py, both services, the facade);
        # a default handle is a disabled tracer + live metrics registry
        self.obs = obs if obs is not None else ObsHandle()

        self._compile_count = 0
        self._phases: Optional[PhasePrograms] = None
        self._dist = None
        self._seen_batch_shapes: set = set()
        # hot-path metric handles, bound once (per-bucket ones on first use)
        m = self.obs.metrics
        self._m_bucket_hits = m.counter("bucket_cache_hits_total")
        self._m_bucket_misses = m.counter("bucket_cache_misses_total")
        self._m_d2h_bytes = m.counter("d2h_bytes_total")
        self._m_cells: Dict[Tuple[int, int], Tuple[Counter, Counter]] = {}

        def counted_core(N, I, F, chunks, _core=make_parse_core(self.backend)):
            # Python side effect at trace time: counts compiled programs.
            self._bump_compiles()
            return _core(N, I, F, chunks)

        self._jit_batched = jax.jit(self.backend.batch_core(counted_core))

    def _bump_compiles(self) -> None:
        """One device program traced — a re-jit event (trace-time side
        effect, mirrored into the metrics registry)."""
        self._compile_count += 1
        self.obs.metrics.counter("compiled_programs_total").inc()

    # ------------------------------------------------------------- helpers

    @property
    def compile_count(self) -> int:
        """Number of distinct programs traced so far (one per shape bucket)."""
        return self._compile_count

    @property
    def phases(self) -> PhasePrograms:
        """Separately-jitted phase programs over this engine's backend.

        Built lazily (the fused batch path never pays for them); traces are
        counted into ``compile_count`` like every other engine program.
        """
        if self._phases is None:
            self._phases = PhasePrograms(self.backend, on_trace=self._bump_compiles)
        return self._phases

    @property
    def dist(self):
        """The mesh distribution layer (``core/distributed.py``) when this
        engine was built with ``mesh=``; None on a single-device engine.
        Built lazily — a mesh-less engine never imports it."""
        if self.mesh is None:
            return None
        if self._dist is None:
            from .distributed import DistributedEngine

            self._dist = DistributedEngine(self, self.mesh, rules=self.mesh_rules)
        return self._dist

    def classes_of_text(self, text) -> np.ndarray:
        if isinstance(text, (bytes, str)):
            return self.matrices.classes_of_text(text)
        return np.asarray(text, dtype=np.int32)

    def bucket_shape(self, n: int, n_chunks: int) -> Tuple[int, int]:
        """Static (c, k) chunk-grid bucket for a text of length ``n``.

        c is fixed by ``n_chunks``; k rounds up to the next power of two (with
        a floor of ``min_chunk_len``) so arbitrary lengths land in O(log n)
        distinct compiled shapes instead of one per length.  The trade: a text
        just past a bucket edge runs up to ~2x padded cells (identity-PAD
        steps are materialized), in exchange for never paying a re-jit —
        lengths 2^p·c+1 … 2^(p+1)·c share one program.
        """
        c = max(1, n_chunks)
        k = _next_pow2(max(self.min_chunk_len, -(-n // c)))
        return c, k

    def pad_chunks(self, classes: np.ndarray, n_chunks: int) -> np.ndarray:
        """Pad with the identity PAD class to equal static chunks (DESIGN §2)."""
        n = len(classes)
        c = max(1, n_chunks)
        k = max(1, -(-n // c))
        return self._pad_to(classes, c, k)

    def _pad_to(self, classes: np.ndarray, c: int, k: int) -> np.ndarray:
        padded = np.full(c * k, self.tables.pad_class, dtype=np.int32)
        padded[: len(classes)] = classes
        return padded.reshape(c, k)

    # --------------------------------------------------------------- parse

    def parse(self, text, n_chunks: int = 8) -> SLPF:
        """Parse one text through the bucketed batch program (batch slot 1).

        All lengths — including zero — route through the same padded/jitted
        path; PAD chunks are identity, so the bucket padding is semantics-free.
        Sharing the batched program means mixing ``parse`` and ``parse_batch``
        compiles one program per bucket, not two.

        On a mesh engine this is the long-text route: the chunk dim shards
        over EVERY chunk axis ('pod' × 'data').
        """
        if self.mesh is not None:
            return self.dist.parse(text, n_chunks=n_chunks)
        return self.parse_batch([text], n_chunks=n_chunks)[0]

    def parse_batch(self, texts: Sequence, n_chunks: int = 8) -> List[SLPF]:
        """Parse many texts, bucketed by static shape, one device program each.

        Texts are grouped by their (c, k) bucket; each group is padded to a
        power-of-two number of batch slots (extra rows are all-PAD and
        discarded), so the set of compiled programs stays small and static —
        at most one per (bucket, batch-slot) shape, reused across calls.

        On a mesh engine the groups run through the distributed batched
        route instead: batch slots shard over 'data', chunks over 'pod'.
        """
        if self.mesh is not None:
            return self.dist.parse_batch(texts, n_chunks=n_chunks)
        classes_list = [self.classes_of_text(t) for t in texts]
        groups: Dict[Tuple[int, int], List[int]] = {}
        for i, cls in enumerate(classes_list):
            groups.setdefault(self.bucket_shape(len(cls), n_chunks), []).append(i)

        obs, t = self.obs, self.tables
        results: List[Optional[SLPF]] = [None] * len(texts)
        for (c, k), idxs in sorted(groups.items()):
            B = _next_pow2(len(idxs))
            # bucket program-cache accounting: a (B, c, k) shape seen before
            # dispatches a compiled program; a new one is a re-jit event
            if (B, c, k) in self._seen_batch_shapes:
                self._m_bucket_hits.inc()
            else:
                self._seen_batch_shapes.add((B, c, k))
                self._m_bucket_misses.inc()
            group = [classes_list[i] for i in idxs]
            cells, text_cells = B * c * k, sum(len(cls) for cls in group)
            with obs.span(
                "parse.host_prep", bucket=[c, k], cells=cells, text_cells=text_cells
            ):
                batch = np.full((B, c, k), t.pad_class, dtype=np.int32)
                for row, cls in enumerate(group):
                    batch[row] = self._pad_to(cls, c, k)
                batch = jnp.asarray(batch)
            padded_total, text_total = self._cell_counters(c, k)
            padded_total.inc(cells - text_cells)
            text_total.inc(text_cells)
            with obs.span("parse.device", bucket=[c, k], batch=B):
                col0s, colss = jax.block_until_ready(
                    self._jit_batched(t.N, t.I, t.F, batch)
                )
            for i, slpf in zip(idxs, self.fetch_assemble(col0s, colss, group)):
                results[i] = slpf
        return results  # type: ignore[return-value]

    def _cell_counters(self, c: int, k: int) -> Tuple[Counter, Counter]:
        """``padded_cells_total`` / ``text_cells_total`` of one bucket."""
        handles = self._m_cells.get((c, k))
        if handles is None:
            m, label = self.obs.metrics, f"{c}x{k}"
            handles = self._m_cells[(c, k)] = (
                m.counter("padded_cells_total", bucket=label),
                m.counter("text_cells_total", bucket=label),
            )
        return handles

    def fetch_assemble(self, col0s, colss, classes_list) -> List[SLPF]:
        """Copy one program's packed columns to the host (``parse.fetch``)
        and assemble each text's SLPF (``parse.assemble``).

        Row r of ``col0s``/``colss`` (an array or a sequence of arrays)
        belongs to ``classes_list[r]``.  The single-device and mesh routes
        both finish here; callers block on the device first, so the fetch
        span times the copy alone.
        """
        obs = self.obs
        with obs.span("parse.fetch") as sp:
            col0s, colss = jax.device_get((col0s, colss))
            nbytes = sum(a.nbytes for a in jax.tree.leaves((col0s, colss)))
            sp.set_attr("bytes", nbytes)
        self._m_d2h_bytes.inc(nbytes)
        with obs.span("parse.assemble", n_texts=len(classes_list)):
            return [
                self._assemble(col0, cols, classes)
                for col0, cols, classes in zip(col0s, colss, classes_list)
            ]

    def _assemble(self, col0, cols, classes) -> SLPF:
        n = len(classes)
        W = cols.shape[-1]
        packed = np.concatenate(
            [np.asarray(col0)[None], np.asarray(cols).reshape(-1, W)[:n]], axis=0
        )
        columns = unpack_bits(packed, self.tables.ell, axis=-1)
        return SLPF(table=self.table, columns=columns, classes=classes)

    def lower_batch(self, B: int, c: int, k: int, *, sharding=None):
        """Lower the fused batch program of a (B, c, k) bucket without
        running it — for compile checks and compiled-HLO inspection.
        ``sharding`` places every operand (e.g. on a described device)."""
        t = self.tables

        def spec(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        return self._jit_batched.lower(
            spec(t.N.shape, t.N.dtype),
            spec(t.I.shape, t.I.dtype),
            spec(t.F.shape, t.F.dtype),
            spec((B, c, k), jnp.int32),
        )

    # -------------------------------------------------------- observability

    def parse_phase_split(self, text, n_chunks: int = 8) -> SLPF:
        """Parse one text with per-phase spans (the observability route).

        Runs the separately-jitted phase programs — the same bodies the
        fused program composes, bit-identical (the phase-split route of
        ``tests/test_conformance.py``) — so each phase boundary is a real
        host-side seam that can be timed honestly: every span blocks on its
        device result before closing.  Queue-free: this is the direct route
        ``Parser.parse`` takes when tracing is enabled (mesh engines keep
        their fused distributed program and report one ``phase.device_parse``
        span instead — the phases live inside one ``shard_map``).
        """
        obs = self.obs
        classes = self.classes_of_text(text)
        if self.mesh is not None:
            with obs.span("phase.device_parse", n_chars=len(classes)):
                slpf = self.dist.parse(classes, n_chunks=n_chunks)
            return slpf
        c, k = self.bucket_shape(len(classes), n_chunks)
        chunks = jnp.asarray(self._pad_to(classes, c, k))
        t = self.tables
        with obs.span("phase.reach", bucket=[c, k], n_chars=len(classes)):
            P = jax.block_until_ready(self.phases.reach(t.N, chunks))
        with obs.span("phase.join", n_products=c):
            Jf, Jb, col0p = jax.block_until_ready(self.phases.join(P, t.I, t.F))
        with obs.span("phase.build_merge", bucket=[c, k]):
            cols = jax.block_until_ready(
                self.phases.build_merge(t.N, chunks, Jf, Jb)
            )
        with obs.span("phase.host_build", n_chars=len(classes)):
            slpf = self._assemble(np.asarray(col0p), np.asarray(cols), classes)
        return slpf

    def count_accepting(self, text, n_chunks: int = 8) -> int:
        return self.parse(text, n_chunks).count_trees()


def _resolve_engine(
    matrices_or_engine,
    backend: Union[str, ParserBackend, None],
    mesh=None,
    mesh_rules=None,
    min_chunk_len: Optional[int] = None,
) -> ParserEngine:
    """Shared constructor contract of everything layered on the engine
    (ParseService, StreamingParser, StreamService): accept matrices / a
    segment table and build an engine, or accept a prebuilt ParserEngine —
    in which case ``backend=``/``mesh=`` must not also be passed."""
    if isinstance(matrices_or_engine, ParserEngine):
        if backend is not None or mesh is not None:
            raise ValueError(
                "pass backend=/mesh= only when building the engine here; "
                "a prebuilt ParserEngine already owns its backend and mesh"
            )
        return matrices_or_engine
    return ParserEngine(
        matrices_or_engine,
        backend=backend if backend is not None else "jnp",
        mesh=mesh,
        mesh_rules=mesh_rules,
        min_chunk_len=min_chunk_len if min_chunk_len is not None else 8,
    )


def resolve_engine(
    matrices_or_engine,
    backend: Union[str, ParserBackend, None],
    mesh=None,
    mesh_rules=None,
) -> ParserEngine:
    """Deprecated public alias of the internal engine-resolution path.

    The supported way to build the parse runtime is the ``repro.Parser``
    facade (``repro/api.py``), which owns engine and service construction
    from one declarative ``ParserConfig``.  This shim keeps pre-facade call
    sites working one release longer.
    """
    import warnings

    warnings.warn(
        "repro: resolve_engine is deprecated — construct repro.Parser "
        "(repro/api.py) instead; it owns engine/service construction",
        DeprecationWarning,
        stacklevel=2,
    )
    return _resolve_engine(matrices_or_engine, backend, mesh, mesh_rules)
