"""Mesh-native distributed parse runtime: batch × chunk sharding on one engine.

``DistributedEngine`` re-expresses the multi-device parser on top of the
engine's phase contract (``ParserBackend`` phase bodies + the shared
``core/scan.py`` join) instead of carrying a separate sharded code path.
Every route below is the SAME three-phase program the single-device engine
runs — only the placement differs — so outputs are bit-identical to
``ParserEngine.parse``/``parse_batch`` (the {0,1} semiring makes every value
exactly 0 or 1; there is no reduction-order slack to hide behind).

The product-stack all-gather contract
-------------------------------------

All cross-device structure flows through ONE array: the stacked chunk
products ``P`` — axis 0 indexes chunks; the per-chunk payload is the
backend's opaque product representation ((ℓp, ℓp) f32 for jnp/pallas,
(ℓp, W = ℓp/32) uint32 words for packed, which cuts the collective's bytes
32×, and (S, 1+W) gathered feasible-start rows for sparse, which further
shrinks it to the automaton's speculation width S ≤ ℓp — the payload
reduction composes with the placement for free because the collective only
ever sees "axis 0 = chunks").  The contract, shared by all three routes and
by the streaming prefix cache:

  1. reach runs shard-local — each device folds only its own chunk rows into
     products (no communication);
  2. the product stack is all-gathered over the chunk mesh axes, in
     ``linear_index`` order, giving every device the full (c, …) stack —
     O(c · product-bytes) of collective traffic, independent of the text
     length;
  3. the join (``core/scan.py`` ``exclusive_entries``, the same scan the
     Mamba-2 SSD state passing uses) runs replicated on the gathered stack,
     yielding forward/backward entries for every chunk plus the packed text-
     start column C₀ (recovered from ``P[0]ᵀ`` — no backward reach pass);
  4. each device slices its own chunks' entries and runs build&merge
     shard-local, emitting packed SLPF columns under the input sharding.

Because step 2's payload is just "the stacked chunk products", anything that
already holds such a stack plugs in directly: ``core/stream.py``'s product
segment tree flattens to exactly this payload — the in-order leaf frontier
of the tree IS the sealed-product stack, before and after any ``edit``
splice (internal nodes are memoized re-associations the collective never
sees) — so sharded streaming, including post-edit queries, is
``join_products`` over a stack sharded on the chunk axes — no streaming-
specific collective code.

Routes
------

  parse          one text; the chunk dim takes EVERY mesh axis the logical
                 'chunk' rule names (``MeshRules``: 'chunk' → ('pod','data'))
                 — maximum chunk parallelism for one long text, in one
                 program (``chunk_program``).
  parse_phase_split
                 the same text route as three sharded programs with host
                 seams — ``reach_program`` (step 1), ``join_program``
                 (steps 2-3), ``build_merge_program`` (step 4) — built from
                 the same body pieces as ``chunk_program``, so bit-identical
                 to it; each phase's span blocks on its result.  The direct
                 route a traced ``Parser.parse`` takes on a mesh.
  parse_batch    many texts; the slot/bucket batch dim shards over 'data'
                 (pure DP, no collective) and the chunk dim keeps 'pod' —
                 the composition falls out of ``MeshRules``' duplicate-axis
                 dropping once batch is restricted to 'data'.  The all-gather
                 of step 2 then runs over 'pod' only, per batch shard.
  join_products  the streaming route: a (c, ℓp, ℓp) product stack sharded
                 over the chunk axes → replicated (Jf, Jb, packed C₀), through
                 the phase-split route's ``join_program``.

Every route finishes in the engine's ``fetch_assemble`` (spans
``parse.fetch`` / ``parse.assemble``); ``parse`` and ``parse_batch`` open
``parse.host_prep`` (padding and the sharded put) and ``parse.device``
(the blocked program, attr ``chips``) as the single-device route does.

``ParserEngine(mesh=...)`` builds this layer lazily and routes its
``parse``/``parse_batch`` through it, so ``ParseService``, ``StreamService``
and ``StreamingParser`` become mesh-aware by construction, without their own
distribution code.  Texts keep the engine's shape bucketing; chunk and batch
counts additionally round up to multiples of the mesh axis sizes (identity
PAD rows/chunks are semantics-free, so divisibility padding is free).
"""

from __future__ import annotations

from functools import cached_property
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..launch.mesh import mesh_axes_size
from ..parallel.sharding import MeshRules, spec_axes
from .engine import _next_pow2, join_with_col0, _resolve_engine
from .scan import linear_index
from .slpf import SLPF

_REP = PartitionSpec()


def _entry(axes: Tuple[str, ...]):
    """PartitionSpec entry for one dim from a flat mesh-axis tuple."""
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else axes


def _gather(x: jnp.ndarray, axes: Tuple[str, ...], axis: int) -> jnp.ndarray:
    """all_gather over possibly-several mesh axes, concatenated along ``axis``
    in ``linear_index`` order; identity when ``axes`` is empty."""
    if not axes:
        return x
    return jax.lax.all_gather(x, tuple(axes), axis=axis, tiled=True)


def _own_entries(Jf, Jb, axes: Tuple[str, ...], f: int, axis: int):
    """This device's ``f`` chunks' join entries, sliced along ``axis`` of the
    replicated (…, c, ℓp) entries at its ``linear_index`` over ``axes``."""
    start = linear_index(axes) * f
    return (
        jax.lax.dynamic_slice_in_dim(Jf, start, f, axis),
        jax.lax.dynamic_slice_in_dim(Jb, start, f, axis),
    )


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


class DistributedEngine:
    """Mesh-aware front-end over one ``ParserEngine``'s backend and buckets.

    Usually reached as ``ParserEngine(mesh=...).dist``; also constructible
    standalone from matrices / a segment table / a prebuilt engine.  Sharding
    specs resolve through ``parallel/sharding.py``'s ``MeshRules`` — the
    logical 'chunk' axis and 'data' batch axis, filtered to whatever axes the
    given mesh actually has (a 1-axis host mesh degrades gracefully: absent
    axes replicate).
    """

    def __init__(self, matrices_or_engine, mesh, *, backend=None, rules=None):
        self.engine = _resolve_engine(matrices_or_engine, backend)
        self.mesh = mesh
        self.rules = rules if rules is not None else MeshRules()
        # single-text route: the chunk dim takes every mesh axis the 'chunk'
        # rule names — all of ('pod','data') that exist on this mesh
        self.chunk_axes = self.rules.resolve_axes("chunk", mesh)
        # batched route: batch is pure DP over 'data'; MeshRules' duplicate-
        # axis dropping then leaves 'pod' (when present) for the chunk dim
        bspec = self.rules.with_overrides(batch="data").resolve(
            ("batch", "chunk"), mesh
        )
        self.batch_axes = spec_axes(bspec, 0)
        self.batch_chunk_axes = spec_axes(bspec, 1)

    # ------------------------------------------------------------- geometry

    @property
    def chunk_devices(self) -> int:
        """Devices the single-text route splits the chunk dim across."""
        return mesh_axes_size(self.mesh, self.chunk_axes)

    @property
    def batch_devices(self) -> int:
        """Devices the batched route splits the batch dim across."""
        return mesh_axes_size(self.mesh, self.batch_axes)

    @property
    def batch_chunk_devices(self) -> int:
        """Devices the batched route splits the chunk dim across."""
        return mesh_axes_size(self.mesh, self.batch_chunk_axes)

    def _bump(self):
        # Python side effect at trace time, like the engine's counted_core
        # (routes through the engine so the metrics registry sees it too)
        self.engine._bump_compiles()

    def _product_nbytes(self) -> int:
        """Bytes of ONE chunk product in the backend's representation —
        the unit of the all-gather payload accounting (packed words and
        sparse rows shrink it automatically)."""
        t = self.engine.tables
        eye = self.engine.backend.identity_product(t.ell_pad, dtype=t.N.dtype)
        return int(eye.size) * eye.dtype.itemsize

    def _count_allgather(self, n_products: int, gather_axes) -> int:
        """Record the product-stack collective payload for one dispatch and
        return it in bytes.

        The contract's step 2 moves the full (c, …) stack to every device;
        the counted payload is the gathered stack's bytes (text-length
        independent).  A degenerate mesh (no gather axes) moves nothing.
        """
        if not gather_axes:
            return 0
        nbytes = n_products * self._product_nbytes()
        self.engine.obs.metrics.counter("allgather_payload_bytes_total").inc(nbytes)
        return nbytes

    def _program(self, body, in_specs, out_specs):
        """``body`` as one jitted ``shard_map`` over this mesh, every operand
        and result placed as its spec says; each trace counts as a compile."""

        def counted(*args):
            self._bump()
            return body(*args)

        program = jax.shard_map(
            counted,
            mesh=self.mesh,
            check_vma=False,
            in_specs=in_specs,
            out_specs=out_specs,
        )
        return jax.jit(
            program,
            in_shardings=self._named(in_specs),
            out_shardings=self._named(out_specs),
        )

    def _named(self, specs):
        """NamedSharding(s) on this mesh for one spec or a tuple of specs."""
        if isinstance(specs, PartitionSpec):
            return NamedSharding(self.mesh, specs)
        return tuple(NamedSharding(self.mesh, s) for s in specs)

    # ------------------------------------------------- single-text programs
    #
    # The single-text route's shard-local pieces: the fused chunk program
    # composes them, the phase-split route runs each as its own program.

    @property
    def _chunk_spec(self) -> PartitionSpec:
        return PartitionSpec(_entry(self.chunk_axes))

    def _join_local(self, P, I, F):
        """Step 2 + 3: all-gather this device's (f, …) products over the
        chunk axes, then the replicated join."""
        return join_with_col0(self.engine.backend, _gather(P, self.chunk_axes, 0), I, F)

    def _build_merge_local(self, N, chunks, Jf, Jb):
        """Step 4: build&merge of this device's (f, k) chunks from its slice
        of the replicated entries."""
        Jf, Jb = _own_entries(Jf, Jb, self.chunk_axes, chunks.shape[0], axis=0)
        return self.engine.backend.build_merge_packed(N, chunks, Jf, Jb)

    @cached_property
    def chunk_program(self):
        """Jitted single-text route: chunks (c, k) sharded over chunk_axes,
        reach → all-gather + join → build&merge in one program."""
        backend, spec = self.engine.backend, self._chunk_spec

        def body(N, I, F, chunks):  # chunks: (f, k) shard-local rows
            P = backend.reach(N, chunks)                  # (f, …) local
            Jf, Jb, col0p = self._join_local(P, I, F)     # replicated
            return col0p, self._build_merge_local(N, chunks, Jf, Jb)

        return self._program(body, (_REP, _REP, _REP, spec), (_REP, spec))

    @cached_property
    def reach_program(self):
        """Phase-split route, reach: (N, chunks) → products, both sharded
        over the chunk axes (no communication)."""
        spec = self._chunk_spec
        return self._program(self.engine.backend.reach, (_REP, spec), spec)

    @cached_property
    def join_program(self):
        """Sharded (c, …) product stack → replicated (Jf, Jb, packed C₀):
        the phase-split route's join and the streaming route."""
        spec = self._chunk_spec
        return self._program(self._join_local, (spec, _REP, _REP), (_REP,) * 3)

    @cached_property
    def build_merge_program(self):
        """Phase-split route, build&merge: sharded chunks and replicated
        entries → packed columns sharded over the chunk axes."""
        spec = self._chunk_spec
        return self._program(
            self._build_merge_local, (_REP, spec, _REP, _REP), spec
        )

    # ---------------------------------------------------- batched program

    @cached_property
    def batched_program(self):
        """Jitted batched route: (B, c, k) with batch over 'data', chunks
        over 'pod'."""
        backend = self.engine.backend
        b_axes, c_axes = self.batch_axes, self.batch_chunk_axes
        spec_in = PartitionSpec(_entry(b_axes), _entry(c_axes))
        spec_b = PartitionSpec(_entry(b_axes))

        def body(N, I, F, batch):  # batch: (B_loc, c_loc, k) shard-local
            reach_b = backend.lift_batch(lambda ch: backend.reach(N, ch))
            P_local = reach_b(batch)                      # (B_loc, c_loc, ℓp, ℓp)
            P_all = _gather(P_local, c_axes, axis=1)      # (B_loc, c, ℓp, ℓp)
            join_b = backend.lift_batch(
                lambda Pa: join_with_col0(backend, Pa, I, F)
            )
            Jf, Jb, col0p = join_b(P_all)                 # (B_loc, c, ℓp) ×2
            Jf_loc, Jb_loc = _own_entries(Jf, Jb, c_axes, P_local.shape[1], axis=1)
            bm_b = backend.lift_batch(
                lambda ch, ef, eb: backend.build_merge_packed(N, ch, ef, eb)
            )
            M = bm_b(batch, Jf_loc, Jb_loc)               # (B_loc, c_loc, k, W)
            return col0p, M

        return self._program(body, (_REP, _REP, _REP, spec_in), (spec_b, spec_in))

    # ------------------------------------------------- streaming join route

    def join_products(self, P: jnp.ndarray):
        """Sharded-stack join — the streaming contract.

        ``P`` (c, ℓp, ℓp) is a stacked chunk-product prefix (e.g. the
        streaming cache's sealed products + tail); it lives sharded over the
        chunk axes and is all-gathered once before the replicated scan.
        Returns (Jf, Jb, packed C₀), all replicated.  The stack pads with
        identity products to a multiple of the chunk device count —
        identities are no-ops for both scan directions, so entries at real
        indices are unchanged (a power-of-two input stack stays power-of-two,
        keeping the compiled-shape set bounded).
        """
        t = self.engine.tables
        c = int(P.shape[0])
        c_pad = _round_up(max(c, 1), self.chunk_devices)
        if c_pad != c:
            eye = self.engine.backend.identity_product(t.ell_pad, dtype=t.N.dtype)
            P = jnp.concatenate(
                [P, jnp.broadcast_to(eye, (c_pad - c,) + eye.shape)], axis=0
            )
        self._count_allgather(c_pad, self.chunk_axes)
        return self.join_program(P, t.I, t.F)

    # ---------------------------------------------------------------- parse

    def _put_text(self, text, n_chunks: Optional[int]):
        """Bucket one text for the single-text route and place its padded
        chunks over the chunk axes (span ``parse.host_prep``).

        ``n_chunks`` rounds up to a multiple of the chunk device count
        (default: one bucket-padded chunk row per device, at least 8 rows
        total).  Returns (classes, (c, k), sharded chunks)."""
        eng = self.engine
        csz = self.chunk_devices
        c_req = n_chunks if n_chunks is not None else max(8, csz)
        c_req = _round_up(max(1, c_req), csz)
        classes = eng.classes_of_text(text)
        c, k = eng.bucket_shape(len(classes), c_req)
        cells, text_cells = c * k, len(classes)
        with eng.obs.span(
            "parse.host_prep", bucket=[c, k], cells=cells, text_cells=text_cells
        ):
            placed = self._named(self._chunk_spec)
            chunks = jax.block_until_ready(
                jax.device_put(eng._pad_to(classes, c, k), placed)
            )
        eng._count_cells(c, k, cells, text_cells)
        return classes, (c, k), chunks

    def parse(self, text, n_chunks: Optional[int] = None) -> SLPF:
        """One text, the chunk dim sharded over EVERY chunk axis.

        The long-text route: one device program, reach/build&merge shard-
        local, one product-stack all-gather (spans ``parse.host_prep``,
        ``parse.device``, then the engine's ``parse.fetch`` /
        ``parse.assemble``).
        """
        eng, t = self.engine, self.engine.tables
        classes, (c, k), chunks = self._put_text(text, n_chunks)
        self._count_allgather(c, self.chunk_axes)
        with eng.obs.span(
            "parse.device", bucket=[c, k], batch=1, chips=self.chunk_devices,
            sub_chains=eng.backend.sub_chains(k),
        ):
            col0, cols = jax.block_until_ready(
                self.chunk_program(t.N, t.I, t.F, chunks)
            )
        return eng.fetch_assemble((col0,), (cols,), [classes])[0]

    def parse_phase_split(self, text, n_chunks: Optional[int] = None) -> SLPF:
        """``parse`` as three sharded programs with host seams, each phase
        span blocked on its result: ``phase.reach`` (shard-local products),
        ``phase.join`` (the all-gather and the replicated join; attributes
        ``gather_bytes`` and ``chips``), ``phase.build_merge`` (shard-local)
        and ``phase.host_build`` (fetch and assembly).  The same body pieces
        as ``chunk_program``, so bit-identical to ``parse``."""
        eng, t = self.engine, self.engine.tables
        obs = eng.obs
        classes, (c, k), chunks = self._put_text(text, n_chunks)
        m = eng.backend.sub_chains(k)
        with obs.span(
            "phase.reach", bucket=[c, k], n_chars=len(classes), sub_chains=m
        ):
            P = jax.block_until_ready(self.reach_program(t.N, chunks))
        gather_bytes = self._count_allgather(c, self.chunk_axes)
        with obs.span(
            "phase.join", n_products=c, gather_bytes=gather_bytes,
            chips=self.chunk_devices,
        ):
            Jf, Jb, col0p = jax.block_until_ready(self.join_program(P, t.I, t.F))
        with obs.span("phase.build_merge", bucket=[c, k], sub_chains=m):
            cols = jax.block_until_ready(
                self.build_merge_program(t.N, chunks, Jf, Jb)
            )
        with obs.span("phase.host_build", n_chars=len(classes)):
            return eng.fetch_assemble((col0p,), (cols,), [classes])[0]

    def parse_batch(self, texts: Sequence, n_chunks: int = 8) -> List[SLPF]:
        """Many texts: batch slots over 'data' × chunks over 'pod'.

        Identical grouping/bucketing to ``ParserEngine.parse_batch``; batch
        slots additionally round up to a multiple of the batch device count
        and chunk counts to the chunk device count (all-PAD rows/chunks are
        identity, discarded on assembly).  Spans and cell counters as on
        the single-device route.
        """
        eng = self.engine
        obs = eng.obs
        csz = self.batch_chunk_devices
        dsz = self.batch_devices
        c_req = _round_up(max(1, n_chunks), csz)
        classes_list = [eng.classes_of_text(t) for t in texts]
        groups = {}
        for i, cls in enumerate(classes_list):
            groups.setdefault(eng.bucket_shape(len(cls), c_req), []).append(i)

        t = eng.tables
        spec_in = PartitionSpec(_entry(self.batch_axes), _entry(self.batch_chunk_axes))
        results: List[Optional[SLPF]] = [None] * len(texts)
        for (c, k), idxs in sorted(groups.items()):
            B = _round_up(_next_pow2(len(idxs)), dsz)
            group = [classes_list[i] for i in idxs]
            cells, text_cells = B * c * k, sum(len(cls) for cls in group)
            with obs.span(
                "parse.host_prep", bucket=[c, k], cells=cells, text_cells=text_cells
            ):
                batch = np.full((B, c, k), t.pad_class, dtype=np.int32)
                for row, cls in enumerate(group):
                    batch[row] = eng._pad_to(cls, c, k)
                batch = jax.block_until_ready(
                    jax.device_put(batch, self._named(spec_in))
                )
            eng._count_cells(c, k, cells, text_cells)
            self._count_allgather(B * c, self.batch_chunk_axes)
            with obs.span(
                "parse.device", bucket=[c, k], batch=B, chips=dsz * csz,
                sub_chains=eng.backend.sub_chains(k),
            ):
                col0s, colss = jax.block_until_ready(
                    self.batched_program(t.N, t.I, t.F, batch)
                )
            for i, slpf in zip(idxs, eng.fetch_assemble(col0s, colss, group)):
                results[i] = slpf
        return results  # type: ignore[return-value]
