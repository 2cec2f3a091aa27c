"""Multi-tenant fleet engine: one device program serves many automata.

Everything below ``FleetEngine`` batches over *text*: chunks within a text
(the paper's decomposition), batch slots across texts (``parse_batch``).
Production RE traffic is thousands of *distinct patterns* — and nothing in
reach/compose/join/build&merge depends on *which* automaton's tables are
bound: every phase body takes (N, I, F) as operands (``core/backend.py``'s
contract), so the tenant axis vmaps exactly like the batch-slot axis.

Three pieces make that serve-able:

  automaton bucketing   ``pad_matrices_bundle`` (core/matrices.py) pads each
                        tenant's tables to a shared pow2 bucket shape —
                        ℓp to the next power of two (floor: the backend's
                        ``min_lane_pad``) and the class axis likewise, with
                        PAD relocated to the bucket's uniform last index.
                        Tenants bucket by (backend variant, class bucket,
                        ℓp bucket); padding is semantics-free (unreachable
                        states, identity classes), so each tenant's SLPF is
                        bit-identical to its solo ``Parser``'s.

  tenant-batched phases ``_BucketRunner`` stacks member tables on a leading
                        tenant axis and jits ONE program per bucket:
                        ``backend.lift_batch(backend.batch_core(core))`` —
                        the same two seams the mesh route uses — running
                        (tenant, batch-slot, chunk) in a single dispatch.
                        Compiled-program count scales with #buckets × the
                        pow2 (T, B, c, k) shape set, NOT with #tenants.
                        Sparse buckets bind the backend at the member-max
                        feasible width (``SparseBackend.bind_shape``): a
                        width ≥ any member's own bound stays exact, so a
                        dense-fallback tenant can share a bucket with a
                        reduced one.

  table compile cache   building an automaton (segment table → matrices →
                        padded bundle) is the per-tenant compile cost; the
                        process-wide ``_TABLE_CACHE`` memoizes it keyed on
                        (normalized regex, backend variant, ℓp bucket) —
                        ``normalize_regex`` is the parsed AST's canonical
                        form, so syntactic variants of one pattern share an
                        entry.  ``table_cache_hits_total`` /
                        ``table_cache_misses_total`` make the cache
                        observable per fleet.

``repro.ParserFleet`` (repro/api.py) is the supported facade over this
engine; ``serve/parse_service.py``'s ``FleetParseService`` adds the
weighted-fair queue.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import ObsHandle
from .backend import ParserBackend, SparseBackend, get_backend
from .engine import make_parse_core
from .matrices import (
    ParserMatrices,
    build_matrices,
    feasible_width_bound,
    pad_matrices_bundle,
    unpack_bits,
)
from .slpf import SLPF


def _next_pow2(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


# ---------------------------------------------------------------- tenant spec


@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """Core-level description of one fleet tenant (the jax-free subset of
    ``repro.ParserConfig`` the engine needs; the facade converts)."""

    regex: str
    backend: str = "jnp"
    kernel: bool = False
    feasible_depth: int = 1
    n_chunks: int = 8
    min_chunk_len: int = 8
    weight: float = 1.0
    max_pending: Optional[int] = None

    def backend_key(self) -> str:
        """Bucket-key component: backends with different static behavior
        (kernel toggle, feasible depth) must not share a compiled program."""
        key = self.backend
        if self.kernel:
            key += "+kernel"
        if self.backend == "sparse" and self.feasible_depth != 1:
            key += f"+d{self.feasible_depth}"
        return key

    def make_backend(self) -> ParserBackend:
        if self.backend == "sparse":
            return SparseBackend(kernel=self.kernel, depth=self.feasible_depth)
        if self.backend == "packed" and self.kernel:
            from .backend import PackedBackend

            return PackedBackend(kernel=True)
        return get_backend(self.backend)


# ----------------------------------------------------------- compile cache


def normalize_regex(pattern: str) -> str:
    """Canonical structural form of a pattern — the cache-key normalizer.

    Parses to the AST and renders its (deterministic, frozen-dataclass)
    repr, so syntactic variants that parse identically — whitespace-free
    reformattings, redundant alternation nesting the parser flattens —
    share one cache entry, while semantically distinct patterns (including
    explicit groups, which own paren numbers) never collide.
    """
    from .regex import parse_regex

    return repr(parse_regex(pattern))


@dataclasses.dataclass
class CompiledTenantTables:
    """One automaton compiled + padded to its fleet bucket shape (host side)."""

    matrices: ParserMatrices
    N: np.ndarray            # (Ab, Lb, Lb) f32 — PAD = index Ab-1 = identity
    I: np.ndarray            # (Lb,) f32
    F: np.ndarray            # (Lb,) f32
    ell: int                 # true segment count
    ell_pad: int             # Lb: pow2 ℓp bucket
    n_classes: int           # Ab: pow2 class bucket (incl. PAD)
    pad_class: int           # Ab - 1
    width_bound: int         # depth-1 feasible width (sparse bucket input)


def _compile_tables(matrices: ParserMatrices, min_lane_pad: int) -> CompiledTenantTables:
    ell = matrices.n_segments
    lb = _next_pow2(max(min_lane_pad, ell))
    ab = _next_pow2(matrices.N.shape[0])
    N, I, F = pad_matrices_bundle(matrices, ell_pad=lb, n_classes=ab)
    return CompiledTenantTables(
        matrices=matrices,
        N=N,
        I=I,
        F=F,
        ell=ell,
        ell_pad=lb,
        n_classes=ab,
        pad_class=ab - 1,
        width_bound=feasible_width_bound(matrices),
    )


# (normalized regex, backend variant, ℓp bucket) → CompiledTenantTables.
# Process-wide: every fleet in the process shares it, so two fleets serving
# the same pattern set compile its tables once.
_TABLE_CACHE: Dict[Tuple[str, str, int], CompiledTenantTables] = {}
# (normalized regex, backend variant) → ℓp bucket: the bucket is a function
# of the pattern + backend (derived while building), so lookups that have
# not built yet resolve their full key through this index.
_TABLE_CACHE_LP: Dict[Tuple[str, str], int] = {}
_TABLE_CACHE_LOCK = threading.Lock()


def compiled_tenant_tables(
    regex: str,
    backend_key: str,
    min_lane_pad: int,
    metrics=None,
) -> CompiledTenantTables:
    """Cache front: padded tenant tables, built at most once per key.

    Hit/miss counters land on the calling fleet's registry (the cache is
    process-wide; attribution is per fleet).
    """
    norm = normalize_regex(regex)
    with _TABLE_CACHE_LOCK:
        lp = _TABLE_CACHE_LP.get((norm, backend_key))
        entry = _TABLE_CACHE.get((norm, backend_key, lp)) if lp is not None else None
    if entry is not None:
        if metrics is not None:
            metrics.counter("table_cache_hits_total").inc()
        return entry
    if metrics is not None:
        metrics.counter("table_cache_misses_total").inc()
    from .segments import compute_segments

    ct = _compile_tables(build_matrices(compute_segments(regex)), min_lane_pad)
    with _TABLE_CACHE_LOCK:
        _TABLE_CACHE_LP[(norm, backend_key)] = ct.ell_pad
        _TABLE_CACHE[(norm, backend_key, ct.ell_pad)] = ct
    return ct


def table_cache_stats() -> Dict[str, Any]:
    with _TABLE_CACHE_LOCK:
        return {
            "entries": len(_TABLE_CACHE),
            "keys": sorted((k[1], k[2]) for k in _TABLE_CACHE),
        }


def clear_table_cache() -> None:
    """Test hook: forget every compiled table (counters are per-registry)."""
    with _TABLE_CACHE_LOCK:
        _TABLE_CACHE.clear()
        _TABLE_CACHE_LP.clear()


# ---------------------------------------------------------------- tenants


@dataclasses.dataclass
class TenantState:
    tid: str
    spec: TenantSpec
    tables: CompiledTenantTables
    bucket_key: Tuple[str, int, int]   # (backend variant, Ab, Lb)
    row: int                           # row in the bucket's table stack

    def classes_of_text(self, text) -> np.ndarray:
        if isinstance(text, (bytes, str)):
            return self.tables.matrices.classes_of_text(text)
        return np.asarray(text, dtype=np.int32)

    def text_bucket(self, n: int) -> Tuple[int, int]:
        c = max(1, self.spec.n_chunks)
        k = _next_pow2(max(self.spec.min_chunk_len, -(-n // c)))
        return c, k


class _BucketRunner:
    """One automaton bucket: stacked member tables + ONE jitted program.

    The program is ``jit(lift_batch(batch_core(core)))`` — the fused
    three-phase core lifted over batch slots, then over the tenant axis with
    tables mapped as per-row operands.  Each distinct pow2 (T, B, c, k)
    shape traces once; ``jnp.take`` gathers the active tenants' rows from
    the resident device stack per call, so adding a tenant never retraces
    (the stack pads to pow2 rows) except when a sparse bucket's shared
    width S grows.
    """

    def __init__(self, key: Tuple[str, int, int], backend: ParserBackend, obs, on_trace):
        self.key = key
        self.backend = backend
        self.obs = obs
        self._on_trace = on_trace
        _, self.n_classes, self.ell_pad = key
        self.pad_class = self.n_classes - 1
        self.tenant_rows: Dict[str, int] = {}
        self._host: List[CompiledTenantTables] = []
        self._stack: Optional[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]] = None
        self._jit = None
        self._seen_shapes: set = set()
        # steady-state serving re-gathers the same tenant rows every call;
        # keyed on the row tuple, the gathered device operands are reused
        # so a warm dispatch is ONE program launch (reset with the stack)
        self._gather_cache: Dict[Tuple[int, ...], Tuple] = {}

    # --------------------------------------------------------- membership

    def add(self, tid: str, ct: CompiledTenantTables) -> int:
        row = len(self._host)
        self.tenant_rows[tid] = row
        self._host.append(ct)
        self._stack = None                       # restack lazily (pow2 rows)
        self._gather_cache.clear()
        if isinstance(self.backend, SparseBackend):
            # the bucket runs every member at the shared width S = pow2 of
            # the member maximum (dense fallback S = Lb when it reaches Lb);
            # a width ≥ a member's own bound keeps its gathers exact.  A
            # grown S changes product shapes → drop the compiled set.
            old = self.backend._width
            raw = max(t.width_bound for t in self._host)
            self.backend.bind_shape(self.ell_pad, raw)
            if self.backend._width != old:
                self._jit = None
                self._seen_shapes.clear()
        return row

    @property
    def n_tenants(self) -> int:
        return len(self._host)

    # ------------------------------------------------------------ program

    def _ensure_program(self):
        if self._jit is None:
            core = make_parse_core(self.backend)

            def counted(N, I, F, chunks):
                self._on_trace()                 # trace-time compile counter
                return core(N, I, F, chunks)

            self._jit = jax.jit(
                self.backend.lift_batch(self.backend.batch_core(counted))
            )
        if self._stack is None:
            T = len(self._host)
            Tp = _next_pow2(T)
            ab, lb = self.n_classes, self.ell_pad
            N = np.empty((Tp, ab, lb, lb), dtype=np.float32)
            I = np.empty((Tp, lb), dtype=np.float32)
            F = np.empty((Tp, lb), dtype=np.float32)
            for r, ct in enumerate(self._host):
                N[r], I[r], F[r] = ct.N, ct.I, ct.F
            # pad rows replicate row 0: always a valid automaton for every
            # backend (their chunks are all-PAD and their outputs discarded)
            N[T:], I[T:], F[T:] = N[0], I[0], F[0]
            self._stack = (jnp.asarray(N), jnp.asarray(I), jnp.asarray(F))

    def run(
        self,
        c: int,
        k: int,
        per_tenant: Dict[str, List[np.ndarray]],
    ) -> Dict[str, List[Tuple[np.ndarray, np.ndarray]]]:
        """One device dispatch for every (tenant, text) of one (c, k) grid.

        ``per_tenant`` maps tid → class arrays; returns tid → [(col0, cols)]
        aligned with the input lists (packed uint32, bucket-width words).
        """
        self._ensure_program()
        tids = list(per_tenant)
        Ta = len(tids)
        Tp = _next_pow2(Ta)
        B = _next_pow2(max(len(v) for v in per_tenant.values()))
        m = self.obs.metrics
        shape = (Tp, B, c, k)
        if shape in self._seen_shapes:
            m.counter("bucket_cache_hits_total").inc()
        else:
            self._seen_shapes.add(shape)
            m.counter("bucket_cache_misses_total").inc()
        rows = np.zeros(Tp, dtype=np.int32)      # pad rows gather row 0
        chunks = np.full((Tp, B, c, k), self.pad_class, dtype=np.int32)
        flat = chunks.reshape(Tp, B, c * k)      # fill texts in place
        for t, tid in enumerate(tids):
            rows[t] = self.tenant_rows[tid]
            for b, classes in enumerate(per_tenant[tid]):
                flat[t, b, : len(classes)] = classes
        row_key = tuple(rows.tolist())
        operands = self._gather_cache.get(row_key)
        if operands is None:
            Ns, Is, Fs = self._stack
            idx = jnp.asarray(rows)
            operands = (
                jnp.take(Ns, idx, axis=0),
                jnp.take(Is, idx, axis=0),
                jnp.take(Fs, idx, axis=0),
            )
            self._gather_cache[row_key] = operands
        col0s, colss = self._jit(*operands, jnp.asarray(chunks))
        col0s = np.asarray(col0s)
        colss = np.asarray(colss)
        return {
            tid: [
                (col0s[t, b], colss[t, b])
                for b in range(len(per_tenant[tid]))
            ]
            for t, tid in enumerate(tids)
        }


# ------------------------------------------------------------------ engine


class _FleetBackendInfo:
    """Engine-duck-typing shim: services report ``engine.backend.name``."""

    name = "fleet"


class FleetEngine:
    """Many automata, one engine pool: per-bucket tenant-batched programs.

    Quacks like ``ParserEngine`` where the service layer needs it
    (``obs``, ``compile_count``, ``backend.name``); parsing goes through
    ``parse_batch([(tenant_id, text), ...])`` or the per-bucket
    ``run_bucket`` the fleet service drives.
    """

    def __init__(self, obs: Optional[ObsHandle] = None):
        self.obs = obs if obs is not None else ObsHandle()
        self.backend = _FleetBackendInfo()
        self._tenants: Dict[str, TenantState] = {}
        self._buckets: Dict[Tuple[str, int, int], _BucketRunner] = {}
        self._compile_count = 0

    def _bump_compiles(self) -> None:
        self._compile_count += 1
        self.obs.metrics.counter("compiled_programs_total").inc()

    @property
    def compile_count(self) -> int:
        """Device programs traced across every bucket — grows with the
        number of (backend, ℓp-bucket) pairs × pow2 shapes, not tenants."""
        return self._compile_count

    @property
    def tenants(self) -> Dict[str, TenantState]:
        return dict(self._tenants)

    @property
    def n_buckets(self) -> int:
        return len(self._buckets)

    def bucket_sizes(self) -> Dict[Tuple[str, int, int], int]:
        return {k: r.n_tenants for k, r in self._buckets.items()}

    # ---------------------------------------------------------- membership

    def add_tenant(
        self,
        tid: str,
        spec: TenantSpec,
        matrices: Optional[ParserMatrices] = None,
    ) -> TenantState:
        """Register one tenant: compile-or-cache its tables, place it in its
        automaton bucket (creating the bucket's backend + program slot on
        first membership)."""
        if tid in self._tenants:
            raise ValueError(f"fleet tenant {tid!r} already registered")
        if spec.backend == "auto":
            # static backend selection (repro.analyze): resolve before the
            # bucket key is derived, so auto tenants land in the bucket of
            # the backend they actually run on
            from ..analyze.pattern import analyze_matrices, resolve_auto_backend

            if matrices is not None:
                chosen = analyze_matrices(matrices).recommended_backend
            else:
                chosen = resolve_auto_backend(spec.regex, spec.feasible_depth)
            spec = dataclasses.replace(spec, backend=chosen)
            self.obs.metrics.counter(
                "auto_backend_selected_total", backend=chosen
            ).inc()
        backend_key = spec.backend_key()
        min_lane = spec.make_backend().min_lane_pad
        if matrices is not None:
            ct = _compile_tables(matrices, min_lane)   # prebuilt: bypass cache
        else:
            ct = compiled_tenant_tables(
                spec.regex, backend_key, min_lane, metrics=self.obs.metrics
            )
        key = (backend_key, ct.n_classes, ct.ell_pad)
        runner = self._buckets.get(key)
        if runner is None:
            backend = spec.make_backend()
            backend.check_ell_pad(ct.ell_pad)
            if isinstance(backend, SparseBackend):
                backend.bind_shape(ct.ell_pad, ct.width_bound)
            runner = _BucketRunner(key, backend, self.obs, self._bump_compiles)
            self._buckets[key] = runner
        row = runner.add(tid, ct)
        ts = TenantState(tid=tid, spec=spec, tables=ct, bucket_key=key, row=row)
        self._tenants[tid] = ts
        m = self.obs.metrics
        m.gauge("fleet_tenants").set(len(self._tenants))
        m.gauge("fleet_buckets").set(len(self._buckets))
        return ts

    def tenant(self, tid: str) -> TenantState:
        ts = self._tenants.get(tid)
        if ts is None:
            raise KeyError(f"unknown fleet tenant {tid!r}")
        return ts

    # ------------------------------------------------------------- parsing

    def request_plan(self, tid: str, text) -> Tuple[np.ndarray, Tuple]:
        """(classes, bucket) of one request — the service's submit-time hook.

        The bucket is (automaton bucket, (c, k) text bucket): requests batch
        together exactly when they share a compiled program's operand shape.
        """
        ts = self.tenant(tid)
        classes = ts.classes_of_text(text)
        return classes, (ts.bucket_key, ts.text_bucket(len(classes)))

    def run_bucket(
        self, bucket: Tuple, items: Sequence[Tuple[str, np.ndarray]]
    ) -> List[SLPF]:
        """Serve one same-bucket group in a single tenant-batched dispatch."""
        bkey, (c, k) = bucket
        runner = self._buckets[bkey]
        per_tenant: Dict[str, List[np.ndarray]] = {}
        slots: List[Tuple[str, int]] = []
        for tid, classes in items:
            lst = per_tenant.setdefault(tid, [])
            slots.append((tid, len(lst)))
            lst.append(classes)
        out = runner.run(c, k, per_tenant)
        results = []
        for (tid, b), (_, classes) in zip(slots, items):
            col0, cols = out[tid][b]
            results.append(self._assemble(self.tenant(tid), col0, cols, classes))
        return results

    def parse_batch(self, items: Sequence[Tuple[str, Any]]) -> List[SLPF]:
        """Parse [(tenant_id, text), ...]: group by (automaton bucket,
        (c, k)), one tenant-batched device program per group, results in
        input order — bit-identical per tenant to a serial per-tenant loop."""
        plans = []
        groups: Dict[Tuple, List[int]] = {}
        for i, (tid, text) in enumerate(items):
            classes, bucket = self.request_plan(tid, text)
            plans.append((tid, classes, bucket))
            groups.setdefault(bucket, []).append(i)
        results: List[Optional[SLPF]] = [None] * len(items)
        for bucket, idxs in sorted(groups.items()):
            group_items = [(plans[i][0], plans[i][1]) for i in idxs]
            for i, slpf in zip(idxs, self.run_bucket(bucket, group_items)):
                results[i] = slpf
        return results  # type: ignore[return-value]

    def parse(self, tid: str, text) -> SLPF:
        return self.parse_batch([(tid, text)])[0]

    def _assemble(
        self, ts: TenantState, col0: np.ndarray, cols: np.ndarray, classes
    ) -> SLPF:
        n = len(classes)
        W = cols.shape[-1]
        packed = np.concatenate(
            [np.asarray(col0)[None], np.asarray(cols).reshape(-1, W)[:n]], axis=0
        )
        columns = unpack_bits(packed, ts.tables.ell, axis=-1)
        return SLPF(
            table=ts.tables.matrices.table,
            columns=columns,
            classes=np.asarray(classes, dtype=np.int32),
        )
