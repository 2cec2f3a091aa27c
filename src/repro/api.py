"""The public parse API: one facade, one declarative config, one result type.

The paper's tool exposes parsing as ONE operation — text in, shared parse
forest with match/children/tree accessors out (Sect. 4.2 / App. A).  This
module is that surface for the whole runtime grown in PRs 1-4:

  ``ParserConfig``   a frozen, validated, dict-round-trippable description of
                     a parser: the RE, the phase backend (jnp / pallas /
                     packed, with the Pallas-kernel toggle), the chunk-split
                     and bucket policy (PaREM's chunk model: serial is
                     ``n_chunks=1``, chunked is ``n_chunks>1``, distributed
                     is ``mesh=``), streaming seal policy, admission budgets,
                     and SLO targets (per-bucket p50/p99 latency goals +
                     default deadline).

  ``Parser``         the facade.  Owns engine and service construction —
                     callers never assemble ``ParserEngine`` /
                     ``ParseService`` / ``StreamService`` by hand (direct
                     construction is deprecated).  One synchronous surface
                     (``parse`` / ``parse_batch``), one asynchronous
                     submission surface (``submit`` → ``ParseTicket``), one
                     streaming surface (``open_stream`` → ``ParserStream``),
                     and ``stats()`` aggregating both services plus SLO
                     conformance.

  ``ParseResult``    first-class result wrapping the ``SLPF``: ``ok``,
                     ``matches(group)``, ``children(span)``, ``trees(limit)``,
                     timing/backend metadata, and ``forest`` (the SLPF
                     itself) for everything forest-level.

  ``ParseTicket``    deadline-aware asynchronous handle: ``done()`` /
                     ``result()`` / ``cancel()``.  ``submit(text,
                     deadline_s=...)`` runs deadline-aware admission — a
                     request whose shape bucket's observed p99 latency
                     already exceeds the remaining deadline is rejected with
                     ``repro.errors.AdmissionError`` before any device work
                     (the ROADMAP SLO item; cold buckets predict 0.0 and
                     admit).

Every error is typed (``repro/errors.py``); every route stays bit-identical
to the direct engine paths (enforced by ``tests/test_conformance.py``, where
the facade is a first-class conformance route).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple, Union

import numpy as np

from .core.backend import ParserBackend, get_backend, list_backends, register_backend
from .core.engine import ParserEngine
from .core.matrices import ParserMatrices, build_matrices
from .core.segments import SegmentTable, compute_segments
from .core.slpf import SLPF
from .errors import (
    AdmissionError,
    BudgetExceeded,
    ParseError,
    PathologicalPatternError,
    SessionNotFound,
)
from .obs import ObsConfig, ObsHandle
from .serve.parse_service import ParseRequest, ParseService
from .serve.stream_service import StreamService

# Mesh axes of the declarative ``mesh="host"`` spec (launch/mesh.py's
# make_parse_mesh): chunks shard over 'pod', batch slots over 'data'.
_HOST_MESH_AXES = ("pod", "data")


def _is_pow2(x: int) -> bool:
    return x >= 1 and (x & (x - 1)) == 0


# ------------------------------------------------------------------ config


@dataclasses.dataclass(frozen=True)
class SLOTargets:
    """Latency objectives applied per device-program bucket.

    ``p50_s``/``p99_s`` are the per-bucket targets ``Parser.stats()`` grades
    observed latency against; ``default_deadline_s`` is the admission
    deadline ``submit``/``append`` use when the caller passes none (None ⇒
    no implicit deadline — everything admits).
    """

    p50_s: Optional[float] = None
    p99_s: Optional[float] = None
    default_deadline_s: Optional[float] = None

    def __post_init__(self):
        for name in ("p50_s", "p99_s", "default_deadline_s"):
            v = getattr(self, name)
            if v is not None and v <= 0.0:
                raise ValueError(f"SLOTargets.{name} must be positive, got {v!r}")
        if self.p50_s is not None and self.p99_s is not None and self.p50_s > self.p99_s:
            raise ValueError(
                f"SLOTargets.p50_s ({self.p50_s}) must not exceed p99_s ({self.p99_s})"
            )


@dataclasses.dataclass(frozen=True)
class ParserConfig:
    """Declarative, validated, dict-round-trippable parser description.

    Validation happens at construction (``__post_init__``) so an invalid
    config never reaches device code: unknown backend names, a kernel toggle
    on a backend without kernels, non-power-of-two bucket policy, mesh rules
    without a mesh, and mesh axes that cannot resolve on the declared mesh
    all raise ``ValueError`` immediately.

    ``to_dict()``/``from_dict()`` round-trip exactly (plain JSON-able
    values), and two Parsers built from a config and its round-trip produce
    bit-identical SLPFs (tested).
    """

    # what to parse
    regex: str
    # phase backend: a registered name, or "auto" — the static analyzer
    # (repro.analyze) picks dense/packed/sparse from the pattern's modeled
    # roofline before any device code; kernel=True selects the backend's
    # Pallas-kernel reach path where one exists (pallas is always kernels)
    backend: str = "jnp"
    kernel: bool = False
    # static-analysis admission policy: "warn" (default) analyzes the
    # pattern at construction and warns on pathological ambiguity, "strict"
    # rejects it with repro.errors.PathologicalPatternError, "off" skips the
    # construction-time analysis (stats()["analysis"] still computes lazily)
    analyze: str = "warn"
    # sparse backend only: feasible-prefix depth — how many leading chunk
    # characters prune the speculative start-state set (PaREM boundary info);
    # deeper prunes harder at the cost of d sequential mat-vecs per chunk
    feasible_depth: int = 1
    # chunk-split policy (PaREM's model): 1 = serial, >1 = chunked; the
    # bucket policy rounds chunk lengths to pow2 with this floor
    n_chunks: int = 8
    min_chunk_len: int = 8
    # batched serving
    max_batch: int = 8
    max_pending: Optional[int] = None
    # weighted-fair share when this config serves as a fleet tenant (or for
    # this parser's streams): scheduling vtime advances by chars/weight
    weight: float = 1.0
    # streaming seal/bucket policy (pow2 geometric sealing)
    first_seal_len: int = 8
    max_seal_len: Optional[int] = None
    cache_budget_bytes: Optional[int] = None
    max_pending_chars: Optional[int] = None
    # distribution: None = single device; "host" = a ('pod','data') mesh over
    # every visible device (launch/mesh.py make_parse_mesh).  mesh_rules maps
    # logical axes ('chunk', 'batch') to mesh axes; values must resolve on
    # the declared mesh.
    mesh: Optional[str] = None
    mesh_rules: Optional[Tuple[Tuple[str, Tuple[str, ...]], ...]] = None
    # service-level objectives (admission + stats grading)
    slo: Optional[SLOTargets] = None
    # observability (repro/obs): None = metrics only (tracing off); an
    # ObsConfig (or its dict) switches on spans / JSONL logs / profiler
    # annotations
    obs: Optional[ObsConfig] = None

    def __post_init__(self):
        if not isinstance(self.regex, str) or not self.regex:
            raise ValueError("ParserConfig.regex must be a non-empty pattern string")
        known = list_backends()
        if self.backend != "auto" and self.backend not in known:
            raise ValueError(
                f"unknown parse backend {self.backend!r}; known: "
                f"{known + ['auto']}"
            )
        if self.analyze not in ("off", "warn", "strict"):
            raise ValueError(
                f"analyze must be 'off', 'warn', or 'strict', got "
                f"{self.analyze!r}"
            )
        if self.kernel and self.backend == "jnp":
            raise ValueError(
                "kernel=True selects a Pallas kernel path; the 'jnp' backend "
                "has none (use backend='pallas' or backend='packed')"
            )
        if self.kernel and self.backend == "auto":
            raise ValueError(
                "kernel=True is a per-backend toggle; backend='auto' lets "
                "the analyzer choose — pick an explicit backend to force "
                "its kernel path"
            )
        if self.feasible_depth < 1:
            raise ValueError(
                f"feasible_depth must be >= 1, got {self.feasible_depth}"
            )
        if self.feasible_depth != 1 and self.backend not in ("sparse", "auto"):
            raise ValueError(
                "feasible_depth tunes the sparse backend's start-state "
                f"pruning; backend {self.backend!r} has no speculation to "
                "prune (use backend='sparse')"
            )
        if self.n_chunks < 1:
            raise ValueError(f"n_chunks must be >= 1, got {self.n_chunks}")
        for name in ("min_chunk_len", "first_seal_len"):
            v = getattr(self, name)
            if not _is_pow2(v):
                raise ValueError(
                    f"{name} must be a power of two (the bucket policy "
                    f"compiles one program per pow2 shape), got {v}"
                )
        if self.max_seal_len is not None and not _is_pow2(self.max_seal_len):
            raise ValueError(
                f"max_seal_len must be a power of two, got {self.max_seal_len}"
            )
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.weight <= 0:
            raise ValueError(f"weight must be > 0, got {self.weight}")
        for name in ("max_pending", "cache_budget_bytes", "max_pending_chars"):
            v = getattr(self, name)
            if v is not None and v < 1:
                raise ValueError(f"{name} must be positive or None, got {v}")
        if self.mesh not in (None, "host"):
            raise ValueError(
                f"mesh must be None (single device) or 'host' (a "
                f"{_HOST_MESH_AXES} mesh over every device), got {self.mesh!r}"
            )
        # normalize mesh_rules: accept a mapping / iterable of pairs; store a
        # canonical hashable tuple-of-pairs with tuple axis values
        if self.mesh_rules is not None:
            if self.mesh is None:
                raise ValueError("mesh_rules requires mesh to be set")
            items = (
                self.mesh_rules.items()
                if isinstance(self.mesh_rules, Mapping)
                else self.mesh_rules
            )
            norm = []
            for name, axes in items:
                if axes is None:
                    axes_t: Tuple[str, ...] = ()
                elif isinstance(axes, str):
                    axes_t = (axes,)
                else:
                    axes_t = tuple(axes)
                for a in axes_t:
                    if a not in _HOST_MESH_AXES:
                        raise ValueError(
                            f"mesh_rules[{name!r}] names mesh axis {a!r} which "
                            f"does not resolve on the declared mesh (axes: "
                            f"{_HOST_MESH_AXES})"
                        )
                norm.append((str(name), axes_t))
            object.__setattr__(self, "mesh_rules", tuple(sorted(norm)))
        if self.slo is not None and isinstance(self.slo, Mapping):
            object.__setattr__(self, "slo", SLOTargets(**dict(self.slo)))
        if self.obs is not None and isinstance(self.obs, Mapping):
            object.__setattr__(self, "obs", ObsConfig(**dict(self.obs)))

    # ------------------------------------------------------- dict round-trip

    def to_dict(self) -> Dict[str, Any]:
        """Plain JSON-able dict; ``from_dict`` round-trips it exactly."""
        d = dataclasses.asdict(self)
        if self.mesh_rules is not None:
            d["mesh_rules"] = {name: list(axes) for name, axes in self.mesh_rules}
        return d

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "ParserConfig":
        d = dict(d)
        unknown = set(d) - {f.name for f in dataclasses.fields(cls)}
        if unknown:
            raise ValueError(f"unknown ParserConfig keys: {sorted(unknown)}")
        return cls(**d)

    def replace(self, **kw) -> "ParserConfig":
        return dataclasses.replace(self, **kw)

    # ------------------------------------------------------------- builders

    def build_backend(self, resolved: Optional[str] = None) -> ParserBackend:
        """Instantiate the configured phase backend (kernel toggle applied).

        ``resolved`` supplies the analyzer's choice when this config says
        ``backend="auto"`` (the facade passes it); "auto" itself is not
        instantiable."""
        from .core.backend import PackedBackend, SparseBackend

        name = resolved if resolved is not None else self.backend
        if name == "auto":
            raise ValueError(
                'backend="auto" resolves through the static analyzer; '
                "build_backend needs the resolved name (use repro.analyze."
                "resolve_auto_backend or construct a Parser)"
            )
        if name == "sparse":
            return SparseBackend(kernel=self.kernel, depth=self.feasible_depth)
        if name == "packed" and self.kernel:
            return PackedBackend(kernel=True)
        return get_backend(name)

    def build_mesh(self):
        """The declared device mesh, or None on a single-device config."""
        if self.mesh is None:
            return None
        from .launch.mesh import make_parse_mesh

        return make_parse_mesh()

    def build_mesh_rules(self):
        """``MeshRules`` with this config's overrides, or None for defaults."""
        if self.mesh_rules is None:
            return None
        from .parallel.sharding import MeshRules

        overrides = {
            name: (axes if len(axes) != 1 else axes[0]) or None
            for name, axes in self.mesh_rules
        }
        return MeshRules().with_overrides(**overrides)


# ------------------------------------------------------------------ results


@dataclasses.dataclass
class ParseResult:
    """First-class parse result: the forest plus accessors and metadata.

    The forest-level query API of the paper's tool (Sect. 4.2 / App. A)
    lives here; anything deeper (arcs, packing, compression) is reachable
    through ``forest`` — the ``SLPF`` itself.
    """

    forest: SLPF
    backend: str
    bucket: Optional[Tuple[int, int]] = None
    latency_s: Optional[float] = None
    n_chunks: Optional[int] = None
    # sparse backend only: the observed speculation width of this parse —
    # per-chunk feasible-start-set sizes vs the ℓp the paper speculates on
    # ({"width_mean", "width_max", "n_chunks_real", "product_rows",
    #   "ell_pad", "depth"}); None on dense backends
    speculation: Optional[Dict[str, Any]] = None
    # the request's trace ID when the parser's tracer is enabled — the key
    # into the span log (obs/export.py validate_span_tree); else None
    trace_id: Optional[str] = None

    # ------------------------------------------------------------- queries

    @property
    def ok(self) -> bool:
        """Did the text match the RE (non-empty clean forest)?"""
        return self.forest.accepted

    @property
    def slpf(self) -> SLPF:
        """Alias of ``forest`` (the shared linearized parse forest)."""
        return self.forest

    def count_trees(self) -> int:
        return self.forest.count_trees()

    def matches(self, group: int, limit: Optional[int] = 1000) -> List[Tuple[int, int]]:
        """(start, end) spans of a numbered group / operator pair (App. A)."""
        return self.forest.get_matches(group, limit=limit)

    def children(
        self, span: Tuple[int, int], limit: Optional[int] = 1000
    ) -> List[Tuple[int, int, int]]:
        """Direct child spans of a match span, from the tree structure.

        For each LST (up to ``limit``) containing a paren pair matching
        ``span`` exactly, collect the (group, start, end) pairs DIRECTLY
        nested under it (paper ``getChildren``).  The paren nesting stack is
        walked per tree, so only immediate children are reported — not every
        transitively contained span.
        """
        from .core.numbering import CLOSE, OPEN

        span = (int(span[0]), int(span[1]))
        syms = self.forest.table.numbered.symbols
        out: Dict[Tuple[int, int, int], None] = {}
        for path in self.forest.iter_trees(limit=limit):
            # stack entries: [group num, start boundary, collected children]
            stack: List[List[Any]] = []
            for r, q in enumerate(path):
                for sid in self.forest.table.segs[q][:-1]:
                    s = syms[sid]
                    if s.kind == OPEN:
                        stack.append([s.num, r, []])
                    elif s.kind == CLOSE:
                        num, st, kids = stack.pop()
                        if stack:
                            stack[-1][2].append((num, st, r))
                        if (st, r) == span:
                            for kid in kids:
                                out[kid] = None
        return sorted(out)

    def trees(self, limit: Optional[int] = None, *, paths: bool = False) -> List:
        """Up to ``limit`` LSTs — rendered parenthesized strings by default,
        raw segment-id paths with ``paths=True``."""
        if paths:
            return list(self.forest.iter_trees(limit=limit))
        return [
            self.forest.lst_string(p) for p in self.forest.iter_trees(limit=limit)
        ]


# ------------------------------------------------------------------ tickets


class ParseTicket:
    """Asynchronous handle for one submitted parse (``Parser.submit``).

    The underlying request is already past deadline-aware admission; the
    ticket resolves it: ``done()`` is a free check, ``result()`` drives the
    service until THIS request is served (batching with whatever else is
    queued) and returns the ``ParseResult``, ``cancel()`` drops it from the
    queue if no batch has picked it up yet.
    """

    def __init__(
        self,
        parser: "Parser",
        service: ParseService,
        request: ParseRequest,
        deadline_s: Optional[float] = None,
    ):
        self._parser = parser
        self._service = service
        self._request = request
        self._result: Optional[ParseResult] = None
        self._cancelled = False
        self.deadline_s = deadline_s   # the admitted remaining budget

    @property
    def rid(self) -> int:
        return self._request.rid

    def done(self) -> bool:
        return self._request.done

    def cancel(self) -> bool:
        """Drop the request if it has not been served; True on success."""
        if self._request.done:
            return False
        self._cancelled = self._service.cancel(self._request.rid)
        return self._cancelled

    def result(self) -> ParseResult:
        """Serve (if needed) and return the result; raises on a cancelled
        ticket."""
        if self._result is not None:
            return self._result
        if self._cancelled:
            raise ParseError(f"parse request {self._request.rid} was cancelled")
        while not self._request.done:
            if not self._service.step():
                raise ParseError(
                    f"parse request {self._request.rid} is no longer queued"
                )
        self._service.reap(self._request)
        req = self._request
        if req.trace_id is not None:
            # the root span closes here — collection ends the request's
            # lifetime; queue-wait/compute children were emitted at pickup
            # against the pre-minted root id
            self._parser.engine.obs.emit(
                "parse.request",
                t_start_s=req.submitted_at,
                duration_s=req.latency_s,
                trace_id=req.trace_id,
                span_id=req.root_span_id,
                bucket=list(req.bucket) if req.bucket else None,
                n_chars=len(req.classes) if req.classes is not None else 0,
            )
        self._result = self._parser._wrap(
            req.slpf,
            bucket=req.bucket,
            latency_s=req.latency_s,
            trace_id=req.trace_id,
            root_span_id=req.root_span_id,
            tenant=req.tenant,
        )
        return self._result


# ------------------------------------------------------------------ streams


class ParserStream:
    """One streaming session of ``Parser.open_stream`` (context manager).

    Appends go through the shared ``StreamService`` — concurrent sessions
    batch their tail pieces into one device reach — and carry the same
    deadline-aware admission as ``submit``.  ``result()`` materializes the
    current prefix's ``ParseResult``; ``accepted`` is the O(1) streaming
    acceptance state.
    """

    def __init__(self, parser: "Parser", service: StreamService, sid: int):
        self._parser = parser
        self._service = service
        self._sid = sid
        self._closed = False

    @property
    def sid(self) -> int:
        return self._sid

    @property
    def n(self) -> int:
        """Characters absorbed into the prefix so far (queued appends not
        yet drained are excluded)."""
        return self._service._session(self._sid).parser.n

    @property
    def n_sealed_chunks(self) -> int:
        """Sealed chunk products resident in this stream's prefix cache."""
        return self._service._session(self._sid).parser.n_sealed_chunks

    def append(self, text, *, deadline_s: Optional[float] = None) -> int:
        """Queue text onto this stream; returns chars queued (admission may
        raise ``AdmissionError``/``BudgetExceeded``)."""
        if deadline_s is None:
            deadline_s = self._parser._default_deadline_s()
        return self._service.append(self._sid, text, deadline_s=deadline_s)

    @property
    def accepted(self) -> bool:
        """Is the current prefix a valid text (drains this session only)?"""
        return self._service.accepted(self._sid)

    def edit(self, lo: int, hi: int, replacement) -> int:
        """Splice the prefix: replace characters ``[lo, hi)`` with
        ``replacement``; returns the new prefix length.

        O(log n) device work — the stream's product segment tree re-reaches
        only the spliced chunks and re-composes one leaf-to-root path; the
        result is bit-identical to a cold parse of the edited text.  Drains
        this session's queued appends first (the range addresses the
        post-append prefix).
        """
        return self._service.edit(self._sid, lo, hi, replacement)

    def delete(self, lo: int, hi: int) -> int:
        """Remove characters ``[lo, hi)`` — ``edit`` with an empty
        replacement."""
        return self._service.edit(self._sid, lo, hi, "")

    def insert(self, pos: int, text) -> int:
        """Insert ``text`` before position ``pos`` — a zero-width
        ``edit``."""
        return self._service.edit(self._sid, pos, pos, text)

    def result(self) -> ParseResult:
        """ParseResult of the full current prefix (drains this session)."""
        t0 = time.perf_counter()
        slpf = self._service.slpf(self._sid)
        return self._parser._wrap(slpf, latency_s=time.perf_counter() - t0)

    def close(self) -> None:
        if not self._closed:
            self._service.close(self._sid)
            self._closed = True

    def __enter__(self) -> "ParserStream":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# ------------------------------------------------------------------- facade


class Parser:
    """The one public parser: built from a ``ParserConfig`` (or a pattern).

        p = repro.Parser("(a|b|ab)+")             # defaults
        p = repro.Parser(ParserConfig(regex=..., backend="packed",
                                      mesh="host", slo=SLOTargets(...)))

    Owns every lower layer: the ``ParserEngine`` (backend, bucket policy,
    mesh placement), a lazy ``ParseService`` (batched one-shot requests) and
    a lazy ``StreamService`` (streaming sessions) — both over the SAME
    engine, so all routes share one compiled-program set.  ``stats()``
    aggregates both services plus SLO conformance.
    """

    def __init__(
        self,
        config: Union[ParserConfig, str, Mapping[str, Any]],
        *,
        matrices: Optional[ParserMatrices] = None,
    ):
        if isinstance(config, str):
            config = ParserConfig(regex=config)
        elif isinstance(config, Mapping):
            config = ParserConfig.from_dict(config)
        if not isinstance(config, ParserConfig):
            raise TypeError(
                f"Parser takes a ParserConfig, a pattern string, or a config "
                f"dict; got {type(config).__name__}"
            )
        self.config = config
        if matrices is None:
            matrices = build_matrices(compute_segments(config.regex))
        self.matrices = matrices
        # one ObsHandle for the whole parser: the engine carries it, every
        # layer (services, streams, distribution) records into it
        self.obs = ObsHandle.from_config(config.obs)
        # static analysis (repro.analyze leg 1): runs at construction when
        # the config wants a verdict (analyze != "off") or needs one
        # (backend == "auto"); otherwise stats()["analysis"] computes lazily
        self._analysis = None
        resolved = config.backend
        if config.backend == "auto" or config.analyze != "off":
            report = self._analyze()
            m = self.obs.metrics
            m.counter("analyzer_verdicts_total", verdict=report.verdict).inc()
            if report.verdict == "pathological":
                if config.analyze == "strict":
                    m.counter(
                        "admission_rejects_total",
                        service="analyze",
                        cause="pathological",
                    ).inc()
                    raise PathologicalPatternError(
                        f"pattern {config.regex!r} is pathologically "
                        "ambiguous (an iterator with a nullable body admits "
                        "unboundedly many parse trees per text); "
                        'analyze="strict" rejects it at construction',
                        pattern=config.regex,
                        ambiguity=report.ambiguity,
                    )
                if config.analyze == "warn":
                    warnings.warn(
                        f"repro: pattern {config.regex!r} is pathologically "
                        "ambiguous — forest size is unbounded per text "
                        '(analyze="strict" rejects such patterns)',
                        UserWarning,
                        stacklevel=2,
                    )
            if config.backend == "auto":
                resolved = report.recommended_backend
                m.counter("auto_backend_selected_total", backend=resolved).inc()
        self._resolved_backend = resolved
        self.engine = ParserEngine(
            matrices,
            backend=config.build_backend(resolved),
            min_chunk_len=config.min_chunk_len,
            mesh=config.build_mesh(),
            mesh_rules=config.build_mesh_rules(),
            obs=self.obs,
        )
        self._parse_service: Optional[ParseService] = None
        self._stream_service: Optional[StreamService] = None
        self._artifacts = None
        # per-bucket observed speculation widths (sparse backend only)
        self._spec_buckets: Dict[Tuple[int, int], Dict[str, Any]] = {}

    @classmethod
    def from_matrices(
        cls,
        matrices_or_table: Union[ParserMatrices, SegmentTable],
        config: Union[ParserConfig, str, Mapping[str, Any], None] = None,
    ) -> "Parser":
        """Build a Parser over pre-generated matrices / a segment table.

        The advanced entry point for parsers whose RE exists only as an AST
        or whose tables were generated elsewhere; ``config.regex`` is then
        informational.  ``config`` defaults to the given pattern-less
        defaults.
        """
        if isinstance(matrices_or_table, SegmentTable):
            matrices_or_table = build_matrices(matrices_or_table)
        if config is None:
            config = ParserConfig(regex="<prebuilt>")
        elif isinstance(config, str):
            config = ParserConfig(regex=config)
        elif isinstance(config, Mapping):
            config = ParserConfig.from_dict(config)
        return cls(config, matrices=matrices_or_table)

    # ------------------------------------------------------------- plumbing

    @property
    def backend_name(self) -> str:
        return self.engine.backend.name

    @property
    def compile_count(self) -> int:
        return self.engine.compile_count

    def _analyze(self):
        if self._analysis is None:
            from .analyze import analyze_matrices

            # from_matrices parsers carry a placeholder pattern: analyze the
            # automaton alone (the AST legs fall back to matrix facts)
            pattern = self.config.regex
            if pattern == "<prebuilt>":
                pattern = None
            self._analysis = analyze_matrices(
                self.matrices,
                pattern=pattern,
                depth=max(4, self.config.feasible_depth),
            )
        return self._analysis

    @property
    def analysis(self):
        """The static ``AnalysisReport`` (``repro.analyze`` leg 1), memoized:
        feasible-start width bounds, ambiguity verdict, product density, the
        per-backend cost model and the recommended backend."""
        return self._analyze()

    @property
    def table(self) -> SegmentTable:
        return self.engine.table

    @property
    def artifacts(self):
        """Full ``ParallelArtifacts`` (NFA/DFA/ME-DFA…) for introspection.

        Built lazily — parsing never needs the exponential DFA, only the
        matrices — and only constructible when the config carries a real
        pattern (not ``from_matrices``' placeholder)."""
        if self._artifacts is None:
            from .core.reference import ParallelArtifacts

            self._artifacts = ParallelArtifacts.generate(self.matrices.table)
        return self._artifacts

    @property
    def groups(self) -> List[int]:
        """Numbered group ids extractable via ``ParseResult.matches``."""
        from .core.numbering import OPEN, OP_GROUP

        return sorted(
            {
                s.num
                for s in self.table.numbered.symbols
                if s.kind == OPEN and s.op == OP_GROUP
            }
        )

    def _default_deadline_s(self) -> Optional[float]:
        slo = self.config.slo
        return slo.default_deadline_s if slo is not None else None

    def _speculation(
        self, slpf: SLPF, bucket: Optional[Tuple[int, int]]
    ) -> Optional[Dict[str, Any]]:
        """Observed speculation width of one parse (sparse backend only).

        Recomputes, host-side, the feasible-start-set size of each chunk the
        engine's bucket policy produced for this text — the states a chunk
        processor actually speculates on vs the paper's ℓp.  All-PAD padding
        chunks carry no speculation and are excluded.
        """
        if self.backend_name != "sparse":
            return None
        from .core.matrices import feasible_start_widths

        eng = self.engine
        classes = slpf.classes
        c, k = bucket if bucket is not None else eng.bucket_shape(
            len(classes), self.config.n_chunks
        )
        chunks = np.asarray(eng._pad_to(classes, c, k)).reshape(c, k)
        widths = feasible_start_widths(
            eng.tables.N, chunks, depth=self.config.feasible_depth
        )
        real = widths[widths >= 0]
        spec = {
            "width_mean": float(real.mean()) if real.size else 0.0,
            "width_max": int(real.max()) if real.size else 0,
            "n_chunks_real": int(real.size),
            "product_rows": int(eng.backend._width),
            "ell_pad": int(eng.tables.ell_pad),
            "depth": self.config.feasible_depth,
        }
        agg = self._spec_buckets.setdefault(
            (c, k), {"parses": 0, "width_mean": 0.0, "width_max": 0}
        )
        agg["parses"] += 1
        agg["width_mean"] += (spec["width_mean"] - agg["width_mean"]) / agg["parses"]
        agg["width_max"] = max(agg["width_max"], spec["width_max"])
        self.obs.metrics.histogram("speculation_width").observe(spec["width_max"])
        return spec

    def _wrap(
        self,
        slpf: SLPF,
        *,
        bucket: Optional[Tuple[int, int]] = None,
        latency_s: Optional[float] = None,
        trace_id: Optional[str] = None,
        root_span_id: Optional[str] = None,
        tenant: Optional[str] = None,  # ticket plumbing; one-automaton
                                       # parsers have nothing per-tenant
    ) -> ParseResult:
        # the request's root span is written before this runs, so the
        # speculation span names its parent explicitly
        span = (
            self.obs.span(
                "parse.speculation", trace_id=trace_id, parent_id=root_span_id
            )
            if root_span_id is not None
            else contextlib.nullcontext()
        )
        with span:
            speculation = self._speculation(slpf, bucket)
        return ParseResult(
            forest=slpf,
            backend=self.backend_name,
            bucket=bucket,
            latency_s=latency_s,
            n_chunks=self.config.n_chunks,
            speculation=speculation,
            trace_id=trace_id,
        )

    @property
    def parse_service(self) -> ParseService:
        """The batched request service (built lazily, facade-owned)."""
        if self._parse_service is None:
            c = self.config
            self._parse_service = ParseService._internal(
                self.engine,
                max_batch=c.max_batch,
                n_chunks=c.n_chunks,
                max_pending=c.max_pending,
            )
            # the facade's traffic is one tenant; its weight only matters
            # when sharing a queue (tests / embedders may add more)
            self._parse_service.register_tenant("default", weight=c.weight)
            self._parse_service.set_pattern_guard(
                self._analysis.verdict if self._analysis is not None else "ok",
                c.analyze,
            )
        return self._parse_service

    @property
    def stream_service(self) -> StreamService:
        """The streaming session service (built lazily, facade-owned)."""
        if self._stream_service is None:
            c = self.config
            self._stream_service = StreamService._internal(
                self.engine,
                max_batch=c.max_batch,
                first_seal_len=c.first_seal_len,
                max_seal_len=c.max_seal_len,
                cache_budget_bytes=c.cache_budget_bytes,
                max_pending_chars=c.max_pending_chars,
            )
            self._stream_service.set_pattern_guard(
                self._analysis.verdict if self._analysis is not None else "ok",
                c.analyze,
            )
        return self._stream_service

    # ---------------------------------------------------------------- parse

    def submit(
        self, text, *, deadline_s: Optional[float] = None
    ) -> ParseTicket:
        """Deadline-aware asynchronous submission; returns a ``ParseTicket``.

        Admission runs NOW: a bucket whose observed p99 exceeds the
        remaining ``deadline_s`` raises ``AdmissionError`` (typed, before
        any queueing); ``max_pending`` overflow raises ``BudgetExceeded``.
        No deadline (and no config default) admits unconditionally.
        """
        if deadline_s is None:
            deadline_s = self._default_deadline_s()
        svc = self.parse_service
        req = svc.submit_request(text, deadline_s=deadline_s)
        return ParseTicket(self, svc, req, deadline_s=deadline_s)

    def parse(self, text, *, deadline_s: Optional[float] = None) -> ParseResult:
        """Parse one text synchronously through the same admission path as
        ``submit`` (stats/SLO observe it).

        On a mesh config this is the long-text route: the engine's
        single-text distributed program shards the chunk dim over EVERY
        chunk mesh axis ('pod' × 'data') — ``parse_batch`` instead keeps
        batch slots over 'data' and chunks over 'pod'.

        With tracing on (``ParserConfig(obs=ObsConfig(enabled=True))``)
        the call runs queue-free through the engine's phase-split route
        (``ParserEngine.parse_phase_split``, bit-identical to the fused
        program) so the span log carries one ``parse.request`` root with
        real per-phase children and ``parse.speculation``.  ``submit``
        keeps the fused route with tracing on, and its spans split that
        route's time: queue wait, the batch's compute (host prep, device,
        fetch, assembly) and speculation.
        """
        if self.obs.enabled or self.engine.mesh is not None:
            from .serve.parse_service import BucketStats

            if deadline_s is None:
                deadline_s = self._default_deadline_s()
            svc = self.parse_service
            classes = self.engine.classes_of_text(text)
            bucket = self.engine.bucket_shape(len(classes), self.config.n_chunks)
            svc._admit(bucket, deadline_s)
            stats = svc._buckets.setdefault(bucket, BucketStats())
            obs = self.obs
            trace_id = obs.new_trace_id()
            t0 = time.perf_counter()
            with obs.span(
                "parse.request",
                trace_id=trace_id,
                bucket=list(bucket),
                backend=self.backend_name,
                n_chars=len(classes),
            ) as root:
                if obs.enabled:
                    slpf = self.engine.parse_phase_split(
                        classes, n_chunks=self.config.n_chunks
                    )
                else:
                    slpf = self.engine.parse(classes, n_chunks=self.config.n_chunks)
            latency = time.perf_counter() - t0
            # admission/SLO learn this route too; it never queues, so the
            # whole latency is compute
            stats.record(latency, queue_s=0.0, compute_s=latency)
            m = obs.metrics
            m.counter("requests_total", service="parse").inc()
            m.counter("served_total", service="parse").inc()
            m.counter("chars_total", service="parse").inc(len(classes))
            return self._wrap(
                slpf, bucket=bucket, latency_s=latency, trace_id=trace_id,
                root_span_id=root.span_id,
            )
        return self.submit(text, deadline_s=deadline_s).result()

    def parse_batch(
        self, texts: Sequence, *, deadline_s: Optional[float] = None
    ) -> List[ParseResult]:
        """Parse many texts through the bucket-batched service; results are
        returned in input order.

        Admission is all-or-nothing: if any text is rejected
        (``AdmissionError``/``BudgetExceeded``), the already-queued ones are
        cancelled before the error propagates — no orphaned requests are
        left consuming the queue budget.
        """
        tickets: List[ParseTicket] = []
        try:
            for t in texts:
                tickets.append(self.submit(t, deadline_s=deadline_s))
        except Exception:
            for ticket in tickets:
                ticket.cancel()
            raise
        return [t.result() for t in tickets]

    def open_stream(self, *, weight: Optional[float] = None) -> ParserStream:
        """Open a streaming session (incremental appends over the shared
        prefix-cache service); close it with ``.close()`` / ``with``.

        ``weight`` sets the session's weighted-fair share of the service's
        batched absorption (default: the config's ``weight``)."""
        w = self.config.weight if weight is None else weight
        return ParserStream(
            self, self.stream_service, self.stream_service.open(weight=w)
        )

    def count_accepting(self, text) -> int:
        return self.parse(text).count_trees()

    # ---------------------------------------------------------------- stats

    def _slo_grade(self, buckets: Mapping) -> Dict[Any, Dict[str, Any]]:
        slo = self.config.slo
        out: Dict[Any, Dict[str, Any]] = {}
        for bucket, b in buckets.items():
            grade: Dict[str, Any] = {
                "p50_s": b["p50_latency_s"],
                "p99_s": b["p99_latency_s"],
                "queue_depth": b["queue_depth"],
            }
            if slo is not None and slo.p50_s is not None:
                grade["p50_ok"] = b["p50_latency_s"] <= slo.p50_s
            if slo is not None and slo.p99_s is not None:
                grade["p99_ok"] = b["p99_latency_s"] <= slo.p99_s
            out[bucket] = grade
        return out

    def stats(self) -> Dict[str, Any]:
        """One aggregated view over both services + SLO conformance.

        ``parse``/``stream`` are the raw service stats (present once the
        corresponding service has been touched); ``metrics`` is the
        registry snapshot — the counter/gauge/histogram source of truth the
        service dicts are views over; ``slo.buckets``
        grades every observed bucket against the config targets
        (``p50_ok``/``p99_ok`` appear only when targets are set);
        ``speculation`` (sparse backend only, else None) reports the carried
        product rows S vs ℓp and the per-bucket observed feasible-start
        widths (mean/max over parses); ``analysis`` is the static analyzer's
        report (``repro.analyze``: width bounds, ambiguity verdict, density,
        per-backend cost model, recommended backend), computed lazily and
        memoized — the typed ``AnalysisReport`` is on ``Parser.analysis``.
        """
        slo = self.config.slo
        # evaluate each service's stats property ONCE: it rebuilds the full
        # dict (queue scan + percentile windows), and two reads could even
        # disagree if the queue moves between them
        ps = self._parse_service.stats if self._parse_service is not None else None
        ss = self._stream_service.stats if self._stream_service is not None else None
        if self.backend_name == "sparse":
            speculation: Optional[Dict[str, Any]] = {
                "product_rows": int(self.engine.backend._width),
                "ell_pad": int(self.engine.tables.ell_pad),
                "depth": self.config.feasible_depth,
                "buckets": {b: dict(v) for b, v in self._spec_buckets.items()},
            }
        else:
            speculation = None
        return {
            "backend": self.backend_name,
            "compile_count": self.compile_count,
            "pending": (ps["pending"] if ps else 0) + (ss["pending"] if ss else 0),
            "parse": ps,
            "stream": ss,
            "metrics": self.obs.metrics.snapshot(),
            "analysis": self._analyze().to_dict(),
            "speculation": speculation,
            "slo": {
                "targets": dataclasses.asdict(slo) if slo is not None else None,
                "parse_buckets": self._slo_grade(ps["buckets"] if ps else {}),
                "stream_buckets": self._slo_grade(ss["buckets"] if ss else {}),
            },
        }

    def close(self) -> None:
        """Flush observability sinks (the JSONL span log, if configured)."""
        self.obs.close()

    def __enter__(self) -> "Parser":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# -------------------------------------------------------------------- fleet


class ParserFleet:
    """Many regexes, one engine pool: the multi-tenant facade.

        fleet = repro.ParserFleet({
            "errors":  "ERROR: .*",
            "api":     ParserConfig(regex="GET /[a-z]+", weight=2.0),
        })
        fleet.parse("errors", line).ok
        fleet.parse_batch([("errors", l1), ("api", l2), ...])

    Each tenant is a ``ParserConfig`` (or pattern string / config dict) —
    the same declarative surface as ``Parser`` — but instead of one engine
    per config, every tenant's transition tables are padded into a shared
    pow2 automaton bucket (``core/fleet.py``) and served by ONE
    tenant-batched device program per bucket: compile count and launch
    overhead scale with the number of (backend, ℓp-bucket) pairs, not
    tenants, while every result stays bit-identical to that tenant's solo
    ``Parser``.  Table builds go through a process-wide compile cache keyed
    on (normalized regex, backend, ℓp-bucket) — fleets, or re-added
    tenants, sharing a pattern never recompile it.

    Serving is the weighted-fair scheduler (``FleetParseService``): each
    tenant's ``ParserConfig.weight`` is its fair share, ``max_pending`` its
    private queue budget, ``slo`` its own grading targets in ``stats()``.
    """

    def __init__(
        self,
        tenants: Optional[Mapping[str, Union[ParserConfig, str, Mapping[str, Any]]]] = None,
        *,
        max_batch: int = 32,
        max_pending: Optional[int] = None,
        obs: Union[ObsConfig, Mapping[str, Any], None] = None,
    ):
        from .core.fleet import FleetEngine
        from .serve.parse_service import FleetParseService

        if obs is not None and isinstance(obs, Mapping):
            obs = ObsConfig(**dict(obs))
        self.obs = ObsHandle.from_config(obs)
        self.engine = FleetEngine(obs=self.obs)
        self._service = FleetParseService._internal(
            self.engine, max_batch=max_batch, max_pending=max_pending
        )
        self._configs: Dict[str, ParserConfig] = {}
        # tenant -> backend actually served (backend="auto" resolved)
        self._backends: Dict[str, str] = {}
        for name, cfg in (tenants or {}).items():
            self.add(name, cfg)

    # ---------------------------------------------------------------- tenants

    def add(
        self,
        name: str,
        config: Union[ParserConfig, str, Mapping[str, Any]],
        *,
        matrices: Optional[ParserMatrices] = None,
    ) -> "ParserFleet":
        """Register a tenant (chainable).  ``matrices`` bypasses the regex
        compile path for pre-built tables (``Parser.from_matrices`` analog)."""
        from .core.fleet import TenantSpec

        if isinstance(config, str):
            config = ParserConfig(regex=config)
        elif isinstance(config, Mapping):
            config = ParserConfig.from_dict(config)
        if not isinstance(config, ParserConfig):
            raise TypeError(
                f"fleet tenant config must be a ParserConfig, pattern string, "
                f"or config dict; got {type(config).__name__}"
            )
        if config.mesh is not None:
            raise ValueError(
                "fleet tenants run on the shared single-device engine pool; "
                "mesh configs are not supported (use a dedicated Parser)"
            )
        # static analysis at admission (repro.analyze leg 1): same policy as
        # Parser construction, but the reject is an ADMISSION event — the
        # fleet keeps serving its other tenants
        if config.analyze != "off" and matrices is None:
            from .analyze.pattern import cached_report

            report = cached_report(
                config.regex, max(4, config.feasible_depth)
            )
            m = self.obs.metrics
            m.counter("analyzer_verdicts_total", verdict=report.verdict).inc()
            if report.verdict == "pathological":
                if config.analyze == "strict":
                    m.counter(
                        "admission_rejects_total",
                        service="fleet",
                        cause="pathological",
                    ).inc()
                    raise PathologicalPatternError(
                        f"fleet tenant {name!r}: pattern {config.regex!r} is "
                        "pathologically ambiguous (an iterator with a "
                        "nullable body admits unboundedly many parse trees "
                        'per text); analyze="strict" rejects it at admission',
                        pattern=config.regex,
                        ambiguity=report.ambiguity,
                    )
                warnings.warn(
                    f"repro: fleet tenant {name!r} pattern {config.regex!r} "
                    "is pathologically ambiguous — forest size is unbounded "
                    'per text (analyze="strict" rejects such tenants)',
                    UserWarning,
                    stacklevel=2,
                )
        spec = TenantSpec(
            regex=config.regex,
            backend=config.backend,
            kernel=config.kernel,
            feasible_depth=config.feasible_depth,
            n_chunks=config.n_chunks,
            min_chunk_len=config.min_chunk_len,
            weight=config.weight,
            max_pending=config.max_pending,
        )
        self._service.add_tenant(name, spec, matrices=matrices)
        self._configs[name] = config
        # the engine resolves backend="auto" (core/fleet.py) — record what
        # this tenant actually runs on for stats()/results
        self._backends[name] = self.engine.tenant(name).spec.backend
        return self

    @property
    def tenants(self) -> Dict[str, ParserConfig]:
        return dict(self._configs)

    def config_of(self, tenant: str) -> ParserConfig:
        try:
            return self._configs[tenant]
        except KeyError:
            raise KeyError(f"unknown fleet tenant {tenant!r}") from None

    def groups_of(self, tenant: str) -> List[int]:
        """Numbered group ids of one tenant's pattern (``Parser.groups``
        analog), usable with ``ParseResult.matches``."""
        from .core.numbering import OPEN, OP_GROUP

        table = self.engine.tenant(tenant).tables.matrices.table
        return sorted(
            {
                s.num
                for s in table.numbered.symbols
                if s.kind == OPEN and s.op == OP_GROUP
            }
        )

    # ------------------------------------------------------------------ parse

    def _default_deadline_s(self, tenant: str) -> Optional[float]:
        slo = self.config_of(tenant).slo
        return slo.default_deadline_s if slo is not None else None

    def submit(
        self, tenant: str, text, *, deadline_s: Optional[float] = None
    ) -> ParseTicket:
        """Deadline-aware asynchronous submission for one tenant — the same
        admission contract as ``Parser.submit`` plus the tenant's own
        ``max_pending`` budget (``BudgetExceeded``)."""
        if deadline_s is None:
            deadline_s = self._default_deadline_s(tenant)
        req = self._service.submit_request(
            text, deadline_s=deadline_s, tenant=tenant
        )
        return ParseTicket(self, self._service, req, deadline_s=deadline_s)

    def parse(
        self, tenant: str, text, *, deadline_s: Optional[float] = None
    ) -> ParseResult:
        """Parse one text under one tenant's automaton (sync)."""
        return self.submit(tenant, text, deadline_s=deadline_s).result()

    def parse_batch(
        self,
        items: Sequence[Tuple[str, Any]],
        *,
        deadline_s: Optional[float] = None,
    ) -> List[ParseResult]:
        """Parse ``[(tenant, text), ...]``; results in input order.

        Same-bucket requests — across tenants — share one tenant-batched
        device program per step.  Admission is all-or-nothing, as in
        ``Parser.parse_batch``.
        """
        tickets: List[ParseTicket] = []
        try:
            for tenant, text in items:
                tickets.append(self.submit(tenant, text, deadline_s=deadline_s))
        except Exception:
            for ticket in tickets:
                ticket.cancel()
            raise
        return [t.result() for t in tickets]

    def _wrap(
        self,
        slpf: SLPF,
        *,
        bucket=None,
        latency_s: Optional[float] = None,
        trace_id: Optional[str] = None,
        root_span_id: Optional[str] = None,  # ticket plumbing; the fleet
                                             # observes no speculation
        tenant: Optional[str] = None,
    ) -> ParseResult:
        cfg = self._configs.get(tenant) if tenant is not None else None
        backend = self._backends.get(tenant) if tenant is not None else None
        return ParseResult(
            forest=slpf,
            backend=backend if backend is not None else "fleet",
            bucket=bucket,
            latency_s=latency_s,
            n_chunks=cfg.n_chunks if cfg is not None else None,
            speculation=None,
            trace_id=trace_id,
        )

    # ------------------------------------------------------------------ stats

    @property
    def compile_count(self) -> int:
        """Device programs compiled fleet-wide — O(#buckets × shapes),
        independent of the tenant count."""
        return self.engine.compile_count

    def stats(self) -> Dict[str, Any]:
        """The fleet-wide serving view.

        ``tenants`` carries each tenant's weighted-fair and latency state
        plus an SLO grade against ITS config targets; ``fleet`` reports the
        bucket economy (tenants per automaton bucket, compile count,
        process-wide table-cache state) — the number that should stay flat
        as tenants multiply.
        """
        from .core.fleet import table_cache_stats

        s = self._service.stats
        tenants: Dict[str, Any] = {}
        for name, d in s["tenants"].items():
            cfg = self._configs.get(name)
            grade: Dict[str, Any] = {
                "p50_s": d["p50_latency_s"],
                "p99_s": d["p99_latency_s"],
            }
            slo = cfg.slo if cfg is not None else None
            if slo is not None and slo.p50_s is not None:
                grade["p50_ok"] = d["p50_latency_s"] <= slo.p50_s
            if slo is not None and slo.p99_s is not None:
                grade["p99_ok"] = d["p99_latency_s"] <= slo.p99_s
            tenants[name] = {
                **d,
                "backend": self._backends.get(name),
                "slo": grade,
            }
        return {
            "backend": "fleet",
            "pending": s["pending"],
            "peak_queue_depth": s["peak_queue_depth"],
            "batches_run": s["batches_run"],
            "compile_count": self.compile_count,
            "buckets": s["buckets"],
            "tenants": tenants,
            "fleet": {
                "n_tenants": len(self._configs),
                "n_buckets": self.engine.n_buckets,
                "bucket_sizes": {
                    "|".join(map(str, k)): v
                    for k, v in sorted(self.engine.bucket_sizes().items())
                },
                "table_cache": table_cache_stats(),
            },
            "metrics": self.obs.metrics.snapshot(),
        }

    def close(self) -> None:
        """Flush observability sinks (the JSONL span log, if configured)."""
        self.obs.close()

    def __enter__(self) -> "ParserFleet":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


__all__ = [
    "AdmissionError",
    "BudgetExceeded",
    "ObsConfig",
    "ParseError",
    "ParseResult",
    "ParseTicket",
    "Parser",
    "ParserBackend",
    "ParserConfig",
    "ParserFleet",
    "ParserStream",
    "PathologicalPatternError",
    "SLOTargets",
    "SLPF",
    "SessionNotFound",
    "get_backend",
    "list_backends",
    "register_backend",
]
