"""Compiled-program lint: invariants every jitted phase program must hold.

The fleet shares its jitted programs across tenants (one per shape bucket —
that is the whole compile-count story), so one rotted program slows every
tenant on its bucket.  Three classes of rot have bitten jax codebases of this
shape, all detectable statically from the jaxpr / optimized HLO without
running a single batch:

  host-callback   a ``pure_callback`` / ``io_callback`` / debug print left
                  inside a jitted phase body forces a device→host round trip
                  per invocation — instrumentation must stay at trace time
                  (the engine's ``notify()`` pattern) or on the host side of
                  the phase seams.
  f64             a stray float64 / complex128 promotion (x64 mode leaking
                  in, a numpy scalar widening a weak type) doubles reach's
                  bytes and halves MXU throughput.
  dynamic-shape   a non-static dimension breaks the shape-bucketing contract
                  (programs are compiled per (c, k) bucket; dynamic dims
                  would recompile per input or fall off the fast path).

``lint_engine`` walks every phase program of an engine at given buckets,
linting both the traced jaxpr (recursively through pjit/scan/cond
sub-jaxprs) and the backend-compiled optimized HLO text, and returns typed
``LintFinding``s.  ``scripts/analyze_gate.py`` runs it over every registered
backend and fails CI on any finding; its seeded self-tests push known-bad
programs through the same functions so the gate itself cannot rot.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, Iterator, List, Sequence, Tuple

_BAD_DTYPES = ("float64", "complex128")

#: substrings of HLO custom-call lines that indicate a host round trip
_HLO_CALLBACK_MARKERS = ("callback", "outside_compilation", "host_compute")


@dataclasses.dataclass(frozen=True)
class LintFinding:
    """One violated invariant in one compiled program."""

    rule: str      # "host-callback" | "f64" | "dynamic-shape"
    program: str   # e.g. "packed:reach@4x32"
    detail: str

    def __str__(self) -> str:
        return f"[{self.rule}] {self.program}: {self.detail}"


# ------------------------------------------------------------- jaxpr walk


def _sub_jaxprs(params: Dict[str, Any]) -> Iterator[Any]:
    """Yield every inner Jaxpr hiding in an eqn's params (pjit's ``jaxpr``,
    scan/while bodies, cond ``branches``, custom_jvp ``call_jaxpr`` …) —
    duck-typed so it tracks jax versions."""
    for v in params.values():
        items = v if isinstance(v, (tuple, list)) else (v,)
        for item in items:
            if hasattr(item, "eqns"):          # raw Jaxpr
                yield item
            elif hasattr(item, "jaxpr"):       # ClosedJaxpr
                yield item.jaxpr


def _walk_eqns(jaxpr) -> Iterator[Any]:
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from _walk_eqns(sub)


def lint_jaxpr(closed_jaxpr, program: str) -> List[LintFinding]:
    """Lint one traced program (a ClosedJaxpr) against all three rules."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    findings: List[LintFinding] = []

    def check_aval(aval, where: str) -> None:
        dtype = getattr(aval, "dtype", None)
        if dtype is not None and str(dtype) in _BAD_DTYPES:
            findings.append(
                LintFinding("f64", program, f"{where} has dtype {dtype}")
            )
        for dim in getattr(aval, "shape", ()):
            if not isinstance(dim, int):
                findings.append(
                    LintFinding(
                        "dynamic-shape",
                        program,
                        f"{where} has non-static dim {dim!r}",
                    )
                )

    for var in jaxpr.invars + jaxpr.outvars:
        check_aval(getattr(var, "aval", None) or var, "program boundary")

    for eqn in _walk_eqns(jaxpr):
        name = eqn.primitive.name
        if "callback" in name or name in ("outside_call", "host_call"):
            findings.append(
                LintFinding(
                    "host-callback",
                    program,
                    f"primitive '{name}' runs on the host inside the jitted body",
                )
            )
        for var in eqn.outvars:
            check_aval(getattr(var, "aval", None), f"'{name}' output")
    return findings


# --------------------------------------------------------------- HLO scan


def lint_hlo_text(hlo_text: str, program: str) -> List[LintFinding]:
    """Lint optimized HLO text: catches promotions the compiler *kept* (a
    jaxpr-level f64 constant-folded away is fine; one surviving to HLO is
    real bytes) and host custom-calls that entered below the jaxpr level."""
    findings: List[LintFinding] = []
    for lineno, line in enumerate(hlo_text.splitlines(), 1):
        if "f64[" in line or "c128[" in line:
            findings.append(
                LintFinding(
                    "f64", program, f"HLO line {lineno}: {line.strip()[:120]}"
                )
            )
        if "custom-call" in line and any(
            marker in line for marker in _HLO_CALLBACK_MARKERS
        ):
            findings.append(
                LintFinding(
                    "host-callback",
                    program,
                    f"HLO line {lineno}: {line.strip()[:120]}",
                )
            )
    return findings


# ------------------------------------------------------------ engine lint


def _phase_programs(engine, c: int, k: int):
    """The engine's separately-jitted phase programs with abstract args at
    bucket (c, k), ready to lower."""
    import jax
    import jax.numpy as jnp

    t = engine.tables
    eye = engine.backend.identity_product(t.ell_pad, dtype=t.N.dtype)
    chunks_sds = jax.ShapeDtypeStruct((c, k), jnp.int32)
    P_sds = jax.ShapeDtypeStruct((c,) + eye.shape, eye.dtype)
    J_sds = jax.ShapeDtypeStruct((c, t.ell_pad), jnp.float32)
    phases = engine.phases
    return {
        "reach": (phases.reach, (t.N, chunks_sds)),
        "join": (phases.join, (P_sds, t.I, t.F)),
        "build_merge": (phases.build_merge, (t.N, chunks_sds, J_sds, J_sds)),
    }


def lint_program(prog, args: Tuple, program: str) -> List[LintFinding]:
    """Lint one jittable callable at abstract args: jaxpr walk + compiled
    optimized-HLO scan.  ``args`` may mix concrete arrays and
    ``ShapeDtypeStruct``s (anything ``.lower`` accepts)."""
    import jax

    findings = lint_jaxpr(jax.make_jaxpr(prog)(*args), program)
    findings += lint_hlo_text(prog.lower(*args).compile().as_text(), program)
    return findings


def lint_engine(
    engine,
    buckets: Sequence[Tuple[int, int]] = ((4, 32),),
    label: str = "",
) -> List[LintFinding]:
    """Lint every phase program of one engine at the given (c, k) buckets.

    Programs are named ``<label>:<phase>@<c>x<k>``.  Each novel bucket costs
    one trace + compile per phase (the same programs real traffic at that
    bucket would compile anyway — jit caches by shape, so a warm engine
    pays nothing extra).
    """
    findings: List[LintFinding] = []
    for c, k in buckets:
        for phase, (prog, args) in _phase_programs(engine, int(c), int(k)).items():
            findings += lint_program(prog, args, f"{label}:{phase}@{c}x{k}")
    return findings


def lint_report(findings: Iterable[LintFinding]) -> str:
    """Human-readable multi-line summary (empty string when clean)."""
    return "\n".join(str(f) for f in findings)
