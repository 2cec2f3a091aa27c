"""CI static-analysis gate: lint every compiled phase program, prove the
lint can still catch violations, and hold the analyzer's public promises.

    PYTHONPATH=src python scripts/analyze_gate.py

Four checks:

  1. program lint (``repro.analyze.program``) over EVERY registered backend
     at two (c, k) buckets — no host callbacks inside jitted phase bodies,
     no f64/c128 promotion, no dynamic shapes.  Any finding fails the gate
     with the offending program named.
  2. seeded-violation self-tests — a throwaway program with an injected f64
     promotion and one with an injected ``pure_callback`` MUST be flagged;
     if either slips through, the lint itself has rotted and the gate fails.
  3. fleet compile economy: tenants over few automaton buckets compile
     O(#buckets) programs, never O(#tenants) — the invariant the shared
     jitted programs exist to provide.
  4. ``backend="auto"`` resolution is sound: the analyzer picks a registered
     backend and the auto parser's forest is bit-identical to the same
     config with the chosen backend named explicitly.

Exits non-zero on the first violated invariant, printing which one.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parents[1] / "src"))

import numpy as np

import repro
from repro.analyze import lint_engine, lint_jaxpr, lint_program, lint_report

GATE_PATTERN = "(a|b|ab)+"
GATE_BUCKETS = ((4, 32), (8, 32))


def check_backends() -> None:
    for backend in repro.list_backends():
        p = repro.Parser(repro.ParserConfig(regex=GATE_PATTERN, backend=backend))
        findings = lint_engine(p.engine, buckets=GATE_BUCKETS, label=backend)
        assert not findings, (
            f"{backend}: compiled phase programs violate lint invariants:\n"
            + lint_report(findings)
        )
        n = len(GATE_BUCKETS) * 3
        print(f"ok: {backend:7s} — {n} phase programs clean at "
              f"{'/'.join(f'{c}x{k}' for c, k in GATE_BUCKETS)}")


def check_seeded_violations() -> None:
    import jax
    import jax.numpy as jnp

    # f64 promotion: must surface in BOTH the jaxpr walk and the HLO scan
    with jax.enable_x64(True):
        prog = jax.jit(lambda x: x.astype(jnp.float64) * 2.0)
        args = (jax.ShapeDtypeStruct((8, 8), jnp.float32),)
        findings = lint_program(prog, args, "selftest:f64")
    rules = {f.rule for f in findings}
    assert "f64" in rules, (
        "seeded f64 promotion was NOT caught — the lint has rotted "
        f"(findings: {lint_report(findings) or 'none'})"
    )
    print(f"ok: selftest — seeded f64 promotion caught "
          f"({len(findings)} findings)")

    # host callback: must surface in the jaxpr walk
    def cb(x):
        return jax.pure_callback(
            lambda a: np.asarray(a), jax.ShapeDtypeStruct((8,), jnp.float32), x
        )

    findings = lint_jaxpr(jax.make_jaxpr(jax.jit(cb))(jnp.ones(8)), "selftest:cb")
    rules = {f.rule for f in findings}
    assert "host-callback" in rules, (
        "seeded pure_callback was NOT caught — the lint has rotted "
        f"(findings: {lint_report(findings) or 'none'})"
    )
    print(f"ok: selftest — seeded host callback caught "
          f"({len(findings)} findings)")


def check_fleet_compile_economy() -> None:
    from repro.core.fleet import clear_table_cache

    clear_table_cache()
    tenants = {
        f"t{i}": repro.ParserConfig(regex="(a|b)*abb", n_chunks=4)
        for i in range(5)
    }
    tenants["sp"] = repro.ParserConfig(
        regex="(a|b)*abb", backend="sparse", n_chunks=4
    )
    with repro.ParserFleet(tenants) as fleet:
        fleet.parse_batch([(tid, "ababb") for tid in tenants])
        n_buckets = fleet.engine.n_buckets
        assert fleet.compile_count == n_buckets, (
            f"fleet compiled {fleet.compile_count} programs for {n_buckets} "
            f"buckets over {len(tenants)} tenants — compile count must be "
            "O(#buckets), not O(#tenants)"
        )
    print(f"ok: fleet   — {len(tenants)} tenants -> {n_buckets} buckets -> "
          f"{n_buckets} compiled programs")


def check_auto_backend() -> None:
    auto = repro.Parser(repro.ParserConfig(regex=GATE_PATTERN, backend="auto"))
    chosen = auto.backend_name
    assert chosen in repro.list_backends(), (
        f'backend="auto" resolved to unregistered backend {chosen!r}'
    )
    explicit = repro.Parser(
        repro.ParserConfig(regex=GATE_PATTERN, backend=chosen)
    )
    for text in ("abab" * 8, "ba" * 7, "a", "abba" * 5):
        fa = auto.parse(text).forest
        fe = explicit.parse(text).forest
        assert np.array_equal(fa.columns, fe.columns) and np.array_equal(
            fa.classes, fe.classes
        ), f'backend="auto" forest diverged from {chosen!r} on {text!r}'
    print(f'ok: auto    — resolves to {chosen!r}, bit-identical forests')


def main() -> None:
    check_backends()
    check_seeded_violations()
    check_fleet_compile_economy()
    check_auto_backend()
    print("analyze gate: all checks passed")


if __name__ == "__main__":
    main()
