"""repro.analyze: static diagnostics, auto backend, admission policy, lint.

Four clusters:

  * pattern leg — ambiguity verdicts on hand-written + REgen fixtures, the
    static feasible-start width bounds validated against the widths the
    sparse backend actually observes (bound >= observed; the carried pow2
    bucket is the tightest one over the depth-1 bound), density/cost sanity,
    and the hardcoded lane-pad mirror staying true to ``core/backend.py``;
  * facade policy — ``analyze="off"|"warn"|"strict"`` at ``Parser``
    construction and ``ParserFleet.add``, the typed
    ``PathologicalPatternError``, the service-level pattern guard, and
    ``stats()["analysis"]``;
  * ``backend="auto"`` — resolves to a registered backend and parses
    bit-identically to that backend named explicitly, solo and in a fleet;
  * program leg — every registered backend's compiled phase programs lint
    clean; seeded f64 / host-callback / dynamic-shape violations are caught.
"""

import subprocess
import sys
import warnings
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parents[1] / "src"))

import repro
from repro.analyze import (
    AnalysisReport,
    analyze_matrices,
    analyze_pattern,
    backend_cost_model,
    choose_backend,
    feasible_width_bounds,
    lint_engine,
    lint_hlo_text,
    lint_jaxpr,
    lint_program,
    sparse_width_bucket,
)
from repro.analyze.pattern import _MIN_LANE_PAD
from repro.core.backend import _BACKENDS, get_backend
from repro.core.matrices import build_matrices, feasible_start_widths
from repro.core.numbering import number_regex
from repro.core.segments import compute_segments
from repro.data.regen import random_regex, sample_string
from repro.errors import ParseError, PathologicalPatternError

PATHOLOGICAL = ["(a*)*", "(a?)+", "(a*)+", "((a|b)*)*"]
# "x(yz|y)*z?" is genuinely ambiguous: "xyz" parses as x·(yz) or x·(y)·z
FINITE = ["a|a", "(a|b|ab)+", "(ab|ba|abba)+", "x(yz|y)*z?"]
UNAMBIGUOUS = ["abc", "a*b", "(ab|a)*", "(a|b)*abb"]
WIDTH_SEEDS = [11, 23, 47, 101]


# ------------------------------------------------------------ pattern leg


@pytest.mark.parametrize("pattern", PATHOLOGICAL)
def test_pathological_fixtures(pattern):
    r = analyze_pattern(pattern)
    assert r.ambiguity == "pathological"
    assert r.verdict == "pathological"


@pytest.mark.parametrize("pattern", FINITE)
def test_finitely_ambiguous_fixtures(pattern):
    r = analyze_pattern(pattern)
    assert r.ambiguity == "finite"
    assert r.verdict == "ok"


@pytest.mark.parametrize("pattern", UNAMBIGUOUS)
def test_unambiguous_fixtures(pattern):
    r = analyze_pattern(pattern)
    assert r.ambiguity == "unambiguous"
    assert r.ambiguity_exact
    assert r.verdict == "ok"


def test_regen_corpus_analyzes():
    """Every REgen pattern gets a definite, internally consistent report."""
    for seed in WIDTH_SEEDS:
        rng = np.random.Generator(np.random.Philox(seed))
        ast = random_regex(7, rng)
        m = build_matrices(compute_segments(number_regex(ast)))
        r = analyze_matrices(m)
        assert r.ambiguity in ("unambiguous", "finite", "pathological")
        assert r.recommended_backend in ("jnp", "packed", "sparse")
        assert len(r.width_bounds) >= 1 and r.width_bounds[0] <= r.ell_pad
        # bounds shrink (or hold) with depth: deeper prefixes prune harder
        assert all(
            a >= b for a, b in zip(r.width_bounds, r.width_bounds[1:])
        )


def test_report_schema_round_trips():
    import json

    d = analyze_pattern("(a|b|ab)+").to_dict()
    json.dumps(d)  # JSON-able end to end
    for key in (
        "pattern", "ell", "ell_pad", "n_classes", "nullable", "ambiguity",
        "ambiguity_exact", "width_bounds", "width_exact", "width_bucket",
        "density", "cost", "recommended_backend", "verdict",
    ):
        assert key in d, f"stats()['analysis'] schema lost {key!r}"
    assert set(d["cost"]) == {"jnp", "pallas", "packed", "sparse"}


def _spec_parser(pattern_or_matrices, depth, n_chunks=4):
    cfg = repro.ParserConfig(
        regex="placeholder", backend="sparse", feasible_depth=depth,
        n_chunks=n_chunks, analyze="off",
    )
    if isinstance(pattern_or_matrices, str):
        return repro.Parser(cfg.replace(regex=pattern_or_matrices))
    return repro.Parser.from_matrices(
        pattern_or_matrices, cfg.replace(regex="<prebuilt>")
    )


def _corpus_text(ast_or_pattern, rng, n_chars):
    """A text of EXACTLY n_chars drawn from the pattern's language samples
    (full chunks: every chunk's leading chars are real, so the per-depth
    bounds apply to what the backend observes)."""
    from repro.core import regex as rx

    node = (
        rx.parse_regex(ast_or_pattern)
        if isinstance(ast_or_pattern, str)
        else ast_or_pattern
    )
    text = b""
    for _ in range(64):
        text += sample_string(node, rng, max_rep=3) or b"a"
        if len(text) >= n_chars:
            break
    return (text + b"a" * n_chars)[:n_chars]


@pytest.mark.parametrize("key", UNAMBIGUOUS + FINITE + [f"seed:{s}" for s in WIDTH_SEEDS])
@pytest.mark.parametrize("depth", [1, 2])
def test_static_width_bound_vs_observed(key, depth):
    """The acceptance check: static bound >= every observed speculation
    width, and the pow2 bucket the backend carries is the tightest bucket
    over the depth-1 bound (tight within one pow2 step by construction)."""
    rng = np.random.Generator(np.random.Philox(abs(hash(key)) % 2**31))
    if key.startswith("seed:"):
        ast = random_regex(7, np.random.Generator(np.random.Philox(int(key[5:]))))
        m = build_matrices(compute_segments(number_regex(ast)))
        p = _spec_parser(m, depth)
        report = analyze_matrices(m, depth=depth)
        sample_src = ast
    else:
        p = _spec_parser(key, depth)
        report = analyze_pattern(key, depth=depth)
        sample_src = key
    c, k = p.engine.bucket_shape(1, 4)[0], None  # c fixed by config
    # full chunks: text length = c * k for the smallest bucket
    c = 4
    k = p.engine.bucket_shape(c * p.config.min_chunk_len, c)[1]
    n = c * k
    observed = []
    for _ in range(6):
        res = p.parse(_corpus_text(sample_src, rng, n))
        spec = res.speculation
        assert spec is not None and spec["depth"] == depth
        observed.append(spec["width_max"])
    bound = report.width_bounds[depth - 1]
    assert max(observed) <= bound, (
        f"{key}@d{depth}: observed width {max(observed)} exceeds the "
        f"static bound {bound}"
    )
    # the backend's carried product rows = bucket(depth-1 bound): tightest
    # pow2 over the bound (within one bucket of any observed width)
    carried = int(p.engine.backend._width)
    assert carried == sparse_width_bucket(
        report.width_bounds[0], report.ell_pad
    )
    if carried < report.ell_pad:  # reduced: pow2-tight over the bound
        assert carried < 2 * max(report.width_bounds[0], 8)


def test_width_bounds_match_runtime_fold():
    """The static frontier and the runtime per-chunk fold agree exactly when
    every class sequence of the text is enumerated at depth 1."""
    m = build_matrices(compute_segments("(a|b|ab)+"))
    N = np.asarray(m.N)
    bounds, exact = feasible_width_bounds(N, 1)
    assert exact
    n_real = N.shape[0] - 1
    widths = []
    for a in range(n_real):
        chunk = np.array([[a]], dtype=np.int64)
        w = feasible_start_widths(N, chunk, depth=1)
        widths.append(int(w[0]))
    assert bounds[0] == max(widths)


def test_min_lane_pad_mirror_matches_backends():
    """The analyzer's jax-free lane-pad table must track core/backend.py."""
    for name, lane in _MIN_LANE_PAD.items():
        assert get_backend(name).min_lane_pad == lane, (
            f"analyze/pattern.py's _MIN_LANE_PAD[{name!r}]={lane} no longer "
            "matches the real backend — update the mirror"
        )
    assert set(_MIN_LANE_PAD) == set(_BACKENDS)


def test_cost_model_prefers_reduction():
    """A width-reduced automaton models sparse fastest; unreduced never
    recommends sparse; pallas is never auto-picked."""
    cost = backend_cost_model(40, width_bucket_32=4)
    assert choose_backend(cost, reduced=True) == "sparse"
    assert choose_backend(cost, reduced=False) in ("packed", "jnp")
    for ell in (8, 40, 200, 1000):
        for w in (2, 16, 200):
            c = backend_cost_model(ell, w)
            assert choose_backend(c, reduced=True) != "pallas"
            for name in ("jnp", "pallas", "packed", "sparse"):
                assert c[name]["t_total"] > 0


def test_density_profile_bounds():
    r = analyze_pattern("(a|b|ab)+")
    d = r.density
    assert 0.0 < d["class_mean"] <= d["class_max"] <= 1.0
    assert d["union"] <= d["saturation"] <= 1.0


# -------------------------------------------------------- facade policy


def test_strict_rejects_pathological_at_construction():
    with pytest.raises(PathologicalPatternError) as ei:
        repro.Parser(repro.ParserConfig(regex="(a*)*", analyze="strict"))
    err = ei.value
    assert err.pattern == "(a*)*"
    assert err.ambiguity == "pathological"
    assert isinstance(err, ValueError) and isinstance(err, ParseError)


def test_warn_mode_warns_and_serves():
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        p = repro.Parser("(a?)+")  # analyze="warn" is the default
    assert any(
        issubclass(w.category, UserWarning) and "pathologically" in str(w.message)
        for w in caught
    )
    assert p.parse("aa").ok  # pathological != broken; warn still serves


def test_off_mode_skips_construction_analysis():
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        p = repro.Parser(repro.ParserConfig(regex="(a*)*", analyze="off"))
    assert not any(issubclass(w.category, UserWarning) for w in caught)
    # stats() still computes the report lazily
    assert p.stats()["analysis"]["verdict"] == "pathological"


def test_analyze_knob_validated():
    with pytest.raises(ValueError, match="analyze"):
        repro.ParserConfig(regex="ab", analyze="loud")


def test_config_round_trips_new_fields():
    cfg = repro.ParserConfig(regex="(a|b)+", backend="auto", analyze="strict")
    assert repro.ParserConfig.from_dict(cfg.to_dict()) == cfg


def test_fleet_strict_rejects_and_keeps_serving():
    fleet = repro.ParserFleet({"good": "(a|b|ab)+"})
    with pytest.raises(PathologicalPatternError):
        fleet.add("bad", repro.ParserConfig(regex="(a*)*", analyze="strict"))
    assert sorted(fleet.tenants) == ["good"]
    assert fleet.parse("good", "ab").ok  # rejection is per tenant


def test_service_pattern_guard_blocks_admission():
    p = repro.Parser(repro.ParserConfig(regex="(a|b|ab)+", analyze="warn"))
    svc = p.parse_service
    svc.set_pattern_guard("pathological", "strict")
    with pytest.raises(PathologicalPatternError):
        p.parse("ab")
    svc.set_pattern_guard("pathological", "warn")  # non-strict: serves
    assert p.parse("ab").ok
    ss = p.stream_service
    ss.set_pattern_guard("pathological", "strict")
    sid = ss.open()
    with pytest.raises(PathologicalPatternError):
        ss.append(sid, "ab")


def test_analysis_report_on_parser_and_metrics():
    p = repro.Parser(repro.ParserConfig(regex="(a|b|ab)+"))
    assert isinstance(p.analysis, AnalysisReport)
    s = p.stats()
    assert s["analysis"]["verdict"] == "ok"
    from repro.obs import validate_metric_names

    snap = s["metrics"]
    validate_metric_names(snap)
    flat = {str(k): v for k, v in snap.items()}
    assert flat["analyzer_verdicts_total"][0]["labels"]["verdict"] == "ok"


# ------------------------------------------------------- backend="auto"


def test_auto_backend_bit_identical():
    """Acceptance: auto parses bit-identically to its selected backend
    across the conformance corpus patterns."""
    rng = np.random.Generator(np.random.Philox(7))
    for pattern in UNAMBIGUOUS + FINITE:
        auto = repro.Parser(repro.ParserConfig(
            regex=pattern, backend="auto", n_chunks=4, analyze="off",
        ))
        chosen = auto.backend_name
        assert chosen in repro.list_backends()
        explicit = repro.Parser(repro.ParserConfig(
            regex=pattern, backend=chosen, n_chunks=4, analyze="off",
        ))
        for _ in range(4):
            text = _corpus_text(pattern, rng, int(rng.integers(1, 24)))
            fa = auto.parse(text).forest
            fe = explicit.parse(text).forest
            assert np.array_equal(fa.columns, fe.columns)
            assert np.array_equal(fa.classes, fe.classes)
            assert fa.count_trees() == fe.count_trees()


def test_auto_backend_in_fleet_bit_identical():
    fleet = repro.ParserFleet({
        "auto": repro.ParserConfig(regex="(a|b|ab)+", backend="auto"),
    })
    resolved = fleet.stats()["tenants"]["auto"]["backend"]
    assert resolved in repro.list_backends()
    fleet.add("explicit", repro.ParserConfig(regex="(a|b|ab)+", backend=resolved))
    for text in ("abab", "ba", "abba" * 3):
        ra = fleet.parse("auto", text)
        re_ = fleet.parse("explicit", text)
        assert ra.backend == resolved
        assert np.array_equal(ra.forest.columns, re_.forest.columns)


def test_auto_validation_rules():
    with pytest.raises(ValueError, match="kernel"):
        repro.ParserConfig(regex="ab", backend="auto", kernel=True)
    repro.ParserConfig(regex="ab", backend="auto", feasible_depth=2)  # ok
    with pytest.raises(ValueError, match="auto"):
        repro.ParserConfig(regex="ab", backend="auto").build_backend()


# ---------------------------------------------------------- program leg


@pytest.mark.parametrize("backend", sorted(_BACKENDS))
def test_phase_programs_lint_clean(backend):
    p = repro.Parser(repro.ParserConfig(
        regex="(a|b|ab)+", backend=backend, analyze="off",
    ))
    findings = lint_engine(p.engine, buckets=((4, 32),), label=backend)
    assert findings == [], [str(f) for f in findings]


def test_lint_catches_seeded_f64():
    import jax
    import jax.numpy as jnp

    with jax.enable_x64(True):
        prog = jax.jit(lambda x: x.astype(jnp.float64) * 2.0)
        findings = lint_program(
            prog, (jax.ShapeDtypeStruct((4, 4), jnp.float32),), "t:f64"
        )
    assert "f64" in {f.rule for f in findings}
    assert all(f.program == "t:f64" for f in findings)


def test_lint_catches_seeded_callback():
    import jax
    import jax.numpy as jnp

    def cb(x):
        return jax.pure_callback(
            lambda a: np.asarray(a), jax.ShapeDtypeStruct((4,), jnp.float32), x
        )

    findings = lint_jaxpr(jax.make_jaxpr(jax.jit(cb))(jnp.ones(4)), "t:cb")
    assert "host-callback" in {f.rule for f in findings}


def test_lint_hlo_text_scans():
    bad = "  %x.1 = f64[4,4]{1,0} convert(%p.0)\n"
    assert {f.rule for f in lint_hlo_text(bad, "t")} == {"f64"}
    cb = '  %y = f32[4]{0} custom-call(%p), custom_call_target="xla_ffi_python_cpu_callback"\n'
    assert {f.rule for f in lint_hlo_text(cb, "t")} == {"host-callback"}
    assert lint_hlo_text("  %z = f32[4]{0} add(%a, %b)\n", "t") == []


# --------------------------------------------------------------- compat


def test_launch_analysis_reexports_roofline():
    from repro.analyze import roofline
    from repro.launch import analysis

    assert analysis.Roofline is roofline.Roofline
    assert analysis.PEAK_FLOPS == roofline.PEAK_FLOPS
    assert analysis.analyze_compiled is roofline.analyze_compiled
    assert analysis.collective_bytes is roofline.collective_bytes


def test_bench_trend_new_gate_is_informational(tmp_path):
    """A BENCH file absent at --base reports as a new gate, exit 0."""
    repo_root = Path(__file__).parents[1]
    target = repo_root / "BENCH_analyze_selftest_newgate.json"
    target.write_text(
        '{"name": "selftest", "timestamp": "2026-01-01T00:00:00", '
        '"config": {}, "metrics": {"rows": [{"name": "throughput_x", '
        '"value": 123.0, "derived": "texts/s"}]}}'
    )
    try:
        proc = subprocess.run(
            [sys.executable, "scripts/bench_trend.py", "--base", "HEAD"],
            cwd=repo_root, capture_output=True, text=True,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "new gate" in proc.stdout
        assert "123.0" in proc.stdout
    finally:
        target.unlink()
