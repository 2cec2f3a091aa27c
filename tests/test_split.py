"""Long chunks as sub-chains inside the packed and sparse backends.

``PackedBackend.reach`` and ``build_merge_packed`` cut every chunk of static
length k ≥ ``SPLIT_MIN_LEN`` into m = k / ``SUB_CHAIN_LEN`` contiguous
sub-chains: one scan over all c·m sub-chains, a sequential fold (reach) or
a forward and a backward act scan over the m sub-products (build&merge).
This file checks that the split is invisible in the answers, that the
program does not grow with m, and that the spans and counters say when it
ran:

  * bit-identity — the split against the serial parser and against the
    unsplit programs (products and packed columns), for both backends, below,
    at and above the threshold (two values of m), on texts that end
    mid-sub-chain, all-PAD sub-chains and the empty text; the fused and
    phase-split routes in process, the mesh routes on 8 virtual devices;
  * program size — the fused program's HLO at m and 4m, one trace per
    bucket, and an unchanged program below the threshold;
  * engagement — ``sub_chains`` on ``parse.device`` / ``phase.reach`` /
    ``phase.build_merge`` and ``split_dispatches_total`` per bucket.

Most cases shrink the threshold in-test (64 steps, sub-chains of 16) so the
CPU runs them in seconds; the ``real`` case and the size tests keep the
backend's own constants.
"""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax.numpy as jnp

import repro
import repro.core.backend as be
from repro.core.engine import ParserEngine
from repro.core.reference import ParallelArtifacts
from repro.core.serial import parse_serial_matrix

TRAFFIC_RE = r"((GET|POST|PUT) /([a-z0-9]|/)* ([0-9]{3}) (ok|err|-)\n)+"
SMALL_MIN_LEN, SMALL_SUB_LEN = 64, 16
OFF = 1 << 40                                 # no chunk is this long


def _traffic_text(n: int, seed: int = 2503) -> str:
    """The first n bytes of seeded TRAFFIC records (cut mid-record unless a
    record ends at n)."""
    rng = np.random.default_rng(seed)
    alpha = list("abcdefghijklmnopqrstuvwxyz0123456789/")
    parts, size = [], 0
    while size < n:
        path = "".join(rng.choice(alpha, size=int(rng.integers(0, 7))))
        rec = "%s /%s %03d %s\n" % (
            rng.choice(["GET", "POST", "PUT"]), path,
            int(rng.integers(0, 1000)), rng.choice(["ok", "err", "-"]),
        )
        parts.append(rec)
        size += len(rec)
    return "".join(parts)[:n]


@pytest.fixture(scope="module")
def art():
    return ParallelArtifacts.generate(TRAFFIC_RE)


def _set_split(monkeypatch, min_len: int, sub_len: int) -> None:
    monkeypatch.setattr(be, "SPLIT_MIN_LEN", min_len)
    monkeypatch.setattr(be, "SUB_CHAIN_LEN", sub_len)


# regime → (text length, n_chunks, min_chunk_len, shrunk constants?, m)
REGIMES = {
    "below": (100, 4, 8, True, 1),      # k = 32 < 64
    "at": (203, 4, 8, True, 4),         # k = 64: last chunk ends mid-sub-chain
    "above": (700, 4, 8, True, 16),     # k = 256: chunk 2 part PAD, chunk 3 all PAD
    "empty": (0, 4, 64, True, 4),       # k = 64 of PAD only
    "real": (10_000, 1, 8, False, 32),  # the backend's own constants: k = 16384
}
LOCAL_CASES = [
    (backend, route, regime)
    for backend in ("packed", "sparse")
    for route in ("fused", "phase_split")
    for regime in REGIMES
]
MESH_CASES = [(backend, "mesh", "all") for backend in ("packed", "sparse")]


def _split_bit_identity_mesh(backend: str) -> None:
    """The fused and phase-split mesh routes and the batched (data × pod)
    route on 8 virtual devices, split, against the serial parser."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, repro
import repro.core.backend as be
from repro.core.serial import parse_serial_matrix
assert len(jax.devices()) == 8
be.SPLIT_MIN_LEN, be.SUB_CHAIN_LEN = %d, %d
TEXT = %r
cfg = dict(regex=%r, backend=%r, n_chunks=8, mesh="host")
fused = repro.Parser(repro.ParserConfig(**cfg))
split = repro.Parser(repro.ParserConfig(obs={"enabled": True}, **cfg))
eng = fused.engine
seen = set()
for n in (0, 200, 450, 1500):            # k = 8, 32 (m 1); 64 (m 4); 256 (m 16)
    t = TEXT[:n]
    want = parse_serial_matrix(eng.matrices, t).columns
    k = eng.bucket_shape(n, 8)[1]
    m = eng.backend.sub_chains(k)
    seen.add(m)
    assert np.array_equal(fused.parse(t).forest.columns, want), n
    r = split.parse(t)
    assert np.array_equal(r.forest.columns, want), n
    spans = {s.name: s for s in split.obs.tracer.drain()}
    assert spans["phase.reach"].attrs["sub_chains"] == m, n
    assert spans["phase.build_merge"].attrs["sub_chains"] == m, n
pair = [TEXT[:1500], TEXT[7:1390]]
for got, t in zip(eng.parse_batch(pair, n_chunks=8), pair):
    assert np.array_equal(got.columns, parse_serial_matrix(eng.matrices, t).columns)
assert seen == {1, 4, 16}, seen
print("SPLIT-MESH-OK")
""" % (SMALL_MIN_LEN, SMALL_SUB_LEN, _traffic_text(1500), TRAFFIC_RE, backend)
    env = {"PYTHONPATH": str(Path(__file__).parents[1] / "src"), "PATH": "/usr/bin:/bin"}
    env.update({k: v for k, v in os.environ.items() if k not in env})
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SPLIT-MESH-OK" in out.stdout


def _route(eng: ParserEngine, route: str, text: str, n_chunks: int):
    if route == "fused":
        return eng.parse(text, n_chunks=n_chunks).pack()
    return eng.parse_phase_split(text, n_chunks=n_chunks).pack()


@pytest.mark.parametrize(
    "backend,route,regime", LOCAL_CASES + MESH_CASES,
    ids=["-".join(c) for c in LOCAL_CASES + MESH_CASES],
)
def test_split_bit_identity(art, monkeypatch, backend, route, regime):
    if route == "mesh":
        _split_bit_identity_mesh(backend)
        return
    n, n_chunks, min_chunk_len, shrunk, m_want = REGIMES[regime]
    text = _traffic_text(n)
    min_len, sub_len = (
        (SMALL_MIN_LEN, SMALL_SUB_LEN) if shrunk else (be.SPLIT_MIN_LEN, be.SUB_CHAIN_LEN)
    )

    def engine():
        return ParserEngine(art.matrices, backend=backend, min_chunk_len=min_chunk_len)

    # the unsplit programs, traced while the split is off
    _set_split(monkeypatch, OFF, sub_len)
    whole = engine()
    c, k = whole.bucket_shape(n, n_chunks)
    chunks = jnp.asarray(whole._pad_to(whole.classes_of_text(text), c, k))
    t = whole.tables
    want_cols = _route(whole, route, text, n_chunks)
    want_P = np.asarray(whole.phases.reach(t.N, chunks))
    assert whole.backend.sub_chains(k) == 1

    _set_split(monkeypatch, min_len, sub_len)
    split = engine()
    assert split.backend.sub_chains(k) == m_want
    got_cols = _route(split, route, text, n_chunks)
    got_P = np.asarray(split.phases.reach(t.N, chunks))

    assert np.array_equal(got_P, want_P)
    assert np.array_equal(got_cols, want_cols)
    serial = parse_serial_matrix(art.matrices, text).columns
    got = (split.parse(text, n_chunks=n_chunks) if route == "fused"
           else split.parse_phase_split(text, n_chunks=n_chunks))
    assert np.array_equal(got.columns, serial)


# ------------------------------------------------------------ program size


def _fused_hlo(eng: ParserEngine, c: int, k: int) -> str:
    return eng.lower_batch(1, c, k).as_text()


def test_split_program_size_does_not_grow_with_m():
    p = repro.Parser(repro.ParserConfig(regex=TRAFFIC_RE, backend="auto", n_chunks=1))
    eng = p.engine
    k1, k4 = be.SPLIT_MIN_LEN, 4 * be.SPLIT_MIN_LEN
    m1, m4 = eng.backend.sub_chains(k1), eng.backend.sub_chains(k4)
    assert m1 > 1 and m4 == 4 * m1
    small, large = len(_fused_hlo(eng, 1, k1)), len(_fused_hlo(eng, 1, k4))
    assert abs(large - small) < 0.10 * small, (small, large)
    # no associative scan, no unrolled sub-chain loop: a fixed set of loops
    text = _fused_hlo(eng, 1, k4)
    assert text.count("stablehlo.while") == _fused_hlo(eng, 1, k1).count("stablehlo.while")

    p.close()


def test_split_parse_traces_one_program_per_bucket():
    p = repro.Parser(repro.ParserConfig(regex=TRAFFIC_RE, backend="auto", n_chunks=1))
    eng = p.engine
    for i, k in enumerate((be.SPLIT_MIN_LEN, 4 * be.SPLIT_MIN_LEN)):
        assert eng.backend.sub_chains(k) > 1
        eng.parse(_traffic_text(k - 100), n_chunks=1)
        assert eng.compile_count == i + 1
        eng.parse(_traffic_text(k - 300, seed=7), n_chunks=1)
        assert eng.compile_count == i + 1
    p.close()


def test_split_sub_threshold_program_is_unchanged(monkeypatch):
    k = be.SPLIT_MIN_LEN // 2

    def lowered():
        p = repro.Parser(repro.ParserConfig(regex=TRAFFIC_RE, backend="auto", n_chunks=8))
        assert p.engine.backend.sub_chains(k) == 1
        text = _fused_hlo(p.engine, 8, k)
        p.close()
        return text

    with_split = lowered()
    monkeypatch.setattr(be, "SPLIT_MIN_LEN", OFF)
    assert lowered() == with_split


# ------------------------------------------------------------- engagement


def _counter(p, name, **labels) -> float:
    for series in p.stats()["metrics"].get(name, []):
        if series["labels"] == labels:
            return series["value"]
    return 0.0


@pytest.mark.parametrize("n,m_want", [(100, 1), (203, 4), (700, 16)],
                         ids=["below", "at", "above"])
def test_split_spans_and_counter_engagement(monkeypatch, n, m_want):
    _set_split(monkeypatch, SMALL_MIN_LEN, SMALL_SUB_LEN)
    p = repro.Parser(repro.ParserConfig(
        regex=TRAFFIC_RE, backend="auto", n_chunks=4, obs={"enabled": True},
    ))
    c, k = p.engine.bucket_shape(n, 4)
    label = f"{c}x{k}"
    text = _traffic_text(n)
    for _ in range(2):                       # two fused dispatches
        p.submit(text).result()
    p.engine.parse_phase_split(text, n_chunks=4)
    spans = p.obs.tracer.drain()
    device = [s for s in spans if s.name == "parse.device"]
    assert [s.attrs["sub_chains"] for s in device] == [m_want, m_want]
    for name in ("phase.reach", "phase.build_merge"):
        (span,) = [s for s in spans if s.name == name]
        assert span.attrs["sub_chains"] == m_want
    assert _counter(p, "split_dispatches_total", bucket=label) == (
        2 if m_want > 1 else 0)
    if m_want == 1:
        assert "split_dispatches_total" not in p.stats()["metrics"]
    p.close()
