"""Benchmark harness — one function per paper table / figure.

Prints ``name,param,value,derived`` CSV rows.  ``--quick`` (default) shrinks
text sizes for CI; ``--full`` reproduces paper-scale measurements on a larger
machine.  Mapping to the paper:

  tab5            Tab. 5   — NFA/DFA/ME-DFA state counts for e(k)
  fig20           Fig. 20  — segment count vs RE size over random REs
  generation      Sect.5.2 — parser-generation time per benchmark RE
  parse_times     Fig. 15  — absolute parsing time (serial DFA / engine c=1/8)
  speedup         Fig.16/18— two-phase work model + measured phase ratio
  batched_throughput      — texts/sec of the bucketed batch front-end,
                            jnp vs pallas-interpret, batch 1/8/64
  streaming_append        — amortized cost per appended byte of the
                            StreamingParser prefix cache vs a cold full
                            re-parse per append (``--smoke`` = CI-tiny sizes)
  edit_splice             — mid-text splice cost of the product segment tree
                            vs a linear cold re-parse: ~log(n) growth gate +
                            ≥4× speedup at the largest prefix + bit-identity
                            at every size; writes BENCH_edit_splice.json
  sharded_throughput      — distributed runtime: 1-device vs all-host-device
                            mesh at fixed batch (+ one long chunk-sharded
                            text); run under
                            XLA_FLAGS=--xla_force_host_platform_device_count=8
  packed_throughput       — bit-packed uint32 backend vs jnp f32 at ℓ=257
                            states: bit-identity gate + SLPF-path bytes
                            moved (≥8× cut gate; packing gives 32×)
  speculation_throughput  — sparse feasible-start backend vs dense packed at
                            ℓ=257: bit-identity gate + strictly-fewer
                            product-path bytes on REs whose feasible width
                            < ℓp/2; writes BENCH_speculation.json
  multi_tenant_throughput — ParserFleet: T=32 mixed regexes served by ONE
                            tenant-batched device program vs a per-tenant
                            serial Parser loop: bit-identity gate + ≥4×
                            throughput + compile count O(#buckets);
                            writes BENCH_multi_tenant.json
  recognizer      Fig. 16r — recognition cost (reach+join only)
  memory          App. C   — SLPF bytes/char, packed and compressed
  engine_roofline §Roofline— per-cell terms (from the dry-run JSON)

All parse-RUNTIME access goes through the public facade (``repro.api``:
``Parser`` / ``ParserConfig`` — the supported surface, see ROADMAP "Public
API"); only the paper-faithful measurement ORACLES (``core/reference``,
``core/serial``, REgen) are still imported from their internal modules —
they are baselines, not the runtime.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np


def _time(fn, reps=3):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_tab5(rows):
    from repro.api import Parser

    for k in range(1, 10):
        art = Parser(f"(a|b)*a(a|b){{{k}}}").artifacts
        rows.append(("tab5.segments", k, art.table.n, "count (2k+7; see EXPERIMENTS §Paper-validation)"))
        rows.append(("tab5.dfa_states", k, art.dfa.n_states, f"paper={2**(k+1)+1}"))
        rows.append(("tab5.medfa_states", k, art.medfa.n_states, "count"))
        rows.append(("tab5.medfa_entries", k, len(art.medfa.initial), "=segments (linear in k)"))


def bench_fig20(rows, quick):
    from benchmarks.benchmark_res import regen_suite
    from repro.core.numbering import number_regex
    from repro.core.segments import compute_segments
    from repro.core import regex as rx

    n = 40 if quick else 200
    suite = regen_suite(n, 5, 60, seed=7)
    sizes, segs = [], []
    for _, ast in suite:
        numbered = number_regex(ast)
        t = compute_segments(numbered)
        sizes.append(rx.node_size(ast))
        segs.append(t.n)
    sizes = np.array(sizes, float)
    segs = np.array(segs, float)
    slope = float((sizes * segs).sum() / (sizes * sizes).sum())
    corr = float(np.corrcoef(sizes, segs)[0, 1])
    rows.append(("fig20.n_res", 0, len(sizes), "count"))
    rows.append(("fig20.seg_per_size_slope", 0, round(slope, 3), "paper~3.2"))
    rows.append(("fig20.pearson", 0, round(corr, 3), "paper~0.52"))
    rows.append(("fig20.seg_range", 0, f"{int(segs.min())}-{int(segs.max())}", "paper 8-1435"))


def bench_generation(rows):
    from benchmarks.benchmark_res import BENCHMARKS
    from repro.api import Parser

    for name, pattern in BENCHMARKS.items():
        # full generation through the facade: matrices + all automata
        dt = _time(lambda: Parser(pattern).artifacts, reps=3)
        art = Parser(pattern).artifacts
        rows.append((f"generation.{name}", art.table.n, round(dt * 1e3, 2), "ms (paper 5-29ms)"))


def bench_parse_times(rows, quick):
    from benchmarks.benchmark_res import BENCHMARKS, make_text_exact
    from repro.api import Parser, ParserConfig
    from repro.core.serial import parse_serial_dfa

    # NOTE: engine times include the bucketed shape padding (parse rounds the
    # chunk length up to a power of two — up to ~2x cells near a bucket edge),
    # the compile-free steady-state cost a serving deployment actually pays.
    # Chunk policy is declarative: n_chunks=1 is PaREM's serial split,
    # n_chunks=8 the chunked one.
    n = 20_000 if quick else 2_000_000
    for name in BENCHMARKS:
        p1 = Parser(ParserConfig(regex=BENCHMARKS[name], n_chunks=1))
        p8 = Parser(ParserConfig(regex=BENCHMARKS[name], n_chunks=8))
        art = p1.artifacts
        text = make_text_exact(name, n, seed=1)
        t_dfa = _time(lambda: parse_serial_dfa(art.matrices, text, art.dfa, art.rdfa, art.nfa), reps=1)
        t_eng1 = _time(lambda: p1.parse(text), reps=2)
        t_eng8 = _time(lambda: p8.parse(text), reps=2)
        rows.append((f"parse.{name}.serial_dfa", len(text), round(t_dfa * 1e3, 1), "ms"))
        rows.append((f"parse.{name}.engine_c1", len(text), round(t_eng1 * 1e3, 1), "ms"))
        rows.append((f"parse.{name}.engine_c8", len(text), round(t_eng8 * 1e3, 1), "ms"))


def bench_speedup(rows, quick):
    """Paper Fig. 16/18.  Wall-clock multi-core speed-up is unobservable on
    this 1-core container; we measure the reach/build phase-work ratio of the
    paper-faithful reference and evaluate the paper's own two-stage model:
    speedup(c) ≈ c / (1 + w_reach/w_build) with both phases serialized —
    ≈ c/2 when the phases weigh the same (paper Sect. 5.2 'Discussion'); the
    transpose-backward variant (DESIGN §2) halves reach work → ceiling 2c/3."""
    from benchmarks.benchmark_res import BIGDATA_RE, make_text_exact
    from repro.core.reference import ParallelArtifacts, build_phase, reach_phase

    art = ParallelArtifacts.generate(BIGDATA_RE)
    text = make_text_exact("BIGDATA", 4_000 if quick else 100_000, seed=2)
    classes = art.matrices.classes_of_text(text)
    ell = art.table.n

    chunk = classes[:2000]
    t_reach = _time(lambda: reach_phase(art.medfa, chunk, ell), reps=2)
    t_build = _time(
        lambda: build_phase(art.dfa, art.nfa, frozenset(range(ell)), chunk, ell),
        reps=2,
    )
    w = t_reach / max(t_build, 1e-9)
    rows.append(("speedup.reach_over_build_work", len(chunk), round(w, 2), "measured phase ratio"))
    for c in (2, 4, 8, 16, 32, 64):
        paper = c / (1.0 + 1.0)            # reach ≈ build&merge (paper model)
        ours = c / (1.0 + w / 2.0)         # bwd reach free (DESIGN §2)
        rows.append((f"speedup.model.c{c}", c,
                     f"paper~{paper:.1f}x ours~{ours:.1f}",
                     "two-stage model"))


def bench_batched_throughput(rows, quick):
    """Batched serving throughput (texts/sec) of the shape-bucketed front-end.

    Measures ``ParserEngine.parse_batch`` at batch 1 / 8 / 64 on both phase
    backends — ``jnp`` (pure-XLA device program) and ``pallas`` (the Mosaic
    kernels; interpret mode on CPU, so its numbers here gauge correctness
    cost only, not TPU speed).  ``compiles`` in the derived column is the
    engine's cumulative program count: it grows only when a new
    (chunk-bucket, batch-slot) shape first appears — roughly one per batch
    size plus one per length bucket the jittered lengths straddle — and the
    timed repeat calls add none (no per-length or per-call re-jit).
    """
    from benchmarks.benchmark_res import BIGDATA_RE, make_text_exact
    from repro.api import Parser, ParserConfig

    import jax

    # keep targets clear of the pow2 bucket edge: make_text_exact may overshoot
    # by a few records, which at n=2^m would spill one text into the next
    # (double-width) bucket and pollute the timed batch with a straggler.
    n = 240 if quick else 16_000
    for backend in ("jnp", "pallas"):
        if backend == "pallas" and not quick and jax.default_backend() != "tpu":
            # full-size interpret-mode grids (k≈4096) take hours on CPU and
            # measure nothing the quick run doesn't already cover.
            rows.append(("batched.pallas.skipped", 0, 0,
                         "full pallas bench needs a TPU (interpret too slow)"))
            continue
        parser = Parser(ParserConfig(
            regex=BIGDATA_RE, backend=backend, n_chunks=4, max_batch=64
        ))
        for batch in (1, 8, 64):
            texts = [
                make_text_exact("BIGDATA", n - (i % 7), seed=i) for i in range(batch)
            ]
            parser.parse_batch(texts)                   # warm the program cache
            dt = _time(lambda: parser.parse_batch(texts), reps=2)
            rows.append((
                f"batched.{backend}.b{batch}", batch,
                round(batch / max(dt, 1e-9), 1),
                f"texts/s n~{n} compiles={parser.compile_count}",
            ))


def bench_streaming_append(rows, quick, smoke=False):
    """Streaming append cost (core/stream.py) vs cold full re-parse.

    Streams a text in fixed-size appends and reports, at geometric prefix
    checkpoints, the per-byte append cost inside that window — flat across
    checkpoints ⇒ the amortized incremental work is sublinear in prefix
    length (the prefix cache only re-reaches the appended piece + an
    O(log n) join) — against the cost a naive server pays to re-parse the
    whole prefix on every append.  A warm pass runs first so the numbers
    exclude one-time bucket compiles (``compiles`` column shows the total).
    """
    from benchmarks.benchmark_res import BIGDATA_RE, make_text_exact
    from repro.api import Parser, ParserConfig

    parser = Parser(ParserConfig(regex=BIGDATA_RE))
    n_target = 1_500 if smoke else (12_000 if quick else 400_000)
    step = 50 if smoke else (100 if quick else 1_000)
    text = make_text_exact("BIGDATA", n_target, seed=5)
    n = len(text)
    checkpoints = sorted({n // 4, n // 2, n})

    def stream_pass():
        stream = parser.open_stream()
        total, done, nxt, marks = 0.0, 0, 0, []
        for lo in range(0, n, step):
            piece = text[lo : lo + step]
            t0 = time.perf_counter()
            stream.append(piece)
            stream.accepted              # drain THIS session + O(1) join query
            total += time.perf_counter() - t0
            done += len(piece)
            while nxt < len(checkpoints) and done >= checkpoints[nxt]:
                marks.append((done, total))
                nxt += 1
        return stream, marks

    warm, _ = stream_pass()              # warm: traces every bucketed shape
    warm.close()
    stream, marks = stream_pass()

    prev_n, prev_t = 0, 0.0
    for cp_n, cp_t in marks:
        win_bytes = max(cp_n - prev_n, 1)
        win_per_byte = (cp_t - prev_t) / win_bytes
        rows.append((f"streaming.append_us_per_byte.n{cp_n}", cp_n,
                     round(win_per_byte * 1e6, 3),
                     "flat across checkpoints => sublinear in prefix"))
        prefix = text[:cp_n]
        parser.parse(prefix)             # warm this parse bucket (same engine)
        t_cold = _time(lambda: parser.parse(prefix), reps=2)
        per_append = (cp_t - prev_t) / max(win_bytes / step, 1)
        rows.append((f"streaming.reparse_speedup.n{cp_n}", cp_n,
                     round(t_cold / max(per_append, 1e-9), 1),
                     f"cold reparse {t_cold*1e3:.1f}ms vs "
                     f"{per_append*1e6:.0f}us/append"))
        prev_n, prev_t = cp_n, cp_t
    rows.append(("streaming.amortized_us_per_byte", n,
                 round(marks[-1][1] / n * 1e6, 3),
                 f"{step}B appends; compiles={parser.compile_count}; "
                 f"{stream.n_sealed_chunks} sealed chunks"))
    ok = np.array_equal(
        stream.result().forest.pack(), parser.parse(text).forest.pack()
    )
    stream.close()
    rows.append(("streaming.bit_identical", n, int(ok),
                 "stream SLPF == cold parse (must be 1)"))
    if not ok:
        raise SystemExit(
            "streaming_append: stream SLPF diverged from cold parse"
        )  # make the CI smoke invocation a real gate, not a printout


def bench_edit_splice(rows, quick, smoke=False):
    """Mid-text splice cost (the product segment tree) vs linear re-parse.

    For geometrically growing prefix sizes n, times a fixed-width
    ``ParserStream.edit`` (splice + acceptance query) at spread positions
    and the cold re-parse an editor without the tree would pay.  Two gates:
    the edit cost must grow ~log(n) — far below the x(n_hi/n_lo) a linear
    re-join would show — and at the largest prefix the splice must beat the
    cold re-parse >= 4x.  Same-bytes replacements keep the text constant, so
    the edited stream's SLPF is byte-compared against the cold parse at
    every size (a real gate, not a printout).
    """
    from benchmarks.benchmark_res import BIGDATA_RE, make_text_exact
    from repro.api import Parser, ParserConfig

    parser = Parser(ParserConfig(
        regex=BIGDATA_RE, first_seal_len=32, max_seal_len=64
    ))
    sizes = [512, 2048, 8192] if quick else [2048, 8192, 32768]
    span, reps = 8, 12
    edit_t, speedup, t_cold = {}, {}, {}
    for n in sizes:
        text = make_text_exact("BIGDATA", n, seed=9)
        stream = parser.open_stream()
        stream.append(text)
        stream.accepted                     # drain + warm the query path
        for i in (0, 1):                    # warm the splice piece buckets
            stream.edit(i, i + span, text[i : i + span])
        ts = []
        for i in range(reps):
            lo = (i * 2654435761) % (n - span)   # deterministic spread
            repl = text[lo : lo + span]          # same bytes: text invariant
            t0 = time.perf_counter()
            stream.edit(lo, lo + span, repl)
            stream.accepted
            ts.append(time.perf_counter() - t0)
        edit_t[n] = sorted(ts)[len(ts) // 2]     # median: compile-spike-proof
        parser.parse(text)                       # warm the cold bucket
        t_cold[n] = _time(lambda: parser.parse(text), reps=2)
        speedup[n] = t_cold[n] / max(edit_t[n], 1e-9)
        rows.append((f"edit.us_per_edit.n{n}", n, round(edit_t[n] * 1e6, 1),
                     f"{span}-char splice + acceptance query"))
        rows.append((f"edit.reparse_speedup.n{n}", n, round(speedup[n], 1),
                     f"cold reparse {t_cold[n]*1e3:.2f}ms vs "
                     f"{edit_t[n]*1e6:.0f}us/edit"))
        ok = np.array_equal(
            stream.result().forest.pack(), parser.parse(text).forest.pack()
        )
        stream.close()
        rows.append((f"edit.bit_identical.n{n}", n, int(ok),
                     "edited stream SLPF == cold parse (must be 1)"))
        if not ok:
            raise SystemExit(
                "edit_splice: edited stream SLPF diverged from cold parse"
            )
    n_lo, n_hi = sizes[0], sizes[-1]
    growth = edit_t[n_hi] / max(edit_t[n_lo], 1e-9)
    linear = n_hi / n_lo
    rows.append(("edit.cost_growth", n_hi, round(growth, 2),
                 f"splice cost x{growth:.1f} over a x{linear:.0f} prefix "
                 f"(log-like; linear would be ~x{linear:.0f}, "
                 f"gate <= x{linear / 2:.0f})"))
    rows.append(("edit.edit_throughput", n_hi,
                 round(1.0 / max(edit_t[n_hi], 1e-9), 1),
                 f"edits/s at n={n_hi} ({span}-char splice + acceptance)"))
    if growth > linear / 2:
        raise SystemExit(
            f"edit_splice: splice cost grew x{growth:.1f} over a "
            f"x{linear:.0f} prefix — not O(log n) "
            f"(gate <= x{linear / 2:.0f})"
        )
    if speedup[n_hi] < 4.0:
        raise SystemExit(
            f"edit_splice: splice only {speedup[n_hi]:.1f}x faster than cold "
            f"re-parse at n={n_hi} (gate >= 4x)"
        )


def bench_sharded_throughput(rows, quick, smoke=False):
    """Distributed parse runtime: 1-device vs multi-device mesh, fixed batch.

    Measures ``parse_batch`` (batch over 'data' × chunks over 'pod',
    ``core/distributed.py``) and the single-long-text chunk-sharded route on
    a plain engine vs a ``ParserEngine(mesh=...)`` over every host device.
    Needs >1 device — CI runs it under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``, where the
    "devices" share the same CPU cores: the numbers gauge partitioning
    overhead, not speedup (real scaling needs a TPU pod slice).  ``--smoke``
    additionally gates on bit-identity vs the single-device engine.
    """
    import jax
    import numpy as np

    from benchmarks.benchmark_res import BIGDATA_RE, make_text_exact
    from repro.api import Parser, ParserConfig

    n_dev = len(jax.devices())
    if n_dev < 2:
        rows.append(("sharded.skipped", n_dev, 0,
                     "needs XLA_FLAGS=--xla_force_host_platform_device_count=8"))
        return
    n = 200 if smoke else (2_000 if quick else 64_000)
    batch = 8
    texts = [make_text_exact("BIGDATA", n - (i % 5), seed=i) for i in range(batch)]
    long_text = make_text_exact("BIGDATA", 4 * n, seed=99)

    # distribution is declarative on the facade: mesh=None vs mesh="host"
    cfg = ParserConfig(regex=BIGDATA_RE, n_chunks=8, max_batch=batch)
    p1 = Parser(cfg)
    pM = Parser(cfg.replace(mesh="host"))
    mesh = pM.engine.mesh

    base = p1.parse_batch(texts)                      # warm + reference
    got = pM.parse_batch(texts)
    ok = all(
        np.array_equal(g.forest.pack(), b.forest.pack())
        for g, b in zip(got, base)
    )
    ok = ok and np.array_equal(
        pM.parse(long_text).forest.pack(), p1.parse(long_text).forest.pack()
    )
    rows.append(("sharded.bit_identical", n_dev, int(ok),
                 "mesh == 1-device SLPF (must be 1)"))
    if not ok:
        raise SystemExit("sharded_throughput: mesh parse diverged from 1-device")

    dt1 = _time(lambda: p1.parse_batch(texts), reps=2)
    dtM = _time(lambda: pM.parse_batch(texts), reps=2)
    rows.append((f"sharded.batch.1dev.b{batch}", 1,
                 round(batch / max(dt1, 1e-9), 1), f"texts/s n~{n}"))
    rows.append((f"sharded.batch.mesh{n_dev}dev.b{batch}", n_dev,
                 round(batch / max(dtM, 1e-9), 1),
                 f"texts/s ratio={dt1 / max(dtM, 1e-9):.2f}x "
                 f"mesh={dict(mesh.shape)}"))
    dl1 = _time(lambda: p1.parse(long_text), reps=2)
    dlM = _time(lambda: pM.parse(long_text), reps=2)
    rows.append((f"sharded.long.1dev", len(long_text),
                 round(dl1 * 1e3, 1), "ms single long text"))
    rows.append((f"sharded.long.mesh{n_dev}dev", len(long_text),
                 round(dlM * 1e3, 1),
                 f"ms chunk-sharded ratio={dl1 / max(dlM, 1e-9):.2f}x"))


def bench_packed_throughput(rows, quick, smoke=False):
    """Bit-packed uint32 backend vs jnp f32 at production automaton scale.

    Uses the e(k) family (2k+7 segments; Tab. 5) at k=125 → ℓ = 257 ≥ 256
    states, built WITHOUT the exponential DFA (segments + matrices only), and

      * gates on bit-identity packed vs jnp on a random a/b text (always —
        the CI smoke invocation is a real gate);
      * reports SLPF-path bytes moved — the chunk-product boundary that is
        the reach output, the join input, the streaming cache entry AND the
        distributed all-gather payload — for both layouts, gating on the
        acceptance bar (≥ 8× reduction at ℓ ≥ 256; the uint32 packing gives
        exactly 32×), measured off the real device arrays, plus the packed
        vs f32 transition-table traffic of one reach step;
      * times parse on both backends (CPU wall-clock favors the f32 path's
        BLAS matmuls — the bytes rows are the TPU-relevant signal; VPU
        word-op throughput needs the real-TPU ROADMAP item).
    """
    import jax.numpy as jnp

    from repro.api import Parser, ParserConfig
    from repro.core.segments import compute_segments

    # e(k) at k=125 has an exponential DFA — build from segments only
    # (``from_matrices``: the facade path for pre-generated tables)
    table = compute_segments("(a|b)*a(a|b){125}")
    ell = table.n
    p_j = Parser.from_matrices(table, ParserConfig(regex="<e125>", n_chunks=8))
    p_p = Parser.from_matrices(
        p_j.matrices, ParserConfig(regex="<e125>", backend="packed", n_chunks=8)
    )
    n = 300 if smoke else (2_000 if quick else 50_000)
    rng = np.random.default_rng(0)
    text = bytes(rng.choice([97, 98], size=n))

    base = p_j.parse(text)
    got = p_p.parse(text)
    ok = np.array_equal(base.forest.pack(), got.forest.pack())
    rows.append(("packed.bit_identical", ell, int(ok),
                 "packed == jnp SLPF (must be 1)"))
    if not ok:
        raise SystemExit("packed_throughput: packed backend diverged from jnp")

    # SLPF-path bytes: stacked chunk products from each backend's real reach
    eng_j, eng_p = p_j.engine, p_p.engine
    classes = eng_j.classes_of_text(text)
    c, k = eng_j.bucket_shape(len(classes), 8)
    chunks = jnp.asarray(eng_j._pad_to(classes, c, k))
    P_f32 = eng_j.phases.reach(eng_j.tables.N, chunks)
    P_pck = eng_p.phases.reach(eng_p.tables.N, chunks)
    b_f32 = int(P_f32.size) * P_f32.dtype.itemsize
    b_pck = int(P_pck.size) * P_pck.dtype.itemsize
    ratio = b_f32 / b_pck
    rows.append(("packed.product_stack_bytes.f32", ell, b_f32,
                 f"(c={c}) reach→join boundary / all-gather payload"))
    rows.append(("packed.product_stack_bytes.packed", ell, b_pck,
                 f"{ratio:.0f}x fewer bytes moved (gate ≥8x at ℓ≥256)"))
    if ell >= 256 and ratio < 8.0:
        raise SystemExit(
            f"packed_throughput: bytes reduction {ratio:.1f}x < 8x at ℓ={ell}"
        )
    # per-step transition-row traffic of the reach loop (N[class] per char)
    lp = eng_j.tables.ell_pad
    rows.append(("packed.reach_step_bytes", ell,
                 f"{lp * lp * 4}->{lp * (lp // 32) * 4}",
                 "f32 vs packed N-row bytes per reach char"))

    for name, p in (("jnp", p_j), ("packed", p_p)):
        p.parse(text)                          # warm the bucket program
        dt = _time(lambda: p.parse(text), reps=2)
        rows.append((f"packed.parse_ms.{name}", n, round(dt * 1e3, 1),
                     f"ms n={n} compiles={p.compile_count}"))


def bench_speculation_throughput(rows, quick, smoke=False):
    """Speculation-width reduction: sparse feasible-start backend at ℓ=257.

    Two benchmark REs at exactly ℓ = 257 segments (ℓp = 288, W = 9):

      e125    ``(a|b)*a(a|b){125}``  — a 2-letter automaton whose classes
              admit ~ℓ/2 start states (width 129 < ℓp/2 = 144: a qualifying
              but near-worst case for the reduction);
      cyc25   a 25-letter cyclic literal tuned to ℓ = 257 — each class
              admits ~ℓ/25 states (width 12), the PaREM regime where
              boundary information prunes speculation hard.

    Gates (the CI smoke invocation runs all of them):
      * sparse SLPF bit-identical to the jnp oracle on both REs;
      * product-path bytes moved (reach output = join input = streaming
        cache entry = all-gather payload) STRICTLY below the dense packed
        backend at ℓ=257 on every RE whose measured feasible width < ℓp/2
        — the acceptance bar; both REs qualify.

    Also reports measured speculation width (mean/max vs ℓp) and parse
    wall-clock per backend (CPU numbers gauge overhead only; the bytes rows
    are the TPU-relevant signal).  Returns the structured measurement set;
    ``main()`` writes it under ``metrics["report"]`` of the schema-shared
    ``BENCH_speculation.json`` at the repo root — the perf trajectory entry
    ROADMAP asks for, now validated by ``repro.obs.export``.
    """
    import string

    import jax.numpy as jnp

    from repro.api import Parser, ParserConfig
    from repro.core.matrices import feasible_start_widths
    from repro.core.segments import compute_segments

    unit25 = string.ascii_lowercase[:25] * 10 + "abcd"   # tuned: ℓ = 257
    cases = {
        "e125": ("(a|b)*a(a|b){125}",
                 lambda rng, n: bytes(rng.choice([97, 98], size=n))),
        "cyc25": (f"({unit25})*",
                  lambda rng, n: (unit25.encode()
                                  * (n // len(unit25) + 1))[: n - n % len(unit25)]),
    }
    n = 300 if smoke else (2_000 if quick else 50_000)
    report = {"ell_target": 257, "n_chars": n, "cases": {}}

    for cname, (pattern, make_text) in cases.items():
        table = compute_segments(pattern)
        ell = table.n
        p_j = Parser.from_matrices(
            table, ParserConfig(regex=f"<{cname}>", n_chunks=8)
        )
        p_p = Parser.from_matrices(
            p_j.matrices,
            ParserConfig(regex=f"<{cname}>", backend="packed", n_chunks=8),
        )
        p_s = Parser.from_matrices(
            p_j.matrices,
            ParserConfig(regex=f"<{cname}>", backend="sparse", n_chunks=8),
        )
        rng = np.random.default_rng(0)
        text = make_text(rng, n)

        base = p_j.parse(text)
        got = p_s.parse(text)
        ok = np.array_equal(base.forest.pack(), got.forest.pack())
        rows.append((f"speculation.{cname}.bit_identical", ell, int(ok),
                     "sparse == jnp SLPF (must be 1)"))
        if not ok:
            raise SystemExit(
                f"speculation_throughput: sparse diverged from jnp on {cname}"
            )

        # product-path bytes: stacked chunk products off each backend's reach
        eng_p, eng_s = p_p.engine, p_s.engine
        classes = eng_p.classes_of_text(text)
        c, k = eng_p.bucket_shape(len(classes), 8)
        chunks = jnp.asarray(eng_p._pad_to(classes, c, k))
        P_pck = eng_p.phases.reach(eng_p.tables.N, chunks)
        P_sp = eng_s.phases.reach(eng_s.tables.N, chunks)
        b_pck = int(P_pck.size) * P_pck.dtype.itemsize
        b_sp = int(P_sp.size) * P_sp.dtype.itemsize
        lp = int(eng_s.tables.ell_pad)
        S = int(eng_s.backend._width)
        widths = feasible_start_widths(eng_s.tables.N, np.asarray(chunks))
        real = widths[widths >= 0]
        w_mean = float(real.mean()) if real.size else 0.0
        w_max = int(real.max()) if real.size else 0
        rows.append((f"speculation.{cname}.width", ell,
                     f"mean={w_mean:.1f} max={w_max}",
                     f"feasible-start states vs ℓp={lp} (rows carried S={S})"))
        rows.append((f"speculation.{cname}.product_stack_bytes.packed", ell,
                     b_pck, f"(c={c}) dense packed product path"))
        rows.append((f"speculation.{cname}.product_stack_bytes.sparse", ell,
                     b_sp,
                     f"{b_pck / b_sp:.2f}x fewer bytes (gate: strict < at "
                     f"ℓ=257 when width < ℓp/2)"))
        if w_max < lp // 2 and b_sp >= b_pck:
            raise SystemExit(
                f"speculation_throughput: sparse bytes {b_sp} not strictly "
                f"below packed {b_pck} on {cname} (width {w_max} < ℓp/2)"
            )

        timings = {}
        for bname, p in (("packed", p_p), ("sparse", p_s)):
            p.parse(text)                      # warm the bucket program
            dt = _time(lambda: p.parse(text), reps=2)
            timings[bname] = dt
            rows.append((f"speculation.{cname}.parse_ms.{bname}", n,
                         round(dt * 1e3, 1),
                         f"ms n={n} compiles={p.compile_count}"))

        report["cases"][cname] = {
            "pattern": pattern,
            "ell": ell,
            "ell_pad": lp,
            "product_rows": S,
            "bit_identical": bool(ok),
            "speculation_width": {"mean": w_mean, "max": w_max,
                                  "n_chunks_real": int(real.size)},
            "bytes_moved": {
                "packed": b_pck,
                "sparse": b_sp,
                "ratio_packed_over_sparse": b_pck / b_sp,
                "n_stacked_chunks": int(c),
            },
            "throughput": {
                bname: {"parse_s": dt, "chars_per_s": n / max(dt, 1e-9)}
                for bname, dt in timings.items()
            },
        }

    rows.append(("speculation.json", 0, "BENCH_speculation.json",
                 "machine-readable perf trajectory entry"))
    return report


def bench_multi_tenant_throughput(rows, quick, smoke=False):
    """Multi-tenant fleet: tenant-batched device programs vs per-tenant loop.

    T=32 tenants over 8 distinct patterns of the e(k) family (ℓ = 2k+7 for
    k = 1..8 — mixed true ℓ, one shared (Ab, ℓp) automaton bucket), each
    with its own text.  Two routes, both warm:

      serial   one solo ``Parser`` per tenant, 32 separate device dispatches
               per sweep — the pre-fleet deployment model;
      fleet    ``ParserFleet.parse_batch`` — ONE tenant-batched device
               program serves all 32 (tenant axis vmapped like the batch
               axis; ``core/fleet.py``).

    Gates (the CI smoke invocation runs all of them):
      * every fleet result bit-identical to its tenant's solo oracle;
      * fleet throughput ≥ 4× the serial loop at T=32 (CPU/interpret);
      * fleet compile count O(#buckets): ≤ 2 programs per automaton bucket
        (NOT per tenant), and table-cache misses = #distinct patterns.

    Returns the structured report written under ``metrics["report"]`` of
    ``BENCH_multi_tenant.json`` — the perf-trajectory entry
    ``scripts/bench_trend.py`` tracks.
    """
    from repro.api import Parser, ParserConfig, ParserFleet
    from repro.core.fleet import clear_table_cache

    T = 32
    # short texts are the regime this feature exists for (thousands of
    # small per-tenant requests, per-dispatch overhead dominant); modes
    # scale timing repetitions, not text length
    n = 16
    reps = 3 if quick else 5   # best-of; smoke keeps 3 (timing noise guard)
    patterns = [f"(a|b)*a(a|b){{{k}}}" for k in range(1, 9)]
    configs = {
        f"t{i:02d}": ParserConfig(regex=patterns[i % len(patterns)], n_chunks=2)
        for i in range(T)
    }
    rng = np.random.default_rng(42)
    texts = {
        tid: bytes(rng.choice([97, 98], size=n - (i % 5)).astype(np.uint8))
        for i, tid in enumerate(configs)
    }
    items = [(tid, texts[tid]) for tid in configs]

    clear_table_cache()                        # deterministic cache counters
    fleet = ParserFleet(configs, max_batch=T)
    solos = {tid: Parser(cfg) for tid, cfg in configs.items()}

    # bit-identity gate: the tenant-batched route vs each tenant's oracle
    got = fleet.parse_batch(items)             # also warms the fleet program
    oracle = {tid: solos[tid].parse(texts[tid]) for tid in configs}  # + warms
    ok = all(
        np.array_equal(r.forest.pack(), oracle[tid].forest.pack())
        for (tid, _), r in zip(items, got)
    )
    rows.append(("multi_tenant.bit_identical", T, int(ok),
                 "fleet == per-tenant solo SLPF (must be 1)"))
    if not ok:
        raise SystemExit(
            "multi_tenant_throughput: fleet diverged from per-tenant oracles"
        )

    def serial_sweep():
        for tid in configs:
            solos[tid].parse(texts[tid])

    dt_serial = _time(serial_sweep, reps=reps)
    dt_fleet = _time(lambda: fleet.parse_batch(items), reps=reps)
    thr_serial = T / max(dt_serial, 1e-9)
    thr_fleet = T / max(dt_fleet, 1e-9)
    speedup = dt_serial / max(dt_fleet, 1e-9)
    rows.append(("multi_tenant.serial_throughput", T,
                 round(thr_serial, 1), f"texts/s n~{n} (32 dispatches/sweep)"))
    rows.append(("multi_tenant.fleet_throughput", T,
                 round(thr_fleet, 1),
                 f"texts/s n~{n} (tenant-batched, 1 dispatch/sweep)"))
    rows.append(("multi_tenant.speedup", T, round(speedup, 2),
                 "fleet vs per-tenant serial loop (gate ≥4x at T=32)"))
    if speedup < 4.0:
        raise SystemExit(
            f"multi_tenant_throughput: fleet speedup {speedup:.2f}x < 4x "
            f"at T={T}"
        )

    # compile economy gates: programs per BUCKET, table builds per PATTERN
    n_buckets = fleet.engine.n_buckets
    compiles = fleet.compile_count
    rows.append(("multi_tenant.buckets", T, n_buckets,
                 f"automaton buckets for {T} tenants"))
    rows.append(("multi_tenant.compile_count", T, compiles,
                 "device programs (gate: ≤ 2 per bucket, not per tenant)"))
    if compiles > 2 * n_buckets:
        raise SystemExit(
            f"multi_tenant_throughput: {compiles} compiled programs for "
            f"{n_buckets} buckets — compile count is not O(#buckets)"
        )
    snap = {str(k): v for k, v in fleet.obs.metrics.snapshot().items()}
    misses = snap.get("table_cache_misses_total", [{"value": 0}])[0]["value"]
    hits = snap.get("table_cache_hits_total", [{"value": 0}])[0]["value"]
    rows.append(("multi_tenant.table_cache", T,
                 f"miss={int(misses)} hit={int(hits)}",
                 f"builds = {len(patterns)} distinct patterns (gate)"))
    if int(misses) != len(patterns):
        raise SystemExit(
            f"multi_tenant_throughput: {int(misses)} table builds for "
            f"{len(patterns)} distinct patterns"
        )

    return {
        "tenants": T,
        "n_chars": n,
        "distinct_patterns": len(patterns),
        "bit_identical": bool(ok),
        "buckets": n_buckets,
        "compile_count": int(compiles),
        "table_cache": {"misses": int(misses), "hits": int(hits)},
        "throughput": {
            "serial": {"sweep_s": dt_serial, "texts_per_s": thr_serial},
            "fleet": {"sweep_s": dt_fleet, "texts_per_s": thr_fleet},
            "speedup_fleet_over_serial": speedup,
        },
    }


def bench_recognizer(rows, quick):
    from benchmarks.benchmark_res import BIGDATA_RE, make_text_exact
    from repro.core.reference import ParallelArtifacts
    from repro.core.serial import recognize

    art = ParallelArtifacts.generate(BIGDATA_RE)
    text = make_text_exact("BIGDATA", 20_000 if quick else 500_000, seed=3)
    t_rec = _time(lambda: recognize(art.matrices, text, art.dfa), reps=2)
    rows.append(("recognizer.serial_dfa", len(text), round(t_rec * 1e3, 1), "ms"))


def bench_memory(rows, quick):
    from benchmarks.benchmark_res import BIGDATA_RE, make_text_exact

    import repro

    parser = repro.Parser(BIGDATA_RE)
    sizes = (1_000, 10_000) if quick else (10_000, 100_000, 1_000_000)
    for n in sizes:
        text = make_text_exact("BIGDATA", n, seed=4)
        s = parser.parse(text).forest
        packed = s.pack()
        comp = repro.compress(s)
        rows.append((f"memory.packed_bytes_per_char.n{n}", n,
                     round(packed.nbytes / max(len(text), 1), 3), "B/char"))
        rows.append((f"memory.compressed_bytes_per_char.n{n}", n,
                     round(comp.nbytes() / max(len(text), 1), 4),
                     f"{len(comp.states)} states; {len(comp.overrides)} overrides"))


def bench_engine_roofline(rows):
    p = Path(__file__).resolve().parents[1] / "experiments" / "dryrun_results.json"
    if not p.exists():
        rows.append(("engine_roofline.missing", 0, 0, "run repro.launch.dryrun first"))
        return
    d = json.loads(p.read_text())
    for k, v in sorted(d.items()):
        if not v.get("ok") or v.get("skipped"):
            continue
        rows.append(
            (f"roofline.{k}", v["chips"],
             round(v.get("roofline_fraction", 0.0), 4),
             f"bottleneck={v.get('bottleneck')}")
        )


def _json_value(v):
    """Coerce a CSV-row value to a JSON-native type (numpy scalars -> python)."""
    if hasattr(v, "item"):
        v = v.item()
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    return str(v)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", default=True)
    ap.add_argument("--full", dest="quick", action="store_false")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-tiny sizes (implies --quick)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--no-bench-json", dest="bench_json", action="store_false",
                    default=True,
                    help="skip writing BENCH_<gate>.json perf-trajectory files")
    args = ap.parse_args(argv)
    if args.smoke:
        args.quick = True
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    rows = []
    benches = {
        "tab5": lambda: bench_tab5(rows),
        "fig20": lambda: bench_fig20(rows, args.quick),
        "generation": lambda: bench_generation(rows),
        "parse_times": lambda: bench_parse_times(rows, args.quick),
        "speedup": lambda: bench_speedup(rows, args.quick),
        "batched_throughput": lambda: bench_batched_throughput(rows, args.quick),
        "streaming_append": lambda: bench_streaming_append(
            rows, args.quick, args.smoke
        ),
        "edit_splice": lambda: bench_edit_splice(
            rows, args.quick, args.smoke
        ),
        "sharded_throughput": lambda: bench_sharded_throughput(
            rows, args.quick, args.smoke
        ),
        "packed_throughput": lambda: bench_packed_throughput(
            rows, args.quick, args.smoke
        ),
        "speculation_throughput": lambda: bench_speculation_throughput(
            rows, args.quick, args.smoke
        ),
        "multi_tenant_throughput": lambda: bench_multi_tenant_throughput(
            rows, args.quick, args.smoke
        ),
        "recognizer": lambda: bench_recognizer(rows, args.quick),
        "memory": lambda: bench_memory(rows, args.quick),
        "engine_roofline": lambda: bench_engine_roofline(rows),
    }
    from repro.obs.export import write_bench_json

    repo_root = Path(__file__).resolve().parents[1]
    config = {"quick": args.quick, "smoke": args.smoke, "only": args.only}
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        start = len(rows)
        extra = fn()
        wall_s = time.time() - t0
        print(f"# {name} done in {wall_s:.1f}s", file=sys.stderr)
        if not args.bench_json:
            continue
        # every gate leaves one BENCH_<gate>.json perf-trajectory entry with
        # the shared {name, timestamp, config, metrics} schema; the CSV rows
        # the gate produced go under metrics["rows"], richer per-gate
        # structures (the speculation report) under metrics["report"]
        metrics = {
            "rows": [
                {"name": r, "param": _json_value(p), "value": _json_value(v),
                 "derived": str(d)}
                for r, p, v, d in rows[start:]
            ],
            "wall_s": round(wall_s, 3),
        }
        if extra is not None:
            metrics["report"] = extra
        bench_name = {
            "speculation_throughput": "speculation",
            "multi_tenant_throughput": "multi_tenant",
        }.get(name, name)
        out = write_bench_json(bench_name, config=config, metrics=metrics,
                               out_dir=repo_root)
        print(f"# wrote {out.name}", file=sys.stderr)
    print("name,param,value,derived")
    for name, param, value, derived in rows:
        print(f"{name},{param},{value},{derived}")


if __name__ == "__main__":
    main()
