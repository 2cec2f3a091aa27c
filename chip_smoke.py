#!/usr/bin/env python3
"""Bring-up smoke run: the parser's main path on one TPU chip, end to end.

Drives the public facade (``repro.Parser``, ``ParserStream``,
``ParserFleet``) once at the paper's long-text size and checks every result
bit for bit against the host serial oracle (``core/serial.py``) or the
reference ``jnp`` backend:

  (a) device    the platform must be ``tpu``; anything else exits non-zero
  (b) long      2 MB TRAFFIC log through backends auto (→ sparse), jnp, packed
  (c) requests  64 TRAFFIC texts of 64 B – 4 KB: parse_batch, then submit
  (d) stream    ~1 MB appended in uneven pieces, then one mid-text edit
  (e) fleet     the four benchmark patterns, one mixed parse_batch
  (f) dense     e(125) (ℓ = 257), 256 KB a/b text: jnp, pallas, packed and
                sparse kernels; each kernel program must hold a Mosaic
                ``tpu_custom_call`` (no interpret mode)

``--chips 4`` runs only the two routes of ``ParserConfig(mesh="host")`` on a
(2, 2) pod×data mesh — the chunk-sharded 2 MB text and an 8-text batch —
against a single-device ``Parser`` in the same process.

The per-phase times printed are bring-up wall times with compilation split
out, not benchmark numbers.  The last line of stdout is one JSON object
naming the device; it is printed only when every phase passed.  Inputs come
from ``benchmarks/benchmark_res.py`` and fixed seeds.  One process, no
children.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the mesh routes on four chips
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# workload sizes (the paper's --full long text; requests as served logs)
LONG_TEXT_BYTES = 2_000_000
N_REQUESTS = 64
REQUEST_BYTES = (64, 4096)
STREAM_BYTES = 1_000_000
STREAM_PIECE_BYTES = (1, 65_536)
FLEET_TEXTS_PER_TENANT = 4
FLEET_BYTES = (256, 4096)
DENSE_TEXT_BYTES = 262_144
MESH_BATCH_TEXTS = 8

_TRACE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds spent tracing and lowering, and in the backend compiler (or
    fetching its result from the persistent cache — the only part the cache
    can shorten), and persistent-cache hits, from JAX's monitoring."""

    def __init__(self):
        import jax

        self.trace_s = 0.0
        self.backend_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    @property
    def seconds(self) -> float:
        return self.trace_s + self.backend_s

    def _on_duration(self, event, duration, **_):
        if event in _TRACE_EVENTS:
            self.trace_s += duration
        elif event == _BACKEND_EVENT:
            self.backend_s += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def same_forest(a, b) -> bool:
    import numpy as np

    return np.array_equal(a.pack(), b.pack())


def lengths(rng, n, lo_hi):
    """n log-uniform lengths in [lo, hi] — many short records, a long tail."""
    import numpy as np

    lo, hi = lo_hi
    return np.exp(rng.uniform(np.log(lo), np.log(hi), size=n)).astype(int)


class Oracle:
    """The host serial DFA parser over a Parser's own tables."""

    def __init__(self, parser):
        from repro.core.automata import build_dfa, build_nfa

        self.matrices = parser.matrices
        self.nfa = build_nfa(self.matrices.table)
        self.dfa = build_dfa(self.nfa)
        self.rdfa = build_dfa(self.nfa.reverse())

    def __call__(self, text):
        from repro.core.serial import parse_serial_dfa

        return parse_serial_dfa(self.matrices, text, self.dfa, self.rdfa, self.nfa)


# ------------------------------------------------------------------ phases


def phase_long_text(ctx):
    from benchmarks.benchmark_res import TRAFFIC_RE, make_text_exact
    from repro import Parser, ParserConfig

    text = make_text_exact("TRAFFIC", LONG_TEXT_BYTES, seed=1)
    parsers = {
        name: Parser(ParserConfig(regex=TRAFFIC_RE, backend=name))
        for name in ("auto", "jnp", "packed")
    }
    check(
        parsers["auto"].backend_name == "sparse",
        f"auto resolved to {parsers['auto'].backend_name}, expected sparse",
    )
    ctx["traffic"] = parsers["auto"]
    ctx["oracle"] = Oracle(parsers["auto"])
    want = ctx["oracle"](text)
    wrong = []
    for name, p in parsers.items():
        got = p.parse(text)
        if not (got.ok and same_forest(got.forest, want)):
            wrong.append(f"{name} (ok={got.ok})")
    check(not wrong, f"forest != serial oracle: {', '.join(wrong)}")
    return f"{len(text)} B, backends auto(sparse)/jnp/packed == oracle"


def phase_requests(ctx):
    import numpy as np

    from benchmarks.benchmark_res import make_text_exact

    p, oracle = ctx["traffic"], ctx["oracle"]
    rng = np.random.default_rng(3)
    texts = [
        make_text_exact("TRAFFIC", int(n), seed=100 + i)
        for i, n in enumerate(lengths(rng, N_REQUESTS, REQUEST_BYTES))
    ]
    wants = [oracle(t) for t in texts]
    batch = p.parse_batch(texts)
    singles = [p.submit(t).result() for t in texts]
    for i, (want, a, b) in enumerate(zip(wants, batch, singles)):
        check(a.ok and b.ok, f"request {i} rejected")
        check(same_forest(a.forest, want), f"parse_batch request {i} != oracle")
        check(same_forest(b.forest, want), f"submit request {i} != oracle")
    sizes = [len(t) for t in texts]
    return f"{len(texts)} texts, {min(sizes)}–{max(sizes)} B, batch+submit == oracle"


def phase_stream(ctx):
    import numpy as np

    from benchmarks.benchmark_res import make_text_exact

    p = ctx["traffic"]
    text = make_text_exact("TRAFFIC", STREAM_BYTES, seed=2)
    rng = np.random.default_rng(4)
    with p.open_stream() as s:
        pos = n_pieces = 0
        while pos < len(text):
            step = int(rng.integers(*STREAM_PIECE_BYTES))
            s.append(text[pos:pos + step])
            pos += step
            n_pieces += 1
        # replace one mid-text record with another valid record
        lo = text.index(b"\n", len(text) // 2) + 1
        hi = text.index(b"\n", lo) + 1
        repl = b"PUT /chip/smoke 200 ok\n"
        s.edit(lo, hi, repl)
        edited = text[:lo] + repl + text[hi:]
        got = s.result()
    cold = p.parse(edited)
    check(cold.ok and got.ok, "edited stream text rejected")
    check(same_forest(got.forest, cold.forest), "stream result != cold parse")
    return f"{len(text)} B in {n_pieces} appends + 1 edit == cold parse"


def phase_fleet(ctx):
    import numpy as np

    from benchmarks.benchmark_res import BENCHMARKS, make_text_exact
    from repro import Parser, ParserConfig, ParserFleet

    configs = {n: ParserConfig(regex=r, backend="auto") for n, r in BENCHMARKS.items()}
    fleet = ParserFleet(configs)
    rng = np.random.default_rng(5)
    items = [
        (name, make_text_exact(name, int(n), seed=200 + 10 * j + i))
        for j, name in enumerate(configs)
        for i, n in enumerate(lengths(rng, FLEET_TEXTS_PER_TENANT, FLEET_BYTES))
    ]
    items = [items[i] for i in rng.permutation(len(items))]
    got = fleet.parse_batch(items)
    solo = {n: Parser(c) for n, c in configs.items()}
    for (name, text), res in zip(items, got):
        want = solo[name].parse(text)
        check(res.ok, f"fleet {name}: text rejected")
        check(same_forest(res.forest, want.forest), f"fleet {name} != solo Parser")
    return f"{len(items)} mixed requests over {len(configs)} tenants == solo"


def assert_mosaic(parser, n_chars: int) -> None:
    """The parser's compiled program runs its Pallas kernels in Mosaic."""
    from repro.kernels.ops import use_interpret

    check(not use_interpret(), "kernels would run in interpret mode")
    c, k = parser.engine.bucket_shape(n_chars, parser.config.n_chunks)
    hlo = parser.engine.lower_batch(1, c, k).compile().as_text()
    check("tpu_custom_call" in hlo, f"{parser.backend_name}: no tpu_custom_call")


def phase_dense(ctx):
    import numpy as np

    from repro import Parser, ParserConfig
    from repro.core.segments import compute_segments

    table = compute_segments("(a|b)*a(a|b){125}")
    ref = Parser.from_matrices(table, ParserConfig(regex="<e125>"))
    rng = np.random.default_rng(6)
    cls = rng.choice([97, 98], size=DENSE_TEXT_BYTES).astype(np.uint8)
    cls[-126] = 97                      # accepted: 'a' 126 from the end
    text = cls.tobytes()
    want = ref.parse(text)
    check(want.ok, "e(125) text rejected by jnp")
    for backend, kernel in (("pallas", False), ("packed", True), ("sparse", True)):
        p = Parser.from_matrices(
            ref.matrices,
            ParserConfig(regex="<e125>", backend=backend, kernel=kernel),
        )
        got = p.parse(text)
        label = backend + ("+kernel" if kernel else "")
        check(same_forest(got.forest, want.forest), f"{label} != jnp at ℓ=257")
        assert_mosaic(p, len(text))
    return (
        f"ℓ={table.n}, {len(text)} B: pallas, packed+kernel, sparse+kernel "
        "== jnp, each with tpu_custom_call"
    )


def phase_mesh(ctx):
    import jax
    import numpy as np

    from benchmarks.benchmark_res import TRAFFIC_RE, make_text_exact
    from repro import Parser, ParserConfig

    mesh_p = Parser(ParserConfig(regex=TRAFFIC_RE, backend="auto", mesh="host"))
    solo = Parser(ParserConfig(regex=TRAFFIC_RE, backend="auto"))
    mesh = mesh_p.engine.mesh
    dist = mesh_p.engine.dist
    check(dict(mesh.shape) == {"pod": 2, "data": 2}, f"mesh shape {dict(mesh.shape)}")
    check(
        set(mesh.devices.flat) == set(jax.devices()) and mesh.devices.size == 4,
        "mesh does not span the four devices",
    )
    check(dist.chunk_devices == 4, f"long text splits over {dist.chunk_devices} devices")
    check(
        dist.batch_devices * dist.batch_chunk_devices == 4,
        "batch route does not use all four devices",
    )
    text = make_text_exact("TRAFFIC", LONG_TEXT_BYTES, seed=1)
    got = mesh_p.parse(text)
    check(got.ok, "mesh: 2 MB TRAFFIC text rejected")
    check(same_forest(got.forest, solo.parse(text).forest), "mesh parse != one device")
    # the compiled long-text program is partitioned over all four devices
    t = mesh_p.engine.tables
    c, k = mesh_p.engine.bucket_shape(len(text), 8)
    chunks = jax.ShapeDtypeStruct((c, k), np.int32)
    compiled = dist.chunk_program.lower(t.N, t.I, t.F, chunks).compile()
    in_devices = compiled.input_shardings[0][3].device_set
    check(len(in_devices) == 4, f"chunk operand on {len(in_devices)} devices")
    check("all-gather" in compiled.as_text(), "no product all-gather")
    texts = [make_text_exact("TRAFFIC", 4096 * (i + 1), seed=300 + i)
             for i in range(MESH_BATCH_TEXTS)]
    for i, (a, b) in enumerate(zip(mesh_p.parse_batch(texts), solo.parse_batch(texts))):
        check(a.ok, f"mesh batch text {i} rejected")
        check(same_forest(a.forest, b.forest), f"mesh batch text {i} != one device")
    return (
        f"(2, 2) pod×data over {mesh.devices.size} devices: {len(text)} B parse "
        f"and {len(texts)}-text batch == one device"
    )


ONE_CHIP_PHASES = (
    ("b.long_text", phase_long_text),
    ("c.requests", phase_requests),
    ("d.stream", phase_stream),
    ("e.fleet", phase_fleet),
    ("f.dense_kernels", phase_dense),
)
FOUR_CHIP_PHASES = (("mesh", phase_mesh),)


def run_phases(phases, clock) -> bool:
    """Run every phase, print its outcome and bring-up times; True iff all
    passed.  A failing phase prints its traceback and the run goes on, so
    one chip call reports every phase."""
    ok = True
    ctx = {}
    for name, fn in phases:
        tr0, be0, h0 = clock.trace_s, clock.backend_s, clock.cache_hits
        t0 = time.perf_counter()
        try:
            detail, status = fn(ctx), "PASS"
        except Exception:
            traceback.print_exc()
            detail, status, ok = "see traceback on stderr", "FAIL", False
        wall = time.perf_counter() - t0
        trace_s, backend_s = clock.trace_s - tr0, clock.backend_s - be0
        print(
            f"phase {name}: {status} — {detail} | bring-up wall {wall:.2f} s "
            f"(compile {trace_s + backend_s:.2f} s = trace+lower {trace_s:.2f} s "
            f"+ backend {backend_s:.2f} s, other {wall - trace_s - backend_s:.2f} s, "
            f"cache hits {clock.cache_hits - h0})",
            flush=True,
        )
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1: the one-chip phases (default); 4: only the mesh routes",
    )
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir() or not (
        ROOT / "benchmarks" / "benchmark_res.py"
    ).is_file():
        print(
            f"chip_smoke: {ROOT} is not a checkout of the parser "
            "(src/repro and benchmarks/ are missing)",
            file=sys.stderr,
        )
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu":
        print(
            f"chip_smoke: JAX found no TPU (platform {platform!r}); "
            "this run needs the chip and does not fall back",
            file=sys.stderr,
        )
        return 1
    if len(devices) < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
            f"JAX sees {len(devices)}",
            file=sys.stderr,
        )
        return 1
    print(f"device: platform={platform} kind={kind} count={len(devices)}")
    print(f"compile cache: {cache_dir}")
    print("times below are bring-up wall times, not benchmark numbers")

    clock = CompileClock()
    t0 = time.perf_counter()
    phases = FOUR_CHIP_PHASES if args.chips == 4 else ONE_CHIP_PHASES
    ok = run_phases(phases, clock)
    print(
        f"total bring-up wall {time.perf_counter() - t0:.2f} s "
        f"(compile {clock.seconds:.2f} s = trace+lower {clock.trace_s:.2f} s "
        f"+ backend {clock.backend_s:.2f} s, cache hits {clock.cache_hits})"
    )
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": platform, "kind": kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
