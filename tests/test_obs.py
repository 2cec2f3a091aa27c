"""Observability layer (repro/obs): tracer, metrics registry, exporters.

Covers the acceptance bars of the observability PR:
  * span mechanics — contextvars parenting, retroactive ``emit`` against a
    pre-minted root id, disabled-tracer no-ops, the bounded ring buffer;
  * ``validate_span_tree`` structural guarantees (one root, parents resolve,
    child durations bounded) and its failure modes;
  * ``MetricsRegistry`` — the METRIC_CATALOG rot guard (unknown name is a
    ``KeyError`` at creation time), counter monotonicity, labeled series,
    Prometheus rendering;
  * the shared BENCH_*.json perf-trajectory schema;
  * facade integration — a traced ``Parser.parse`` leaves a complete span
    tree of the phase-split route in the JSONL log, and a ``submit`` →
    ticket.result() round trip one of the fused route (queue wait, batch
    compute over host prep / device / fetch / assembly, speculation),
    batch riders included; tracing changes no forest; ``Parser.stats()``
    is a live registry view;
  * the fused route's counters (padded / text cells per bucket, device to
    host bytes), and the mesh routes' shared fetch / assembly spans;
  * the split queue-wait / compute latency windows wrap independently at
    ``LATENCY_WINDOW`` samples (regression: one window used to conflate
    wait with compute).
"""

import json

import numpy as np
import pytest

import repro
from repro.obs import (
    METRIC_CATALOG,
    MetricsRegistry,
    Tracer,
    prometheus_text,
    read_spans_jsonl,
    validate_bench_report,
    validate_metric_names,
    validate_span_dict,
    validate_span_tree,
    write_bench_json,
)
from repro.serve.parse_service import LATENCY_WINDOW, BucketStats

PATTERN = "(a|b|ab)+"


# ------------------------------------------------------------------ tracer


def test_span_nesting_parents_via_context():
    tr = Tracer(enabled=True)
    tid = tr.new_trace_id()
    with tr.span("parse.request", trace_id=tid) as root:
        with tr.span("phase.reach") as child:
            pass
    spans = tr.drain()
    assert [s.name for s in spans] == ["phase.reach", "parse.request"]
    reach, req = spans
    assert reach.trace_id == tid          # inherited from the open parent
    assert reach.parent_id == req.span_id
    assert req.parent_id is None
    assert req.duration_s >= reach.duration_s >= 0.0


def test_emit_accepts_preminted_root_id():
    # the service pattern: children are written mid-flight against a root id
    # minted at submit; the root span itself lands only at collection
    tr = Tracer(enabled=True)
    tid = tr.new_trace_id()
    root_id = tr._new_span_id()
    tr.emit("parse.queue_wait", t_start_s=1.0, duration_s=0.5,
            trace_id=tid, parent_id=root_id)
    tr.emit("parse.request", t_start_s=1.0, duration_s=2.0,
            trace_id=tid, span_id=root_id)
    dicts = [s.to_dict() for s in tr.drain()]
    for d in dicts:
        validate_span_dict(d)
    tree = validate_span_tree(dicts, tid)
    assert tree["root"]["span_id"] == root_id
    assert [c["name"] for c in tree["children"]] == ["parse.queue_wait"]


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    assert tr.new_trace_id() is None
    with tr.span("parse.request") as sp:
        sp.set_attr("ignored", 1)         # NullSpan: attribute sink
    assert tr.emit("x", t_start_s=0.0, duration_s=0.0) is None
    assert tr.drain() == []


def test_ring_buffer_bounded():
    tr = Tracer(enabled=True, max_spans=4)
    for i in range(10):
        tr.emit(f"s{i}", t_start_s=float(i), duration_s=0.0)
    names = [s.name for s in tr.drain()]
    assert names == ["s6", "s7", "s8", "s9"]


def test_validate_span_tree_failure_modes():
    def span(name, sid, parent=None):
        return {"name": name, "trace_id": "t", "span_id": sid,
                "parent_id": parent, "t_start_s": 0.0, "duration_s": 1.0,
                "attrs": {}}

    with pytest.raises(ValueError, match="no spans"):
        validate_span_tree([], "t")
    with pytest.raises(ValueError, match="2 roots"):
        validate_span_tree([span("a", "1"), span("b", "2")], "t")
    with pytest.raises(ValueError, match="not in trace"):
        validate_span_tree([span("a", "1"), span("b", "2", parent="missing")],
                           "t")
    # direct children summing past the root wall-clock is a broken tree
    bad = [span("root", "1"),
           span("c1", "2", parent="1"), span("c2", "3", parent="1")]
    with pytest.raises(ValueError, match="exceed root"):
        validate_span_tree(bad, "t")


# ----------------------------------------------------------------- metrics


def test_unknown_metric_name_is_keyerror():
    reg = MetricsRegistry()
    with pytest.raises(KeyError, match="unknown metric"):
        reg.counter("requests_totl")      # typo must fail loudly
    with pytest.raises(KeyError):
        reg.gauge("no_such_gauge")
    with pytest.raises(KeyError):
        reg.histogram("no_such_histogram")


def test_counter_monotonic_and_labeled_series():
    reg = MetricsRegistry()
    a = reg.counter("requests_total", service="parse")
    b = reg.counter("requests_total", service="stream")
    a.inc()
    a.inc(2)
    b.inc()
    assert a.value == 3 and b.value == 1  # distinct labeled series
    assert reg.counter("requests_total", service="parse") is a
    validate_metric_names(reg.snapshot())
    text = prometheus_text(reg.snapshot())
    assert 'repro_requests_total{service="parse"} 3.0' in text
    assert 'repro_requests_total{service="stream"} 1.0' in text


def test_validate_metric_names_rejects_unknown():
    with pytest.raises(KeyError):
        validate_metric_names(["requests_total", "made_up_metric"])
    validate_metric_names(METRIC_CATALOG)  # the catalog validates itself


# ------------------------------------------------------------ BENCH schema


def test_bench_json_roundtrip(tmp_path):
    out = write_bench_json(
        "unit", config={"quick": True}, metrics={"rows": [{"v": 1}]},
        out_dir=tmp_path, timestamp=123.0,
    )
    assert out.name == "BENCH_unit.json"
    d = json.loads(out.read_text())
    validate_bench_report(d)
    assert d["name"] == "unit" and d["timestamp"] == 123.0
    assert d["metrics"]["rows"] == [{"v": 1}]


def test_bench_schema_violations(tmp_path):
    good = {"name": "x", "timestamp": 1.0, "config": {}, "metrics": {}}
    validate_bench_report(good)
    for break_it in (
        lambda d: d.pop("metrics"),
        lambda d: d.update(extra=1),
        lambda d: d.update(name=""),
        lambda d: d.update(timestamp=0),
        lambda d: d.update(config=[]),
    ):
        d = dict(good)
        break_it(d)
        with pytest.raises(ValueError):
            validate_bench_report(d)
    with pytest.raises(TypeError):        # must be JSON round-trippable
        write_bench_json("bad", config={}, metrics={"x": object()},
                         out_dir=tmp_path)


# ------------------------------------------------- facade integration


@pytest.fixture()
def traced_parser(tmp_path):
    log = tmp_path / "spans.jsonl"
    p = repro.Parser(repro.ParserConfig(
        regex=PATTERN, n_chunks=4,
        obs={"enabled": True, "span_log": str(log)},
    ))
    yield p, log
    p.close()


FUSED_CHILDREN = ["parse.host_prep", "parse.device", "parse.fetch",
                  "parse.assemble"]


def _children(spans, parent):
    return sorted((s for s in spans if s["parent_id"] == parent["span_id"]
                   and s["trace_id"] == parent["trace_id"]),
                  key=lambda s: s["t_start_s"])


def _request_tree(spans, trace_id):
    """The fused route's tree of one request: the root's direct children by
    name, and the children of its ``parse.batch_compute``."""
    tree = validate_span_tree(spans, trace_id)
    root = tree["root"]
    assert root["name"] == "parse.request"
    direct = {s["name"]: s for s in _children(spans, root)}
    assert set(direct) == {"parse.queue_wait", "parse.batch_compute",
                           "parse.speculation"}
    return root, direct, _children(spans, direct["parse.batch_compute"])


def test_traced_parse_emits_complete_span_tree(traced_parser):
    p, log = traced_parser
    r = p.parse("abab" * 8)
    assert r.ok and r.trace_id is not None
    spans = read_spans_jsonl(log)
    for d in spans:
        validate_span_dict(d)
    tree = validate_span_tree(spans, r.trace_id)
    assert tree["root"]["name"] == "parse.request"
    child_names = {c["name"] for c in tree["children"]}
    assert {"phase.reach", "phase.join", "phase.build_merge",
            "phase.host_build", "parse.speculation"} <= child_names


def test_trace_id_survives_submit_roundtrip(traced_parser):
    p, log = traced_parser
    ticket = p.submit("abab" * 4)
    r = ticket.result()
    assert r.ok and r.trace_id is not None
    _, _, compute_children = _request_tree(read_spans_jsonl(log), r.trace_id)
    assert [s["name"] for s in compute_children] == FUSED_CHILDREN


@pytest.mark.parametrize("backend", ["jnp", "sparse"])
def test_fused_children_fit_their_parents(backend):
    p = repro.Parser(repro.ParserConfig(
        regex=PATTERN, n_chunks=4, backend=backend, obs={"enabled": True},
    ))
    r = p.submit("ab" * 40).result()
    spans = [s.to_dict() for s in p.obs.tracer.drain()]
    root, direct, kids = _request_tree(spans, r.trace_id)
    compute = direct["parse.batch_compute"]
    assert sum(s["duration_s"] for s in kids) <= compute["duration_s"]
    end = compute["t_start_s"] + compute["duration_s"]
    for s in kids:
        assert compute["t_start_s"] <= s["t_start_s"]
        assert s["t_start_s"] + s["duration_s"] <= end
    assert (direct["parse.queue_wait"]["duration_s"] + compute["duration_s"]
            <= root["duration_s"])
    # speculation runs once the root is written, and names it as parent
    spec = direct["parse.speculation"]
    assert spec["t_start_s"] >= root["t_start_s"] + root["duration_s"]
    assert (r.speculation is not None) == (backend == "sparse")
    p.close()


def test_batch_riders_get_complete_trees(traced_parser):
    p, log = traced_parser
    tickets = [p.submit(t) for t in ("ab" * 5, "abab" * 3, "b" * 9)]
    results = [t.result() for t in tickets]
    spans = read_spans_jsonl(log)
    with_children = []
    for r in results:
        _, direct, kids = _request_tree(spans, r.trace_id)
        assert direct["parse.batch_compute"]["attrs"]["batch_size"] == 3
        if kids:
            with_children.append(kids)
    # the batch head's compute span is live and holds the engine's spans;
    # the riders' retroactive copies have none
    assert len(with_children) == 1
    assert [s["name"] for s in with_children[0]] == FUSED_CHILDREN
    assert with_children[0][0]["attrs"]["text_cells"] == 10 + 12 + 9


def test_untraced_parser_records_no_spans():
    p = repro.Parser(repro.ParserConfig(regex=PATTERN, n_chunks=4))
    p.parse("abab")
    p.submit("ab").result()
    p.engine.parse_phase_split("abab")
    assert p.obs.tracer.drain() == []
    assert "spans_recorded_total" not in p.stats()["metrics"]
    p.close()


def _value(p, name, **labels):
    for series in p.stats()["metrics"].get(name, []):
        if series["labels"] == labels:
            return series["value"]
    return 0.0


def test_cell_counters_count_padding():
    p = repro.Parser(repro.ParserConfig(regex=PATTERN, n_chunks=4))
    n = 37
    c, k = p.engine.bucket_shape(n, 4)
    assert (c, k) == (4, 16)
    p.parse("ab" * 18 + "a")
    assert _value(p, "text_cells_total", bucket="4x16") == n
    assert _value(p, "padded_cells_total", bucket="4x16") == c * k - n
    # three same-bucket texts ride one batch of B = 4 slots
    tickets = [p.submit("ab" * 17), p.submit("a" * 40), p.submit("b" * 33)]
    for t in tickets:
        t.result()
    assert _value(p, "text_cells_total", bucket="4x16") == n + 34 + 40 + 33
    assert _value(p, "padded_cells_total", bucket="4x16") == (
        (c * k - n) + (4 * c * k - (34 + 40 + 33)))
    p.close()


def test_d2h_bytes_counts_fetched_arrays():
    p = repro.Parser(repro.ParserConfig(regex=PATTERN, n_chunks=4,
                                        obs={"enabled": True}))
    eng = p.engine
    texts = ["ab" * 9, "ba" * 7, "abab"]
    classes = [eng.classes_of_text(t) for t in texts]
    c, k = eng.bucket_shape(18, 4)
    batch = np.full((4, c, k), eng.tables.pad_class, dtype=np.int32)
    for row, cls in enumerate(classes):
        batch[row] = eng._pad_to(cls, c, k)
    t = eng.tables
    col0s, colss = eng._jit_batched(t.N, t.I, t.F, batch)
    want = np.asarray(col0s).nbytes + np.asarray(colss).nbytes
    before = _value(p, "d2h_bytes_total")
    eng.parse_batch(texts, n_chunks=4)
    assert _value(p, "d2h_bytes_total") - before == want
    fetch = [s for s in p.obs.tracer.drain() if s.name == "parse.fetch"]
    assert [s.attrs["bytes"] for s in fetch] == [want]
    p.close()


def test_parse_phase_split_emits_phase_spans(traced_parser):
    p, _ = traced_parser
    text = "ab" * 21
    split = p.engine.parse_phase_split(text, n_chunks=4)
    names = [s.name for s in p.obs.tracer.drain()]
    assert names == ["phase.reach", "phase.join", "phase.build_merge",
                     "phase.host_build"]
    assert np.array_equal(split.pack(), p.submit(text).result().forest.pack())


MESH_PHASES = ["parse.host_prep", "phase.reach", "phase.join",
               "phase.build_merge", "phase.host_build"]


def test_mesh_direct_route_spans():
    # a (1, 1) mesh on the plain suite's one device: the sharded phase
    # programs run without collectives, through the same spans as a real mesh
    p = repro.Parser(repro.ParserConfig(
        regex=PATTERN, n_chunks=4, mesh="host", obs={"enabled": True},
    ))
    plain = repro.Parser(repro.ParserConfig(regex=PATTERN, n_chunks=4))
    text = "ab" * 12
    r = p.parse(text)
    spans = [s.to_dict() for s in p.obs.tracer.drain()]
    tree = validate_span_tree(spans, r.trace_id)
    root = tree["root"]
    direct = [s["name"] for s in _children(spans, root)]
    assert direct == MESH_PHASES + ["parse.speculation"]
    host_build = _children(spans, root)[MESH_PHASES.index("phase.host_build")]
    assert [s["name"] for s in _children(spans, host_build)] == [
        "parse.fetch", "parse.assemble"]
    assert "phase.device_parse" not in {s["name"] for s in spans}
    assert np.array_equal(r.forest.pack(), plain.parse(text).forest.pack())
    plain.close()
    p.close()


@pytest.mark.parametrize("route", ["parse", "parse_batch"])
def test_mesh_fused_route_spans_carry_cells_and_chips(route):
    p = repro.Parser(repro.ParserConfig(
        regex=PATTERN, n_chunks=4, mesh="host", obs={"enabled": True},
    ))
    eng = p.engine
    texts = ["ab" * 9 + "a", "ba" * 5]
    if route == "parse":
        texts = texts[:1]
        eng.parse(texts[0], n_chunks=4)
    else:
        eng.parse_batch(texts, n_chunks=4)
    n = [len(t) for t in texts]
    c, k = eng.bucket_shape(max(n), 4)
    B = 1 if route == "parse" else 2
    spans = {s.name: s for s in p.obs.tracer.drain()}
    assert list(spans) == ["parse.host_prep", "parse.device", "parse.fetch",
                           "parse.assemble"]
    prep, device = spans["parse.host_prep"], spans["parse.device"]
    assert prep.attrs == {"bucket": [c, k], "cells": B * c * k,
                          "text_cells": sum(n)}
    assert device.attrs == {"bucket": [c, k], "batch": B, "chips": 1,
                            "sub_chains": 1}
    label = f"{c}x{k}"
    assert _value(p, "text_cells_total", bucket=label) == sum(n)
    assert _value(p, "padded_cells_total", bucket=label) == B * c * k - sum(n)
    p.close()


def test_mesh_join_gather_bytes_match_the_counter():
    p = repro.Parser(repro.ParserConfig(
        regex=PATTERN, n_chunks=4, mesh="host", obs={"enabled": True},
    ))
    dist = p.engine.dist
    before = _value(p, "allgather_payload_bytes_total")
    p.parse("ab" * 21)
    join = [s for s in p.obs.tracer.drain() if s.name == "phase.join"]
    assert len(join) == 1
    moved = _value(p, "allgather_payload_bytes_total") - before
    assert join[0].attrs["gather_bytes"] == moved == 4 * dist._product_nbytes()
    assert join[0].attrs["chips"] == 1 and join[0].attrs["n_products"] == 4
    p.close()


@pytest.mark.slow
def test_mesh_routes_fetch_and_assemble_4dev():
    """Both mesh routes on four virtual CPU devices (the device count is
    fixed at jax start, so a fresh process sets it): the chunk-sharded
    direct parse and the batch-sharded service route finish through the
    engine's fetch / assembly spans, with forests equal to one device's."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np, repro
assert len(jax.devices()) == 4
cfg = dict(regex="(a|b|ab)+", n_chunks=4)
mesh = repro.Parser(repro.ParserConfig(mesh="host", obs={"enabled": True}, **cfg))
one = repro.Parser(repro.ParserConfig(**cfg))
for route in ("parse", "submit"):
    text = "ab" * 19
    r = mesh.parse(text) if route == "parse" else mesh.submit(text).result()
    assert np.array_equal(r.forest.pack(), one.parse(text).forest.pack())
    names = [s.name for s in mesh.obs.tracer.drain() if s.trace_id == r.trace_id]
    assert "parse.fetch" in names and "parse.assemble" in names, (route, names)
print("MESH-SPANS-OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).parents[1] / "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MESH-SPANS-OK" in out.stdout


def test_traced_route_bit_identical_to_fused(traced_parser):
    p, _ = traced_parser
    plain = repro.Parser(repro.ParserConfig(regex=PATTERN, n_chunks=4))
    text = "ab" * 37
    want = plain.parse(text).forest.pack()
    assert np.array_equal(p.parse(text).forest.pack(), want)
    assert np.array_equal(p.submit(text).result().forest.pack(), want)
    plain.close()


def test_stream_appends_form_span_trees(traced_parser):
    p, log = traced_parser
    with p.open_stream() as stream:
        stream.append("abab")
        stream.append("ab" * 10)
        assert stream.accepted
    spans = read_spans_jsonl(log)
    roots = [s for s in spans if s["name"] == "stream.append"]
    assert len(roots) == 2
    for root in roots:
        tree = validate_span_tree(spans, root["trace_id"])
        names = {c["name"] for c in tree["children"]}
        assert {"stream.append_queue_wait", "stream.append_compute"} <= names


def test_stats_is_live_registry_view(traced_parser):
    p, _ = traced_parser

    def served():
        snap = p.stats()["metrics"]
        return sum(s["value"] for s in snap.get("requests_total", []))

    p.parse("abab")
    first = served()
    p.parse("abab")
    p.submit("abab").result()
    second = served()
    assert second == first + 2            # counters only ever move up
    validate_metric_names(p.stats()["metrics"])


# ------------------------------------------- latency window split


def test_bucket_stats_windows_wrap_independently():
    s = BucketStats()
    # 100 fast-queue samples, then LATENCY_WINDOW + 100 slow-queue samples:
    # once wrapped, the window must contain ONLY the recent regime
    for _ in range(100):
        s.record(0.2, queue_s=0.0, compute_s=0.2)
    for _ in range(LATENCY_WINDOW + 100):
        s.record(1.5, queue_s=1.0, compute_s=0.5)
    assert len(s.window) == LATENCY_WINDOW
    assert len(s.queue_window) == LATENCY_WINDOW
    assert len(s.compute_window) == LATENCY_WINDOW
    d = s.as_dict()
    assert d["p50_queue_s"] == d["p99_queue_s"] == 1.0
    assert d["p50_compute_s"] == d["p99_compute_s"] == 0.5
    assert d["p50_latency_s"] == d["p99_latency_s"] == 1.5
    # lifetime aggregates still see every sample
    assert d["served"] == LATENCY_WINDOW + 200
    assert d["max_latency_s"] == 1.5


def test_bucket_stats_single_positional_record():
    # pre-split call sites record latency only; the split windows stay empty
    s = BucketStats()
    s.record(5.0)
    d = s.as_dict()
    assert d["p99_latency_s"] == 5.0
    assert d["p50_queue_s"] == 0.0 and d["p50_compute_s"] == 0.0


def test_window_quantile_nearest_rank():
    # nearest-rank: a 2-sample window's p99 is its slowest OBSERVED sample,
    # not an interpolated value just below it (the admission predictor must
    # not under-report)
    s = BucketStats()
    s.record(0.1)
    s.record(0.5)
    assert s.latency_quantile_s(99.0) == 0.5
