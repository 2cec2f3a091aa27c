"""The four ``.mesh`` readers: each takes the median of its phase span, in ms,
on made-up spans and on the spans a traced ``mesh="host"`` parser records."""

import json

import pytest

from harness import runner

MESH_READERS = {
    "reach_ms.mesh": "phase.reach",
    "join_ms.mesh": "phase.join",
    "build_merge_ms.mesh": "phase.build_merge",
    "host_build_ms.mesh": "phase.host_build",
}


def _reader(name):
    return runner.load_module(runner.BENCH / "metrics" / f"{name}.py")


@pytest.mark.parametrize("name", sorted(MESH_READERS))
def test_mesh_reader_takes_the_median_of_its_span(name):
    from repro.obs.trace import Span

    span = MESH_READERS[name]
    spans = [Span(span, None, str(i), None, 0.0, d) for i, d in enumerate((0.3, 0.1, 0.2))]
    spans.append(Span("phase.device_parse", None, "x", None, 0.0, 9.0))
    assert _reader(name).read({"spans": spans}) == pytest.approx(200.0)
    assert _reader(name).read({"spans": spans[3:]}) is None


@pytest.fixture(scope="module")
def mesh_spans():
    """Spans a traced ``mesh="host"`` parser records on the CPU's one device
    (a (1, 1) mesh): the same route and span names as on four chips."""
    from repro import Parser, ParserConfig
    from repro.obs import ObsConfig

    config = json.loads((runner.BENCH / "configs" / "traffic_log_mesh4.json").read_text())
    kw = dict(config["parser"], n_chunks=4)
    p = Parser(ParserConfig(regex=config["regex"], obs=ObsConfig(enabled=True), **kw))
    text = b"GET /a1 200 ok\nPUT / 404 -\n" * 40
    for _ in range(3):
        p.parse(text)
    spans = p.obs.tracer.drain()
    p.close()
    return spans


@pytest.mark.parametrize("name", sorted(MESH_READERS))
def test_mesh_reader_reads_the_recorded_route(name, mesh_spans):
    ms = sorted(s.duration_s * 1e3 for s in mesh_spans if s.name == MESH_READERS[name])
    assert len(ms) == 3
    assert _reader(name).read({"spans": mesh_spans}) == ms[1] > 0
