"""The reduction from a profiler trace to busy time, idle share and breakdown."""

import gzip
import shutil
from pathlib import Path

import numpy as np
import pytest

from harness import trace

DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A trace recorded on one TPU v5e: a batch of eight 64 B TRAFFIC
    requests and one single parse, inside ``bench.generate``/``bench.parse``
    annotations."""
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(DATA / "tiny_parse.xplane.pb.gz") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.extract(str(path))


def test_recorded_trace_window_and_busy(tiny):
    s = trace.summarize(tiny)
    assert len(tiny.devices) == 1 and tiny.devices[0].name == "/device:TPU:0"
    assert s.window_s == pytest.approx(0.010388027, abs=1e-9)
    # two programs ran: 319.251 us and 82.013 us; their ops lie inside them
    assert s.busy_s == pytest.approx(0.000401264, abs=1e-9)
    idle = 100 * (1 - s.busy_s / s.window_s)
    assert 95 < idle < 100
    assert not s.dropped


def test_recorded_trace_breakdown(tiny):
    b = trace.summarize(tiny).breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "while.6"
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    # the longest idle stretch is the host waiting on the device-to-host copy
    # of the results; the per-request speculation-width pass is among the rest
    names = [n for n, _ in b["idle_gaps"]]
    assert names[0] == "$array.py:631 _value"
    assert "$matrices.py:431 feasible_start_widths" in names
    # every idle second of the window is attributed somewhere
    s = trace.summarize(tiny)
    assert sum(s.idle_by_host.values()) == pytest.approx(s.window_s - s.busy_s, rel=1e-9)


def test_union_and_gaps():
    iv = np.array([[5, 7], [0, 2], [1, 3], [10, 12], [6, 9]], dtype=float)
    u = trace.union(iv, 0, 11)
    assert u.tolist() == [[0, 3], [5, 9], [10, 11]]
    assert trace.gaps(u, -1, 11).tolist() == [[-1, 0], [3, 5], [9, 10]]
    assert trace.union(np.zeros((0, 2)), 0, 1).shape == (0, 2)


def _events(device_spans, host, window):
    devs = [
        trace.DeviceTrace(f"/device:TPU:{i}", np.array(sp, float).reshape(-1, 2), ops, None)
        for i, (sp, ops) in enumerate(device_spans)
    ]
    names = [n for n, _, _ in host]
    iv = np.array([(a, b) for _, a, b in host], float).reshape(-1, 2)
    return trace.Events(devs, trace.HostTrace(names, iv), window)


def test_mean_over_devices():
    ev = _events(
        [
            ([[0, 600e6]], {"all-gather.3": 2e6, "fusion.1": 500e6}),
            ([[0, 200e6]], {"all-gather.3": 4e6, "fusion.1": 150e6}),
        ],
        [("bench.parse", 0, 1e9), ("$engine.py:408 _assemble", 600e6, 1e9)],
        (0.0, 1e9),
    )
    s = trace.summarize(ev)
    assert s.window_s == 1.0
    assert s.busy_s == pytest.approx(0.4)
    assert dict(s.breakdown()["device_ops"]) == pytest.approx(
        {"fusion.1": 0.325, "all-gather.3": 0.003})
    # device 0 idles 0.4 s and device 1 0.8 s: the mean is 0.6 s, split by
    # what the host was doing in each stretch of each gap
    assert s.idle_by_host["$engine.py:408 _assemble"] == pytest.approx(0.4 / 2 + 0.4 / 2)
    assert s.idle_by_host["bench.parse"] == pytest.approx(0.4 / 2)
    assert sum(s.idle_by_host.values()) == pytest.approx(0.6)


def test_window_ends_where_the_profiler_dropped_events():
    ev = _events([([[0, 600e6]], {})], [("bench.parse", 0, 1e9)], (0.0, 1e9))
    ev.devices[0].dropped_from_ns = 800e6
    s = trace.summarize(ev)
    assert s.dropped and s.window_s == pytest.approx(0.8) and s.busy_s == pytest.approx(0.6)
    reader = __import__("harness.runner", fromlist=["load_module"]).load_module(
        Path(__file__).resolve().parents[1] / "metrics" / "device_idle_share.requests.py")
    assert reader.read({"profile": s}) is None
    ev.devices[0].dropped_from_ns = None
    assert reader.read({"profile": trace.summarize(ev)}) == pytest.approx(40.0)


def test_short_gaps_are_summed_apart():
    spans = [[i * 4e3, i * 4e3 + 3e3] for i in range(100)]       # 1 us bubbles
    ev = _events([(spans, {})], [("bench.parse", 0, 400e3)], (0.0, 400e3))
    s = trace.summarize(ev)
    assert list(s.idle_by_host) == ["device op-to-op (< 10 us)"]
    assert s.idle_by_host["device op-to-op (< 10 us)"] == pytest.approx(100e-6)


def test_host_timeline_nests_and_window_needs_an_annotation():
    host = trace.HostTrace(
        ["$a.py:1 f", "$a.py:2 g", "$a.py:3 h"],
        np.array([[0, 10], [2, 4], [6, 12]], float),
    )
    segs, labels = trace.host_timeline(host)
    assert segs.tolist() == [[0, 2], [2, 4], [4, 6], [6, 12]]
    assert labels == ["$a.py:1 f", "$a.py:2 g", "$a.py:1 f", "$a.py:3 h"]
    with pytest.raises(ValueError):
        trace.window_of(host)
