"""The end-to-end arithmetic of both kind runners, on a stand-in parser whose
timing the test controls."""

import math
import time
import types

import jax
import pytest

from harness import runner
from harness.compile_clock import CompileClock
from harness.reference import Reference, unpack_columns
from harness.stats import nearest_rank


class Stub:
    """Answers like the program (the reference's forest) after ``delay``
    seconds; once the window is open, ``stall`` adds seconds to the n-th
    call and ``fail`` raises on the calls whose index it holds."""

    def __init__(self, pattern, delay, stall=None, fail=()):
        self.ref = Reference(pattern)
        self.delay, self.stall, self.fail, self.calls = delay, stall or {}, set(fail), -1 << 40
        self.config = types.SimpleNamespace(n_chunks=8, max_batch=8)
        self.engine = types.SimpleNamespace(bucket_shape=lambda n, c: (c, max(8, 1 << (max(1, -(-n // c)) - 1).bit_length())))

    def parse(self, text):
        i = self.calls
        self.calls += 1
        if i in self.fail:
            raise RuntimeError("refused")
        time.sleep(self.delay + self.stall.get(i, 0.0))
        cols = unpack_columns(self.ref.packed_columns(text), self.ref.ell)
        return types.SimpleNamespace(forest=types.SimpleNamespace(columns=cols), ok=bool(cols[-1].any()))

    def submit(self, text):
        i = self.calls
        if i in self.fail:
            self.calls += 1
            raise RuntimeError("refused")
        return types.SimpleNamespace(result=lambda: self.parse(text))


def _run(cell, stub, seconds):
    run = runner.Run(cell, 11, seconds, False, time.perf_counter(), jax.devices()[:1],
                     CompileClock(), lambda m: None)
    run.build_parser = lambda traced=False: stub
    opened = run.window_open

    def window_open():                  # arm the stand-in: count calls from here
        stub.calls = 0
        return opened()

    run.window_open = window_open
    return run, cell.kind.run(run)


@pytest.fixture
def long_cell():
    cell = runner.load_cell("traffic_long")
    cell.traffic["text_bytes"] = 2000
    return cell


@pytest.fixture
def request_cell(held_out_root):
    cell = runner.load_cell("traffic_requests", root=held_out_root)
    cell.traffic.update(rate_per_s=100, size_bytes=[64, 256], size_weights=[1, 1],
                        checked_requests=20)
    return cell


def test_text_rate_is_all_bytes_over_all_the_window(long_cell):
    _, out = _run(long_cell, Stub(long_cell.config["regex"], 0.02), 0.5)
    n = out.attempted
    assert out.failed == 0 and n >= 10
    # about 2000 B per 20 ms parse (the stand-in's reference adds a little)
    assert 0.03 < out.end_to_end["text_MBps"] < 0.1
    assert len(out.answers) == long_cell.traffic["checked_parses"]


def test_a_stall_lowers_the_text_rate(long_cell):
    regex = long_cell.config["regex"]
    _, calm = _run(long_cell, Stub(regex, 0.02), 0.6)
    _, stalled = _run(long_cell, Stub(regex, 0.02, stall={3: 0.3}), 0.6)
    assert stalled.end_to_end["text_MBps"] < 0.8 * calm.end_to_end["text_MBps"]


def test_a_parse_that_starts_in_the_window_is_counted(long_cell):
    # one parse of 0.4 s started at once in a 0.1 s window: it finishes and counts
    _, out = _run(long_cell, Stub(long_cell.config["regex"], 0.4), 0.1)
    assert out.attempted == 1 and out.end_to_end["text_MBps"] > 0


def test_a_stall_raises_the_request_tail(request_cell):
    regex = request_cell.config["regex"]
    _, calm = _run(request_cell, Stub(regex, 0.001), 1.0)
    _, stalled = _run(request_cell, Stub(regex, 0.001, stall={40: 0.3}), 1.0)
    assert calm.failed == 0 and calm.attempted == 100
    # the stall delays every request due during it: well over 5 % of them
    assert stalled.end_to_end["request_p95_ms"] > 100
    assert calm.end_to_end["request_p95_ms"] < 50


def test_a_failed_request_counts_as_late(request_cell):
    regex = request_cell.config["regex"]
    _, out = _run(request_cell, Stub(regex, 0.001, fail=range(0, 100, 10)), 1.0)
    assert out.failed == 10
    # 10 % of requests never answered: the 95th percentile is infinitely late
    assert math.isinf(out.end_to_end["request_p95_ms"])


def test_nearest_rank_sorts_failures_last():
    assert nearest_rank([3.0, 1.0, 2.0, math.inf], 50) == 2.0
    assert nearest_rank([3.0, 1.0, 2.0, math.inf], 95) == math.inf
    assert nearest_rank(list(range(1, 101)), 95) == 95
