"""Closed loop of long-text parses: ``Parser.parse``, one at a time.

Traffic parameters (``bench/traffic/<mix>.json``):

    text_bytes       length of every text (whole records plus the template's
                     suffix, at most this many bytes)
    checked_parses   answers kept, by seeded reservoir sampling over the
                     window's parses, for the comparison with the reference
    profiled_parses  parses under the profiler in a ``--trace 1`` run
    phase_split_parses  parses of the phase-split route in a ``--trace 1``
                     run, for the phase spans

``text_MBps`` is the bytes of text whose parse completed over the time from
the window's start to the last completion; a parse that starts before the
window's end is finished and counted.  A ``--trace 1`` run profiles
``profiled_parses`` parses of the same route, then runs
``phase_split_parses`` parses through the phase-split route
(``ObsConfig(enabled=True)``) for the phase spans.  The profiler records
every step of the parse's while loops, so stopping it takes minutes for a
2 MB text; that, not the window, sets a traced run's length.
"""

from __future__ import annotations

import time
import traceback

from harness import profile
from harness.device import memory_peak_bytes
from harness.runner import Answer, Outcome
from harness.stats import median
from harness.textgen import Pool

GENERATE, PARSE = "bench.generate", "bench.parse"


class Reservoir:
    """k answers drawn uniformly from a stream of unknown length (seeded)."""

    def __init__(self, k: int, rng):
        self.k, self.rng, self.seen, self.kept = k, rng, 0, []

    def offer(self, make):
        self.seen += 1
        if len(self.kept) < self.k:
            self.kept.append(make())
            return
        j = int(self.rng.integers(0, self.seen))
        if j < self.k:
            self.kept[j] = make()


def _answer(text, res):
    return lambda: Answer(text, res.forest.columns, res.ok)


def run(run) -> Outcome:
    p = run.params
    pool = Pool(run.template(), run.rng(0), p["text_bytes"])
    pick = run.rng(1)
    run.mark("inputs")
    parser = run.build_parser()
    traced = run.build_parser(traced=True) if run.trace else None
    run.mark("parser")
    parser.parse(pool.take(pick))                # compiles, or loads from the cache
    if traced is not None:
        traced.parse(pool.take(pick))            # the phase-split programs
    run.mark("warm-up")
    keep = Reservoir(int(p["checked_parses"]), run.rng(2))
    attempted = failed = 0
    layer = {}

    t0 = run.window_open()
    if not run.trace:
        done_bytes, last, took = 0, t0, []
        while time.perf_counter() - t0 < run.seconds:
            text = pool.take(pick)
            attempted += 1
            t = time.perf_counter()
            try:
                res = parser.parse(text)
            except Exception:
                traceback.print_exc()
                failed += 1
                continue
            last = time.perf_counter()
            took.append(last - t)
            done_bytes += len(text)
            keep.offer(_answer(text, res))
            del res
        run.window_closed()
        mbps = done_bytes / 1e6 / (last - t0) if done_bytes else 0.0
        run.log(
            f"{len(took)} parses of {p['text_bytes']} B in {last - t0:.3f} s; "
            f"per parse median {median(took) if took else 0:.4f} s, "
            f"min {min(took, default=0):.4f} s, max {max(took, default=0):.4f} s"
        )
        e2e = {"text_MBps": mbps}
    else:
        def profiled():
            nonlocal attempted
            import jax

            for _ in range(int(p["profiled_parses"])):
                with jax.profiler.TraceAnnotation(GENERATE):
                    text = pool.take(pick)
                attempted += 1
                with jax.profiler.TraceAnnotation(PARSE):
                    res = parser.parse(text)
                keep.offer(_answer(text, res))
                del res

        layer["profile"] = profile.capture(profiled, run.devices, run.log)
        spans = []
        for _ in range(int(p["phase_split_parses"])):
            attempted += 1
            traced.parse(pool.take(pick))
            spans.extend(traced.obs.tracer.drain())
        layer["spans"] = spans
        run.window_closed()
        e2e = {}
    mem = memory_peak_bytes(run.devices)
    del parser, traced
    return Outcome(
        attempted=attempted,
        failed=failed,
        end_to_end=e2e,
        answers=keep.kept,
        memory_peak_bytes=mem,
        layer_data=layer,
    )
