"""Open loop of requests: ``Parser.submit(text).result()`` on a fixed schedule.

Traffic parameters (``bench/traffic/<mix>.json``):

    rate_per_s        offered load; round(rate × seconds) requests are due
                      inside the window, at exponential gaps (the same
                      multiset of gaps for every seed, in a seeded order)
    size_bytes        the request sizes of the mix; each request is whole
    size_weights      records summing to at most a size drawn from
                      ``size_bytes`` with these weights (the same multiset of
                      sizes for every seed, in a seeded order)
    source            where the size law and the arrival process come from
    checked_requests  answers compared with the reference: a seeded sample
    longest_checked   ... plus this many of the longest requests
    profile_seconds   length of the profiled stretch of a ``--trace 1`` run,
                      at the same rate, before the traced window

The service is synchronous (``result()`` drives ``step()``), so the loop
submits every request already due before it drives the oldest ticket, and
each request is timed from when it was due to when its ``result()``
returned.  A request that fails or is refused counts as infinitely late.
"""

from __future__ import annotations

import time
import traceback
from collections import deque

import numpy as np

from harness import profile
from harness.device import memory_peak_bytes
from harness.runner import Answer, Outcome
from harness.stats import nearest_rank
from harness.textgen import mix_sizes, poisson_arrivals

def _schedule(run, template, seconds: float, stream: int):
    p = run.params
    n = max(1, round(p["rate_per_s"] * seconds))
    sizes = mix_sizes(n, p["size_bytes"], p["size_weights"], run.rng(stream))
    texts = template.texts(run.rng(stream + 1), sizes)
    due = poisson_arrivals(n, p["rate_per_s"], run.rng(stream + 2))
    return texts, due


def _drive(parser, texts, due, seconds, keep=()):
    """Run one schedule; returns latencies (s, inf if failed), the answers of
    ``keep``, the generator's lateness, the backlog when ``seconds`` passed
    and the largest backlog seen."""
    n = len(texts)
    lat = np.full(n, np.inf)
    answers, late = {}, np.zeros(n)
    queue = deque()
    backlog = None
    completed = i = peak = 0
    failed = 0
    t0 = time.perf_counter()
    while i < n or queue:
        now = time.perf_counter() - t0
        peak = max(peak, i - completed)
        if backlog is None and now >= seconds:
            backlog = i - completed
        while i < n and due[i] <= now:
            late[i] = now - due[i]
            try:
                queue.append((i, parser.submit(texts[i])))
            except Exception:
                traceback.print_exc()
                failed += 1
                completed += 1
            i += 1
        if queue:
            j, ticket = queue.popleft()
            try:
                res = ticket.result()
                lat[j] = time.perf_counter() - t0 - due[j]
                if j in keep:
                    answers[j] = Answer(texts[j], res.forest.columns, res.ok)
            except Exception:
                traceback.print_exc()
                failed += 1
            completed += 1
        elif i < n:
            wait = due[i] - (time.perf_counter() - t0)
            if wait > 0:
                time.sleep(wait)
    if backlog is None:
        backlog = 0
    return lat, answers, late, backlog, peak, failed


def _warm(run, parser, texts):
    """Run every (batch, bucket) program the schedule can use, once."""
    eng, n_chunks = parser.engine, parser.config.n_chunks
    buckets = sorted({eng.bucket_shape(len(t), n_chunks) for t in texts})
    sizes = [c * k for c, k in buckets]
    tmpl = run.template()
    # the service batches up to max_batch requests and pads a batch to a
    # power of two, so a bucket runs at these batch sizes
    top = 1 << (parser.config.max_batch - 1).bit_length()
    batches = [1 << i for i in range(top.bit_length())]
    for size in sizes:
        warm = tmpl.texts(run.rng(90 + size), [size] * batches[-1])
        for b in batches:
            tickets = [parser.submit(t) for t in warm[:b]]
            for t in tickets:
                t.result()
    run.log(f"warmed {len(buckets)} buckets x {len(batches)} batch sizes")


def run(run) -> Outcome:
    p = run.params
    template = run.template()
    texts, due = _schedule(run, template, run.seconds, 0)
    n = len(texts)
    pick = run.rng(3)
    sample = set(pick.choice(n, size=min(n, int(p["checked_requests"])), replace=False).tolist())
    sample |= set(np.argsort([len(t) for t in texts])[-int(p["longest_checked"]):].tolist())
    layer = {}
    prof_attempted = 0
    if run.trace:
        ptexts, pdue = _schedule(run, template, p["profile_seconds"], 10)
        prof_attempted = len(ptexts)
    run.mark("inputs")
    parser = run.build_parser(traced=run.trace)
    run.mark("parser")
    _warm(run, parser, texts)
    run.mark("warm-up")

    t0 = run.window_open()
    if run.trace:
        def profiled():
            import jax

            with jax.profiler.TraceAnnotation("bench.requests"):
                _drive(parser, ptexts, pdue, p["profile_seconds"])

        layer["profile"] = profile.capture(profiled, run.devices, run.log)
        run.log(f"profiled {len(ptexts)} requests in {time.perf_counter() - t0:.3f} s")
        parser.obs.tracer.drain()
        m = parser.obs.metrics
        served0 = m.counter("served_total", service="parse").value
        batches0 = m.counter("batches_total", service="parse").value
    lat, answers, late, backlog, peak, failed = _drive(parser, texts, due, run.seconds, sample)
    run.window_closed()
    mem = memory_peak_bytes(run.devices)
    run.log(
        f"{n} requests due in {run.seconds} s at {p['rate_per_s']}/s: "
        f"{int(np.isfinite(lat).sum())} served, {failed} failed; backlog at the "
        f"window's end {backlog}, at most {peak}; generator lateness p50 "
        f"{nearest_rank(late.tolist(), 50) * 1e3:.3f} ms, max {late.max() * 1e3:.3f} ms "
        f"(due at {due[late.argmax()]:.3f} s); "
        f"latency p50 {nearest_rank(lat.tolist(), 50) * 1e3:.3f} ms"
    )
    if run.trace:
        layer["spans"] = parser.obs.tracer.drain()
        layer["served"] = m.counter("served_total", service="parse").value - served0
        layer["batches"] = m.counter("batches_total", service="parse").value - batches0
        layer["max_batch"] = parser.config.max_batch
    del parser
    return Outcome(
        attempted=n + prof_attempted,
        failed=failed,
        end_to_end={"request_p95_ms": nearest_rank(lat.tolist(), 95) * 1e3},
        answers=[answers[j] for j in sorted(answers)],
        memory_peak_bytes=mem,
        layer_data=layer,
    )
