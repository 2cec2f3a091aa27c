"""Run a stretch of work under the JAX profiler and reduce its trace.

The trace is written under ``TMPDIR`` and deleted once reduced; only the
``trace.Summary`` survives the call.
"""

from __future__ import annotations

import glob
import os
import tempfile
import time

from . import trace


def capture(work, devices, log) -> trace.Summary:
    import jax

    with tempfile.TemporaryDirectory(prefix="bench-trace-") as d:
        jax.profiler.start_trace(d)
        t = time.perf_counter()
        try:
            work()
        finally:
            t_work = time.perf_counter() - t
            jax.profiler.stop_trace()
            t_stop = time.perf_counter() - t - t_work
        files = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one .xplane.pb under {d}, found {files}")
        t = time.perf_counter()
        events = trace.extract(files[0])
        summary = trace.summarize(events)
        log(
            f"trace: {os.path.getsize(files[0])} bytes, {len(events.devices)} device(s), "
            f"window {summary.window_s:.4f} s, busy {summary.busy_s:.4f} s, "
            f"op events dropped by the profiler: {summary.dropped}; traced work "
            f"{t_work:.2f} s, stop {t_stop:.2f} s, read {time.perf_counter() - t:.2f} s"
        )
    if len(events.devices) != len(devices):
        raise RuntimeError(
            f"the trace holds {len(events.devices)} TPU planes, the run uses {len(devices)}"
        )
    return summary
