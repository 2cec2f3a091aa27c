"""The window's pause watch tells a pause of the process's own code from one
in which it did not run."""

import gc
import re
import time

from harness.stalls import StallWatch


def _reading(line):
    m = re.match(r"longest pause ([\d.]+) ms at ([\d.]+) s, process CPU ([\d.]+) ms", line)
    return tuple(float(x) for x in m.groups())


def test_a_busy_pause_holding_the_interpreter_reads_its_cpu():
    t0 = time.perf_counter()
    sum(range(1_000_000))
    n = int(1_000_000 * 0.4 / max(time.perf_counter() - t0, 1e-6))
    watch = StallWatch(tick=0.002).start()
    time.sleep(0.05)
    t0 = time.perf_counter()
    sum(range(n))                  # one C call: the interpreter is held throughout
    busy = time.perf_counter() - t0
    pause, at, cpu = _reading(watch.stop())
    assert pause >= 0.8 * busy * 1e3
    assert cpu >= 100                              # the process ran through it


def test_a_sleep_is_no_pause():
    watch = StallWatch(tick=0.002).start()
    time.sleep(0.3)                                # releases the interpreter
    pause, _, _ = _reading(watch.stop())
    assert pause < 100


def test_garbage_collection_is_timed_and_the_hook_removed():
    before = list(gc.callbacks)
    watch = StallWatch().start()
    gc.collect()
    line = watch.stop()
    assert watch.gc_passes >= 1 and "garbage collection" in line
    assert gc.callbacks == before
