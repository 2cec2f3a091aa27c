"""Pauses of the benchmark's own process inside the window.

A request held up by a stall reads late whatever held it: the garbage
collector, the host not running the process, or a device call that did not
return.  ``StallWatch`` tells these apart.  A thread wakes every ``tick``
seconds; the longest time between two of its wake-ups is the longest pause,
reported with the process's CPU time inside it: near the pause's length
where the process's own code held the interpreter (garbage collection is
also timed apart), near 0 where the host did not run the process.  A device
call that stalls releases the interpreter, so the thread wakes on time and
the stall shows only as latency.
"""

from __future__ import annotations

import gc
import threading
import time


class StallWatch:
    def __init__(self, tick: float = 0.005):
        self.tick = tick
        self.longest = (0.0, 0.0, 0.0)       # pause (s), CPU in it (s), start (s into the watch)
        self.gc_passes, self.gc_s, self.gc_longest_s = 0, 0.0, 0.0
        self._gc_start = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, name="stall-watch", daemon=True)

    def _on_gc(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._gc_start = now
        elif self._gc_start is not None:
            took, self._gc_start = now - self._gc_start, None
            self.gc_passes += 1
            self.gc_s += took
            self.gc_longest_s = max(self.gc_longest_s, took)

    def _watch(self):
        t_prev, c_prev = time.perf_counter(), time.process_time()
        while not self._stop.wait(self.tick):
            t, c = time.perf_counter(), time.process_time()
            if t - t_prev > self.longest[0]:
                self.longest = (t - t_prev, c - c_prev, t_prev - self.t0)
            t_prev, c_prev = t, c

    def start(self) -> "StallWatch":
        self.t0 = time.perf_counter()
        gc.callbacks.append(self._on_gc)
        self._thread.start()
        return self

    def stop(self) -> str:
        """Stop watching; returns the readings as one line."""
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)
        pause, cpu, at = self.longest
        return (
            f"longest pause {pause * 1e3:.3f} ms at {at:.3f} s, process CPU "
            f"{cpu * 1e3:.3f} ms in it; garbage collection {self.gc_passes} passes, "
            f"{self.gc_s * 1e3:.3f} ms, longest {self.gc_longest_s * 1e3:.3f} ms"
        )
