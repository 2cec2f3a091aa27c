"""From a JAX profiler trace (``.xplane.pb``) to device busy time and breakdowns.

What is read, per TPU device plane (``/device:TPU:<i>``):

- ``XLA Modules``: one event per program execution.  Busy time is the union
  of these intervals with the op intervals below, clipped to the window.
  A long parse is one program whose while loops run hundreds of thousands
  of steps, each traced op by op; past a few million events the profiler
  drops the rest (an ``XLA TraceMe`` event "Trace Buffers Dropped" marks
  where), the program's own event with them, so the traced window is cut
  at the first drop.
- ``XLA Ops`` and ``Async XLA Ops``: time per op, for the breakdown.

On the host plane (``/host:CPU``), the Python thread's line (``python`` or
``python3``, after the interpreter) holds the benchmark's
``TraceAnnotation`` spans (``bench.*``), whose extent is the traced window,
and the Python tracer's function events.  Flattened to the innermost event
open at each instant, they say what the host was doing in each idle gap.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW_PREFIX = "bench."
_OP_LINES = ("XLA Ops", "Async XLA Ops")
# gaps shorter than this are the device's own op-to-op bubbles; they are
# summed under one name rather than matched to a host event one by one
SHORT_GAP_S = 10e-6


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class DeviceTrace:
    name: str
    intervals: np.ndarray              # (k, 2) ns: programs and ops
    op_ns: Dict[str, float]            # total ns per op name
    dropped_from_ns: Optional[float]   # where the profiler began dropping events


@dataclasses.dataclass
class HostTrace:
    names: List[str]
    intervals: np.ndarray              # (k, 2) ns, python line events


@dataclasses.dataclass
class Events:
    devices: List[DeviceTrace]
    host: HostTrace
    window_ns: Tuple[float, float]


def extract(path: str) -> Events:
    """Read what the reduction needs from one ``.xplane.pb`` file."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host = [], HostTrace([], np.zeros((0, 2)))
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            spans, op_ns, drops = [], {}, []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    spans.extend((e.start_ns, e.end_ns) for e in line.events)
                elif line.name in _OP_LINES:
                    for e in line.events:
                        spans.append((e.start_ns, e.end_ns))
                        k = op_name(e.name)
                        op_ns[k] = op_ns.get(k, 0.0) + e.duration_ns
                elif line.name == "XLA TraceMe":
                    drops.extend(e.start_ns for e in line.events if "Dropped" in e.name)
            devices.append(DeviceTrace(
                plane.name, np.asarray(spans, dtype=float).reshape(-1, 2), op_ns,
                min(drops) if drops else None,
            ))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                # the Python thread's line (named after the interpreter)
                # holds the annotations and the Python tracer's events
                evs = list(line.events)
                if any(e.name.startswith(WINDOW_PREFIX) for e in evs):
                    host = HostTrace(
                        [e.name for e in evs],
                        np.asarray([(e.start_ns, e.end_ns) for e in evs], dtype=float).reshape(-1, 2),
                    )
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    try:
        lo, hi = window_of(host)
    except ValueError as e:
        seen = [(pl.name, [ln.name for ln in pl.lines]) for pl in data.planes if "host" in pl.name]
        raise ValueError(f"{e}; host planes and lines: {seen}") from None
    return Events(devices, host, (lo, hi))


def window_of(host: HostTrace) -> Tuple[float, float]:
    """The extent of the benchmark's ``bench.*`` annotations."""
    marks = [i for i, n in enumerate(host.names) if n.startswith(WINDOW_PREFIX)]
    if not marks:
        raise ValueError(f"no {WINDOW_PREFIX}* annotation in the trace")
    return float(host.intervals[marks, 0].min()), float(host.intervals[marks, 1].max())


def union(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Disjoint sorted (k, 2) intervals covering the union, clipped to [lo, hi]."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = np.clip(intervals, lo, hi)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.concatenate([[True], iv[1:, 0] > reach[:-1]])
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    ends = reach[np.concatenate([idx[1:] - 1, [len(iv) - 1]])]
    return np.stack([starts, ends], axis=1)


def gaps(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The complement of disjoint sorted intervals within [lo, hi]."""
    edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def host_timeline(host: HostTrace) -> Tuple[np.ndarray, List[str]]:
    """The python line as disjoint (k, 2) segments, each labelled with the
    innermost event open in it.  Python-tracer events nest, so one sweep
    with a stack flattens them."""
    order = np.lexsort((-host.intervals[:, 1], host.intervals[:, 0]))
    segs, labels, stack = [], [], []
    cursor = host.intervals[order[0], 0] if len(order) else 0.0

    def emit(upto, label):
        nonlocal cursor
        if upto > cursor:
            segs.append((cursor, upto))
            labels.append(label)
            cursor = upto

    for i in order:
        s, e = host.intervals[i]
        while stack and stack[-1][0] <= s:
            end, label = stack.pop()
            emit(end, label)
        emit(s, stack[-1][1] if stack else "idle host")
        stack.append((e, host.names[i]))
    while stack:
        end, label = stack.pop()
        emit(end, label)
    return np.asarray(segs, dtype=float).reshape(-1, 2), labels


def attribute(gap_iv: np.ndarray, segs: np.ndarray, labels: List[str], weight: float,
              into: Dict[str, float]) -> None:
    """Add ``weight`` x each gap's seconds to the host activity that covered
    them, split where the activity changes; uncovered time is "idle host"."""
    for a, b in gap_iv:
        lo = int(np.searchsorted(segs[:, 1], a, side="right"))
        hi = int(np.searchsorted(segs[:, 0], b, side="left"))
        covered = 0.0
        for k in range(lo, hi):
            ov = min(b, segs[k, 1]) - max(a, segs[k, 0])
            if ov > 0:
                into[labels[k]] = into.get(labels[k], 0.0) + weight * ov / 1e9
                covered += ov
        if b - a > covered:
            into["idle host"] = into.get("idle host", 0.0) + weight * (b - a - covered) / 1e9


@dataclasses.dataclass
class Summary:
    """A traced window reduced to what the result line reports."""

    window_s: float
    busy_s: float                        # mean over devices
    busy_per_device_s: List[float]
    op_s: List[Dict[str, float]]         # per device: seconds per op name
    idle_by_host: Dict[str, float]       # mean over devices: idle s per host activity
    dropped: bool                        # the window was cut where events were dropped

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops: Dict[str, float] = {}
        for d in self.op_s:
            for n, s in d.items():
                ops[n] = ops.get(n, 0.0) + s / len(self.op_s)
        order = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
        return {
            "device_ops": [[n, float(s)] for n, s in order(ops)],
            "idle_gaps": [[n, float(s)] for n, s in order(self.idle_by_host)],
        }


def summarize(ev: Events) -> Summary:
    lo, hi = ev.window_ns
    # past the first drop the device record is incomplete: the window ends there
    drops = [d.dropped_from_ns for d in ev.devices if d.dropped_from_ns is not None]
    hi = min([hi] + drops)
    busy, op_s, idle = [], [], {}
    segs, labels = host_timeline(ev.host)
    for d in ev.devices:
        u = union(d.intervals, lo, hi)
        busy.append(float((u[:, 1] - u[:, 0]).sum()) / 1e9)
        op_s.append({n: ns / 1e9 for n, ns in d.op_ns.items()})
        g = gaps(u, lo, hi)
        length = (g[:, 1] - g[:, 0]) / 1e9
        short = length < SHORT_GAP_S
        if short.any():
            key = "device op-to-op (< 10 us)"
            idle[key] = idle.get(key, 0.0) + float(length[short].sum()) / len(ev.devices)
        attribute(g[~short], segs, labels, 1.0 / len(ev.devices), idle)
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=float(np.mean(busy)) if busy else 0.0,
        busy_per_device_s=busy,
        op_s=op_s,
        idle_by_host=idle,
        dropped=any(d.dropped_from_ns is not None for d in ev.devices),
    )
