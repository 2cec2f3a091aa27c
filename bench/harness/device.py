"""The device a run measures: platform check, peaks table, memory peak."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


class DeviceError(RuntimeError):
    """The machine cannot run this cell: no TPU, or not the cell's chips."""


def check_devices(chips: int):
    """The first ``chips`` TPU devices JAX sees, or ``DeviceError``.

    A run never falls back to the CPU: a number from another platform would
    be written under a device metric's name."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise DeviceError(
            f"JAX found no TPU (platform {platform!r}); the benchmark measures "
            "the chip and does not fall back"
        )
    if len(devices) != chips:
        raise DeviceError(
            f"the cell asks for {chips} chip(s), JAX sees {len(devices)}"
        )
    return devices


def peaks_of(device_kind: str) -> dict:
    """Published peaks of one chip of this kind (``peaks.json``)."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise DeviceError(
            f"device kind {device_kind!r} is not in {PEAKS_FILE.name}; add its "
            "published peaks with their source"
        )
    return table[device_kind]


def describe(devices) -> dict:
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device, as the runtime reports it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)
