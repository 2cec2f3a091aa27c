"""Production mesh construction (multi-pod dry-run spec).

``make_production_mesh`` is a FUNCTION so importing this module never touches
jax device state.  Shapes: single-pod ``(16, 16) = ('data', 'model')`` — one
v5e pod, 256 chips; multi-pod ``(2, 16, 16) = ('pod', 'data', 'model')`` —
512 chips.  The 'pod' axis carries batch (pure DP) and the parser's chunk
axis; it generalizes to any pod count (1000+ nodes) because nothing in the
sharding rules binds to its extent.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(shape: Tuple[int, ...] = (1,), axes: Tuple[str, ...] = ("data",)):
    """Small mesh over whatever devices exist (tests / smoke runs)."""
    n = 1
    for s in shape:
        n *= s
    avail = len(jax.devices())
    if n > avail:
        shape, axes = (avail,), ("data",)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_parse_mesh(*, max_pods: int = 2):
    """('pod', 'data') host mesh over every available device — the distributed
    parser's test/bench shape (chunks over 'pod', batch over 'data').

    Uses ``max_pods`` pods when the device count divides evenly, else a single
    pod; a 1-device host degenerates to a (1, 1) mesh (the sharded programs
    still run, with no collectives resident)."""
    n = len(jax.devices())
    pods = max_pods if n >= max_pods and n % max_pods == 0 else 1
    return jax.make_mesh(
        (pods, n // pods), ("pod", "data"), axis_types=(AxisType.Auto,) * 2
    )


def mesh_axes_size(mesh, axes) -> int:
    """Product of the named mesh axes' sizes (1 for the empty tuple)."""
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def mesh_chips(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
