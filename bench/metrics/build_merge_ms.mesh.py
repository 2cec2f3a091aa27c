"""Median duration of the ``phase.build_merge`` span per parse, in ms: the shard-local build&merge on every chip.
Read from the mesh's phase-split route (``ObsConfig(enabled=True)``) of a
``--trace 1`` run, whose spans block on their device results."""

from harness.stats import median


def read(data):
    ms = [s.duration_s * 1e3 for s in data.get("spans", ()) if s.name == "phase.build_merge"]
    return median(ms) if ms else None
