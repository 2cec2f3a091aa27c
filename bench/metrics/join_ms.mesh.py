"""Median duration of the ``phase.join`` span per parse, in ms: the product all-gather over the chips and the replicated join.
Read from the mesh's phase-split route (``ObsConfig(enabled=True)``) of a
``--trace 1`` run, whose spans block on their device results."""

from harness.stats import median


def read(data):
    ms = [s.duration_s * 1e3 for s in data.get("spans", ()) if s.name == "phase.join"]
    return median(ms) if ms else None
