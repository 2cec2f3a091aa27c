"""95th percentile (nearest rank) of the ``parse.queue_wait`` span over every
request of a ``--trace 1`` run's window, in ms: submit to batch pickup."""

from harness.stats import nearest_rank


def read(data):
    ms = [s.duration_s * 1e3 for s in data.get("spans", ()) if s.name == "parse.queue_wait"]
    return nearest_rank(ms, 95.0) if ms else None
