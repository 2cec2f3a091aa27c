"""Requests per batched device program over the window, as a share of the
service's ``max_batch``, in %: ``served_total`` over ``batches_total``."""


def read(data):
    if not data.get("batches"):
        return None
    return 100.0 * data["served"] / data["batches"] / data["max_batch"]
