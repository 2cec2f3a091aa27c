"""Share of the profiled stretch of an open loop of requests in which no
operation ran on the device, in %, averaged over the chips used.  Nothing
is read where the profiler dropped events: the window it kept then covers
only part of the stretch."""


def read(data):
    prof = data.get("profile")
    if prof is None or prof.window_s <= 0 or prof.dropped:
        return None
    return 100.0 * (1.0 - prof.busy_s / prof.window_s)
