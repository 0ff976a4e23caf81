from benchmarks.metrics._shared import trace_share


def read(rec):
    """Time in collective operations during which nothing else ran on the
    device, over the traced window; mean over the devices."""
    return trace_share(rec, "collective_exposed_s", "window_s")
