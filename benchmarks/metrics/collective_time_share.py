from benchmarks.metrics._shared import trace_share


def read(rec):
    """All time in collective operations, a `-start` to its `-done`
    included, over the traced window; mean over the devices."""
    return trace_share(rec, "collective_s", "window_s")
