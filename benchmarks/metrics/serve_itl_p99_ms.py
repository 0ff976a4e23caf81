from benchmarks.metrics._shared import percentile_ms


def read(rec):
    """Gap between consecutive tokens of one request, over all gaps that
    ended in the window."""
    return percentile_ms(rec.get("itl_s"), 99)
