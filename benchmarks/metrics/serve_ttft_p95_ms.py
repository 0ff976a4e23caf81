from benchmarks.metrics._shared import percentile_ms


def read(rec):
    """Submit to first token, over requests submitted in the window."""
    return percentile_ms(rec.get("ttft_s"), 95)
