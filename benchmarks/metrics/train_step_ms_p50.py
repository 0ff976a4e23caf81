from benchmarks.metrics._shared import percentile_ms


def read(rec):
    """Host clock from one loss fetch to the next."""
    return percentile_ms(rec.get("step_times_s"), 50) \
        if "tokens_per_step" in rec else None
