from benchmarks.metrics._shared import percentile_ms


def read(rec):
    """Host clock around `engine.step()`, which ends in a read-back."""
    return percentile_ms(rec.get("step_times_s"), 50) \
        if "out_tokens" in rec else None
