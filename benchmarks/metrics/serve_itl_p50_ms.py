from benchmarks.metrics._shared import percentile_ms


def read(rec):
    return percentile_ms(rec.get("itl_s"), 50)
