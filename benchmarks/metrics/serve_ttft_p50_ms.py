from benchmarks.metrics._shared import percentile_ms


def read(rec):
    return percentile_ms(rec.get("ttft_s"), 50)
