from benchmarks.metrics._shared import peak_hbm_gb as read  # noqa: F401
