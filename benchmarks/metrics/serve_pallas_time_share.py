from benchmarks.metrics._shared import pallas_time_share as read  # noqa: F401
