from benchmarks.metrics._shared import device_idle_share as read  # noqa: F401
