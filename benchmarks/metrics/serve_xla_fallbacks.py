from benchmarks.metrics._shared import xla_fallbacks as read  # noqa: F401
