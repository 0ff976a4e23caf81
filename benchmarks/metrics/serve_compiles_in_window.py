from benchmarks.metrics._shared import compiles_in_window as read  # noqa: F401
