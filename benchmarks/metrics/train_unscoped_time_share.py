from benchmarks.scope_reduce import unscoped_share as read  # noqa: F401
