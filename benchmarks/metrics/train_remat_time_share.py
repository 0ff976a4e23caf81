from benchmarks.scope_reduce import remat_share as read  # noqa: F401
