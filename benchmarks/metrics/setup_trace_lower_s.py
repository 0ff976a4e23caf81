from benchmarks.setup_account import read as _read


def read(rec):
    """`trace_s + lower_s` of the engines' build and compile records: host
    Python and MLIR, paid at a cache hit and a miss alike (a kernel body
    unrolled at trace time, unrolled layers). None where the program keeps no set-up account."""
    return _read(rec, "setup_trace_lower_s")
