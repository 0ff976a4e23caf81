from benchmarks import scope_reduce


def read(rec):
    """`ds.loop_exit`: the final norm between passes, the exit gate, the
    choice of the pass the head reads."""
    return scope_reduce.share(rec, ["ds.loop_exit"])
