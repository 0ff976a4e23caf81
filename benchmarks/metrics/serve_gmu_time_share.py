from benchmarks import scope_reduce


def read(rec):
    return scope_reduce.share(rec, ["ds.gmu"])
