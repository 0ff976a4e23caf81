from benchmarks import scope_reduce


def read(rec):
    return scope_reduce.share(rec, ["ds.ssm_in", "ds.ssm_scan", "ds.ssm_step",
                                   "ds.ssm_out"])
