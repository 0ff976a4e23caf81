from benchmarks import scope_reduce


def read(rec):
    """The MoE layers' device time: router, dispatch, the two grouped
    matmuls and the combine."""
    return scope_reduce.share(rec, ["ds.moe_route", "ds.moe_dispatch",
                                    "ds.grouped_matmul", "ds.moe_combine"])
