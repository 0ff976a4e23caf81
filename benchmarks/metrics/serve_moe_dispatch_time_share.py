from benchmarks import scope_reduce


def read(rec):
    """What the sort engine costs around its matmuls."""
    return scope_reduce.share(rec, ["ds.moe_route", "ds.moe_dispatch",
                                    "ds.moe_combine"])
