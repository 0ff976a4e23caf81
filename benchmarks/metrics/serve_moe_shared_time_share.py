from benchmarks import scope_reduce


def read(rec):
    """The shared expert's share of device busy time (`ds.moe_shared`:
    every token passes through it, beside the routed experts)."""
    return scope_reduce.share(rec, ["ds.moe_shared"])
