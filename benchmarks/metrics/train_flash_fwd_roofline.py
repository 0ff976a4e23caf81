from benchmarks import kernel_costs, scope_reduce


def read(rec):
    """The least time one forward call could take, from its shapes, over
    the mean time of one call in the traced steps."""
    seconds = scope_reduce.seconds_per_call(
        rec, ["ds.flash_fwd"], per=["ds.flash_fwd"])
    if seconds is None:
        return None
    return scope_reduce.roofline(
        rec, *kernel_costs.flash_fwd(*scope_reduce.flash_shape(rec)),
        seconds)
