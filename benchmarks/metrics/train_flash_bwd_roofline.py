from benchmarks import kernel_costs, scope_reduce


def read(rec):
    """The least time one layer's backward could take over the mean time
    it took: the dq and dkv calls of a layer together (counted by the
    dkv calls), or the one fused call of the single-block kernel."""
    seconds = scope_reduce.seconds_per_call(
        rec, scope_reduce.FLASH_BACKWARD,
        per=["ds.flash_bwd_dkv", "ds.flash_bwd"])
    if seconds is None:
        return None
    return scope_reduce.roofline(
        rec, *kernel_costs.flash_bwd(*scope_reduce.flash_shape(rec)),
        seconds)
