from benchmarks import scope_reduce


def read(rec):
    """Flash forward in a serving program is the segmented prefill."""
    return scope_reduce.share(rec, ["ds.flash_fwd"])
