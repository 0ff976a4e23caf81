from benchmarks import scope_reduce


def read(rec):
    """The window layers' attention kernels' share of device busy time:
    their prefill (`ds.flash_fwd_window`) and their decode
    (`ds.paged_decode_window`); the full layers keep `ds.flash_fwd` and
    `ds.paged_decode`."""
    return scope_reduce.share(rec, ["ds.flash_fwd_window",
                                    "ds.paged_decode_window"])
