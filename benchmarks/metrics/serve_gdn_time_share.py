from benchmarks import scope_reduce


def read(rec):
    """A Gated DeltaNet layer's mixer: projections and convolution, the
    two delta-rule kernels, the gated norm and the out-projection."""
    return scope_reduce.share(rec, ["ds.gdn_in", "ds.gdn_chunk",
                                    "ds.gdn_step", "ds.gdn_out"])
