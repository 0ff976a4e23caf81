from benchmarks import scope_reduce


def read(rec):
    """The absorbed latent decode kernel's share of device busy time
    (`ds.paged_decode_latent`)."""
    return scope_reduce.share(rec, ["ds.paged_decode_latent"])
