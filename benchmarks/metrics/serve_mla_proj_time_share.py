from benchmarks import scope_reduce


def read(rec):
    """The latent layers' projections' share of device busy time: the
    low-rank query (`ds.mla_q`) and cache row (`ds.mla_kv`), prefill's
    expansion to heads (`ds.mla_expand`) and decode's absorption
    (`ds.mla_absorb`)."""
    return scope_reduce.share(rec, ["ds.mla_q", "ds.mla_kv",
                                    "ds.mla_expand", "ds.mla_absorb"])
