from benchmarks import scope_reduce


def read(rec):
    """The pass loop's OWN share of device busy time: what lies under
    `ds.loop` and in no inner scope (slicing, carried-state copies; a
    copied pool or a re-stacked weight shows here)."""
    return scope_reduce.share(rec, ["ds.loop"])
