from benchmarks.metrics._shared import stat_share_of_window


def read(rec):
    return stat_share_of_window(rec, "build_inputs_s")
