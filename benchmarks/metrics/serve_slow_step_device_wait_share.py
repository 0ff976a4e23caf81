from benchmarks.metrics._shared import stat_share_of_window


def read(rec):
    """The part of `serve_slow_step_share` the host spent waiting on the
    device (`stats["slow_excess_device_wait_s"]`: the slow steps' excess
    inside the `device_wait` span); the rest is the host's."""
    return stat_share_of_window(rec, "slow_excess_device_wait_s")
