from benchmarks.metrics.train_slow_step_share import window_timelines


def read(rec):
    """Of the window's steps, those at whose end the device had already
    finished everything it was given (the newest loss `is_ready()` at the
    next `train_batch` entry): the host left the device's queue empty.
    Must be 0 in a loop that fetches one step late."""
    timelines = window_timelines(rec)
    if timelines is None:
        return None
    return sum(t["starved_steps"] for t in timelines)
