def window_timelines(rec):
    """The train engines' step timelines over the window's steps: the
    newest `rec["attempted"]` records of each (`telemetry.step_report`:
    a train record runs from one `train_batch` entry to the next). None
    where the program has no such report."""
    try:
        from deeperspeed_tpu.runtime.telemetry import step_report
    except ImportError:
        return None
    report = step_report(last=rec["attempted"])
    return [t for t in report["timelines"] if t["engine"] == "train"] \
        or None


def read(rec):
    """Seconds the window's slow steps ran over their typical step, over
    the window. 0.0 in a run without a stall."""
    timelines = window_timelines(rec)
    if timelines is None:
        return None
    return 100.0 * sum(t["slow_step_excess_s"] for t in timelines) \
        / rec["window_s"]
