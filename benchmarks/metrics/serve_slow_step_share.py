from benchmarks.metrics._shared import stat_share_of_window


def read(rec):
    """Seconds the window's slow steps ran over their key's typical step
    (`stats["slow_step_excess_s"]`, summed by the serve engine's
    `StepTimeline`: `runtime/telemetry.py`), over the window: the share
    of the run a stall took. 0.0 in a run without one; None where the
    program keeps no timeline."""
    return stat_share_of_window(rec, "slow_step_excess_s")
