"""Arithmetic the metric readers share. A reader takes the run's record
(`rec`: what the driver measured and counted, the reduced trace under
`trace`, the cell under `spec`) and returns one number, or None where the
run holds nothing for it to read."""

import numpy as np


def percentile_ms(values_s, q):
    if not values_s:
        return None
    return float(np.percentile(np.asarray(values_s), q)) * 1e3


def trace_share(rec, part, whole):
    """100 * trace[part] / trace[whole], from the reduced trace."""
    t = rec.get("trace")
    if not t or not t.get(whole):
        return None
    return 100.0 * t[part] / t[whole]


def pallas_time_share(rec):
    """Device time in Mosaic custom calls over device busy time."""
    return trace_share(rec, "mosaic_s", "busy_s")


def device_idle_share(rec):
    t = rec.get("trace")
    return None if not t else 100.0 * t["idle_share"]


def compiles_in_window(rec):
    return rec.get("compiles_in_window")


def xla_fallbacks(rec):
    """Dispatchers that took XLA on a TPU where they have a kernel."""
    report = rec.get("dispatch")
    return None if report is None else len(report["xla_on_tpu"])


def peak_hbm_gb(rec):
    peak = rec.get("memory_peak_bytes")
    return None if peak is None else peak / 1e9


def stat_share_of_window(rec, key):
    stats = rec.get("stats")
    if not stats or key not in stats:
        return None
    return 100.0 * stats[key] / rec["window_s"]
