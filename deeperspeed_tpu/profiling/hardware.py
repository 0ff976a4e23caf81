"""Per-device-kind hardware peaks (shared by the in-engine telemetry
layer `runtime/telemetry.py` and the schedule planner's analytic cost
model, `deeperspeed_tpu/planner`).

One table per quantity, several consumers: the telemetry layer turns
`compiled.cost_analysis()` flops into a live `Train/Samples/mfu`
scalar, and the planner prices candidate schedules (compute from peak
flops, collectives from ICI bandwidth). Keeping the tables here means
the consumers can never disagree about what "peak" means for a chip.
(The benchmark keeps its own, `benchmarks/peaks.json`.)

Import-light on purpose: no jax at module scope — callers hand in device
objects (or kind strings), so config parsing never pays a backend init.
"""

# bf16 peak FLOPS by TPU generation (public spec sheet numbers). Matched
# as substrings against the lowercased `device_kind`.
PEAK_FLOPS_BY_KIND = {
    "v5 lite": 197e12, "v5e": 197e12,
    "v5p": 459e12, "v5": 459e12,
    "v4": 275e12,
    "v6": 918e12, "v6e": 918e12,
}


# Per-chip ICI all-gather/reduce-scatter bandwidth in bytes/s (public
# spec sheet aggregate link bandwidth, derated to a sustained-collective
# estimate). Matched like PEAK_FLOPS_BY_KIND. The planner's collective
# model divides bucket bytes by this; it is a ranking signal, not a
# simulator — only relative candidate ordering matters.
ICI_BANDWIDTH_BY_KIND = {
    "v5 lite": 180e9, "v5e": 180e9,
    "v5p": 600e9, "v5": 600e9,
    "v4": 300e9,
    "v6": 360e9, "v6e": 360e9,
}

# Fixed per-collective launch/latency cost (seconds). Prices the
# many-tiny-buckets failure mode: a 1 MB bucket ladder pays this per
# bucket and loses to fewer, fatter buckets on the analytic ladder.
COLLECTIVE_LATENCY_S = 5e-6


def _by_kind(device, table, what):
    kind = getattr(device, "device_kind", None)
    if kind is None:
        kind = str(device)
    low = kind.lower()
    for key, val in table.items():
        if key in low:
            return val
    raise ValueError(
        f"no {what} known for device kind {kind!r}: add it to "
        f"deeperspeed_tpu/profiling/hardware.py (known: "
        f"{', '.join(sorted(table))}). A device outside the table is an "
        f"error, never a default.")


def peak_flops_per_chip(device):
    """bf16 peak FLOPS for a jax device (or a device-kind string);
    raises ValueError for a kind the table does not hold."""
    return _by_kind(device, PEAK_FLOPS_BY_KIND, "bf16 peak FLOPS")


def ici_bandwidth_per_chip(device):
    """Sustained per-chip collective bandwidth (bytes/s) for a jax
    device or a device-kind string; raises ValueError for a kind the
    table does not hold."""
    return _by_kind(device, ICI_BANDWIDTH_BY_KIND, "ICI bandwidth")
