"""Analytic cost model for the schedule planner.

Prices a candidate schedule in seconds and bytes BEFORE anything runs:
compute time from the per-device-kind peak-flops table
(`profiling/hardware.py`), collective time from a bytes/bandwidth model
over the explicit schedule's `plan_buckets` bucketing math
(`parallel/schedule.py` — the SAME function the runtime uses to split
layer rows, so the model and the executed schedule can never disagree
about bucket counts), memory from a byte ledger screened against
`hbm_bytes_limit`.

This is a RANKING model, not a simulator: absolute seconds are wrong
everywhere, but the relative ordering of candidates is what prunes the
combinatorial knob space to the small measured ladder (DeepCompile's
argument: plan over a profiled cost model, then verify the survivors on
real steps). Every fudge factor lives in a named module constant.
"""

from dataclasses import dataclass

from ..parallel.schedule import plan_buckets
from ..profiling.hardware import (COLLECTIVE_LATENCY_S,
                                  ici_bandwidth_per_chip,
                                  peak_flops_per_chip)

# Achievable fraction of peak for dense bf16 transformer compute — a
# guess until a four-chip cell calibrates it (not measured in this
# round).
BASE_EFFICIENCY = 0.6

# Full-remat recomputes the forward inside the backward: fwd(1) +
# bwd(2) + recompute(1) over the plain fwd+bwd(3).
REMAT_COMPUTE_FACTOR = 4.0 / 3.0

# Effective FFN-matmul speedup of the delayed-scaling quantized recipes
# (ops/pallas/quant_matmul): int8 doubles MXU issue rate on the FFN
# ~2/3 of the flops, derated for quant/dequant overhead. CPU and
# unsupported generations fall back to XLA emulation — the probe phase
# (not this table) is what catches that.
QUANT_FFN_FACTOR = {None: 1.0, "int8": 0.82, "fp8": 0.85}

# Fraction of collective time XLA's GSPMD scheduling is assumed to hide
# behind compute (no explicit prefetch window to reason about).
GSPMD_OVERLAP = 0.5

# Host<->device link for the offload tiers (PCIe-class, bytes/s).
HOST_LINK_BANDWIDTH = 32e9

# Resident-bytes fudge: runtime buffers, fragmentation (matches the
# `memory_feasible` default safety margin).
MEMORY_SAFETY = 0.92

# Per-layer activation bytes ~= ACT_BYTES_PER_ELEM * batch * seq *
# hidden without remat (attention scores and MLP intermediates
# included); full remat keeps only layer-boundary residuals.
ACT_BYTES_PER_ELEM = 16
ACT_BYTES_PER_ELEM_REMAT = 2


@dataclass(frozen=True)
class ModelShape:
    """The (model geometry, per-chip workload) a plan is keyed on."""
    num_layers: int
    hidden_size: int
    num_heads: int
    seq_len: int
    vocab_size: int
    batch_per_chip: int
    param_count: int = 0        # 0 = estimate from the geometry

    @property
    def params(self):
        if self.param_count:
            return int(self.param_count)
        # embed + 12 h^2 per layer (attn 4h^2 + mlp 8h^2) + final norm
        return (self.vocab_size * self.hidden_size
                + 12 * self.num_layers * self.hidden_size ** 2)

    @property
    def layer_params(self):
        """Params that live inside the layer stack (what the explicit
        schedule gathers per layer; embeddings sit outside the loop)."""
        return 12 * self.num_layers * self.hidden_size ** 2

    def key(self):
        """Stable identity for plan-cache filenames."""
        return (f"l{self.num_layers}-h{self.hidden_size}"
                f"-a{self.num_heads}-s{self.seq_len}"
                f"-v{self.vocab_size}-b{self.batch_per_chip}"
                f"-p{self.params}")

    def flops_per_token(self):
        return (6 * self.params
                + 12 * self.num_layers * self.hidden_size * self.seq_len)


@dataclass(frozen=True)
class Candidate:
    """One point of the schedule knob space."""
    mode: str = "gspmd"            # zero_optimization.schedule.mode
    prefetch_depth: int = 2
    bucket_mb: float = 32.0
    group_layers: int = 4
    remat: bool = False
    offload: str = "none"          # none | cpu | nvme
    quant_ffn: str = None          # None | int8 | fp8

    def label(self):
        bits = [self.mode, f"p{self.prefetch_depth}",
                f"b{int(self.bucket_mb)}", f"g{self.group_layers}"]
        if self.remat:
            bits.append("remat")
        if self.offload != "none":
            bits.append(f"off:{self.offload}")
        if self.quant_ffn:
            bits.append(self.quant_ffn)
        return "/".join(bits)


def hardware_profile(device_kind, hbm_limit=None):
    """Resolve the cost-model inputs for a device-kind string."""
    return {
        "device_kind": device_kind,
        "peak_flops": peak_flops_per_chip(device_kind),
        "ici_bandwidth": ici_bandwidth_per_chip(device_kind),
        "hbm_limit": hbm_limit,
    }


def compute_time_s(cand, shape, hw):
    """Per-chip dense compute time for one step."""
    tokens = shape.batch_per_chip * shape.seq_len
    flops = tokens * shape.flops_per_token() * 3  # fwd + 2x bwd
    if cand.remat:
        flops *= REMAT_COMPUTE_FACTOR
    flops *= QUANT_FFN_FACTOR.get(cand.quant_ffn, 1.0)
    return flops / (hw["peak_flops"] * BASE_EFFICIENCY)


def collective_time_s(cand, shape, hw, world):
    """Exposed (non-overlapped) collective seconds for one step.

    Explicit mode reasons per layer group: each group's bucketed
    all-gather (bucket count from the runtime's own `plan_buckets`) can
    hide behind the compute of the `prefetch_depth` groups ahead of it;
    whatever does not fit that window is exposed. The backward
    reduce-scatters mirror the gathers. GSPMD mode prices the same
    bytes at a flat assumed overlap.
    """
    if world <= 1:
        return 0.0
    itemsize = 2  # bf16 compute params
    layer_elems = shape.layer_params // max(1, shape.num_layers)
    shard_elems = max(1, layer_elems // world)
    per_layer_bytes = layer_elems * itemsize * (world - 1) / world
    wire_s_per_layer = per_layer_bytes / hw["ici_bandwidth"]

    if cand.mode != "explicit":
        total = 2 * shape.num_layers * (wire_s_per_layer
                                        + COLLECTIVE_LATENCY_S)
        return total * (1.0 - GSPMD_OVERLAP)

    buckets = plan_buckets(shard_elems, itemsize,
                           int(cand.bucket_mb * (1 << 20)))
    n_buckets_per_layer = max(1, len(buckets))
    group = max(1, int(cand.group_layers))
    n_groups = max(1, -(-shape.num_layers // group))
    per_group_gather = group * (
        n_buckets_per_layer * COLLECTIVE_LATENCY_S + wire_s_per_layer)
    per_group_compute = compute_time_s(cand, shape, hw) / n_groups
    window = cand.prefetch_depth * per_group_compute
    exposed = max(0.0, per_group_gather - window)
    # first group's gather is cold (nothing to hide behind); gathers and
    # the mirrored reduce-scatters each expose their overflow
    return per_group_gather + 2 * (n_groups - 1) * exposed


def offload_time_s(cand, shape, hw, world):
    """Exposed host-link seconds when a tier holds the param/optimizer
    rows off-device: each step streams the shard down and the grad rows
    back, double-buffered prefetch hides part of it."""
    if cand.offload == "none":
        return 0.0
    shard_bytes = shape.params * 2 / max(1, world)
    transfer = 2 * shard_bytes / HOST_LINK_BANDWIDTH
    return transfer / (1 + max(0, cand.prefetch_depth))


def memory_bytes(cand, shape, world, stage=3):
    """Estimated resident HBM bytes per chip for the candidate."""
    p = shape.params
    itemsize = 2
    param_bytes = p * itemsize
    if stage >= 3:
        resident_params = param_bytes / world
        layer_bytes = (shape.layer_params // max(1, shape.num_layers)
                       ) * itemsize
        # gathered working set: the in-flight window of layer groups
        window_groups = 1 + max(0, cand.prefetch_depth)
        resident_params += (window_groups * cand.group_layers
                            * layer_bytes)
    else:
        resident_params = param_bytes
    grad_bytes = param_bytes / (world if stage >= 2 else 1)
    opt_bytes = 8 * p / (world if stage >= 1 else 1)
    if cand.offload != "none":
        # rows rest tier-side; on-chip cost is the staging buffers
        opt_bytes = 0
        if stage >= 3:
            resident_params = ((1 + max(0, cand.prefetch_depth))
                               * cand.group_layers
                               * (shape.layer_params
                                  // max(1, shape.num_layers)) * itemsize)
    act_elem = (ACT_BYTES_PER_ELEM_REMAT if cand.remat
                else ACT_BYTES_PER_ELEM)
    act_bytes = (shape.batch_per_chip * shape.seq_len * shape.hidden_size
                 * act_elem * shape.num_layers)
    return int(resident_params + grad_bytes + opt_bytes + act_bytes)


def memory_feasible_analytic(cand, shape, world, hbm_limit, stage=3):
    """The analytic screen: None budget never blocks a candidate (the
    same contract as `ops.autotune.memory_feasible`)."""
    if hbm_limit is None:
        return True
    return memory_bytes(cand, shape, world, stage) <= \
        hbm_limit * MEMORY_SAFETY


def step_time_s(cand, shape, hw, world):
    """Total analytic step seconds: compute + exposed collectives +
    exposed offload traffic."""
    return (compute_time_s(cand, shape, hw)
            + collective_time_s(cand, shape, hw, world)
            + offload_time_s(cand, shape, hw, world))
