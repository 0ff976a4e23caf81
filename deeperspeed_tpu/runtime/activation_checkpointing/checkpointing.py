"""Activation checkpointing (reference:
`deepspeed/runtime/activation_checkpointing/checkpointing.py`).

The reference reimplements Megatron's checkpointing: recompute-in-backward
with CUDA RNG state capture/restore (`CudaRNGStatesTracker`), optional
partitioning of saved activations across model-parallel ranks, CPU offload
of checkpoints, and contiguous preallocated buffers.

On TPU each concern maps to a JAX-native mechanism:

- recompute-in-backward         → `jax.checkpoint` (remat).
- RNG capture/restore           → free: JAX PRNG keys are explicit values,
  so recomputation replays dropout identically by construction. The
  tracker API is kept for Megatron-style callers.
- partition_activations         → saved residuals carry a `model`-axis
  sharding constraint, so each MP rank stores 1/mp of every checkpoint.
- cpu_checkpointing             → 'offload_dots' remat policy: saved
  matmul results rest in pinned host memory
  (`offload_dot_with_no_batch_dims`). Host-offload transfers only exist
  under `jax.jit` — eager `jax.grad` over an offloading span raises
  (real training is always jitted).
- contiguous_memory_optimization / synchronize_checkpoint_boundary →
  no-ops: XLA owns allocation and scheduling.
"""

from functools import partial

import jax
import jax.numpy as jnp

from ...utils.logging import logger
from .config import REMAT_POLICY_CHOICES, DeepSpeedActivationCheckpointingConfig

_config = DeepSpeedActivationCheckpointingConfig()
_mpu = None
_configured = False

# ---------------------------------------------------------------------------
# Named remat policies. The JSON `activation_checkpointing.policy` key (and
# the model families' `remat_policy=` knob) select one by name; the model
# forward threads the resolved policy into every `jax.checkpoint` span.
#
# Residual-name tags: the flash-attention custom_vjp fwd marks its saved
# output/LSE with these names so `attn_residuals` can pin exactly the
# tensors the Pallas backward kernels consume — the bwd then never re-runs
# the forward kernel under remat.
# ---------------------------------------------------------------------------

ATTN_OUT_NAME = "ds_attn_out"
ATTN_LSE_NAME = "ds_attn_lse"


def tag_attn_residual(x, name=ATTN_OUT_NAME):
    """Mark an attention residual for name-based remat policies. A no-op
    outside `jax.checkpoint` spans (and for policies that ignore names)."""
    from jax.ad_checkpoint import checkpoint_name
    return checkpoint_name(x, name)


def make_remat_policy(name, offload_src="device", offload_dst="pinned_host"):
    """Named policy -> `jax.checkpoint` policy callable.

    Returns `(policy, is_remat)`: `policy` feeds jax.checkpoint's
    `policy=` (None = save nothing, today's whole-block behavior);
    `is_remat=False` only for 'none', which saves everything — callers may
    skip the checkpoint wrapper entirely.

    - none:           save every intermediate (remat disabled).
    - full:           save nothing; recompute the whole span in backward.
    - dots:           save matmul results excluding batch dims (the
                      classic activations-not-weights split).
    - attn_residuals: save only the flash-attention outputs + LSE
                      (`ATTN_OUT_NAME`/`ATTN_LSE_NAME` tags) so the
                      Pallas bwd kernel never re-runs its forward.
    - offload_dots:   'dots', but saved dots rest in host memory
                      (ZeRO-Offload for activations; honors
                      `cpu_checkpointing`).
    """
    if name is None or name == "full":
        return None, True
    cp = jax.checkpoint_policies
    if name == "none":
        return cp.everything_saveable, False
    if name == "dots":
        return cp.dots_with_no_batch_dims_saveable, True
    if name == "attn_residuals":
        return cp.save_only_these_names(ATTN_OUT_NAME, ATTN_LSE_NAME), True
    if name == "offload_dots":
        return cp.offload_dot_with_no_batch_dims(offload_src,
                                                 offload_dst), True
    raise ValueError(
        f"unknown remat policy {name!r}; valid choices: "
        f"{', '.join(REMAT_POLICY_CHOICES)}")


def configure(mpu_=None, deepspeed_config=None, partition_activations=None,
              contiguous_checkpointing=None, num_checkpoints=None,
              checkpoint_in_cpu=None, synchronize=None, profile=None):
    """Configure the checkpointing subsystem (reference
    `checkpointing.py:769`)."""
    global _config, _mpu, _configured
    _mpu = mpu_
    if deepspeed_config is not None:
        if hasattr(deepspeed_config, "activation_checkpointing_config"):
            _config = deepspeed_config.activation_checkpointing_config
        else:
            _config = DeepSpeedActivationCheckpointingConfig.from_dict(
                deepspeed_config if isinstance(deepspeed_config, dict)
                else {})
    overrides = {
        "partition_activations": partition_activations,
        "contiguous_memory_optimization": contiguous_checkpointing,
        "number_checkpoints": num_checkpoints,
        "cpu_checkpointing": checkpoint_in_cpu,
        "synchronize_checkpoint_boundary": synchronize,
        "profile": profile,
    }
    updates = {k: v for k, v in overrides.items() if v is not None}
    if updates:
        import dataclasses
        _config = dataclasses.replace(_config, **updates)
    _configured = True


def is_configured():
    return _configured


def resolve_policy_name(policy, cpu_checkpointing):
    """The effective policy name for a config block: `cpu_checkpointing`
    promotes the (default/'dots') on-device policy to its host-offload
    form — the reference key spills checkpoints to CPU memory."""
    if cpu_checkpointing and policy in (None, "dots", "offload_dots"):
        return "offload_dots"
    return policy


def _policy():
    name = resolve_policy_name(getattr(_config, "policy", None),
                               _config.cpu_checkpointing)
    if name is None:
        return None  # full remat: save nothing, recompute everything
    return make_remat_policy(name)[0]


def checkpoint(function, *args):
    """Checkpoint a forward span: recompute it during backward (reference
    `checkpointing.py:687`). Dropout/noise inside replays identically
    because PRNG keys are explicit arguments."""
    policy = _policy()
    wrapped = jax.checkpoint(function, policy=policy) if policy is not None \
        else jax.checkpoint(function)

    if _config.partition_activations and _mpu is not None:
        axis = None
        if hasattr(_mpu, "get_slice_parallel_group"):
            axis = _mpu.get_slice_parallel_group()
        if isinstance(axis, str):
            # Shard the span inputs over the model axis so each MP rank
            # holds 1/mp of every saved checkpoint (reference
            # `partition_activations` semantics).
            from jax.sharding import PartitionSpec

            def constrain(x):
                if hasattr(x, "ndim") and x.ndim >= 2:
                    spec = [None] * x.ndim
                    spec[1] = axis
                    try:
                        return jax.lax.with_sharding_constraint(
                            x, PartitionSpec(*spec))
                    except Exception:
                        return x
                return x

            args = tuple(jax.tree_util.tree_map(constrain, a)
                         for a in args)
    return wrapped(*args)


def checkpoint_wrapper(fn):
    """Decorator form."""
    return partial(checkpoint, fn)


# ---------------------------------------------------------------------------
# RNG tracker API (reference `checkpointing.py:198`-): Megatron callers
# expect named RNG states whose capture/restore makes dropout reproducible
# under recompute. With JAX's explicit keys this is bookkeeping only.
# ---------------------------------------------------------------------------

_MODEL_PARALLEL_RNG_TRACKER_NAME = "model-parallel-rng"


class CudaRNGStatesTracker:
    """Named PRNG key registry (name kept for API compatibility)."""

    def __init__(self):
        self.states_ = {}
        self.seeds_ = set()

    def reset(self):
        self.states_ = {}
        self.seeds_ = set()

    def get_states(self):
        return dict(self.states_)

    def set_states(self, states):
        self.states_ = dict(states)

    def add(self, name, seed):
        if seed in self.seeds_:
            raise Exception(f"seed {seed} already present")
        self.seeds_.add(seed)
        if name in self.states_:
            raise Exception(f"RNG state {name} already present")
        self.states_[name] = jax.random.PRNGKey(seed)

    def fork(self, name=_MODEL_PARALLEL_RNG_TRACKER_NAME):
        """Context manager yielding the named key; the stored state is
        advanced so successive forks differ."""
        import contextlib

        @contextlib.contextmanager
        def _fork():
            if name not in self.states_:
                raise Exception(f"RNG state {name} is not added")
            key, sub = jax.random.split(self.states_[name])
            self.states_[name] = key
            yield sub

        return _fork()


_CUDA_RNG_STATE_TRACKER = CudaRNGStatesTracker()


def get_cuda_rng_tracker():
    return _CUDA_RNG_STATE_TRACKER


def model_parallel_cuda_manual_seed(seed):
    """Seed data-parallel and model-parallel RNG streams (reference
    `checkpointing.py:198`): MP ranks get offset seeds so dropout differs
    across tensor-parallel shards of one layer."""
    global _CUDA_RNG_STATE_TRACKER
    mp_rank = 0
    if _mpu is not None and hasattr(_mpu, "get_slice_parallel_rank"):
        mp_rank = _mpu.get_slice_parallel_rank()
    offset = seed + 2718
    model_parallel_seed = offset + mp_rank
    _CUDA_RNG_STATE_TRACKER.reset()
    _CUDA_RNG_STATE_TRACKER.add(_MODEL_PARALLEL_RNG_TRACKER_NAME,
                                model_parallel_seed)
    return jax.random.PRNGKey(seed)


def reset():
    """Reset between batches (reference keeps buffers; we keep nothing)."""


def partition_activations_in_checkpoint(partition_activation):
    import dataclasses
    global _config
    _config = dataclasses.replace(
        _config, partition_activations=partition_activation)
    logger.info(f"**************Partition Activations "
                f"{partition_activation}************")
