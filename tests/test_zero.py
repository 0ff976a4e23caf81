"""ZeRO stage 1/2/3 correctness (parity with reference
`tests/unit/test_zero.py`: stage training correctness incl. unbalanced
gradients, plus the TPU-native assertions — state actually lives sharded on
the mesh and every stage matches an unsharded fp32 baseline bit-for-bit in
fp32).
"""

import numpy as np

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from deeperspeed_tpu.ops.adam.fused_adam import FusedAdam
from deeperspeed_tpu.runtime.zero import (
    FP16_DeepSpeedZeroOptimizer_Stage1, FP16_DeepSpeedZeroOptimizer_Stage2,
    FP16_DeepSpeedZeroOptimizer_Stage3)
from deeperspeed_tpu.runtime.zero.stage1 import (flat_sub_partitions,
                                                 get_group_alignment_padding,
                                                 sub_partition_sizes)

STAGES = {1: FP16_DeepSpeedZeroOptimizer_Stage1,
          2: FP16_DeepSpeedZeroOptimizer_Stage2,
          3: FP16_DeepSpeedZeroOptimizer_Stage3}


def data_mesh():
    return Mesh(np.asarray(jax.devices()[:8]), ("data",))


def mlp_params(hidden=32):
    rng = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(rng)
    return {
        "dense": {"w": jax.random.normal(k1, (hidden, hidden),
                                         jnp.float32) * 0.1,
                  "b": jnp.zeros((hidden,), jnp.float32)},
        # Deliberately non-divisible by 8 along dim 0 (unbalanced grads,
        # reference test_zero.py:13-40).
        "head": {"w": jax.random.normal(k2, (hidden, 17),
                                        jnp.float32) * 0.1},
    }


def loss_fn(params, batch):
    x, y = batch
    h = jnp.tanh(x @ params["dense"]["w"] + params["dense"]["b"])
    out = h @ params["head"]["w"]
    return jnp.mean(jnp.square(out - y))


def batch_for(hidden=32, n=16):
    rng = np.random.default_rng(0)
    return (jnp.asarray(rng.normal(size=(n, hidden)), jnp.float32),
            jnp.asarray(rng.normal(size=(n, 17)), jnp.float32))


# ---------------------------------------------------------------------------
# sub-partition math
# ---------------------------------------------------------------------------

def test_sub_partition_sizes_cover_numel():
    sizes = sub_partition_sizes(103, world=4, sub_partition_count=2)
    assert len(sizes) == 8
    assert sum(sizes) == 103


def test_flat_sub_partitions_round_robin():
    flat = np.arange(12)
    per_rank = flat_sub_partitions(flat, world=2, sub_partition_count=2)
    assert len(per_rank) == 2
    np.testing.assert_array_equal(np.concatenate(per_rank[0]),
                                  [0, 1, 2, 6, 7, 8])
    np.testing.assert_array_equal(np.concatenate(per_rank[1]),
                                  [3, 4, 5, 9, 10, 11])


def test_alignment_padding():
    assert get_group_alignment_padding(10, world=4) == 2
    assert get_group_alignment_padding(8, world=4) == 0
    assert get_group_alignment_padding(10, world=4, alignment=2) == 6


# ---------------------------------------------------------------------------
# stage correctness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stage", [1, 2, 3])
def test_stage_matches_unsharded_baseline(stage):
    """Sharded update == replicated update (fp32, so exact up to reduction
    order)."""
    mesh = data_mesh()
    params = mlp_params()
    batch = batch_for()

    opt = STAGES[stage](FusedAdam(lr=1e-2), mesh=mesh,
                        precision=jnp.float32,
                        param_persistence_threshold=0)
    state = opt.init_state(params)

    base_opt = FusedAdam(lr=1e-2)
    base_state = base_opt.init_state(params)
    base_params = params

    step = jax.jit(opt.step)
    for i in range(5):
        grads = jax.grad(loss_fn)(state.params, batch)
        state, info = step(state, grads)
        assert not bool(info.overflow)

        base_grads = jax.grad(loss_fn)(base_params, batch)
        base_params, base_state = base_opt.update(base_grads, base_state,
                                                  base_params)

    for a, b in zip(jax.tree_util.tree_leaves(state.params),
                    jax.tree_util.tree_leaves(base_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_state_is_sharded_on_mesh(stage):
    mesh = data_mesh()
    opt = STAGES[stage](FusedAdam(lr=1e-2), mesh=mesh,
                        param_persistence_threshold=0)
    state = opt.init_state(mlp_params())

    def is_sharded(x):
        spec = x.sharding.spec
        return any(s is not None for s in spec)

    # masters + moments sharded from stage 1
    assert is_sharded(state.master["dense"]["w"])
    assert is_sharded(state.opt_state.exp_avg["dense"]["w"])
    # compute params sharded at rest only at stage 3
    assert is_sharded(state.params["dense"]["w"]) == (stage == 3)
    # stage-3 shard really is 1/8th per device
    if stage == 3:
        shard = state.params["dense"]["w"].addressable_shards[0]
        assert shard.data.size == state.params["dense"]["w"].size // 8


def test_stage3_unbalanced_param_not_divisible():
    """17-wide head: world=8 doesn't divide any dim evenly; GSPMD pads.
    Training must still match the baseline (reference's unbalanced-gradient
    test intent)."""
    mesh = data_mesh()
    opt = FP16_DeepSpeedZeroOptimizer_Stage3(
        FusedAdam(lr=1e-2), mesh=mesh, precision=jnp.float32,
        param_persistence_threshold=0)
    params = mlp_params()
    state = opt.init_state(params)
    batch = batch_for()
    loss0 = float(loss_fn(state.params, batch))
    step = jax.jit(opt.step)
    for _ in range(10):
        grads = jax.grad(loss_fn)(state.params, batch)
        state, _ = step(state, grads)
    assert float(loss_fn(state.params, batch)) < loss0


def test_stage3_consolidated_state_dict():
    mesh = data_mesh()
    opt = FP16_DeepSpeedZeroOptimizer_Stage3(
        FusedAdam(lr=1e-2), mesh=mesh, precision=jnp.float32,
        param_persistence_threshold=0)
    params = mlp_params()
    state = opt.init_state(params)
    sd = opt.consolidated_fp16_state_dict(state)
    np.testing.assert_allclose(sd["dense"]["w"],
                               np.asarray(params["dense"]["w"]), rtol=1e-6)


@pytest.mark.parametrize("stage", [1, 2])
def test_elastic_state_dict_roundtrip(stage):
    """state_dict written under one layout restores exactly (merge of
    rank-major sub-partitions)."""
    mesh = data_mesh()
    opt = STAGES[stage](FusedAdam(lr=1e-2), mesh=mesh,
                        precision=jnp.float32)
    params = mlp_params()
    state = opt.init_state(params)
    batch = batch_for()
    step = jax.jit(opt.step)
    for _ in range(3):
        grads = jax.grad(loss_fn)(state.params, batch)
        state, _ = step(state, grads)
    sd = opt.state_dict(state)
    assert sd["partition_count"] == 8

    fresh = opt.init_state(params)
    restored = opt.load_state_dict(fresh, sd)
    for a, b in zip(jax.tree_util.tree_leaves(restored.master),
                    jax.tree_util.tree_leaves(state.master)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_overflow_skips_and_rescales():
    mesh = data_mesh()
    opt = FP16_DeepSpeedZeroOptimizer_Stage2(
        FusedAdam(lr=1e-2), mesh=mesh, dynamic_loss_scale=True,
        dynamic_loss_args={"init_scale": 2 ** 10})
    params = mlp_params()
    state = opt.init_state(params)
    before = [np.asarray(l) for l in
              jax.tree_util.tree_leaves(state.master)]
    bad = jax.tree_util.tree_map(
        lambda p: jnp.full(p.shape, jnp.nan, jnp.float32), params)
    state, info = jax.jit(opt.step)(state, bad)
    assert bool(info.overflow)
    assert float(state.scale.cur_scale) == 2 ** 9
    for a, b in zip(before, jax.tree_util.tree_leaves(state.master)):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_zero_shards_replicate_ragged_dims():
    """Params with no dim divisible by the dp world (e.g. a 10-class head
    over 8 ranks) must replicate, not crash device_put (regression)."""
    from deeperspeed_tpu.runtime.zero.partition_parameters import (
        ZeroShardingRules)

    rules = ZeroShardingRules(stage=2, mesh=data_mesh(), data_axis="data")
    spec = rules.master_spec((10,))
    # PartitionSpec(None) ≡ PartitionSpec(): fully replicated
    assert all(ax is None for ax in spec)
    spec2 = rules.master_spec((10, 16))  # dim 1 divides: shard there
    assert spec2 == jax.sharding.PartitionSpec(None, "data")
