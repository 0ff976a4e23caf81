"""Pad-the-master ZeRO sharding for ragged params (reference: the
flatten-and-partition-with-padding scheme of `zero/stage2.py:196-374` and
`zero/stage1.py:328-465`, which shards EVERY param's fp32 state).

A parameter with no dp-divisible dim (e.g. an unpadded 50257 vocab) must
still get 1/dp_world of its fp32 master + moments per device — stored as a
padded flat shard — with an unchanged training trajectory and world-size-
independent checkpoints."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

import deeperspeed_tpu
from deeperspeed_tpu.runtime.zero.partition_parameters import (
    FlatPad, ZeroShardingRules, flat_pad, flat_unpad)

# 1003 is not divisible by 2/4/8 in any dim; 7 neither.
RAGGED_SHAPE = (1003, 7)
DIM = RAGGED_SHAPE[1]


def _ragged_model():
    """Tiny regression model whose weight matrix has no dp-divisible dim."""

    def loss_fn(params, batch, rng):
        x, y = batch
        pred = x @ params["w"] + params["b"]
        return jnp.mean((pred.sum(-1) - y) ** 2)

    return loss_fn


def _params(seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {"w": jax.random.normal(k1, (DIM, RAGGED_SHAPE[0])) * 0.02,
            "b": jax.random.normal(k2, (RAGGED_SHAPE[0],)) * 0.01}


def _engine(stage, seed=0, extra=None):
    config = {
        "train_batch_size": 16,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "steps_per_print": 1000,
    }
    if stage:
        config["zero_optimization"] = {"stage": stage}
    config.update(extra or {})
    engine, *_ = deeperspeed_tpu.initialize(
        model=_ragged_model(), model_parameters=_params(seed),
        config_params=config)
    return engine


def _train(engine, steps=4, seed=1):
    rng = np.random.default_rng(seed)
    losses = []
    for _ in range(steps):
        x = rng.normal(size=(1, 16, DIM)).astype(np.float32)
        y = rng.normal(size=(1, 16)).astype(np.float32)
        losses.append(float(engine.train_batch(batch=(x, y))))
    return np.asarray(losses)


def test_master_pad_info_rules(devices):
    mesh = Mesh(np.asarray(devices), ("data",))
    rules = ZeroShardingRules(stage=1, mesh=mesh)
    info = rules.master_pad_info(RAGGED_SHAPE)
    assert isinstance(info, FlatPad)
    assert info.numel == 1003 * 7
    assert info.padded % 8 == 0 and info.padded >= info.numel
    # evenly-divisible shapes keep dim sharding
    assert rules.master_pad_info((1024, 7)) is None
    # tiny leaves stay replicated
    assert rules.master_pad_info((3,)) is None
    # TP-sharded base keeps its layout
    assert rules.master_pad_info(RAGGED_SHAPE,
                                 base=PartitionSpec("data", None)) is None


def test_flat_pad_roundtrip():
    info = FlatPad(RAGGED_SHAPE, 1003 * 7, 1003 * 7 + 3)
    x = jnp.arange(1003 * 7, dtype=jnp.float32).reshape(RAGGED_SHAPE)
    flat = flat_pad(x, info)
    assert flat.shape == (info.padded,)
    assert float(flat[info.numel:].sum()) == 0.0
    np.testing.assert_array_equal(np.asarray(flat_unpad(flat, info)),
                                  np.asarray(x))


@pytest.mark.parametrize("stage", [1, 2])
def test_ragged_masters_are_sharded(devices, stage):
    """The whole point: 1/8 of the ragged fp32 master+moments per device."""
    engine = _engine(stage)
    master_w = engine.state.master["w"]
    assert master_w.ndim == 1, "ragged master should be flat-padded"
    assert master_w.shape[0] % 8 == 0
    shard_sizes = {s.data.shape for s in master_w.addressable_shards}
    assert shard_sizes == {(master_w.shape[0] // 8,)}
    # moments follow
    m_w = engine.state.opt_state.exp_avg["w"]
    assert m_w.shape == master_w.shape
    assert {s.data.shape for s in m_w.addressable_shards} == shard_sizes
    # compute param keeps natural shape
    assert engine.state.params["w"].shape == (DIM, RAGGED_SHAPE[0])


@pytest.mark.parametrize("stage", [1, 2])
def test_ragged_trajectory_parity(devices, stage):
    base = _train(_engine(0))
    got = _train(_engine(stage))
    np.testing.assert_allclose(got, base, rtol=2e-5, atol=2e-5)


def test_ragged_checkpoint_roundtrip(tmp_path, devices):
    engine = _engine(2)
    _train(engine, steps=3)
    engine.save_checkpoint(str(tmp_path))
    saved_master_w = np.asarray(flat_unpad(engine.state.master["w"],
                                           engine._padinfo["w"]))
    ref_losses = _train(engine, steps=2, seed=9)

    engine2 = _engine(2, seed=3)  # different init; must be overwritten
    engine2.load_checkpoint(str(tmp_path))
    np.testing.assert_allclose(
        np.asarray(flat_unpad(engine2.state.master["w"],
                              engine2._padinfo["w"])),
        saved_master_w, rtol=0, atol=0)
    got_losses = _train(engine2, steps=2, seed=9)
    np.testing.assert_allclose(got_losses, ref_losses, rtol=1e-6, atol=1e-6)

    # ragged fp32 state must be rank-SLICED on disk, not duplicated 8x
    import glob
    from deeperspeed_tpu.checkpoint.serialization import load_obj
    shards = [load_obj(p) for p in sorted(
        glob.glob(str(tmp_path / "global_step3" / "zero_pp_rank_*")))]
    assert len(shards) == 8
    assert shards[0]["fp32_master_dims"]["w"] == "flat"
    numel = 1003 * 7
    per_rank = [np.asarray(s["fp32_master"]["w"]).size for s in shards]
    assert sum(per_rank) == numel
    assert max(per_rank) <= -(-numel // 8)

    # offline recovery script reassembles the natural-shaped fp32 master
    from deeperspeed_tpu.utils.zero_to_fp32 import \
        get_fp32_state_dict_from_zero_checkpoint
    sd = get_fp32_state_dict_from_zero_checkpoint(
        str(tmp_path / "global_step3"))
    assert sd["w"].shape == (DIM, RAGGED_SHAPE[0])
    np.testing.assert_array_equal(sd["w"], saved_master_w)


def test_ragged_onebit_lamb_checkpoint_roundtrip(tmp_path, devices):
    """OnebitLamb's opt state carries fields (per-leaf () scalars like
    frozen_scale) whose pytree STRUCTURE mirrors the masters but whose
    leaves are not layout-shaped; checkpoint layout conversion must leave
    them untouched instead of flat-unpadding them (IndexError on 0-d)."""
    extra = {"optimizer": {"type": "OneBitLamb",
                           "params": {"lr": 1e-4, "freeze_step": 2}},
             "zero_optimization": {"stage": 2}}
    engine = _engine(None, extra=extra)
    _train(engine, steps=3)
    engine.save_checkpoint(str(tmp_path))
    ref_losses = _train(engine, steps=2, seed=9)

    engine2 = _engine(None, seed=3, extra=extra)
    engine2.load_checkpoint(str(tmp_path))
    got_losses = _train(engine2, steps=2, seed=9)
    np.testing.assert_allclose(got_losses, ref_losses, rtol=1e-6, atol=1e-6)


def test_ragged_vocab_embedding_parity(devices):
    """GPT-style: unpadded-vocab embedding + tied softmax stays exact."""
    V, D = 201, 9  # no dim divides the 8-device data axis

    def loss_fn(params, batch, rng):
        toks, targets = batch
        h = params["emb"][toks]
        logits = h @ params["emb"].T
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None],
                                             axis=-1))

    def make(stage, seed=0):
        params = {"emb": jax.random.normal(jax.random.PRNGKey(seed),
                                           (V, D)) * 0.02}
        config = {"train_batch_size": 16,
                  "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                  "steps_per_print": 1000}
        if stage:
            config["zero_optimization"] = {"stage": stage}
        engine, *_ = deeperspeed_tpu.initialize(
            model=loss_fn, model_parameters=params, config_params=config)
        return engine

    def run(engine):
        rng = np.random.default_rng(4)
        out = []
        for _ in range(4):
            toks = rng.integers(0, V, (1, 16, 12), np.int32)
            out.append(float(engine.train_batch(batch=(toks, toks))))
        return np.asarray(out)

    base = run(make(0))
    e2 = make(2)
    got = run(e2)
    assert e2.state.master["emb"].ndim == 1  # flat-padded, sharded
    np.testing.assert_allclose(got, base, rtol=2e-5, atol=2e-5)


def test_ragged_stage3_compute_params_sharded(devices):
    """Stage 3 with a ragged (no dp-divisible dim) param: the COMPUTE
    param also rests flat-padded and 1/dp-sharded (reference stage-3
    partitioning covers every param); the in-step unpad is the param
    all-gather. Trajectory must match the unsharded baseline."""
    extra = {"zero_optimization": {"stage": 3,
                                   "stage3_param_persistence_threshold": 0}}
    engine = _engine(None, extra=extra)
    w = engine.state.params["w"]
    assert w.ndim == 1, "ragged stage-3 compute param should be flat"
    assert w.shape[0] % 8 == 0
    assert {s.data.shape for s in w.addressable_shards} == \
        {(w.shape[0] // 8,)}
    # user-facing view restores the natural shape
    nat = engine.params_to_natural(engine.state.params)
    assert nat["w"].shape == (DIM, RAGGED_SHAPE[0])

    base = _train(_engine(0))
    got = _train(_engine(None, extra=extra))
    np.testing.assert_allclose(got, base, rtol=2e-5, atol=2e-5)


def test_ragged_stage3_checkpoint_roundtrip(tmp_path, devices):
    extra = {"zero_optimization": {"stage": 3,
                                   "stage3_param_persistence_threshold": 0}}
    engine = _engine(None, extra=extra)
    _train(engine, steps=3)
    engine.save_checkpoint(str(tmp_path))
    ref = _train(engine, steps=2, seed=9)
    engine2 = _engine(None, seed=3, extra=extra)
    engine2.load_checkpoint(str(tmp_path))
    got = _train(engine2, steps=2, seed=9)
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)
