"""Checkpoint save/load tests (parity with reference
`tests/unit/test_checkpointing.py`: round-trips across optimizers/zero, tag
handling, elastic resharding)."""

import os

import numpy as np
import pytest

import jax

import deeperspeed_tpu
from tests.simple_model import SimpleModel, random_batches

HIDDEN = 16


def cfg(**overrides):
    base = {
        "train_batch_size": 8,
        "steps_per_print": 100,
        "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
    }
    base.update(overrides)
    return base


def make_engine(config, seed=0):
    model = SimpleModel(hidden_dim=HIDDEN)
    params = model.init_params(jax.random.PRNGKey(seed))
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=params, config_params=config)
    return engine


def params_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)


@pytest.mark.parametrize("config", [
    cfg(),
    cfg(fp16={"enabled": True, "type": "bfloat16"}),
    cfg(zero_optimization={"stage": 1},
        fp16={"enabled": True, "type": "bfloat16"}),
    cfg(zero_optimization={"stage": 2},
        fp16={"enabled": True, "type": "bfloat16"}),
    cfg(zero_optimization={"stage": 3},
        fp16={"enabled": True, "type": "bfloat16"}),
    cfg(scheduler={"type": "WarmupLR",
                   "params": {"warmup_max_lr": 0.01,
                              "warmup_num_steps": 10}}),
], ids=["fp32", "bf16", "zero1", "zero2", "zero3", "sched"])
def test_checkpoint_roundtrip(tmp_path, config):
    engine = make_engine(config, seed=1)
    it = random_batches(20, 8, HIDDEN, seed=1)
    for _ in range(5):
        engine.train_batch(data_iter=it)

    engine.save_checkpoint(str(tmp_path), tag="tag5")
    assert os.path.isfile(tmp_path / "tag5" / "mp_rank_00_model_states.pt")
    assert (tmp_path / "latest").read_text() == "tag5"

    # Train further, then restore: state must match the snapshot exactly.
    snap_params = jax.tree_util.tree_map(np.asarray, engine.state.params)
    snap_steps = engine.global_steps
    for _ in range(3):
        engine.train_batch(data_iter=it)

    engine2 = make_engine(config, seed=2)  # different init
    path, client = engine2.load_checkpoint(str(tmp_path))
    assert path.endswith("tag5")
    params_equal(engine2.state.params, snap_params)
    assert engine2.global_steps == snap_steps

    # Resumed training must follow the same trajectory as uninterrupted.
    it_a = random_batches(10, 8, HIDDEN, seed=77)
    it_b = random_batches(10, 8, HIDDEN, seed=77)
    engine3 = make_engine(config, seed=3)
    engine3.load_checkpoint(str(tmp_path))
    la = [float(engine2.train_batch(data_iter=it_a)) for _ in range(4)]
    lb = [float(engine3.train_batch(data_iter=it_b)) for _ in range(4)]
    np.testing.assert_allclose(la, lb, rtol=1e-5)


def test_checkpoint_client_state(tmp_path):
    engine = make_engine(cfg())
    it = random_batches(2, 8, HIDDEN)
    engine.train_batch(data_iter=it)
    engine.save_checkpoint(str(tmp_path), tag="t",
                           client_state={"my_key": 123})
    engine2 = make_engine(cfg())
    _, client = engine2.load_checkpoint(str(tmp_path), tag="t")
    assert client["my_key"] == 123


def test_checkpoint_zero_files_per_rank(tmp_path):
    engine = make_engine(cfg(zero_optimization={"stage": 2},
                             fp16={"enabled": True, "type": "bfloat16"}))
    it = random_batches(2, 8, HIDDEN)
    engine.train_batch(data_iter=it)
    engine.save_checkpoint(str(tmp_path), tag="z")
    files = sorted(os.listdir(tmp_path / "z"))
    zero_files = [f for f in files if f.startswith("zero_pp_rank_")]
    assert len(zero_files) == engine.dp_world_size
    assert "zero_pp_rank_0_mp_rank_00_optim_states.pt" in zero_files


def test_checkpoint_loss_scale_restored(tmp_path):
    engine = make_engine(cfg(fp16={"enabled": True,
                                   "initial_scale_power": 8}))
    it = random_batches(4, 8, HIDDEN)
    for _ in range(3):
        engine.train_batch(data_iter=it)
    scale_before = engine.loss_scale
    engine.save_checkpoint(str(tmp_path), tag="s")
    engine2 = make_engine(cfg(fp16={"enabled": True,
                                    "initial_scale_power": 8}))
    engine2.load_checkpoint(str(tmp_path), tag="s")
    assert engine2.loss_scale == scale_before


def test_missing_checkpoint_returns_none(tmp_path):
    engine = make_engine(cfg())
    path, client = engine.load_checkpoint(str(tmp_path))
    assert path is None


def test_elastic_resharding_smaller_world(tmp_path):
    """ZeRO checkpoint written at dp=8 reloads on a dp=4 mesh (reference
    elastic checkpointing, `stage2.py:1825-1894`): saved partitions are
    merged and re-sliced, then training continues."""
    from jax.sharding import Mesh
    from tests.simple_model import SimpleModel

    config = cfg(zero_optimization={"stage": 2},
                 fp16={"enabled": True, "type": "bfloat16"})

    model = SimpleModel(hidden_dim=HIDDEN)
    params = model.init_params(jax.random.PRNGKey(0))
    e8, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=params, config_params=config)
    assert e8.dp_world_size == 8
    rng = np.random.default_rng(0)
    x = rng.normal(size=(1, 8, HIDDEN)).astype(np.float32)
    for _ in range(3):
        e8.train_batch(batch=(x, x * 0.1))
    e8.save_checkpoint(str(tmp_path))
    ref = jax.tree_util.tree_map(np.asarray, e8.state.params)

    mesh4 = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    e4, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(
            jax.random.PRNGKey(7)),
        mesh=mesh4,
        config_params=cfg(train_batch_size=8,
                          zero_optimization={"stage": 2},
                          fp16={"enabled": True, "type": "bfloat16"}))
    assert e4.dp_world_size == 4
    path, _ = e4.load_checkpoint(str(tmp_path))
    assert path is not None
    params_equal(e4.state.params, ref)

    # optimizer state survived the merge: training continues from it
    loss = e4.train_batch(batch=(np.repeat(x, 1, axis=0), x * 0.1))
    assert np.isfinite(float(loss))


def test_zero3_consolidated_fp16_state_dict():
    """Reference `engine.py:1820`: every rank gets the full gathered
    params in compute precision; non-ZeRO-3 engines refuse."""
    import pytest
    from tests.simple_model import SimpleModel

    model = SimpleModel(hidden_dim=16)
    engine, *_ = deeperspeed_tpu.initialize(
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        config_params={"train_batch_size": 16, "steps_per_print": 1000,
                       "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                       "fp16": {"enabled": True, "type": "bfloat16"},
                       "zero_optimization": {"stage": 3}})
    sd = engine._zero3_consolidated_fp16_state_dict()
    leaves = jax.tree_util.tree_leaves(sd)
    assert all(isinstance(l, np.ndarray) for l in leaves)
    assert leaves[0].dtype == np.dtype("bfloat16") or \
        str(leaves[0].dtype) == "bfloat16"
    # full (unsharded) shapes
    ref = model.init_params(jax.random.PRNGKey(0))
    for a, b in zip(leaves, jax.tree_util.tree_leaves(ref)):
        assert a.shape == b.shape

    engine0, *_ = deeperspeed_tpu.initialize(
        model=model,
        model_parameters=model.init_params(jax.random.PRNGKey(0)),
        config_params={"train_batch_size": 16, "steps_per_print": 1000,
                       "optimizer": {"type": "Adam",
                                     "params": {"lr": 1e-3}}})
    with pytest.raises(ValueError):
        engine0._zero3_consolidated_fp16_state_dict()
