"""zero.Init / GatheredParameters / external-parameter registry tests
(parity with reference `tests/unit/test_zero_context.py`).
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from deeperspeed_tpu.runtime import zero
from deeperspeed_tpu.runtime.zero.partition_parameters import (
    current_init_context, register_external_parameter,
    unregister_external_parameter)


def data_mesh():
    return Mesh(np.asarray(jax.devices()[:8]), ("data",))


def init_fn(rng):
    k1, k2 = jax.random.split(rng)
    return {
        "w": jax.random.normal(k1, (64, 64), jnp.float32),
        "tiny": jax.random.normal(k2, (4,), jnp.float32),
    }


def test_init_materializes_sharded():
    mesh = data_mesh()
    with zero.Init(mesh=mesh, stage=3, param_persistence_threshold=16) as ctx:
        assert current_init_context() is ctx
        params = ctx.materialize(init_fn, jax.random.PRNGKey(0))
    assert current_init_context() is None

    # big param sharded 1/8 per device, tiny param persisted (replicated)
    w = params["w"]
    assert any(s is not None for s in w.sharding.spec)
    assert w.addressable_shards[0].data.size == w.size // 8
    assert all(s is None for s in params["tiny"].sharding.spec)


def test_init_disabled_leaves_replicated():
    mesh = data_mesh()
    with zero.Init(mesh=mesh, stage=3, enabled=False) as ctx:
        params = ctx.materialize(init_fn, jax.random.PRNGKey(0))
    assert all(s is None for s in params["w"].sharding.spec)


def test_init_values_match_unsharded():
    """Sharded materialization computes the same numbers as plain init."""
    mesh = data_mesh()
    expect = init_fn(jax.random.PRNGKey(0))
    with zero.Init(mesh=mesh, stage=3, param_persistence_threshold=0) as ctx:
        params = ctx.materialize(init_fn, jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(params["w"]),
                               np.asarray(expect["w"]), rtol=1e-6)


def test_gathered_parameters_full_view():
    mesh = data_mesh()
    with zero.Init(mesh=mesh, stage=3, param_persistence_threshold=0) as ctx:
        params = ctx.materialize(init_fn, jax.random.PRNGKey(0))
    with zero.GatheredParameters(params) as full:
        assert isinstance(full["w"], np.ndarray)
        assert full["w"].shape == (64, 64)
        np.testing.assert_allclose(full["w"], np.asarray(params["w"]),
                                   rtol=1e-6)


def test_gathered_parameters_disabled_passthrough():
    params = {"w": jnp.ones((2, 2))}
    with zero.GatheredParameters(params, enabled=False) as out:
        assert out is params


def test_external_parameter_registry():
    module, param = object(), object()
    register_external_parameter(module, param)
    unregister_external_parameter(module, param)


def test_gathered_parameters_write_back():
    """modifier_rank semantics (reference partition_parameters.py:1002):
    mutations under the context survive, re-placed with the original
    shardings."""
    mesh = data_mesh()
    with zero.Init(mesh=mesh, stage=3, param_persistence_threshold=0) as ctx:
        params = ctx.materialize(init_fn, jax.random.PRNGKey(0))
    gp = zero.GatheredParameters(params, modifier_rank=0)
    with gp as full:
        full["w"][0, :] = 7.0
    assert gp.updated is not None
    w = gp.updated["w"]
    assert w.sharding == params["w"].sharding  # stays ZeRO-3 sharded
    np.testing.assert_allclose(np.asarray(w)[0], 7.0)
    np.testing.assert_allclose(np.asarray(w)[1:],
                               np.asarray(params["w"])[1:], rtol=1e-6)


def test_gathered_parameters_read_only_drops_mutations():
    params = {"w": jnp.ones((8, 8))}
    gp = zero.GatheredParameters(params)  # modifier_rank=None
    with gp as full:
        full["w"][:] = 5.0
    assert gp.updated is None
    np.testing.assert_allclose(np.asarray(params["w"]), 1.0)


def test_engine_gathered_parameters_updates_training_state():
    """Mutating under engine.gathered_parameters edits the LIVE sharded
    state: compute params AND fp32 masters, so the next step trains from
    the edited weights."""
    import deeperspeed_tpu

    def loss_fn(params, batch, rng):
        x, y = batch
        return (((x @ params["w"]).sum(-1) - y) ** 2).mean()

    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 24)) * 0.1}
    engine, *_ = deeperspeed_tpu.initialize(
        model=loss_fn, model_parameters=params,
        config_params={"train_batch_size": 16,
                       "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                       "zero_optimization": {"stage": 2},
                       "steps_per_print": 1000})
    with engine.gathered_parameters(modifier_rank=0) as full:
        full["w"][:, 0] = 3.25
    np.testing.assert_allclose(np.asarray(engine.state.params["w"])[:, 0],
                               3.25)
    master_nat = engine.layout_to_natural(engine.state.master)
    np.testing.assert_allclose(np.asarray(master_nat["w"])[:, 0], 3.25)

    # and training proceeds from the edited weights (master drives params)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(1, 16, 8)).astype(np.float32)
    y = rng.normal(size=(1, 16)).astype(np.float32)
    engine.train_batch(batch=(x, y))
    w_after = np.asarray(engine.state.params["w"])
    assert np.allclose(w_after[:, 0], 3.25, atol=0.01)  # moved by ~lr only


def test_engine_gathered_parameters_host_offload_masters():
    """With ZeRO-Offload the gather must read/write the host fp32 masters
    — NOT round everything through the compute dtype."""
    import deeperspeed_tpu

    def loss_fn(params, batch, rng):
        x, y = batch
        return (((x @ params["w"]).sum(-1) - y) ** 2).mean()

    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 24)) * 0.1}
    engine, *_ = deeperspeed_tpu.initialize(
        model=loss_fn, model_parameters=params,
        config_params={"train_batch_size": 16,
                       "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                       "fp16": {"enabled": True, "type": "bfloat16"},
                       "zero_optimization": {
                           "stage": 2,
                           "offload_optimizer": {"device": "cpu"}},
                       "steps_per_print": 1000})
    # plant a value NOT representable in bf16; an untouched leaf's master
    # must keep full fp32 precision through the context
    probe = np.float32(0.1000123)
    engine._host_state["master"][0][0] = probe
    with engine.gathered_parameters(modifier_rank=0) as full:
        assert full["w"].dtype == np.float32
        assert full["w"].ravel()[0] == probe  # gathered FROM host masters
        full["w"][0, 1] = 0.5
    assert engine._host_state["master"][0][0] == probe  # precision kept
    assert engine._host_state["master"][0][engine._host_shapes[0][1]
                                           * 0 + 1] == np.float32(0.5)
    np.testing.assert_allclose(
        np.asarray(engine.state.params["w"], np.float32)[0, 1], 0.5,
        rtol=1e-2)


def test_gathered_parameters_subtree_select(devices):
    """`select` gathers only the requested sub-tree: unselected leaves
    never leave the device (no whole-model host stall), mutations to the
    selected leaves still write back into training state."""
    import deeperspeed_tpu

    def loss_fn(params, batch, rng):
        x, y = batch
        return jnp.mean((x @ params["a"]["w"] + params["b"]["w"] - y) ** 2)

    params = {"a": {"w": jnp.ones((8, 8))}, "b": {"w": jnp.ones((8,))}}
    engine, *_ = deeperspeed_tpu.initialize(
        model=loss_fn, model_parameters=params,
        config_params={"train_batch_size": 16,
                       "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                       "zero_optimization": {"stage": 2},
                       "steps_per_print": 1000})
    with engine.gathered_parameters(modifier_rank=0,
                                    select=["b/"]) as full:
        assert isinstance(full["b"]["w"], np.ndarray)
        assert not isinstance(full["a"]["w"], np.ndarray), \
            "unselected leaf must stay a device array"
        full["b"]["w"][:] = 3.5
    nat = engine.params_to_natural(engine.state.params)
    np.testing.assert_allclose(np.asarray(nat["b"]["w"], np.float32), 3.5)
    np.testing.assert_allclose(np.asarray(nat["a"]["w"], np.float32), 1.0)
