"""Offload-tier tests: C++ aio engine, swappers, native CPU Adam (the TPU
analogues of reference `csrc/aio/py_test` sweeps and
`tests/perf/test_cpu_adam.py` / `tests/unit/test_cpu_adam.py`)."""

import os

import numpy as np
import pytest

from deeperspeed_tpu.ops.adam.cpu_adam_native import (NativeCPUAdam,
                                                      cpu_adam_available)
from deeperspeed_tpu.ops.adam.fused_adam import FusedAdam
from deeperspeed_tpu.runtime.swap_tensor.aio_engine import AsyncIOEngine
from deeperspeed_tpu.runtime.swap_tensor.async_swapper import \
    AsyncTensorSwapper
from deeperspeed_tpu.runtime.swap_tensor.optimizer_swappers import (
    OptimizerSwapper, PipelinedOptimizerSwapper)
from deeperspeed_tpu.runtime.swap_tensor.partitioned_param_swapper import \
    AsyncPartitionedParameterSwapper

pytestmark = pytest.mark.offload

needs_aio = pytest.mark.skipif(not AsyncIOEngine.available(),
                               reason="no C++ toolchain for aio engine")
needs_cpu_adam = pytest.mark.skipif(not cpu_adam_available(),
                                    reason="no C++ toolchain for cpu adam")


@needs_aio
def test_aio_write_read_roundtrip(tmp_path):
    engine = AsyncIOEngine(block_size=4096, thread_count=4)
    data = np.random.default_rng(0).normal(size=(1 << 16,)).astype(
        np.float32)
    path = str(tmp_path / "tensor.swp")
    engine.sync_pwrite(data, path)
    out = np.empty_like(data)
    engine.sync_pread(out, path)
    np.testing.assert_array_equal(out, data)


@needs_aio
def test_aio_async_overlap(tmp_path):
    engine = AsyncIOEngine(thread_count=4)
    tensors = [np.full((1 << 14,), i, np.float32) for i in range(8)]
    for i, t in enumerate(tensors):
        engine.aio_write(t, str(tmp_path / f"t{i}.swp"))
    engine.wait()
    outs = [np.empty((1 << 14,), np.float32) for _ in range(8)]
    for i, o in enumerate(outs):
        engine.aio_read(o, str(tmp_path / f"t{i}.swp"))
    engine.wait()
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(o, tensors[i])


@needs_aio
def test_async_tensor_swapper(tmp_path):
    swapper = AsyncTensorSwapper()
    tensors = [np.random.default_rng(i).normal(size=(1000,)).astype(
        np.float32) for i in range(3)]
    paths = [str(tmp_path / f"s{i}.swp") for i in range(3)]
    swapper.swap_out_tensors(tensors, paths)
    swapper.synchronize_writes()
    buffers = [np.empty((1000,), np.float32) for _ in range(3)]
    swapper.swap_in_tensors(buffers, paths)
    swapper.synchronize_reads()
    for buf, t in zip(buffers, tensors):
        np.testing.assert_array_equal(buf, t)


@needs_aio
def test_partitioned_param_swapper(tmp_path):
    swapper = AsyncPartitionedParameterSwapper(
        nvme_path=str(tmp_path), buffer_count=2, buffer_size=4096)
    p0 = np.random.default_rng(0).normal(size=(32, 32)).astype(np.float32)
    p1 = np.random.default_rng(1).normal(size=(64,)).astype(np.float32)
    swapper.swap_out(0, p0)
    swapper.swap_out(1, p1)
    swapper.synchronize_writes()

    views = swapper.swap_in([0, 1], async_op=False)
    np.testing.assert_array_equal(views[0], p0)
    np.testing.assert_array_equal(views[1], p1)
    assert swapper.available_swap_in_buffers() == 0
    swapper.release([0, 1])
    assert swapper.available_swap_in_buffers() == 2


@needs_aio
@pytest.mark.parametrize("cls", [OptimizerSwapper,
                                 PipelinedOptimizerSwapper])
def test_optimizer_swapper_step(tmp_path, cls):
    swapper = cls(str(tmp_path))
    rng = np.random.default_rng(0)
    groups = {}
    for gid in range(3):
        state = {
            "master": rng.normal(size=(512,)).astype(np.float32),
            "exp_avg": np.zeros((512,), np.float32),
            "exp_avg_sq": np.zeros((512,), np.float32),
        }
        groups[gid] = {k: v.copy() for k, v in state.items()}
        swapper.initialize_group(gid, state)

    def update(gid, state):
        state["master"] = state["master"] + 1.0
        state["exp_avg"] = state["exp_avg"] + 0.5
        return state

    swapper.step([0, 1, 2], update)
    for gid in range(3):
        loaded = swapper.load_group(gid)
        np.testing.assert_allclose(loaded["master"],
                                   groups[gid]["master"] + 1.0)
        np.testing.assert_allclose(loaded["exp_avg"], 0.5)


@needs_cpu_adam
def test_native_cpu_adam_matches_fused():
    """C++ host Adam must match the jax FusedAdam trajectory (reference
    test_cpu_adam.py compares AVX Adam vs torch.optim.Adam)."""
    rng = np.random.default_rng(0)
    n = 4096
    master0 = rng.normal(size=(n,)).astype(np.float32)

    jadam = FusedAdam(lr=0.01, weight_decay=0.01, adam_w_mode=True)
    params = {"w": master0.copy()}
    state = jadam.init_state(params)

    cadam = NativeCPUAdam(lr=0.01, weight_decay=0.01, adam_w_mode=True)
    c_master = master0.copy()
    c_m = np.zeros(n, np.float32)
    c_v = np.zeros(n, np.float32)

    for step in range(5):
        grads = {"w": rng.normal(size=(n,)).astype(np.float32)}
        params, state = jadam.update(grads, state, params)
        cadam.step_flat(c_master, grads["w"], c_m, c_v)

    np.testing.assert_allclose(c_master, np.asarray(params["w"]),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(c_m, np.asarray(state.exp_avg["w"]),
                               rtol=2e-5, atol=2e-6)


@needs_cpu_adam
def test_native_cpu_adam_bf16_shadow():
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    n = 1024
    master = rng.normal(size=(n,)).astype(np.float32)
    grads = rng.normal(size=(n,)).astype(np.float32)
    m = np.zeros(n, np.float32)
    v = np.zeros(n, np.float32)
    bf16 = np.empty(n, np.uint16)
    adam = NativeCPUAdam(lr=0.01)
    adam.step_flat(master, grads, m, v, bf16_out=bf16)
    shadow = bf16.view(np.uint16).astype(np.uint32) << 16
    shadow = shadow.view(np.float32) if False else \
        np.frombuffer(shadow.astype(np.uint32).tobytes(), np.float32)
    np.testing.assert_allclose(shadow, master, rtol=1e-2, atol=1e-2)
    expected = np.asarray(jnp.asarray(master).astype(jnp.bfloat16)
                          .astype(jnp.float32))
    np.testing.assert_allclose(shadow, expected, rtol=1e-6, atol=1e-6)


# --- engine integration ---------------------------------------------------

@needs_cpu_adam
def test_engine_cpu_offload_matches_device(tmp_path):
    """ZeRO-Offload (cpu) must follow the same trajectory as the on-device
    optimizer."""
    import jax
    import deeperspeed_tpu
    from tests.simple_model import SimpleModel, random_batches

    model = SimpleModel(hidden_dim=16)
    params = model.init_params(__import__("jax").random.PRNGKey(7))

    def cfg(offload):
        c = {
            "train_batch_size": 8,
            "steps_per_print": 100,
            "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
            "zero_optimization": {"stage": 2},
        }
        if offload:
            c["zero_optimization"]["offload_optimizer"] = {"device": "cpu"}
        return c

    e_dev, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=params, config_params=cfg(False))
    e_off, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=params, config_params=cfg(True))
    assert e_off.host_offload

    it1 = random_batches(12, 8, 16, seed=3)
    it2 = random_batches(12, 8, 16, seed=3)
    l_dev = [float(e_dev.train_batch(data_iter=it1)) for _ in range(5)]
    l_off = [float(e_off.train_batch(data_iter=it2)) for _ in range(5)]
    np.testing.assert_allclose(l_off, l_dev, rtol=1e-4)


@needs_aio
@needs_cpu_adam
def test_engine_nvme_offload_trains(tmp_path):
    import jax
    import deeperspeed_tpu
    from tests.simple_model import SimpleModel, random_batches

    model = SimpleModel(hidden_dim=16)
    params = model.init_params(__import__("jax").random.PRNGKey(7))
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=params, config_params={
            "train_batch_size": 8,
            "steps_per_print": 100,
            "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
            "zero_optimization": {
                "stage": 2,
                "offload_optimizer": {"device": "nvme",
                                      "nvme_path": str(tmp_path)},
            },
        })
    fixed = next(random_batches(1, 8, 16, seed=4))
    stacked = {0: None}
    import jax as _jax
    batch = _jax.tree_util.tree_map(lambda x: x[None], fixed)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(6)]
    assert losses[-1] < losses[0]
    assert os.listdir(tmp_path / "optimizer")


@needs_cpu_adam
def test_engine_cpu_offload_checkpoint_roundtrip(tmp_path):
    import jax
    import deeperspeed_tpu
    from tests.simple_model import SimpleModel, random_batches

    def make(seed):
        model = SimpleModel(hidden_dim=16)
        params = model.init_params(__import__("jax").random.PRNGKey(seed))
        engine, *_ = deeperspeed_tpu.initialize(
            model=model, model_parameters=params, config_params={
                "train_batch_size": 8,
                "steps_per_print": 100,
                "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
                "zero_optimization": {
                    "stage": 2,
                    "offload_optimizer": {"device": "cpu"},
                },
            })
        return engine

    e1 = make(1)
    it = random_batches(10, 8, 16, seed=5)
    for _ in range(3):
        e1.train_batch(data_iter=it)
    e1.save_checkpoint(str(tmp_path), tag="off")

    e2 = make(2)
    e2.load_checkpoint(str(tmp_path), tag="off")
    it1 = random_batches(6, 8, 16, seed=9)
    it2 = random_batches(6, 8, 16, seed=9)
    la = [float(e1.train_batch(data_iter=it1)) for _ in range(3)]
    lb = [float(e2.train_batch(data_iter=it2)) for _ in range(3)]
    np.testing.assert_allclose(la, lb, rtol=1e-5)


def test_pld_theta_reaches_loss_fn_with_offload():
    """The host-offload grads path must thread pld_theta too."""
    import jax
    import jax.numpy as jnp

    import deeperspeed_tpu

    class PldModel:
        def init_params(self, rng):
            return {"w": jnp.ones((8, 8))}

        def loss_fn(self, params, batch, rng=None, pld_theta=None):
            x, y = batch
            assert pld_theta is not None
            return jnp.mean((x @ params["w"] * pld_theta - y) ** 2)

    model = PldModel()
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(
            jax.random.PRNGKey(0)),
        config_params={"train_batch_size": 8,
                       "optimizer": {"type": "Adam",
                                     "params": {"lr": 1e-2}},
                       "zero_optimization": {
                           "stage": 2,
                           "offload_optimizer": {"device": "cpu"}},
                       "progressive_layer_drop": {"enabled": True,
                                                  "theta": 0.5,
                                                  "gamma": 0.1},
                       "steps_per_print": 100})
    assert engine.host_offload and engine._pld_in_loss
    x = np.ones((1, 8, 8), np.float32)
    losses = [float(engine.train_batch(batch=(x, x))) for _ in range(3)]
    assert np.isfinite(losses).all()
