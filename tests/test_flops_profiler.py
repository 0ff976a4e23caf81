"""Flops profiler tests (parity with reference
`tests/unit/test_flops_profiler.py`: total flops/params/duration reported
for a known model; here flops come from XLA cost analysis so the matmul
count is exact).
"""

import numpy as np

import jax
import jax.numpy as jnp

import deeperspeed_tpu
from deeperspeed_tpu.profiling.flops_profiler.profiler import (
    FlopsProfiler, duration_to_string, flops_to_string, params_to_string,
    profile_fn)
from tests.simple_model import SimpleModel


def test_profile_fn_counts_matmul_flops():
    a = jnp.ones((64, 128), jnp.float32)
    b = jnp.ones((128, 32), jnp.float32)
    prof = profile_fn(lambda a, b: a @ b, a, b)
    # 2*M*N*K FLOPs for one matmul
    assert prof["flops"] >= 2 * 64 * 128 * 32
    assert prof["duration"] > 0


def test_profiler_on_engine_train_step():
    model = SimpleModel(hidden_dim=16, num_layers=2)
    engine, *_ = deeperspeed_tpu.initialize(
        model=model,
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "flops_profiler": {"enabled": True, "profile_step": 1},
        })
    prof = FlopsProfiler(model=model, engine=engine)
    prof.start_profile()
    rng = np.random.default_rng(0)
    batch = (rng.normal(size=(1, 8, 16)).astype(np.float32),
             rng.normal(size=(1, 8, 16)).astype(np.float32))
    prof.profile_train_step(batch)
    flops = prof.get_total_flops()
    params = prof.get_total_params()
    assert flops > 0
    # 2 layers of 16x16 weight + bias + head: at least the raw param count
    assert params >= 2 * (16 * 16 + 16)
    assert prof.get_total_duration() > 0
    prof.end_profile()


def test_string_helpers():
    assert flops_to_string(2e12) == "2.0 TFLOPS"
    assert params_to_string(1.5e6) == "1.5 M"
    assert "ms" in duration_to_string(0.005)


def test_engine_auto_profiles_at_profile_step():
    model = SimpleModel(hidden_dim=8, num_layers=1)
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(
            jax.random.PRNGKey(0)),
        config_params={"train_batch_size": 8 * jax.device_count(),
                       "optimizer": {"type": "Adam",
                                     "params": {"lr": 1e-3}},
                       "flops_profiler": {"enabled": True,
                                          "profile_step": 1},
                       "steps_per_print": 100})
    assert engine.flops_profiler is not None
    x = np.ones((1, 8 * jax.device_count(), 8), np.float32)
    batch = (x, x)
    engine.train_batch(batch=batch)   # step 0 → global_steps 1
    engine.train_batch(batch=batch)   # profiles at global_steps == 1
    # the auto-hook ran the cost analysis and cached the results
    assert engine.flops_profiler.get_total_flops() > 0
    report = engine.flops_profiler.print_model_profile()
    assert "Flops Profiler" in report and "params" in report
