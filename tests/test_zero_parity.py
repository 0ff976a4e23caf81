"""Cross-config loss-parity integration tests (reference:
`tests/model/Megatron_GPT2/run_func_test.py` — baseline-vs-test LM loss
comparison across zero0/1/2/3/offload/gas configs, here as exact
trajectory comparison on the 8-device mesh)."""

import numpy as np
import pytest

import jax

import deeperspeed_tpu
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

STEPS = 5


def _train(config_overrides, gas=1, seed=0):
    cfg = GPTNeoXConfig.tiny()
    model = GPTNeoX(cfg, use_pallas=False)
    params = model.init_params(jax.random.PRNGKey(seed))
    config = {
        "train_batch_size": 16,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "steps_per_print": 1000,
    }
    config.update(config_overrides)
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=params, config_params=config)
    rng = np.random.default_rng(1)
    micro = 16 // gas
    losses = []
    for step in range(STEPS):
        toks = rng.integers(0, cfg.vocab_size, (gas, micro, 32), np.int32)
        losses.append(float(engine.train_batch(batch=(toks, toks))))
    return np.asarray(losses)


@pytest.fixture(scope="module")
def baseline():
    return _train({})  # ZeRO-0 fp32 DP


@pytest.mark.parametrize("overrides", [
    {"zero_optimization": {"stage": 1}},
    {"zero_optimization": {"stage": 2}},
    {"zero_optimization": {"stage": 3}},
], ids=["zero1", "zero2", "zero3"])
def test_zero_stage_matches_baseline(baseline, overrides):
    """Optimizer/grad/param sharding must not change the math: fp32
    trajectories agree with plain DP to float tolerance."""
    got = _train(overrides)
    np.testing.assert_allclose(got, baseline, rtol=2e-4, atol=2e-4)


def test_grad_accumulation_matches_baseline(baseline):
    """gas=2 over half micro-batches sees the same total batch → same
    trajectory."""
    got = _train({}, gas=2)
    np.testing.assert_allclose(got, baseline, rtol=2e-4, atol=2e-4)


def test_offload_matches_baseline(baseline):
    """Host-DRAM optimizer (native C++ Adam) matches the on-device
    update."""
    got = _train({"zero_optimization": {
        "stage": 2, "offload_optimizer": {"device": "cpu"}}})
    np.testing.assert_allclose(got, baseline, rtol=5e-4, atol=5e-4)


def test_bf16_close_to_baseline(baseline):
    """bf16 training follows the fp32 trajectory loosely (same batches,
    reduced precision)."""
    got = _train({"fp16": {"enabled": True, "type": "bfloat16"}})
    np.testing.assert_allclose(got, baseline, rtol=0.05, atol=0.05)
