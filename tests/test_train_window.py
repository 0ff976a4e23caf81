"""Fused multi-step training window (`engine.train_steps`): one jit call
running N whole optimizer steps must reproduce the step-by-step
`train_batch` trajectory and keep host counters in sync."""

import numpy as np
import pytest

import jax

import deeperspeed_tpu
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

N_STEPS = 4
GAS = 2
MICRO = 8


def _make_engine(seed=0, **overrides):
    cfg = GPTNeoXConfig.tiny()
    model = GPTNeoX(cfg, use_pallas=False)
    params = model.init_params(jax.random.PRNGKey(seed))
    config = {
        "train_batch_size": MICRO * GAS,
        "gradient_accumulation_steps": GAS,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "steps_per_print": 1000,
    }
    config.update(overrides)
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=params, config_params=config)
    return engine, cfg


def _batches(cfg, n_steps):
    rng = np.random.default_rng(7)
    toks = rng.integers(0, cfg.vocab_size,
                        (n_steps, GAS, MICRO, 32), np.int32)
    return (toks, toks)


@pytest.mark.parametrize("overrides", [
    {},
    {"zero_optimization": {"stage": 2}},
], ids=["dp", "zero2"])
def test_window_matches_stepwise(overrides):
    batches = None
    engine, cfg = _make_engine(**overrides)
    batches = _batches(cfg, N_STEPS)

    step_losses = []
    for i in range(N_STEPS):
        mb = jax.tree_util.tree_map(lambda x: x[i], batches)
        step_losses.append(float(engine.train_batch(batch=mb)))

    engine2, _ = _make_engine(**overrides)
    window_losses = np.asarray(engine2.train_steps(batches))

    assert window_losses.shape == (N_STEPS,)
    np.testing.assert_allclose(window_losses, step_losses, rtol=2e-4,
                               atol=2e-4)
    assert engine2.global_steps == engine.global_steps == N_STEPS
    assert engine2.global_samples == engine.global_samples
    # params identical after the window
    for a, b in zip(jax.tree_util.tree_leaves(engine.state.params),
                    jax.tree_util.tree_leaves(engine2.state.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-4, atol=2e-4)


def test_window_rng_stream_matches_stepwise():
    """Stochastic models (dropout) must see the SAME per-micro-step RNG
    stream under train_steps as under train_batch (the window derives
    step keys as fold_in(base, micro_steps0 + i*gas))."""
    import jax.numpy as jnp

    def noisy_loss(params, batch, rng):
        x, y = batch
        h = x @ params["w"]
        keep = jax.random.bernoulli(rng, 0.8, h.shape)  # dropout
        h = jnp.where(keep, h / 0.8, 0.0)
        return jnp.mean((h.sum(-1) - y) ** 2)

    def make():
        params = {"w": jax.random.normal(jax.random.PRNGKey(0),
                                         (16, 16)) * 0.1}
        engine, *_ = deeperspeed_tpu.initialize(
            model=noisy_loss, model_parameters=params,
            config_params={"train_batch_size": MICRO * GAS,
                           "gradient_accumulation_steps": GAS,
                           "optimizer": {"type": "Adam",
                                         "params": {"lr": 1e-2}},
                           "steps_per_print": 1000})
        return engine

    rng = np.random.default_rng(3)
    x = rng.normal(size=(N_STEPS, GAS, MICRO, 16)).astype(np.float32)
    y = rng.normal(size=(N_STEPS, GAS, MICRO)).astype(np.float32)

    e1 = make()
    step_losses = [float(e1.train_batch(batch=(x[i], y[i])))
                   for i in range(N_STEPS)]
    e2 = make()
    window_losses = np.asarray(e2.train_steps((x, y)))
    np.testing.assert_allclose(window_losses, step_losses, rtol=1e-5,
                               atol=1e-5)


def test_window_advances_lr_scheduler():
    sched = {"type": "WarmupLR",
             "params": {"warmup_min_lr": 0.0, "warmup_max_lr": 1e-3,
                        "warmup_num_steps": 10}}
    engine, cfg = _make_engine(scheduler=sched)
    batches = _batches(cfg, N_STEPS)
    engine.train_steps(batches)

    ref, _ = _make_engine(scheduler=sched)
    for i in range(N_STEPS):
        ref.train_batch(batch=jax.tree_util.tree_map(
            lambda x: x[i], batches))

    # the window advances the scheduler exactly N_STEPS times
    assert engine.get_lr() == ref.get_lr()
    assert engine.global_steps == N_STEPS


def test_window_rejects_bad_leading_dims():
    engine, cfg = _make_engine()
    toks = np.zeros((N_STEPS, GAS + 1, MICRO, 32), np.int32)
    with pytest.raises(ValueError):
        engine.train_steps((toks, toks))
