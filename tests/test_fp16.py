"""FP16_Optimizer / FP16_UnfusedOptimizer tests (parity with reference
`tests/unit/test_fp16.py`: fp16 training with fused Adam and unfused LAMB,
overflow step-skip, master-weight fidelity, checkpoint round-trip).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu.ops.adam.fused_adam import FusedAdam
from deeperspeed_tpu.ops.lamb.fused_lamb import FusedLamb
from deeperspeed_tpu.runtime.fp16 import FP16_Optimizer, FP16_UnfusedOptimizer


def tiny_params(dtype=jnp.float16):
    rng = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(rng)
    return {
        "w": jax.random.normal(k1, (8, 8), jnp.float32).astype(dtype),
        "b": jax.random.normal(k2, (8,), jnp.float32).astype(dtype),
    }


def quadratic_loss(params, x):
    h = x @ params["w"].astype(jnp.float32) + params["b"].astype(jnp.float32)
    return jnp.mean(jnp.square(h))


@pytest.mark.parametrize("wrapper,base", [
    (FP16_Optimizer, FusedAdam),
    (FP16_UnfusedOptimizer, FusedLamb),
])
def test_fp16_training_decreases_loss(wrapper, base):
    opt = wrapper(base(lr=5e-2), dynamic_loss_scale=True,
                  dynamic_loss_args={"init_scale": 2 ** 8})
    params = tiny_params()
    state = opt.init_state(params)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8), jnp.float32)

    def scaled_grads(state):
        def f(p):
            return opt.scale_loss(quadratic_loss(p, x), state)
        return jax.grad(f)(state.params)

    loss0 = float(quadratic_loss(state.params, x))
    for _ in range(60):
        state, info = opt.step(state, scaled_grads(state))
        assert not bool(info.overflow)
    assert float(quadratic_loss(state.params, x)) < loss0 * 0.5


def test_fp16_masters_match_fp32_reference():
    """One fp16 step with scale=S must equal an fp32 Adam step (masters)."""
    params = tiny_params(jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8), jnp.float32)
    grads = jax.grad(quadratic_loss)(params, x)

    ref_opt = FusedAdam(lr=1e-2)
    ref_state = ref_opt.init_state(params)
    ref_new, _ = ref_opt.update(grads, ref_state, params)

    opt = FP16_Optimizer(FusedAdam(lr=1e-2), static_loss_scale=128.0)
    state = opt.init_state(params)
    scaled = jax.tree_util.tree_map(lambda g: g * 128.0, grads)
    state, info = opt.step(state, scaled)
    flat_ref = jnp.concatenate([ref_new["b"].ravel(), ref_new["w"].ravel()])
    # tree_flatten is alphabetical: b then w.
    np.testing.assert_allclose(np.asarray(state.flat_master),
                               np.asarray(flat_ref), rtol=1e-6)


@pytest.mark.parametrize("wrapper,base", [
    (FP16_Optimizer, FusedAdam),
    (FP16_UnfusedOptimizer, FusedLamb),
])
def test_overflow_skips_step_and_halves_scale(wrapper, base):
    opt = wrapper(base(lr=1e-2), dynamic_loss_scale=True,
                  dynamic_loss_args={"init_scale": 2 ** 8})
    params = tiny_params()
    state = opt.init_state(params)
    before = jax.device_get(state.params)
    bad = jax.tree_util.tree_map(
        lambda p: jnp.full(p.shape, jnp.inf, jnp.float32), params)
    state, info = opt.step(state, bad)
    assert bool(info.overflow)
    assert float(state.scale.cur_scale) == 2 ** 7
    after = jax.device_get(state.params)
    for k in before:
        np.testing.assert_array_equal(np.asarray(before[k], np.float32),
                                      np.asarray(after[k], np.float32))


def test_clip_grad_applied():
    opt = FP16_Optimizer(FusedAdam(lr=0.0), static_loss_scale=1.0,
                         clip_grad=1.0)
    params = tiny_params(jnp.float32)
    state = opt.init_state(params)
    big = jax.tree_util.tree_map(
        lambda p: jnp.full(p.shape, 100.0, jnp.float32), params)
    state, info = opt.step(state, big)
    assert float(info.grad_norm) > 1.0  # reported pre-clip norm


@pytest.mark.parametrize("wrapper,base", [
    (FP16_Optimizer, FusedAdam),
    (FP16_UnfusedOptimizer, FusedLamb),
])
def test_state_dict_roundtrip(wrapper, base):
    opt = wrapper(base(lr=1e-2), dynamic_loss_scale=True,
                  dynamic_loss_args={"init_scale": 2 ** 8})
    params = tiny_params()
    state = opt.init_state(params)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8), jnp.float32)
    g = jax.grad(lambda p: opt.scale_loss(quadratic_loss(p, x), state))(
        state.params)
    state, _ = opt.step(state, g)
    sd = opt.state_dict(state)

    opt2 = wrapper(base(lr=1e-2), dynamic_loss_scale=True,
                   dynamic_loss_args={"init_scale": 2 ** 8})
    fresh = opt2.init_state(params)
    restored = opt2.load_state_dict(fresh, sd)
    assert float(restored.scale.cur_scale) == float(state.scale.cur_scale)
    for a, b in zip(jax.tree_util.tree_leaves(restored.params),
                    jax.tree_util.tree_leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_fp16_step_is_jittable():
    opt = FP16_Optimizer(FusedAdam(lr=1e-2), dynamic_loss_scale=True,
                         dynamic_loss_args={"init_scale": 2 ** 8})
    params = tiny_params()
    state = opt.init_state(params)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8), jnp.float32)

    @jax.jit
    def train_step(state):
        g = jax.grad(lambda p: opt.scale_loss(quadratic_loss(p, x),
                                              state))(state.params)
        new_state, info = opt.step(state, g)
        return new_state, info

    for _ in range(3):
        state, info = train_step(state)
    assert not bool(info.overflow)


def test_fp16_master_weights_and_grads_mode():
    """fp16_master_weights_and_grads: no fp32 master tree (params are
    the masters, optimizer math upcasts per step); with bf16 moments the
    per-param state bytes drop 4x. Training still converges and tracks
    the classic-master run closely at these scales."""
    import numpy as np
    import deeperspeed_tpu
    from deeperspeed_tpu.models.gpt2 import GPT2, GPT2Config

    def run(lean):
        fp16 = {"enabled": True, "type": "bfloat16"}
        opt = {"lr": 1e-3}
        if lean:
            fp16["fp16_master_weights_and_grads"] = True
            opt["state_dtype"] = "bfloat16"
        cfg = GPT2Config.tiny()
        model = GPT2(cfg, use_pallas=False)
        engine, *_ = deeperspeed_tpu.initialize(
            model=model,
            model_parameters=model.init_params(jax.random.PRNGKey(0)),
            config_params={"train_batch_size": 16,
                           "steps_per_print": 1000,
                           "optimizer": {"type": "Adam", "params": opt},
                           "fp16": fp16})
        if lean:
            assert engine.state.master is None
            m_leaf = jax.tree_util.tree_leaves(
                engine.state.opt_state.exp_avg)[0]
            assert m_leaf.dtype == jnp.bfloat16
        toks = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (1, 16, 32), np.int32)
        return [float(engine.train_batch(batch=(toks, toks)))
                for _ in range(8)]

    classic = run(False)
    lean = run(True)
    assert lean[-1] < lean[0] - 0.2, lean
    # bf16 rounding shifts the trajectory slightly, not qualitatively
    assert abs(lean[-1] - classic[-1]) < 0.25, (lean, classic)


def test_fp16_master_mode_rejects_zero_stages():
    import pytest as _pytest
    import deeperspeed_tpu
    from deeperspeed_tpu.models.gpt2 import GPT2, GPT2Config
    from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError
    cfg = GPT2Config.tiny()
    model = GPT2(cfg, use_pallas=False)
    with _pytest.raises(DeepSpeedConfigError):
        deeperspeed_tpu.initialize(
            model=model,
            model_parameters=model.init_params(jax.random.PRNGKey(0)),
            config_params={
                "train_batch_size": 16,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "fp16": {"enabled": True, "type": "bfloat16",
                         "fp16_master_weights_and_grads": True},
                "zero_optimization": {"stage": 2}})
