"""Megatron-GPT2 model family (reference: `tests/model/Megatron_GPT2/` —
func-test loss trajectories under the engine across parallel configs)."""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import deeperspeed_tpu
from deeperspeed_tpu.models.gpt2 import (GPT2, GPT2Config, forward,
                                         init_params)


def test_forward_shapes_and_tied_head():
    cfg = GPT2Config.tiny()
    model = GPT2(cfg, use_pallas=False)
    params = model.init_params(jax.random.PRNGKey(0))
    toks = np.zeros((2, 16), np.int32)
    logits = model.apply(params, toks)
    assert logits.shape == (2, 16, cfg.vocab_size)
    # tied head: no separate output embedding in the tree
    assert "embed_out" not in params
    assert params["embed"]["wpe"].shape == (cfg.max_seq_len,
                                            cfg.hidden_size)


def test_position_embeddings_matter():
    """Without rotary, order information comes from wpe — permuting the
    input changes per-position hidden states."""
    cfg = GPT2Config.tiny()
    model = GPT2(cfg, use_pallas=False)
    params = model.init_params(jax.random.PRNGKey(0))
    toks = np.full((1, 8), 5, np.int32)  # identical tokens at every pos
    logits = np.asarray(model.apply(params, toks))
    # positions see different wpe rows → different causal-prefix outputs
    assert not np.allclose(logits[0, 1], logits[0, 7], atol=1e-5)


def test_trains_under_engine_zero2():
    cfg = GPT2Config.tiny()
    model = GPT2(cfg, use_pallas=False)
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(
            jax.random.PRNGKey(0)),
        config_params={"train_batch_size": 16, "steps_per_print": 1000,
                       "optimizer": {"type": "Adam", "params": {"lr": 2e-3}},
                       "zero_optimization": {"stage": 2}})
    assert engine.dp_world_size == 8
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 16, 32),
                                             np.int32)
    losses = [float(engine.train_batch(batch=(toks, toks)))
              for _ in range(10)]
    assert losses[-1] < losses[0] - 0.3, losses


def test_tp_matches_dense():
    """Megatron column/row-parallel specs reproduce the dense forward."""
    cfg = GPT2Config.tiny(vocab_size=64)
    model = GPT2(cfg, use_pallas=False)
    params = model.init_params(jax.random.PRNGKey(1))
    toks = np.random.default_rng(1).integers(0, 64, (2, 16), np.int32)
    dense = np.asarray(forward(cfg, params, toks, use_pallas=False))

    devices = np.array(jax.devices()[:2]).reshape(2)
    mesh = Mesh(devices, ("model",))
    specs = model.param_specs(params, mesh)
    with mesh:
        sharded = jax.tree_util.tree_map(
            lambda p, s: jax.device_put(
                p, jax.sharding.NamedSharding(mesh, s)), params, specs)
        out = np.asarray(jax.jit(
            lambda p, t: forward(cfg, p, t, use_pallas=False))(sharded,
                                                               toks))
    np.testing.assert_allclose(out, dense, atol=2e-4, rtol=2e-4)


def test_loss_parity_with_gas():
    cfg = GPT2Config.tiny()

    def run(gas):
        model = GPT2(cfg, use_pallas=False)
        engine, *_ = deeperspeed_tpu.initialize(
            model=model, model_parameters=model.init_params(
                jax.random.PRNGKey(0)),
            config_params={"train_batch_size": 16,
                           "gradient_accumulation_steps": gas,
                           "steps_per_print": 1000,
                           "optimizer": {"type": "Adam",
                                         "params": {"lr": 1e-3}}})
        rng = np.random.default_rng(2)
        losses = []
        for _ in range(4):
            toks = rng.integers(0, cfg.vocab_size, (gas, 16 // gas, 32),
                                np.int32)
            losses.append(float(engine.train_batch(batch=(toks, toks))))
        return np.asarray(losses)

    np.testing.assert_allclose(run(1), run(2), rtol=2e-4, atol=2e-4)


def test_scan_blocks_matches_loop():
    """lax.scan over stacked blocks == the Python loop (same math, one
    compiled block body; the GPT2-XL compile-time fix)."""
    import numpy as np
    import dataclasses
    cfg = dataclasses.replace(GPT2Config.tiny(), num_layers=3)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = np.arange(2 * 16, dtype=np.int32).reshape(2, 16) % cfg.vocab_size
    loop = forward(cfg, params, toks, use_pallas=False)
    scan = forward(cfg, params, toks, use_pallas=False, scan_blocks=True)
    np.testing.assert_allclose(np.asarray(scan), np.asarray(loop),
                               rtol=1e-5, atol=1e-5)
    # remat composes with scan
    scan_r = forward(cfg, params, toks, use_pallas=False,
                     scan_blocks=True, remat_blocks=True)
    np.testing.assert_allclose(np.asarray(scan_r), np.asarray(loop),
                               rtol=1e-5, atol=1e-5)
