"""Rematerialization-policy parity tests (tentpole: policy-based remat +
segmented-scan checkpointing).

Fast-lane file (NO `slow` marker): everything here runs on the CPU
backend in seconds — tiny models, XLA-fallback attention, and one
single-block interpret-mode flash kernel case. Policies must never
change the math: loss and grads are compared against the no-remat
baseline at tight tolerances, and `memory_analysis()` pins the memory
ordering (`full` saves strictly less than `none`).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.models import gpt2, gpt_neox
from deeperspeed_tpu.runtime.activation_checkpointing.checkpointing import (
    make_remat_policy)
from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError

CFG = dataclasses.replace(gpt_neox.GPTNeoXConfig.tiny(), num_layers=4)
PARAMS = gpt_neox.init_params(CFG, jax.random.PRNGKey(0))
TOKS = np.random.default_rng(0).integers(0, CFG.vocab_size, (2, 32),
                                         np.int32)


def _loss_and_grads(remat_policy=None, number_checkpoints=None,
                    remat_blocks=False, scan_blocks=False):
    model = gpt_neox.GPTNeoX(CFG, use_pallas=False,
                             remat_blocks=remat_blocks,
                             scan_blocks=scan_blocks,
                             remat_policy=remat_policy,
                             number_checkpoints=number_checkpoints)
    return jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, (TOKS, TOKS))))(PARAMS)


def _assert_tree_close(a, b, atol=1e-6, rtol=1e-5):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   atol=atol, rtol=rtol)


@pytest.fixture(scope="module")
def base_lg():
    """No-remat baseline (loss, grads) — jitted ONCE for the module."""
    return _loss_and_grads()


@pytest.mark.parametrize("policy", ["none", "full", "dots",
                                    "attn_residuals", "offload_dots"])
def test_policy_parity_loss_and_grads(policy, base_lg):
    """Every named policy reproduces the no-remat loss AND grads."""
    base_l, base_g = base_lg
    l, g = _loss_and_grads(remat_policy=policy)
    np.testing.assert_allclose(float(l), float(base_l), rtol=1e-6)
    _assert_tree_close(g, base_g)


@pytest.mark.parametrize("k", [1, 2, 4])
def test_segmented_scan_parity(k, base_lg):
    """number_checkpoints=k (remat at k-group boundaries, scan inside)
    reproduces the no-remat loss and grads — divisible and ragged
    (k=1 → one span; k=4 → per block) groupings alike."""
    base_l, base_g = base_lg
    l, g = _loss_and_grads(remat_policy="dots", number_checkpoints=k)
    np.testing.assert_allclose(float(l), float(base_l), rtol=1e-6)
    _assert_tree_close(g, base_g)


def test_segmented_ragged_and_scan_compose(base_lg):
    """Ragged segment sizes (3 segments over 4 layers) and the composed
    scan_blocks path both stay exact."""
    base_l, base_g = base_lg
    l, g = _loss_and_grads(remat_policy="full", number_checkpoints=3)
    np.testing.assert_allclose(float(l), float(base_l), rtol=1e-6)
    _assert_tree_close(g, base_g)
    l2, g2 = _loss_and_grads(remat_blocks=True, scan_blocks=True)
    np.testing.assert_allclose(float(l2), float(base_l), rtol=1e-6)
    _assert_tree_close(g2, base_g)


def test_gpt2_policy_and_segments_parity():
    cfg = gpt2.GPT2Config(vocab_size=256, max_seq_len=64, hidden_size=32,
                          num_layers=3, num_heads=2)
    params = gpt2.init_params(cfg, jax.random.PRNGKey(1))
    toks = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 32),
                                             np.int32)

    def run(**kw):
        m = gpt2.GPT2(cfg, use_pallas=False, **kw)
        return jax.jit(jax.value_and_grad(
            lambda p: m.loss_fn(p, (toks, toks))))(params)

    base_l, base_g = run()
    for kw in (dict(remat_policy="dots"),
               dict(remat_policy="attn_residuals", number_checkpoints=2),
               dict(number_checkpoints=3)):
        l, g = run(**kw)
        np.testing.assert_allclose(float(l), float(base_l), rtol=1e-6)
        _assert_tree_close(g, base_g)


def test_memory_feasible_screen():
    from deeperspeed_tpu.ops.autotune import memory_feasible

    def f(x):
        return jnp.sum(x * x)

    arg = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    # generous budget fits; a 1-byte budget cannot (when analysis exists)
    fits, stats = memory_feasible(f, (arg,), budget_bytes=1 << 30)
    assert fits
    if stats is not None:
        tight, _ = memory_feasible(f, (arg,), budget_bytes=1)
        assert not tight


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------

def test_unknown_policy_raises_with_choices():
    from deeperspeed_tpu.runtime.activation_checkpointing.config import (
        DeepSpeedActivationCheckpointingConfig)
    with pytest.raises(DeepSpeedConfigError) as ei:
        DeepSpeedActivationCheckpointingConfig.from_dict(
            {"activation_checkpointing": {"policy": "bogus"}})
    msg = str(ei.value)
    for choice in ("none", "full", "dots", "attn_residuals",
                   "offload_dots"):
        assert choice in msg
    with pytest.raises(ValueError):
        make_remat_policy("bogus")


@pytest.mark.parametrize("bad", [0, -3, "two", 1.5, True])
def test_bad_number_checkpoints_rejected_at_parse(bad):
    from deeperspeed_tpu.runtime.activation_checkpointing.config import (
        DeepSpeedActivationCheckpointingConfig)
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedActivationCheckpointingConfig.from_dict(
            {"activation_checkpointing": {"number_checkpoints": bad}})


def test_number_checkpoints_capped_by_layers():
    import deeperspeed_tpu
    model = gpt_neox.GPTNeoX(CFG, use_pallas=False)
    with pytest.raises(DeepSpeedConfigError, match="num_layers"):
        deeperspeed_tpu.initialize(
            model=model, model_parameters=PARAMS,
            config_params={
                "train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "activation_checkpointing": {
                    "number_checkpoints": CFG.num_layers + 1},
            })


def test_config_driven_policy_reaches_model_and_trains():
    """The JSON activation_checkpointing block alone must thread policy +
    segments into the jitted train step with an unchanged trajectory."""
    import deeperspeed_tpu

    def run(extra):
        model = gpt_neox.GPTNeoX(CFG, use_pallas=False)
        cfgp = {"train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "steps_per_print": 10_000}
        cfgp.update(extra)
        engine, *_ = deeperspeed_tpu.initialize(
            model=model, model_parameters=PARAMS, config_params=cfgp)
        stacked = (TOKS[:8].repeat(4, 0)[None][:, :8],
                   TOKS[:8].repeat(4, 0)[None][:, :8])
        losses = [float(engine.train_batch(batch=stacked))
                  for _ in range(2)]
        return model, losses

    base_model, base = run({})
    model, got = run({"activation_checkpointing": {
        "policy": "dots", "number_checkpoints": 2}})
    assert model.remat_policy == "dots"
    assert model.number_checkpoints == 2
    np.testing.assert_allclose(got, base, rtol=1e-5, atol=1e-5)
    # cpu_checkpointing promotes the policy to its host-offload form
    model_off, _ = run({"activation_checkpointing": {
        "policy": "dots", "cpu_checkpointing": True}})
    assert model_off.remat_policy == "offload_dots"


def test_cpu_checkpointing_conflicting_policy_rejected():
    """cpu_checkpointing with a policy whose save set cannot offload is
    a parse-time error, not a silently-dropped knob."""
    from deeperspeed_tpu.runtime.activation_checkpointing.config import (
        DeepSpeedActivationCheckpointingConfig)
    for pol in ("none", "full", "attn_residuals"):
        with pytest.raises(DeepSpeedConfigError, match="cpu_checkpointing"):
            DeepSpeedActivationCheckpointingConfig.from_dict(
                {"activation_checkpointing": {
                    "policy": pol, "cpu_checkpointing": True}})
    # dots promotes cleanly
    cfg = DeepSpeedActivationCheckpointingConfig.from_dict(
        {"activation_checkpointing": {
            "policy": "dots", "cpu_checkpointing": True}})
    assert cfg.policy == "dots" and cfg.cpu_checkpointing


def test_gpt2_bert_reject_moe_and_sp_configs():
    """apply_ds_config on the non-NeoX families must stay a LOUD failure
    for moe/sequence_parallel — accepting the call would silently train
    a dense/non-SP model."""
    import types

    from deeperspeed_tpu.models import bert
    ds = types.SimpleNamespace(moe_params={"num_experts": 4},
                               sequence_parallel_params=None,
                               activation_checkpointing_config=None)
    with pytest.raises(NotImplementedError):
        gpt2.GPT2(gpt2.GPT2Config.tiny()).apply_ds_config(ds)
    with pytest.raises(NotImplementedError):
        bert.BertForPreTraining(bert.BertConfig.tiny()).apply_ds_config(ds)


def test_partition_boundary_builder():
    """make_partition_boundary: None without a >1 model axis; with one,
    the constraint is a value-preserving identity under jit."""
    from jax.sharding import Mesh

    from deeperspeed_tpu.models.gpt_neox import make_partition_boundary
    assert make_partition_boundary(None) is None
    devs = np.asarray(jax.devices("cpu"))
    if devs.size >= 8:
        mesh = Mesh(devs[:8].reshape(4, 2), ("data", "model"))
        fn = make_partition_boundary(mesh)
        assert fn is not None
        x = jnp.arange(2 * 4 * 8, dtype=jnp.float32).reshape(2, 4, 8)
        with mesh:
            y = jax.jit(fn)(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


# ---------------------------------------------------------------------------
# interpret-mode flash kernel guard (tier-1-safe: single-block shape,
# no `slow` marker — the Pallas kernels run in interpreter mode off-TPU)
# ---------------------------------------------------------------------------

def test_attn_residuals_flash_interpret_parity():
    """attn_residuals remat over the REAL flash kernel (interpret mode):
    the custom_vjp's tagged out/LSE residuals must survive the policy
    boundary with exact grads vs the unremat'd kernel."""
    from deeperspeed_tpu.ops.pallas.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (1, 128, 1, 64), jnp.float32) * 0.5
               for kk in ks)

    def span(q, k, v):
        out = flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    policy, _ = make_remat_policy("attn_residuals")
    g_base = jax.jit(jax.grad(span))(q, k, v)
    g_remat = jax.jit(jax.grad(
        jax.checkpoint(span, policy=policy)))(q, k, v)
    np.testing.assert_allclose(np.asarray(g_remat), np.asarray(g_base),
                               rtol=1e-5, atol=1e-6)
