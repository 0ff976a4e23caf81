"""Activation checkpointing tests (parity with reference
`tests/unit/test_activation_checkpointing.py`: checkpointed forward ==
plain forward, same grads, RNG-dependent ops replay identically, config
knobs accepted).
"""

import numpy as np

import jax
import jax.numpy as jnp

from deeperspeed_tpu.runtime.activation_checkpointing import checkpointing


def setup_function(_):
    checkpointing.reset()


def mlp_block(params, x, key):
    h = jnp.tanh(x @ params["w1"])
    # dropout with explicit key — must replay identically under recompute
    keep = jax.random.bernoulli(key, 0.9, h.shape)
    h = jnp.where(keep, h / 0.9, 0.0)
    return h @ params["w2"]


def make_params():
    k = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(k)
    return {"w1": jax.random.normal(k1, (16, 32)) * 0.3,
            "w2": jax.random.normal(k2, (32, 16)) * 0.3}


def test_checkpoint_matches_plain_forward_and_grads():
    checkpointing.configure(deepspeed_config={})
    assert checkpointing.is_configured()
    params = make_params()
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
    key = jax.random.PRNGKey(2)

    def loss_plain(p):
        return jnp.sum(mlp_block(p, x, key) ** 2)

    def loss_ckpt(p):
        return jnp.sum(checkpointing.checkpoint(mlp_block, p, x, key) ** 2)

    np.testing.assert_allclose(float(loss_plain(params)),
                               float(loss_ckpt(params)), rtol=1e-6)
    g1 = jax.grad(loss_plain)(params)
    g2 = jax.grad(loss_ckpt)(params)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        # atol floor: remat reassociates fp32 reductions; near-zero grad
        # elements legitimately differ at the 1e-7 level (failed the old
        # atol=0 bound on some hosts with the SEED code already)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_checkpoint_inside_jit():
    checkpointing.configure(deepspeed_config={})
    params = make_params()
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
    key = jax.random.PRNGKey(2)

    @jax.jit
    def loss(p):
        return jnp.sum(checkpointing.checkpoint(mlp_block, p, x, key) ** 2)

    assert np.isfinite(float(loss(params)))
    g = jax.grad(loss)(params)
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree_util.tree_leaves(g))


def test_cpu_checkpointing_policy():
    """cpu_checkpointing selects the offload-to-host remat policy
    (promoted to `offload_dots` — saved matmul results rest in host
    memory). Host-offload transfers only exist inside jit, so the grad
    must be jitted (eager remat has no TransferToMemoryKind)."""
    checkpointing.configure(deepspeed_config={
        "activation_checkpointing": {"cpu_checkpointing": True}})
    params = make_params()
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
    key = jax.random.PRNGKey(2)

    def loss(p):
        return jnp.sum(checkpointing.checkpoint(mlp_block, p, x, key) ** 2)

    g = jax.jit(jax.grad(loss))(params)
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree_util.tree_leaves(g))


def test_configure_overrides():
    checkpointing.configure(deepspeed_config={},
                            partition_activations=True,
                            num_checkpoints=4)
    cfg = checkpointing._config
    assert cfg.partition_activations
    assert cfg.number_checkpoints == 4


def test_rng_tracker_fork_reproducible():
    tracker = checkpointing.get_cuda_rng_tracker()
    tracker.reset()
    checkpointing.model_parallel_cuda_manual_seed(1234)
    with tracker.fork():
        a = jax.random.normal(tracker.current_key(), (4,)) \
            if hasattr(tracker, "current_key") else None
    # fork twice from the same state → same stream
    tracker.reset()
    checkpointing.model_parallel_cuda_manual_seed(1234)
    with tracker.fork():
        b = jax.random.normal(tracker.current_key(), (4,)) \
            if hasattr(tracker, "current_key") else None
    if a is not None:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
