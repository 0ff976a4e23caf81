"""Fused flat-shard Adam kernel parity (reference:
`tests/unit/test_adamw.py` + `csrc/adam/multi_tensor_adam.cu` parity
strategy — kernel vs framework optimizer within float tolerance)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.ops.pallas.optimizer import (adam_flat_reference,
                                                  fused_adam_flat)


def _rand_state(n, p_dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    p = jnp.asarray(rng.standard_normal(n, dtype=np.float32)).astype(p_dtype)
    g = jnp.asarray(rng.standard_normal(n, dtype=np.float32)) * 0.1
    m = jnp.asarray(rng.standard_normal(n, dtype=np.float32)) * 0.01
    v = jnp.abs(jnp.asarray(rng.standard_normal(n, dtype=np.float32))) * 0.01
    return p, g, m, v


@pytest.mark.parametrize("adam_w", [True, False])
@pytest.mark.parametrize("n", [8 * 1024, 10_000])  # exact tile + ragged
def test_matches_reference(adam_w, n):
    p, g, m, v = _rand_state(n)
    args = dict(lr=1e-3, step=7, weight_decay=0.01, adam_w=adam_w)
    got = fused_adam_flat(p, g, m, v, **args)
    want = adam_flat_reference(p, g, m, v, **args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_bf16_params_fp32_moments():
    p, g, m, v = _rand_state(4096, p_dtype=jnp.bfloat16)
    new_p, new_m, new_v = fused_adam_flat(p, g, m, v, lr=1e-2, step=1)
    assert new_p.dtype == jnp.bfloat16
    assert new_m.dtype == new_v.dtype == jnp.float32
    ref_p, _, _ = adam_flat_reference(p, g, m, v, lr=1e-2, step=1)
    np.testing.assert_allclose(np.asarray(new_p, np.float32),
                               np.asarray(ref_p, np.float32), atol=1e-2)


def test_lr_step_are_traced_no_recompile():
    p, g, m, v = _rand_state(2048)
    before = fused_adam_flat._cache_size()
    out1 = fused_adam_flat(p, g, m, v, lr=1e-3, step=1)
    traces_first = fused_adam_flat._cache_size() - before
    out2 = fused_adam_flat(p, g, m, v, lr=5e-4, step=2)
    traces_total = fused_adam_flat._cache_size() - before
    # different lr/step values must change the result without retracing
    assert not np.allclose(out1[0], out2[0])
    assert traces_total == traces_first, (traces_first, traces_total)


def test_matches_framework_trajectory():
    """Several fused steps track optax-style Adam applied leafwise."""
    import optax
    n = 3000
    p, g, m, v = _rand_state(n)
    m = jnp.zeros_like(m)
    v = jnp.zeros_like(v)
    opt = optax.adamw(1e-3, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    opt_state = opt.init(p)
    p_ref = p
    rng = np.random.default_rng(1)
    for step in range(1, 5):
        g = jnp.asarray(rng.standard_normal(n, dtype=np.float32))
        p, m, v = fused_adam_flat(p, g, m, v, lr=1e-3, step=step,
                                  weight_decay=0.01)
        updates, opt_state = opt.update(g, opt_state, p_ref)
        p_ref = optax.apply_updates(p_ref, updates)
    np.testing.assert_allclose(p, p_ref, rtol=1e-5, atol=1e-5)
