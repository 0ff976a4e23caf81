"""Sequence-parallelism tests: ring and Ulysses attention over an 8-device
mesh must match single-device attention exactly."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from deeperspeed_tpu.parallel.sequence import SequenceParallel

# passes and fits tier-1's rule (`pyproject.toml`, `slow`); `slow` for the
# whole run's budget alone, with the mechanisms no cell runs (ROADMAP D19)
pytestmark = pytest.mark.slow

B, S, H, D = 2, 64, 8, 16


def reference_attention(q, k, v, causal):
    scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


def make_qkv(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, S, H, D), jnp.float32) * 0.5
                 for k in ks)


@pytest.fixture
def seq_mesh(devices):
    return Mesh(np.asarray(devices), ("seq",))


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_parity(seq_mesh, causal):
    q, k, v = make_qkv()
    sp = SequenceParallel(seq_mesh, axis="seq", mode="ring", causal=causal)
    out = jax.jit(sp)(q, k, v)
    ref = reference_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_grads(seq_mesh):
    q, k, v = make_qkv(seed=1)
    sp = SequenceParallel(seq_mesh, axis="seq", mode="ring", causal=True)

    g_ring = jax.jit(jax.grad(lambda q, k, v: jnp.sum(sp(q, k, v) ** 2),
                              argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(reference_attention(q, k, v, True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-3,
                                   err_msg=f"d{name}")


def test_ulysses_attention_parity(seq_mesh):
    q, k, v = make_qkv(seed=2)
    sp = SequenceParallel(seq_mesh, axis="seq", mode="ulysses", causal=True)
    out = jax.jit(sp)(q, k, v)
    ref = reference_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ulysses_head_divisibility(seq_mesh):
    sp = SequenceParallel(seq_mesh, axis="seq", mode="ulysses", causal=True)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    bad = tuple(jax.random.normal(k, (B, S, 4, D)) for k in ks)  # 4 % 8 != 0
    with pytest.raises(Exception):
        jax.block_until_ready(sp(*bad))


def test_ring_balanced_matches_single_device(seq_mesh):
    """Zigzag/striped shard assignment (the causal-ring default when the
    sequence splits into 2n chunks) must match single-device attention —
    each rank holds head+tail chunks, so per-rank causal work is equal."""
    q, k, v = make_qkv(seed=4)
    sp = SequenceParallel(seq_mesh, axis="seq", mode="ring", causal=True,
                          balance=True)
    out = jax.jit(sp)(q, k, v)
    ref = reference_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # contiguous assignment still available via balance=False
    sp_off = SequenceParallel(seq_mesh, axis="seq", mode="ring",
                              causal=True, balance=False)
    np.testing.assert_allclose(np.asarray(jax.jit(sp_off)(q, k, v)),
                               np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_balanced_grads(seq_mesh):
    q, k, v = make_qkv(seed=5)
    sp = SequenceParallel(seq_mesh, axis="seq", mode="ring", causal=True,
                          balance=True)
    g_ring = jax.jit(jax.grad(lambda q, k, v: jnp.sum(sp(q, k, v) ** 2),
                              argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(reference_attention(q, k, v, True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-3,
                                   err_msg=f"d{name}")


def test_ring_balanced_requires_divisible_seq(seq_mesh):
    sp = SequenceParallel(seq_mesh, axis="seq", mode="ring", causal=True,
                          balance=True)
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    bad = tuple(jax.random.normal(kk, (1, 40, 8, D)) for kk in ks)
    with pytest.raises(ValueError):
        sp(*bad)


def test_zigzag_order_pairs_head_and_tail():
    from deeperspeed_tpu.parallel.sequence import zigzag_chunk_order
    order = zigzag_chunk_order(4)
    assert order == [0, 7, 1, 6, 2, 5, 3, 4]
    # every rank's chunk pair sums to 2n-1 → equal causal area
    for r in range(4):
        assert order[2 * r] + order[2 * r + 1] == 7


def test_ring_long_sequence_memory_shape(seq_mesh):
    """Ring attention never materializes [S, S]; spot-check a longer
    sequence still works and matches."""
    s = 256
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (1, s, 8, D), jnp.float32) * 0.5
               for kk in ks)
    sp = SequenceParallel(seq_mesh, axis="seq", mode="ring", causal=True)
    out = jax.jit(sp)(q, k, v)

    scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = jnp.tril(jnp.ones((s, s), bool))
    logits = jnp.where(mask[None, None], logits, -1e30)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
