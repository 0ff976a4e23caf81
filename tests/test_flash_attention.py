"""Flash-attention kernel parity tests (the TPU analogue of the reference's
`test_cuda_forward.py`/`test_cuda_backward.py` kernel-parity strategy):
Pallas kernels vs a pure-XLA reference implementation within tolerance."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.ops.pallas.flash_attention import (
    flash_attention, flash_attention_kbias, flash_attention_supported)


def reference_attention(q, k, v, causal=True, kbias=None):
    B, S, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
    if kbias is not None:
        logits = logits + kbias[:, None, None, :]
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


def make_qkv(b=1, s=256, h=2, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape, dtype) * 0.5 for k in ks)


def test_supported_shapes():
    assert flash_attention_supported((1, 256, 2, 64))
    assert not flash_attention_supported((1, 100, 2, 64))
    assert not flash_attention_supported((1, 256, 2, 48))


@pytest.mark.parametrize("causal", [True, False])
def test_forward_parity(causal):
    q, k, v = make_qkv()
    out = flash_attention(q, k, v, causal)
    ref = reference_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_backward_parity():
    q, k, v = make_qkv(s=256, d=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-3,
                                   err_msg=f"d{name} mismatch")


def test_bf16_forward():
    q, k, v = make_qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, True)
    ref = reference_attention(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2)


# ---------------------------------------------------------------------------
# additive key-bias (fused attention-mask) — reference parity target is
# the mask-taking fused softmax (csrc/transformer/softmax_kernels.cu)
# ---------------------------------------------------------------------------

def make_key_padding_bias(b, s, valid_lens):
    """[B, S] additive bias: 0 for keys < valid_len, -1e30 beyond."""
    cols = np.arange(s)[None, :]
    keep = cols < np.asarray(valid_lens)[:, None]
    return jnp.asarray(np.where(keep, 0.0, -1e30), jnp.float32)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blocks", [(1024, 1024), (128, 128)])
def test_kbias_forward_parity(causal, blocks):
    # blocks (1024,1024) → single-block path at s=256; (128,128) → tiled
    b, s = 3, 256
    q, k, v = make_qkv(b=b, s=s)
    kbias = make_key_padding_bias(b, s, [256, 192, 64])
    bq, bk = blocks
    out = flash_attention_kbias(q, k, v, kbias, causal, None, bq, bk)
    ref = reference_attention(q, k, v, causal, kbias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kbias_finite_bias_forward():
    # finite per-key biases (not just -inf masks) must flow through too
    b, s = 2, 256
    q, k, v = make_qkv(b=b, s=s)
    kbias = jax.random.normal(jax.random.PRNGKey(7), (b, s), jnp.float32)
    out = flash_attention_kbias(q, k, v, kbias, False)
    ref = reference_attention(q, k, v, False, kbias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("blocks", [(1024, 1024), (128, 128)])
def test_kbias_backward_parity(blocks):
    b, s = 2, 256
    q, k, v = make_qkv(b=b, s=s)
    kbias = make_key_padding_bias(b, s, [256, 128])
    bq, bk = blocks

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention_kbias(q, k, v, kbias, False, None, bq, bk) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, False, kbias) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-4, rtol=5e-3,
                                   err_msg=f"d{name} mismatch")


def test_kbias_fully_masked_batch_zeros():
    # a batch whose keys are ALL masked: zero output + zero grads (the
    # poisoned-lse convention), where a naive softmax would emit mean(v)
    b, s = 2, 256
    q, k, v = make_qkv(b=b, s=s)
    kbias = make_key_padding_bias(b, s, [256, 0])

    def loss(q, k, v):
        return jnp.sum(flash_attention_kbias(q, k, v, kbias, False) ** 2)

    out = flash_attention_kbias(q, k, v, kbias, False)
    assert np.all(np.asarray(out[1]) == 0.0)
    dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert np.all(np.asarray(dq[1]) == 0.0)
    assert np.all(np.asarray(dk[1]) == 0.0)
    assert np.all(np.asarray(dv[1]) == 0.0)
    # the live batch is unaffected
    ref = reference_attention(q[:1], k[:1], v[:1], False, kbias[:1])
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                               atol=2e-5, rtol=2e-5)


def test_kbias_bf16():
    b, s = 2, 256
    q, k, v = make_qkv(b=b, s=s, dtype=jnp.bfloat16)
    kbias = make_key_padding_bias(b, s, [200, 96])
    out = flash_attention_kbias(q, k, v, kbias, False)
    ref = reference_attention(q, k, v, False, kbias)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2)


# ---------------------------------------------------------------------------
# in-kernel attention dropout (reference: attn_prob_dropout fused in the
# training transformer kernel) — deterministic hash mask, fwd/bwd agree
# ---------------------------------------------------------------------------

from deeperspeed_tpu.ops.pallas.flash_attention import flash_attention_train


def _zeros_bias(b, s):
    return jnp.zeros((b, s), jnp.float32)


@pytest.mark.parametrize("blocks", [(1024, 1024), (128, 128)])
def test_dropout_rate_and_determinism(blocks):
    b, s = 2, 256
    q, k, v = make_qkv(b=b, s=s)
    bq, bk = blocks
    seed = jnp.asarray([1234], jnp.int32)
    out1 = flash_attention_train(q, k, v, _zeros_bias(b, s), seed,
                                 False, None, bq, bk, 0.5)
    out2 = flash_attention_train(q, k, v, _zeros_bias(b, s), seed,
                                 False, None, bq, bk, 0.5)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    out3 = flash_attention_train(q, k, v, _zeros_bias(b, s),
                                 jnp.asarray([99], jnp.int32),
                                 False, None, bq, bk, 0.5)
    assert np.abs(np.asarray(out1) - np.asarray(out3)).max() > 1e-3

    # rate 0 == the no-dropout kernel exactly
    out0 = flash_attention_train(q, k, v, _zeros_bias(b, s), seed,
                                 False, None, bq, bk, 0.0)
    ref = flash_attention(q, k, v, False, None, bq, bk)
    np.testing.assert_allclose(np.asarray(out0), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)


def test_dropout_unbiased():
    """E[dropout attention] over seeds ≈ deterministic attention."""
    b, s = 1, 128
    q, k, v = make_qkv(b=b, s=s)
    ref = np.asarray(reference_attention(q, k, v, False))
    acc = np.zeros_like(ref)
    n = 64
    # one program for the 64 seeds, not 64 calls of every operation
    dropped = jax.jit(lambda seed: flash_attention_train(
        q, k, v, _zeros_bias(b, s), seed, False, None, 1024, 1024, 0.3))
    for i in range(n):
        acc += np.asarray(dropped(jnp.asarray([i], jnp.int32)))
    err = np.abs(acc / n - ref).mean() / (np.abs(ref).mean() + 1e-9)
    assert err < 0.15, err


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blocks", [(1024, 1024), (128, 128)])
def test_dropout_grads_match_numerical(blocks, causal):
    """With a fixed seed the kernel is a deterministic differentiable
    function; its custom VJP must agree with numerical differentiation
    (this pins the bwd kernels' mask regeneration to the fwd's —
    including the causal-strips branch's absolute coordinates)."""
    from jax.test_util import check_grads
    b, s = 1, 128
    q, k, v = make_qkv(b=b, s=s, h=1)
    seed = jnp.asarray([7], jnp.int32)
    bq, bk = blocks

    def fn(q, k, v):
        return flash_attention_train(q, k, v, _zeros_bias(b, s), seed,
                                     causal, None, bq, bk, 0.25)

    check_grads(fn, (q, k, v), order=1, modes=["rev"], atol=2e-2,
                rtol=2e-2)


def test_dropout_no_bias_matches_zero_bias():
    """kbias=None (no bias refs at all) equals an explicit zeros bias."""
    b, s = 2, 256
    q, k, v = make_qkv(b=b, s=s)
    seed = jnp.asarray([21], jnp.int32)
    out_none = flash_attention_train(q, k, v, None, seed, False, None,
                                     1024, 1024, 0.4)
    out_zero = flash_attention_train(q, k, v, _zeros_bias(b, s), seed,
                                     False, None, 1024, 1024, 0.4)
    np.testing.assert_allclose(np.asarray(out_none),
                               np.asarray(out_zero), atol=1e-6)

    g1 = jax.grad(lambda q: jnp.sum(flash_attention_train(
        q, k, v, None, seed, False, None, 1024, 1024, 0.4) ** 2))(q)
    g2 = jax.grad(lambda q: jnp.sum(flash_attention_train(
        q, k, v, _zeros_bias(b, s), seed, False, None, 1024, 1024,
        0.4) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)


def test_dropout_with_mask_and_causal():
    """dropout composes with the fused key-padding mask and causal."""
    b, s = 2, 256
    q, k, v = make_qkv(b=b, s=s)
    kbias = make_key_padding_bias(b, s, [256, 128])
    seed = jnp.asarray([3], jnp.int32)
    for causal in (False, True):
        out = flash_attention_train(q, k, v, kbias, seed, causal, None,
                                    1024, 1024, 0.2)
        a = np.asarray(out)
        assert np.isfinite(a).all()
        # masked-out keys stay masked: batch 1 rows attend only to
        # first 128 keys; with v's tail replaced, output unchanged
        v2 = v.at[1, 128:].set(99.0)
        out2 = flash_attention_train(q, k, v2, kbias, seed, causal,
                                     None, 1024, 1024, 0.2)
        np.testing.assert_allclose(a[1], np.asarray(out2)[1], atol=1e-5)
