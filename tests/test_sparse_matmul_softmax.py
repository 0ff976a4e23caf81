"""Standalone block-sparse MatMul/Softmax op parity vs dense reference
(mirrors the reference's `tests/unit/test_sparse_attention.py` which checks
the Triton sdd/dsd/dds and softmax kernels against torch dense ops)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.ops.sparse_attention import (MatMul, Softmax,
                                                  dense_to_sparse,
                                                  sparse_to_dense)

# passes and fits tier-1's rule (`pyproject.toml`, `slow`); `slow` for the
# whole run's budget alone, with the mechanisms no cell runs (ROADMAP D19)
pytestmark = pytest.mark.slow

Z, H, BLOCK = 2, 3, 16
NQ, NK = 4, 5


def random_layout(rng, n_q=NQ, n_k=NK):
    layout = (rng.random((H, n_q, n_k)) < 0.5).astype(np.int64)
    layout[:, 0, 0] = 1  # at least one block per head
    return layout


def rand(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape, dtype=np.float32))


@pytest.mark.parametrize("trans_a", [False, True])
@pytest.mark.parametrize("trans_b", [False, True])
def test_sdd(trans_a, trans_b):
    rng = np.random.default_rng(0)
    layout = random_layout(rng)
    m, n, k = NQ * BLOCK, NK * BLOCK, 24
    a = rand(rng, Z, H, *((k, m) if trans_a else (m, k)))
    b = rand(rng, Z, H, *((n, k) if trans_b else (k, n)))
    op = MatMul(layout, BLOCK, "sdd", trans_a=trans_a, trans_b=trans_b)
    got = sparse_to_dense(op(a, b), layout, BLOCK)
    a_eff = jnp.swapaxes(a, -1, -2) if trans_a else a
    b_eff = jnp.swapaxes(b, -1, -2) if trans_b else b
    want = a_eff @ b_eff
    mask = np.repeat(np.repeat(layout, BLOCK, 1), BLOCK, 2)[None]
    np.testing.assert_allclose(got, want * mask, atol=1e-4)


@pytest.mark.parametrize("trans_a", [False, True])
def test_dsd(trans_a):
    rng = np.random.default_rng(1)
    layout = random_layout(rng)
    n = 24
    a_dense = rand(rng, Z, H, NQ * BLOCK, NK * BLOCK)
    mask = np.repeat(np.repeat(layout, BLOCK, 1), BLOCK, 2)[None]
    a_dense = a_dense * mask
    a_sp = dense_to_sparse(a_dense, layout, BLOCK)
    k_dim = NQ * BLOCK if trans_a else NK * BLOCK
    b = rand(rng, Z, H, k_dim, n)
    op = MatMul(layout, BLOCK, "dsd", trans_a=trans_a)
    got = op(a_sp, b)
    a_eff = jnp.swapaxes(a_dense, -1, -2) if trans_a else a_dense
    np.testing.assert_allclose(got, a_eff @ b, atol=1e-4)


@pytest.mark.parametrize("trans_b", [False, True])
def test_dds(trans_b):
    rng = np.random.default_rng(2)
    layout = random_layout(rng)
    m = 24
    b_dense = rand(rng, Z, H, NQ * BLOCK, NK * BLOCK)
    mask = np.repeat(np.repeat(layout, BLOCK, 1), BLOCK, 2)[None]
    b_dense = b_dense * mask
    b_sp = dense_to_sparse(b_dense, layout, BLOCK)
    k_dim = NK * BLOCK if trans_b else NQ * BLOCK
    a = rand(rng, Z, H, m, k_dim)
    op = MatMul(layout, BLOCK, "dds", trans_b=trans_b)
    got = op(a, b_sp)
    b_eff = jnp.swapaxes(b_dense, -1, -2) if trans_b else b_dense
    np.testing.assert_allclose(got, a @ b_eff, atol=1e-4)


def _dense_softmax_reference(scores, layout, scale, rpe=None, kpm=None,
                             am=None, kpm_mode="add", am_mode="add"):
    """Dense reproduction of trsrc/softmax_fwd.tr: scale → +rpe → +masks,
    softmax per row over ACTIVE entries only."""
    mask = np.repeat(np.repeat(np.asarray(layout, bool), BLOCK, 1),
                     BLOCK, 2)[None]
    f = np.asarray(scores, np.float64) * scale
    if rpe is not None:
        f = f + np.asarray(rpe, np.float64)
    if kpm is not None:
        t = np.asarray(kpm, np.float64)
        t = np.where(t == 0, -np.inf, 0.0) if kpm_mode == "mul" else t
        f = f + t[:, None, None, :]
    if am is not None:
        t = np.asarray(am, np.float64)
        t = np.where(t == 0, -np.inf, 0.0) if am_mode == "mul" else t
        f = f + t[None, None]
    f = np.where(mask, f, -np.inf)
    f = f - np.max(f, -1, keepdims=True)
    with np.errstate(invalid="ignore"):
        e = np.exp(f)
        e = np.where(np.isnan(e), 0.0, e)
        s = e.sum(-1, keepdims=True)
        out = np.where(s > 0, e / np.where(s == 0, 1, s), 0.0)
    return out * mask


@pytest.mark.parametrize("kpm_mode,am_mode", [("add", "add"),
                                              ("mul", "mul")])
def test_softmax_masks(kpm_mode, am_mode):
    rng = np.random.default_rng(3)
    layout = random_layout(rng, NQ, NQ)
    s = NQ * BLOCK
    scores = rand(rng, Z, H, s, s)
    mask = np.repeat(np.repeat(layout, BLOCK, 1), BLOCK, 2)[None]
    sp = dense_to_sparse(scores * mask, layout, BLOCK)
    rpe = rand(rng, 1, H, s, s)
    if kpm_mode == "mul":
        kpm = jnp.asarray((rng.random((Z, s)) < 0.8).astype(np.float32))
        am = jnp.asarray((rng.random((s, s)) < 0.9).astype(np.float32))
    else:
        kpm = rand(rng, Z, s) * 0.1
        am = rand(rng, s, s) * 0.1
    op = Softmax(layout, BLOCK)
    got = sparse_to_dense(
        op(sp, scale=0.3, rpe=rpe, key_padding_mask=kpm, attn_mask=am,
           key_padding_mask_mode=kpm_mode, attn_mask_mode=am_mode),
        layout, BLOCK)
    want = _dense_softmax_reference(scores * mask, layout, 0.3,
                                    np.broadcast_to(rpe, scores.shape),
                                    kpm, am, kpm_mode, am_mode)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_softmax_rows_sum_to_one():
    rng = np.random.default_rng(4)
    layout = random_layout(rng, NQ, NQ)
    sp = rand(rng, Z, layout.sum(), BLOCK, BLOCK)
    dense = sparse_to_dense(Softmax(layout, BLOCK)(sp), layout, BLOCK)
    sums = np.asarray(dense).sum(-1)                     # [Z, H, S]
    # Rows with at least one active block normalize to 1; rows of an
    # all-zero layout row-block have nothing to normalize and sum to 0.
    active_row = np.repeat(layout.any(-1), BLOCK, -1)[None]  # [1, H, S]
    want = np.broadcast_to(active_row.astype(np.float64), sums.shape)
    np.testing.assert_allclose(sums, want, atol=1e-5)


def test_softmax_fully_masked_rows_emit_zero():
    """A query row whose every key is padded out must get zero attention
    weight (so dsd(probs, v) contributes nothing), matching the dense
    fallback in sparse_self_attention — not a uniform distribution."""
    rng = np.random.default_rng(9)
    layout = np.ones((H, NQ, NQ), np.int64)
    s = NQ * BLOCK
    sp = rand(rng, Z, layout.sum(), BLOCK, BLOCK)
    kpm = np.ones((Z, s), np.float32)
    kpm[0, :] = 0.0          # batch 0: every key padded out
    got = sparse_to_dense(
        Softmax(layout, BLOCK)(sp, key_padding_mask=jnp.asarray(kpm),
                               key_padding_mask_mode="mul"),
        layout, BLOCK)
    got = np.asarray(got)
    np.testing.assert_array_equal(got[0], 0.0)
    np.testing.assert_allclose(got[1].sum(-1), 1.0, atol=1e-5)


def test_attention_composition_matches_dense():
    """sdd(q,k^T) → softmax → dsd(probs, v): the reference's
    SparseSelfAttention pipeline built from the standalone ops matches
    dense masked attention."""
    rng = np.random.default_rng(5)
    layout = random_layout(rng, NQ, NQ)
    s, d = NQ * BLOCK, 32
    q, k, v = (rand(rng, Z, H, s, d) for _ in range(3))
    scale = 1.0 / np.sqrt(d)

    sdd = MatMul(layout, BLOCK, "sdd", trans_b=True)
    sm = Softmax(layout, BLOCK)
    dsd = MatMul(layout, BLOCK, "dsd")
    got = dsd(sm(sdd(q, k), scale=scale), v)

    scores = (q @ jnp.swapaxes(k, -1, -2))
    probs = _dense_softmax_reference(np.asarray(scores), layout, scale)
    want = probs @ np.asarray(v, np.float64)
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_matmul_softmax_grads_flow():
    """AD supplies the backward (reference hand-writes softmax_bwd.tr and
    the dsd/dds backward LUTs): grads are finite and match a dense ref."""
    rng = np.random.default_rng(6)
    layout = random_layout(rng, NQ, NQ)
    s, d = NQ * BLOCK, 16
    q, k, v = (rand(rng, 1, H, s, d) for _ in range(3))
    sdd = MatMul(layout, BLOCK, "sdd", trans_b=True)
    sm = Softmax(layout, BLOCK)
    dsd = MatMul(layout, BLOCK, "dsd")
    mask = np.repeat(np.repeat(layout, BLOCK, 1), BLOCK, 2)[None]

    def sparse_loss(q, k, v):
        return dsd(sm(sdd(q, k), scale=0.25), v).sum()

    def dense_loss(q, k, v):
        scores = (q @ jnp.swapaxes(k, -1, -2)) * 0.25
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, -1)
        probs = jnp.where(mask.any(-1, keepdims=True), probs, 0.0)
        return (probs @ v).sum()

    g_sp = jax.grad(sparse_loss, argnums=(0, 1, 2))(q, k, v)
    g_dn = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_sp, g_dn):
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, b, atol=1e-3)


def test_jit_compatible():
    rng = np.random.default_rng(7)
    layout = random_layout(rng)
    a = rand(rng, Z, H, NQ * BLOCK, 24)
    b = rand(rng, Z, H, 24, NK * BLOCK)
    op = MatMul(layout, BLOCK, "sdd")
    got = jax.jit(op)(a, b)
    np.testing.assert_allclose(got, op(a, b), atol=1e-5)


def test_roundtrip_dense_sparse():
    rng = np.random.default_rng(8)
    layout = random_layout(rng)
    x = rand(rng, Z, H, NQ * BLOCK, NK * BLOCK)
    mask = np.repeat(np.repeat(layout, BLOCK, 1), BLOCK, 2)[None]
    x = x * mask
    sp = dense_to_sparse(x, layout, BLOCK)
    assert sp.shape == (Z, layout.sum(), BLOCK, BLOCK)
    np.testing.assert_array_equal(sparse_to_dense(sp, layout, BLOCK), x)
