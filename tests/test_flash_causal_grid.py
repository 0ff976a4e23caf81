"""Compacted causal flash grid: the trapezoidal schedule must launch
~n(n+1)/2 (q, k) instances instead of n² (the compile-time invariant),
match the XLA reference numerically on every path, and the heads-batched
(hb > 1) single-block kernels must agree with hb = 1 exactly.

Runs on CPU in interpret mode — fast lane (no slow marker)."""

import importlib
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

fa = importlib.import_module(
    "deeperspeed_tpu.ops.pallas.flash_attention")


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


def make_qkv(b=1, s=512, h=2, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), dtype) * 0.5
                 for k in ks)


# ---------------------------------------------------------------------------
# grid-compaction invariant: trapezoid, not square
# ---------------------------------------------------------------------------

def test_causal_grid_maps_triangle_count():
    for n in (4, 8, 13):
        for order in ("row", "col"):
            qm, km = fa.causal_grid_maps(n, n, 128, 128, order)
            assert len(qm) == n * (n + 1) // 2, (n, order)
            # every scheduled tile is causally alive
            assert np.all(km * 128 <= qm * 128 + 127)
    # non-square blocks: bq=256, bk=128 over s=1024 → rows of k-extent
    # min(8, (qi*256+255)//128 + 1) = 2, 4, 6, 8
    qm, km = fa.causal_grid_maps(4, 8, 256, 128, "row")
    assert len(qm) == 2 + 4 + 6 + 8
    assert np.all(km * 128 <= qm * 256 + 255)


def test_causal_grid_size_matches_maps():
    assert fa.causal_grid_size(512, 128, 128) == 10       # n=4 → 10
    assert fa.causal_grid_size(1024, 128, 128) == 36      # n=8 → 36
    assert fa.causal_grid_size(256, 1024, 1024) == 1      # single block


def test_causal_launch_is_compacted():
    """A causal call with n = S/block ≥ 4 launches the trapezoid (10
    instances at n=4) on fwd AND both backward kernels — not n² = 16."""
    b, s, h, d = 1, 512, 2, 64
    q, k, v = make_qkv(b=b, s=s, h=h, d=d)
    n = s // 128
    tri = n * (n + 1) // 2
    assert n >= 4

    out = fa.flash_attention(q, k, v, True, None, 128, 128)
    assert fa._LAST_GRIDS["fwd"] == (b * h, tri)

    jax.grad(lambda q, k, v: jnp.sum(
        fa.flash_attention(q, k, v, True, None, 128, 128) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    assert fa._LAST_GRIDS["dkv"] == (b * h, tri)
    assert fa._LAST_GRIDS["dq"] == (b * h, tri)

    # the non-causal grid stays dense (nothing to compact)
    fa.flash_attention(q, k, v, False, None, 128, 128)
    assert fa._LAST_GRIDS["fwd"] == (b * h, n, n)
    del out


# ---------------------------------------------------------------------------
# numerical parity of the compacted schedule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256)])
def test_compacted_forward_parity(blocks):
    q, k, v = make_qkv()
    bq, bk = blocks
    out = fa.flash_attention(q, k, v, True, None, bq, bk)
    ref = reference_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128)])
def test_compacted_backward_parity(blocks):
    q, k, v = make_qkv(s=512)
    bq, bk = blocks

    g_flash = jax.grad(lambda q, k, v: jnp.sum(
        fa.flash_attention(q, k, v, True, None, bq, bk) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(
        reference_attention(q, k, v, True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-3,
                                   err_msg=f"d{name} mismatch")


def test_compacted_kbias_parity():
    b, s = 2, 512
    q, k, v = make_qkv(b=b, s=s)
    cols = np.arange(s)[None, :]
    keep = cols < np.asarray([512, 384])[:, None]
    kbias = jnp.asarray(np.where(keep, 0.0, -1e30), jnp.float32)

    out = fa.flash_attention_kbias(q, k, v, kbias, True, None, 128, 128)
    ref = reference_attention(q, k, v, True, kbias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    g = jax.grad(lambda q: jnp.sum(fa.flash_attention_kbias(
        q, k, v, kbias, True, None, 128, 128) ** 2))(q)
    assert np.all(np.isfinite(np.asarray(g)))


def test_compacted_dropout_deterministic_and_grad():
    b, s = 1, 512
    q, k, v = make_qkv(b=b, s=s, h=1)
    seed = jnp.asarray([11], jnp.int32)
    kb = jnp.zeros((b, s), jnp.float32)

    o1 = fa.flash_attention_train(q, k, v, kb, seed, True, None, 128,
                                  128, 0.3)
    o2 = fa.flash_attention_train(q, k, v, kb, seed, True, None, 128,
                                  128, 0.3)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))

    g = jax.grad(lambda q: jnp.sum(fa.flash_attention_train(
        q, k, v, kb, seed, True, None, 128, 128, 0.3) ** 2))(q)
    assert np.all(np.isfinite(np.asarray(g)))


# ---------------------------------------------------------------------------
# heads-batched (hb > 1) single-block kernels vs hb = 1 and the reference
# (ADVICE r5: the hb > 1 fwd/bwd paths had no direct equivalence tests)
# ---------------------------------------------------------------------------

def _force_hb(monkeypatch, hb):
    monkeypatch.setattr(fa, "_mh_heads", lambda s, d, h: hb)


def _loss(fn):
    return lambda *args: jnp.sum(fn(*args) ** 2)


def test_mh_single_block_fwd_matches_hb1_and_reference(monkeypatch):
    b, s, h, d = 2, 256, 4, 64
    q, k, v = make_qkv(b=b, s=s, h=h, d=d)
    cols = np.arange(s)[None, :]
    keep = cols < np.asarray([256, 192])[:, None]
    kbias = jnp.asarray(np.where(keep, 0.0, -1e30), jnp.float32)

    _force_hb(monkeypatch, 4)
    out_mh = fa.flash_attention_kbias(q, k, v, kbias, True)
    _force_hb(monkeypatch, 1)
    out_1 = fa.flash_attention_kbias(q, k, v, kbias, True)

    # hb>1 is a launch-geometry change only: bitwise-equal results
    np.testing.assert_array_equal(np.asarray(out_mh), np.asarray(out_1))
    ref = reference_attention(q, k, v, True, kbias)
    np.testing.assert_allclose(np.asarray(out_mh), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_mh_single_block_bwd_matches_hb1(monkeypatch):
    b, s, h, d = 2, 256, 4, 64
    q, k, v = make_qkv(b=b, s=s, h=h, d=d, seed=3)
    cols = np.arange(s)[None, :]
    keep = cols < np.asarray([224, 256])[:, None]
    kbias = jnp.asarray(np.where(keep, 0.0, -1e30), jnp.float32)

    fn = _loss(lambda q, k, v: fa.flash_attention_kbias(
        q, k, v, kbias, False))
    _force_hb(monkeypatch, 2)
    g_mh = jax.grad(fn, argnums=(0, 1, 2))(q, k, v)
    _force_hb(monkeypatch, 1)
    g_1 = jax.grad(fn, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(g_mh, g_1, "qkv"):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_),
                                      err_msg=f"d{name} hb mismatch")

    g_ref = jax.grad(_loss(lambda q, k, v: reference_attention(
        q, k, v, False, kbias)), argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(g_mh, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-4, rtol=5e-3,
                                   err_msg=f"d{name} vs reference")


def test_mh_single_block_dropout_matches_hb1(monkeypatch):
    """The dropout hash pid (global batch·H + head) must agree between
    the heads-batched and per-head launches — fwd and bwd."""
    b, s, h, d = 2, 128, 4, 64
    q, k, v = make_qkv(b=b, s=s, h=h, d=d, seed=5)
    kbias = jnp.zeros((b, s), jnp.float32)
    seed = jnp.asarray([77], jnp.int32)

    def fwd(q, k, v):
        return fa.flash_attention_train(q, k, v, kbias, seed, True,
                                        None, 1024, 1024, 0.4)

    loss = _loss(lambda q: fwd(q, k, v))
    _force_hb(monkeypatch, 4)
    out_mh = fwd(q, k, v)
    g_mh = jax.grad(loss)(q)
    _force_hb(monkeypatch, 1)
    out_1 = fwd(q, k, v)
    g_1 = jax.grad(loss)(q)

    np.testing.assert_array_equal(np.asarray(out_mh), np.asarray(out_1))
    np.testing.assert_array_equal(np.asarray(g_mh), np.asarray(g_1))


# ---------------------------------------------------------------------------
# grouped KV heads and a window (a planned model's prefill)
# ---------------------------------------------------------------------------

def grouped_window_reference(q, k, v, seg, window):
    B, S, H, D = q.shape
    r = H // k.shape[2]
    k, v = jnp.repeat(k, r, axis=2), jnp.repeat(v, r, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (i - j < window)
    seen = seen[None] & (seg[:, :, None] == seg[:, None, :])
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("window", [None, 200, 128, 130],
                         ids=["full", "w200", "w128", "w130"])
@pytest.mark.parametrize("heads,kv_heads", [(12, 2), (18, 2), (4, 4)],
                         ids=["6_a_group", "9_a_group", "1_a_group"])
def test_grouped_heads_and_window_forward_matches_xla(heads, kv_heads,
                                                      window):
    """The segmented forward with G < H (6 and 9 query heads a KV head are
    Laguna's) and a window on and off, against plain XLA attention, on a
    padded batch (one row full, one of 300 real tokens)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B, S, D = 2, 512, 64
    q = jax.random.normal(ks[0], (B, S, heads, D))
    k = jax.random.normal(ks[1], (B, S, kv_heads, D))
    v = jax.random.normal(ks[2], (B, S, kv_heads, D))
    seg = (jnp.arange(S)[None, :] < jnp.array([512, 300])[:, None]) \
        .astype(jnp.int32)
    got = fa.flash_attention_segmented(q, k, v, seg, True, block_q=128,
                                       block_k=128, window=window)
    want = grouped_window_reference(q, k, v, seg, window)
    real = seg[:, :, None, None]
    np.testing.assert_allclose(got * real, want * real, atol=2e-6, rtol=0)


def test_window_launches_the_band_alone():
    """Tiles wholly behind the window are never launched: at 1024 tokens
    in blocks of 128 the causal trapezoid has 36 tiles, a window of 128
    keeps the diagonal and the one before it (15), as does 129 (the key
    128 back is that tile's first); a window of 130 takes one more a row."""
    assert len(fa.causal_grid_maps(8, 8, 128, 128)[0]) == 36
    qm, km = fa.causal_grid_maps(8, 8, 128, 128, window=128)
    assert len(qm) == 8 + 7
    assert all(q - k in (0, 1) for q, k in zip(qm, km))
    assert len(fa.causal_grid_maps(8, 8, 128, 128, window=129)[0]) == 8 + 7
    assert len(fa.causal_grid_maps(8, 8, 128, 128, window=130)[0]) \
        == 8 + 7 + 6
    q, k, v = make_qkv(s=1024)
    seg = jnp.ones((1, 1024), jnp.int32)
    fa.flash_attention_segmented(q, k, v, seg, True, block_q=128,
                                 block_k=128, window=128)
    assert fa._LAST_GRIDS["fwd"] == (2, 15)
    # the blocks a window layer is given are no wider than its window
    fa.flash_attention_segmented(q, k, v, seg, True, window=256)
    assert fa._LAST_BLOCKS["fwd"] == (256, 256)
