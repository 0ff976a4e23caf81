"""Compacted causal flash grid: the trapezoidal schedule must launch
~n(n+1)/2 (q, k) instances instead of n² (the compile-time invariant),
match the XLA reference numerically on every path, and the heads-batched
(hb > 1) single-block kernels must agree with hb = 1 exactly.

The tiled backward is one fused kernel where a sequence's dq slab fits
VMEM (`ops.autotune.flash_dq_slab_admitted`) and two kernels where it
does not: the `backward` fixture runs a test on both sides of that line.

Runs on CPU in interpret mode — fast lane (no slow marker)."""

import importlib
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.ops import autotune

fa = importlib.import_module(
    "deeperspeed_tpu.ops.pallas.flash_attention")


@pytest.fixture(params=["fused", "two_kernels"])
def backward(request, monkeypatch):
    """The kinds of kernel the tiled backward runs as, on each side of
    `flash_dq_slab_admitted`: ("bwd",), the fused kernel, at every shape
    of this file; and ("dkv", "dq"), what a sequence over the slab's
    budget takes, with the budget taken to nothing (the program has no
    switch: the predicate is a function of the shape)."""
    if request.param == "two_kernels":
        monkeypatch.setattr(autotune, "_FLASH_DQ_SLAB_BUDGET", 0)
        return ("dkv", "dq")
    return ("bwd",)


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


def test_causal_launch_is_compacted(backward):
    """A causal call with n = S/block ≥ 4 launches the trapezoid (10
    instances at n=4) on fwd AND every backward kernel — not n² = 16."""
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
    assert {kind: grid for kind, grid in fa._LAST_GRIDS.items()
            if kind != "fwd"} == dict.fromkeys(backward, (b * h, tri))
    assert fa._LAST_BLOCKS["bwd_variant"] == \
        ("fused-trapezoid" if backward == ("bwd",) else "trapezoid")

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
def test_compacted_backward_parity(blocks, backward):
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
# the heads where the program holds them (`heads_in_place`)
# ---------------------------------------------------------------------------
#
# `flash_attention`'s tiled call hands the kernels q^T, k^T, v^T (and
# dO^T) as [B, H*D, S] and takes out^T (dq^T, dk^T, dv^T) back: the same
# tile bodies on the transposed blocks. Against the same call through the
# [B*H, S, D] copies (`_fwd` / `_bwd` without `in_place`).

def _head_counts():
    report = importlib.import_module(
        "deeperspeed_tpu.ops").dispatch_report()["flash"]["heads"]
    return {kind: dict(n) for kind, n in report.items()}


def _counted_since(before):
    return {kind: {where: n - before[kind][where] for where, n in c.items()}
            for kind, c in _head_counts().items()}


IN_PLACE_CASES = [
    # [B, S, H, G, D], forward blocks, backward blocks, dtype, in place
    ((1, 256, 16, 16, 64), (128, 128), (128, 128), jnp.bfloat16, True),
    ((1, 256, 16, 16, 128), (128, 128), (128, 128), jnp.bfloat16, True),
    # the 16k cell's blocks at a short sequence: block_q != block_k in
    # the forward, another pair in the backward
    ((1, 2048, 2, 2, 64), (1024, 512), (1024, 1024), jnp.bfloat16, True),
    ((2, 768, 2, 2, 64), (256, 128), (128, 384), jnp.float32, True),
    # fewer KV heads than query heads: the heads are moved
    ((1, 256, 4, 2, 64), (128, 128), None, jnp.bfloat16, False),
]


@pytest.mark.parametrize(
    "shape,blocks,bwd_blocks,dtype,in_place", IN_PLACE_CASES,
    ids=["h16-d64", "h16-d128", "blocks-of-16k", "dense-uneven-blocks",
         "grouped-kv-falls-back"])
def test_heads_in_place_match_the_moved_heads(shape, blocks, bwd_blocks,
                                              dtype, in_place):
    """Forward and gradients of `flash_attention` where it reads the heads
    in place, against the same kernels on the moved heads; a shape the
    rule does not admit goes through the copies and says so."""
    B, S, H, G, D = shape
    causal = dtype != jnp.float32     # the float32 case is the dense grid
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q, w = (jax.random.normal(kk, (B, S, H, D), dtype) * 0.5
            for kk in ks[:2])
    k, v = (jax.random.normal(kk, (B, S, G, D), dtype) * 0.5
            for kk in ks[2:])
    scale = 1.0 / math.sqrt(D)
    before = _head_counts()

    def counted(kind):
        return _counted_since(before)[kind]

    out = fa.flash_attention(q, k, v, causal, None, *blocks, bwd_blocks)
    assert counted("fwd") == {"in_place": int(in_place),
                              "moved": int(not in_place)}
    moved, res = fa._fwd(q, k, v, causal, scale, *blocks)
    assert counted("fwd")["moved"] == 1 + int(not in_place)
    # the same sums of the same products: float32 differs by the order
    # XLA's CPU dots take them in, bfloat16 by a rounding of that
    tol = dict(atol=2e-6, rtol=2e-6) if dtype == jnp.float32 else \
        dict(atol=1e-2, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(moved, np.float32), **tol)
    if bwd_blocks is None:
        return      # the backward kernels take one KV head a query head
    grads = jax.grad(lambda *a: jnp.sum(fa.flash_attention(
        *a, causal, None, *blocks, bwd_blocks).astype(jnp.float32)
        * w.astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)
    assert counted("bwd") == {"in_place": 1, "moved": 0}
    wants = fa._bwd(causal, None, *bwd_blocks, res, w)
    assert counted("bwd") == {"in_place": 1, "moved": 1}
    gtol = dict(atol=2e-5, rtol=2e-5) if dtype == jnp.float32 else \
        dict(atol=2e-2, rtol=2e-2)
    for got, want, name in zip(grads, wants, "qkv"):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **gtol,
                                   err_msg=f"d{name}")


def test_a_backward_over_the_slab_budget_moves_the_heads(monkeypatch):
    """The forward's residuals lie by rows; the two kernels of a sequence
    over the slab's budget read [B*H, S, D], so that backward moves the
    residuals and agrees with the fused one."""
    q, k, v = make_qkv(b=1, s=512, h=2, d=64, seed=2)

    def grads():
        return jax.grad(lambda *a: jnp.sum(fa.flash_attention(
            *a, True, None, 128, 128) ** 2), argnums=(0, 1, 2))(q, k, v)

    fused = grads()
    before = dict(fa._HEADS["bwd"])
    monkeypatch.setattr(autotune, "_FLASH_DQ_SLAB_BUDGET", 0)
    two = grads()
    assert set(fa._LAST_GRIDS) == {"fwd", "dkv", "dq"}
    assert fa._HEADS["bwd"] == {"in_place": before["in_place"],
                                "moved": before["moved"] + 1}
    for got, want, name in zip(two, fused, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=2e-5, err_msg=f"d{name}")


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


# ---------------------------------------------------------------------------
# the tile body (PR 33): strips, the masked and the unmasked body
# ---------------------------------------------------------------------------

def masked_reference(q, k, v, seen=None, kbias=None, keep=None, rate=0.0,
                     with_lse=False):
    """Plain fp32 attention under an explicit [B, H, S, S] visibility
    mask, a per-key bias and a dropout keep-mask; rows that see no key
    give zeros (the kernels' poisoned-lse convention: `with_lse` returns
    the [B, H, S] lse beside the output, +1e30 on such a row)."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    r = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, r, axis=2), jnp.repeat(v, r, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    if kbias is not None:
        s = s + kbias[:, None, None, :]
    if seen is not None:
        s = jnp.where(seen, s, -1e30)
    alive = jnp.max(s, axis=-1, keepdims=True) > -1e29
    p = jnp.where(alive, jax.nn.softmax(s, axis=-1), 0.0)
    if keep is not None:
        p = jnp.where(keep, p / (1.0 - rate), 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    if with_lse:
        return out, jnp.where(alive[..., 0],
                              jax.nn.logsumexp(s, axis=-1), 1e30)
    return out


def causal_seen(S, window=None, mask_block=0):
    """[1, 1, S, S]: key j at or before query i (of `mask_block`: at or
    before the last position of i's block), within `window` of it."""
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = j <= (i | (mask_block - 1) if mask_block else i)
    if window is not None:
        seen = seen & (i - j < window)
    return seen[None, None]


def _documents(S):
    """[2, S] segment ids: row 0 has a boundary inside a strip (200), on
    a strip's edge (384) and on a tile's edge (512 at every block here);
    row 1 is one document and a run of pad rows."""
    pos = jnp.arange(S)
    row0 = 1 + (pos >= 200) + (pos >= 384) + (pos >= 512)
    row1 = (pos < S - 150).astype(jnp.int32)
    return jnp.stack([row0, row1]).astype(jnp.int32)


def _layout(S, heads):
    """A block layout with its diagonal, a few further blocks, and one
    query block row with NO active block (rows whose every key is
    masked: the poisoned lse)."""
    n = S // fa.MASK_GRAIN
    rng = np.random.RandomState(0)
    lay = (rng.rand(heads, n, n) < 0.4) | np.eye(n, dtype=bool)[None]
    lay[:, 1, :] = False
    return lay


def _variant(name, q, k, v, blocks, bwd_blocks):
    """(kernel fn of (q, k, v), reference fn of (q, k, v))."""
    B, S, H, _ = q.shape
    bq, bk = blocks
    if name in ("causal", "full"):
        causal = name == "causal"
        return (lambda q, k, v: fa.flash_attention(
                    q, k, v, causal, None, bq, bk, bwd_blocks),
                lambda q, k, v: masked_reference(
                    q, k, v, causal_seen(S) if causal else None))
    if name == "segmented":
        seg = _documents(S)[:B]
        seen = causal_seen(S) & \
            (seg[:, :, None] == seg[:, None, :])[:, None]
        return (lambda q, k, v: fa.flash_attention_segmented(
                    q, k, v, seg, True, None, bq, bk, bwd_blocks),
                lambda q, k, v: masked_reference(q, k, v, seen))
    if name == "kbias":
        bias = jnp.where(jax.random.uniform(jax.random.PRNGKey(7), (B, S))
                         < 0.2, -1e30, 0.0)
        bias = bias + 0.3 * jax.random.normal(jax.random.PRNGKey(8), (B, S))
        return (lambda q, k, v: fa.flash_attention_kbias(
                    q, k, v, bias, True, None, bq, bk),
                lambda q, k, v: masked_reference(
                    q, k, v, causal_seen(S), kbias=bias))
    if name == "dropout":
        rate, seed = 0.25, jnp.array([1234], jnp.int32)
        keep = jnp.stack([jnp.stack([
            fa._dropout_keep(seed[0], jnp.int32(b * H + h), 0, 0, (S, S), rate)
            for h in range(H)]) for b in range(B)])
        return (lambda q, k, v: fa.flash_attention_train(
                    q, k, v, None, seed, True, None, bq, bk,
                    dropout_rate=rate),
                lambda q, k, v: masked_reference(
                    q, k, v, causal_seen(S), keep=keep, rate=rate))
    if name == "layout":
        lay = _layout(S, H)
        fine = jnp.asarray(np.kron(lay, np.ones((fa.MASK_GRAIN,) * 2)) > 0)
        return (fa.make_masked_flash_attention(lay, True, None, bq, bk),
                lambda q, k, v: masked_reference(
                    q, k, v, causal_seen(S) & fine[None]))
    raise ValueError(name)


# blocks with block_q > block_k (the 16k cell's 2:1, scaled down),
# block_q < block_k and equal; (512, 512) walks four strips of two pairs,
# (1024, 1024) at 2,048 tokens eight pairs as the 2k cells do (a tile on
# the diagonal: four pairs strip by strip, then a loop of two whole
# ones; the tile under it: a loop of eight, whose prefetch runs).
# (1024, 512) at 1,024 tokens is the 16k cell's own tile at each of its
# two diagonal offsets (0: two pairs strip by strip, then a loop of two
# whole ones; -512: two pairs strip by strip). (512, 256) is the smallest
# 2:1 tile that walks a strip in two blocks, at its offsets 0 and -256;
# under a key bias, dropout or a layout mask every strip is one masked
# block of 256 keys.
TILE_BODY_CASES = [
    # variant, S, (block_q, block_k), head dim, dtype
    ("causal", 512, (256, 128), 64, jnp.float32),
    ("causal", 512, (128, 256), 128, jnp.float32),
    ("causal", 512, (256, 256), 64, jnp.bfloat16),
    ("causal", 1024, (512, 512), 128, jnp.bfloat16),
    ("causal", 2048, (1024, 1024), 64, jnp.float32),
    ("full", 512, (256, 128), 64, jnp.float32),
    ("full", 512, (128, 256), 128, jnp.bfloat16),
    ("full", 1024, (512, 512), 64, jnp.float32),
    ("segmented", 1024, (256, 128), 64, jnp.float32),
    ("segmented", 1024, (128, 256), 128, jnp.float32),
    ("segmented", 1024, (512, 512), 64, jnp.bfloat16),
    ("segmented", 1024, (256, 256), 128, jnp.float32),
    ("segmented", 2048, (1024, 1024), 64, jnp.float32),
    ("kbias", 512, (256, 128), 64, jnp.float32),
    ("kbias", 512, (128, 256), 128, jnp.bfloat16),
    ("kbias", 1024, (512, 512), 64, jnp.float32),
    ("dropout", 512, (256, 128), 64, jnp.float32),
    ("dropout", 512, (128, 256), 128, jnp.float32),
    ("dropout", 512, (256, 256), 64, jnp.float32),
    ("layout", 512, (256, 128), 64, jnp.float32),
    ("layout", 512, (128, 256), 128, jnp.float32),
    ("layout", 512, (256, 256), 64, jnp.bfloat16),
    ("causal", 1024, (1024, 512), 64, jnp.float32),
    ("causal", 512, (512, 256), 128, jnp.bfloat16),
    ("kbias", 512, (512, 256), 128, jnp.float32),
    ("dropout", 512, (512, 256), 64, jnp.float32),
    ("layout", 512, (512, 256), 128, jnp.float32),
]


@pytest.mark.parametrize(
    "variant,S,blocks,d,dtype", TILE_BODY_CASES,
    ids=[f"{c[0]}-{c[1]}-{c[2][0]}x{c[2][1]}-d{c[3]}-{c[4].__name__}"
         for c in TILE_BODY_CASES])
def test_tile_body_matches_fp32_reference(variant, S, blocks, d, dtype,
                                          backward):
    """Forward, dq, dk and dv of the tiled kernels against plain fp32
    attention, for every mask a tile body knows; the backward as the
    fused kernel and as the two."""
    B, H = (1, 1) if S == 2048 else (2, 2)   # `_documents` row 0 alone
    q, k, v = make_qkv(b=B, s=S, h=H, d=d, dtype=dtype, seed=3)
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)
    kernel, reference = _variant(variant, q, k, v, blocks, blocks)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w)

    out = kernel(q, k, v)
    want = reference(q, k, v)
    grads = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
    wants = jax.grad(loss(reference), argnums=(0, 1, 2))(q, k, v)
    tol = dict(atol=3e-5, rtol=3e-5) if dtype == jnp.float32 else \
        dict(atol=4e-2, rtol=4e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), **tol)
    gtol = dict(atol=2e-4, rtol=2e-3) if dtype == jnp.float32 else \
        dict(atol=8e-2, rtol=8e-2)
    for got, ref, name in zip(grads, wants, "qkv"):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32), **gtol,
                                   err_msg=f"d{name}")
    assert set(fa._LAST_GRIDS) == {"fwd", *backward}


# Where dq leaves the fused kernel: a query row is stored at the last
# column it meets. Blocks 2:1 (a row ends one column after it began),
# 1:2 (two rows end in one column, at consecutive steps), equal, one
# column wide and many; the dense grid (every row ends in the last
# column); documents, whose tiles may be skipped whole.
BOUNDARY_CASES = [
    # variant, S, (block_q, block_k)
    ("causal", 1024, (256, 128)),
    ("causal", 1024, (128, 256)),
    ("causal", 1024, (128, 128)),
    ("causal", 1024, (512, 256)),
    ("causal", 1024, (128, 512)),
    ("causal", 1024, (1024, 128)),
    ("causal", 1024, (128, 1024)),
    ("full", 768, (256, 128)),
    ("full", 768, (128, 256)),
    ("full", 768, (384, 128)),
    ("segmented", 1024, (256, 128)),
    ("segmented", 1024, (128, 256)),
    ("kbias", 512, (128, 256)),
    ("dropout", 512, (256, 128)),
    ("layout", 512, (128, 128)),
    ("kbias", 512, (512, 256)),
    ("layout", 512, (512, 256)),
]


@pytest.mark.parametrize(
    "variant,S,blocks", BOUNDARY_CASES,
    ids=[f"{c[0]}-{c[1]}-{c[2][0]}x{c[2][1]}" for c in BOUNDARY_CASES])
def test_fused_backward_agrees_with_the_two_kernels(variant, S, blocks,
                                                    monkeypatch):
    """The same gradients from the one kernel and from the two: dk and dv
    to a rounding (the same sums, the matmuls' operands the other way
    round), dq to the order of its float32 sum."""
    q, k, v = make_qkv(b=2, s=S, h=2, d=64, seed=11)
    w = jax.random.normal(jax.random.PRNGKey(4), q.shape, jnp.float32)
    kernel, _ = _variant(variant, q, k, v, blocks, blocks)

    def grads():
        return jax.grad(lambda *a: jnp.sum(kernel(*a) * w),
                        argnums=(0, 1, 2))(q, k, v)

    fused = grads()
    assert fa._LAST_BLOCKS["bwd_variant"].startswith("fused-")
    assert set(fa._LAST_GRIDS) == {"fwd", "bwd"}
    monkeypatch.setattr(autotune, "_FLASH_DQ_SLAB_BUDGET", 0)
    two = grads()
    assert not fa._LAST_BLOCKS["bwd_variant"].startswith("fused-")
    assert set(fa._LAST_GRIDS) == {"fwd", "dkv", "dq"}
    for got, want, name in zip(fused, two, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=2e-5,
                                   err_msg=f"d{name}")


def test_dq_block_follows_the_rows_as_they_complete():
    """`_dq_block`, the fused kernel's dq index map, over the column
    walk's schedule: the block held is the row completed last; rows
    complete in ascending order, each at the one tile that is the last of
    its row (`_last_k`); so every block is held for one run of steps that
    starts where the kernel stores it."""
    for n_q, n_k, bq, bk in [(4, 4, 128, 128), (2, 4, 256, 128),
                             (4, 2, 128, 256), (8, 2, 128, 512),
                             (1, 8, 1024, 128), (16, 16, 1024, 1024)]:
        qm, km = fa.causal_grid_maps(n_q, n_k, bq, bk, "col")
        held = [int(fa._dq_block(jnp.int32(qi), jnp.int32(ki), n_k, bq, bk,
                                 True)) for qi, ki in zip(qm, km)]
        stored = [int(qi) for qi, ki in zip(qm, km)
                  if ki == int(fa._last_k(jnp.int32(qi), n_k, bq, bk, True))]
        assert stored == list(range(n_q))
        assert held == sorted(held) and set(held) == set(range(n_q))
        for t, (qi, ki) in enumerate(zip(qm, km)):
            if ki == int(fa._last_k(jnp.int32(qi), n_k, bq, bk, True)):
                assert held[t] == qi
                assert t == 0 or held[t - 1] == max(qi - 1, 0)
    # a dense grid: nothing completes before the last column
    assert [int(fa._dq_block(jnp.int32(qi), jnp.int32(ki), 3, 128, 128,
                             False)) for ki in range(3) for qi in range(2)] \
        == [0, 0, 0, 0, 0, 1]


@pytest.mark.parametrize("s,d,admitted", [
    (2048, 64, True), (2048, 128, True), (16384, 64, True),
    (16384, 128, True), (32768, 64, True), (32768, 128, False),
    (65536, 64, False)])
def test_slab_predicate_is_a_function_of_the_shape(s, d, admitted):
    """S * D * 4 bytes of float32 slab against 8 MiB, and what the
    backward of such a sequence is traced as (nothing runs)."""
    assert autotune.flash_dq_slab_admitted(s, d) is admitted
    if s < 16384:
        return
    spec = jax.ShapeDtypeStruct((1, s, 1, d), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)))(spec, spec, spec)
    names = sorted(name for name, _ in _kernel_jaxprs(jaxpr.jaxpr))
    report = importlib.import_module(
        "deeperspeed_tpu.ops").dispatch_report()["flash"]
    if admitted:
        assert names == ["ds.flash_bwd", "ds.flash_fwd"]
        assert report["bwd_variant"] == "fused-trapezoid"
        assert set(report["masked_tiles"]) == {"fwd", "bwd"}
    else:
        assert names == ["ds.flash_bwd_dkv", "ds.flash_bwd_dq",
                         "ds.flash_fwd"]
        assert report["bwd_variant"] == "trapezoid"
        assert set(report["masked_tiles"]) == {"fwd", "dkv", "dq"}


@pytest.mark.parametrize("window,blocks,heads,kv_heads,d", [
    (200, (256, 128), 4, 4, 64),     # an edge inside a strip
    (100, (256, 256), 6, 2, 128),    # window < block, G < H
    (384, (128, 256), 4, 1, 64),     # an edge on a strip's edge
    (600, (512, 512), 2, 2, 64),     # the band's first tile is crossed
], ids=["w200", "w100_grouped", "w384_grouped", "w600"])
def test_windowed_tile_body_matches_fp32_reference(window, blocks, heads,
                                                   kv_heads, d):
    """The windowed / grouped forward (no backward exists) over
    documents and pad rows, in blocks that put a window's edge inside a
    strip, on one, and a whole block behind it."""
    S = 1024
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (2, S, heads, d)) * 0.5
    k = jax.random.normal(ks[1], (2, S, kv_heads, d)) * 0.5
    v = jax.random.normal(ks[2], (2, S, kv_heads, d)) * 0.5
    seg = _documents(S)
    got = fa.flash_attention_segmented(q, k, v, seg, True,
                                       block_q=blocks[0],
                                       block_k=blocks[1], window=window)
    seen = causal_seen(S, window) & \
        (seg[:, :, None] == seg[:, None, :])[:, None]
    want = masked_reference(q, k, v, seen)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


def _closed_form_masked_tiles(n_q, n_k, bq, bk, causal, window):
    """Tiles of the launched grid that an edge crosses, from the
    inequalities alone (no schedule)."""
    if not causal:
        return 0, n_q * n_k
    masked = launched = 0
    for qi in range(n_q):
        q_lo, q_hi = qi * bq, qi * bq + bq - 1
        for ki in range(n_k):
            k_lo, k_hi = ki * bk, ki * bk + bk - 1
            if k_lo > q_hi:
                continue                  # above the diagonal
            if window is not None and ki < max(q_lo - window + 1, 0) // bk:
                continue                  # behind the band's first tile
            launched += 1
            masked += k_hi > q_lo or (
                window is not None and q_hi - k_lo >= window)
    return masked, launched


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256)])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 200),
                                           (False, None)],
                         ids=["causal", "causal_window", "dense"])
def test_masked_tile_count_is_its_closed_form(blocks, causal, window,
                                              backward):
    """`_LAST_MASKED` records, at trace time, how many of a call's
    launched tiles take the masked body: the diagonal's (and a window's
    far edge's) tiles, none of a dense grid."""
    S = 1024
    bq, bk = blocks
    q, k, v = make_qkv(s=S, h=1)
    if window is None:
        jax.grad(lambda q: fa.flash_attention(
            q, k, v, causal, None, bq, bk, blocks).sum())(q)
        kinds = ("fwd", *backward)
    else:
        fa.flash_attention_segmented(q, k, v, jnp.ones((1, S), jnp.int32),
                                     True, block_q=bq, block_k=bk,
                                     window=window)
        kinds = ("fwd",)
    want = _closed_form_masked_tiles(S // bq, S // bk, bq, bk, causal,
                                     window)
    for kind in kinds:
        assert fa._LAST_MASKED[kind] == want, kind
        assert fa._LAST_MASKED[kind][1] == fa._LAST_GRIDS[kind][1] * (
            1 if causal else fa._LAST_GRIDS[kind][2])
    # the 16k cell's forward: 240 of a head's 272 tiles are unmasked
    assert fa.masked_tile_count(16, 32, 1024, 512, True) == (32, 272)
    assert fa.masked_tile_count(2, 2, 1024, 1024, True) == (2, 3)
    # a bias, a layout mask or dropout sends every tile to the masked body
    assert fa.masked_tile_count(2, 2, 1024, 1024, True, always=True) \
        == (3, 3)


@pytest.mark.parametrize("causal", [False, True], ids=["dense", "causal"])
def test_masked_and_unmasked_body_agree_bit_for_bit(causal):
    """A zero key bias sends every tile through the masked body; without
    it the tiles no edge crosses take the unmasked one. Same blocks, same
    order of operations: the outputs are identical to the bit. (The
    gradients agree to a rounding: the CPU compiler behind interpret
    mode contracts `s * c - lse` to one fused multiply-add in one of the
    two programs and not in the other.)"""
    q, k, v = make_qkv(s=512, h=2)
    zero = jnp.zeros((1, 512), jnp.float32)

    def masked(q, k, v):
        return fa.flash_attention_kbias(q, k, v, zero, causal, None, 128,
                                        128)

    def unmasked(q, k, v):
        return fa.flash_attention(q, k, v, causal, None, 128, 128,
                                  (128, 128))

    np.testing.assert_array_equal(np.asarray(masked(q, k, v)),
                                  np.asarray(unmasked(q, k, v)))
    assert fa._LAST_MASKED["fwd"] == ((4, 10) if causal else (0, 16))
    for fn in (masked, unmasked):
        fn.grads = jax.grad(lambda *a: jnp.sum(fn(*a) ** 2),
                            argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(masked.grads, unmasked.grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-7, rtol=1e-5)


# ---------------------------------------------------------------------------
# the segmented forward's two whole-tile bodies (PR 49)
# ---------------------------------------------------------------------------
#
# A serving prefill's kernel: a tile no edge crosses and whose id slices
# are one document takes the interior body (no iota, compare or select),
# any other tile that runs the edge body, a tile whose id ranges cannot
# overlap none.

def _ids(S, *bounds):
    """[S] segment ids: 1 up to the first bound, 2 up to the next, ...;
    0 (pad) from the last one on."""
    pos = np.arange(S)
    ids = sum((pos < b).astype(np.int32) for b in bounds)
    return np.where(ids > 0, len(bounds) + 1 - ids, 0).astype(np.int32)


def segmented_reference(q, k, v, seg, causal, window, mask_block):
    """(out, lse) of plain fp32 attention within documents: a row that
    sees no key gives zeros and the poisoned lse."""
    seen = (seg[:, :, None] == seg[:, None, :])[:, None]
    if causal:
        seen = seen & causal_seen(q.shape[1], window, mask_block)
    return masked_reference(q, k, v, seen, with_lse=True)


def _segmented_fwd(q, k, v, seg, causal, blocks, window=None, mask_block=0):
    """(out [B, S, H, D], lse [B, H, S]) of the segmented forward."""
    B, S, H, D = q.shape
    out, res = fa._fwd(q, k, v, causal, 1.0 / math.sqrt(D), *blocks,
                       seg=jnp.asarray(seg, jnp.int32).reshape(B, 1, S),
                       window=window, mask_block=mask_block)
    return out, res[-1].reshape(B, H, S)


SEGMENTED_CASES = [
    # name, S, (block_q, block_k), heads, KV heads, head dim, dtype,
    # rows of id bounds, causal, window, mask_block
    # one document over several tiles, then pad rows: interior tiles, the
    # diagonal's, the one that holds the pad boundary (700 lies inside
    # tile 2), a last row of tiles that is all pad
    ("one_document_pad_boundary", 1024, (256, 256), 2, 2, 64, jnp.float32,
     [(700,)], True, None, 0),
    ("two_documents_boundary_in_a_tile", 1024, (256, 256), 2, 2, 128,
     jnp.float32, [(300, 1024)], True, None, 0),
    ("boundary_on_a_tile_edge", 1024, (256, 256), 2, 2, 64, jnp.float32,
     [(512, 1024)], True, None, 0),
    ("pad_rows_fill_the_last_tiles", 1024, (128, 128), 2, 2, 64,
     jnp.bfloat16, [(384,)], True, None, 0),
    ("two_rows_of_their_own_documents", 512, (128, 128), 2, 2, 64,
     jnp.float32, [(200, 450), (512,)], True, None, 0),
    ("blocks_2_to_1", 1024, (256, 128), 2, 2, 64, jnp.float32,
     [(300, 900)], True, None, 0),
    ("blocks_1_to_2", 1024, (128, 256), 2, 2, 128, jnp.bfloat16,
     [(300, 900)], True, None, 0),
    ("window_under_a_block", 1024, (128, 128), 4, 2, 64, jnp.float32,
     [(900,)], True, 100, 0),
    # a band four tiles wide: those between the diagonal and the far edge
    # are interior
    ("window_over_blocks", 1024, (128, 128), 2, 2, 64, jnp.float32,
     [(1024,)], True, 600, 0),
    ("window_documents_grouped", 1024, (256, 256), 6, 2, 128, jnp.bfloat16,
     [(333, 800)], True, 384, 0),
    ("mask_block_4", 1024, (256, 256), 8, 2, 128, jnp.float32,
     [(1022,)], True, None, 4),
    ("mask_block_4_documents", 512, (128, 128), 2, 2, 64, jnp.bfloat16,
     [(200, 508)], True, None, 4),
    ("grouped_kv_heads", 1024, (256, 256), 12, 2, 64, jnp.float32,
     [(1000,)], True, None, 0),
    ("head_dim_256", 512, (128, 128), 2, 2, 256, jnp.float32,
     [(450,)], True, None, 0),
    ("head_dim_256_bf16", 1024, (256, 256), 2, 2, 256, jnp.bfloat16,
     [(600, 1024)], True, None, 0),
    ("head_dim_128_bf16", 1024, (512, 512), 2, 2, 128, jnp.bfloat16,
     [(1024,)], True, None, 0),
    ("head_dim_64_bf16_fat_blocks", 2048, (1024, 1024), 1, 1, 64,
     jnp.bfloat16, [(1500,)], True, None, 0),
    ("packed_not_causal", 512, (128, 128), 2, 2, 64, jnp.float32,
     [(130, 400)], False, None, 0),
]


@pytest.mark.parametrize(
    "name,S,blocks,H,G,d,dtype,bounds,causal,window,mask_block",
    SEGMENTED_CASES, ids=[c[0] for c in SEGMENTED_CASES])
def test_segmented_forward_matches_fp32_reference(
        name, S, blocks, H, G, d, dtype, bounds, causal, window, mask_block):
    """Output and lse of the segmented forward against plain fp32
    attention, on calls whose tiles take the interior body, the edge body
    and none; a pad row's output is its own business (it attends the pad
    rows before it), every other row is held."""
    B = len(bounds)
    ks = jax.random.split(jax.random.PRNGKey(17), 3)
    q = (jax.random.normal(ks[0], (B, S, H, d)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (B, S, G, d)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (B, S, G, d)) * 0.5).astype(dtype)
    seg = jnp.asarray(np.stack([_ids(S, *b) for b in bounds]))
    out, lse = _segmented_fwd(q, k, v, seg, causal, blocks, window,
                              mask_block)
    want, want_lse = segmented_reference(q, k, v, seg, causal, window,
                                         mask_block)
    real = np.asarray(seg > 0)
    tol = dict(atol=3e-5, rtol=3e-5) if dtype == jnp.float32 else \
        dict(atol=4e-2, rtol=4e-2)
    np.testing.assert_allclose(
        np.asarray(out, np.float32) * real[:, :, None, None],
        np.asarray(want) * real[:, :, None, None], **tol)
    np.testing.assert_allclose(np.asarray(lse) * real[:, None, :],
                               np.asarray(want_lse) * real[:, None, :], **tol)
    # the count is the geometry's: what the data adds, only the data knows
    assert fa._LAST_MASKED["fwd"] == fa.masked_tile_count(
        S // blocks[0], S // blocks[1], *blocks, causal, window)


@pytest.mark.parametrize("d,dtype", [(64, jnp.float32), (256, jnp.bfloat16)],
                         ids=["d64_float32", "d256_bfloat16"])
def test_interior_and_edge_body_agree_bit_for_bit(d, dtype, monkeypatch):
    """A dense call over ONE document: every tile is all-visible and takes
    the interior body. Told that no tile holds one document, every tile
    takes the edge body, whose compares then mask nothing: the same
    output and lse, to the bit."""
    q, k, v = make_qkv(s=512, h=2, d=d, dtype=dtype, seed=5)
    seg = np.ones((1, 512), np.int32)
    fa._fwd_call.cache_clear()
    interior = _segmented_fwd(q, k, v, seg, False, (128, 128))
    assert fa._LAST_MASKED["fwd"] == (0, 16)
    facts = fa._segment_facts
    monkeypatch.setattr(
        fa, "_segment_facts",
        lambda sq, sk: (facts(sq, sk)[0], jnp.bool_(False)))
    fa._fwd_call.cache_clear()
    try:
        edge = _segmented_fwd(q, k, v, seg, False, (128, 128))
    finally:
        fa._fwd_call.cache_clear()      # the patched body dies here
    for got, want in zip(edge, interior):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# GLM's two largest buckets at (1024, 1024), Laguna's window layer at
# (512, 512) and a band four tiles of 1,024 wide (the two between the
# diagonal and the far edge are interior): a segmented call reports the
# tiles the GEOMETRY crosses
SEGMENTED_COUNTS = [
    # [B, S, H, D], window, (masked, launched)
    ((1, 16384, 20, 256), None, (16, 136)),
    ((1, 8192, 20, 256), None, (8, 36)),
    ((1, 8192, 72, 128), 512, (31, 31)),
    ((1, 8192, 8, 128), 4096, (12, 30)),
]


@pytest.mark.parametrize("shape,window,count", SEGMENTED_COUNTS,
                         ids=["glm_16k", "glm_8k", "laguna_window_512",
                              "window_4096"])
def test_segmented_call_reports_the_geometrys_masked_tiles(shape, window,
                                                           count):
    """`dispatch_report()["flash"]["masked_tiles"]["fwd"]` of a serving
    prefill, traced at the blocks the rule gives (nothing runs): the
    diagonal's tiles and a window's far edge's over the tiles launched,
    as the closed form counts them."""
    spec = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    jax.make_jaxpr(lambda q, k, v, s: fa.flash_attention_segmented(
        q, k, v, s, True, window=window))(
            spec, spec, spec, jax.ShapeDtypeStruct(shape[:2], jnp.int32))
    report = importlib.import_module(
        "deeperspeed_tpu.ops").dispatch_report()["flash"]
    bq, bk = report["fwd"]
    S = shape[1]
    assert report["masked_tiles"]["fwd"] == count == \
        _closed_form_masked_tiles(S // bq, S // bk, bq, bk, True, window)
    assert report["masked_tiles"]["fwd"][1] == fa._LAST_GRIDS["fwd"][1]


# The most equations the segmented forward may trace to at the serve
# cells' prefill shapes, whole and a body: about 1.3 times what this tree
# counts (168 / 196 / 169 whole; the edge body 53-56, the interior 29). The
# strip walk this kernel does not take is 688-907 (SETUP_CASES): a prefill
# program is built a bucket and a layer kind, so this is the test that
# catches a set-up regression in the serve cells.
SEGMENTED_SETUP_CASES = [
    # [B, S, H, D], KV heads, window, mask_block
    ((1, 16384, 20, 256), 20, None, 0),
    ((1, 8192, 72, 128), 8, 512, 0),
    ((1, 2048, 32, 128), 4, None, 4),
]
SEGMENTED_BUDGET = {"kernel": 255, "edge": 75, "interior": 40}


@pytest.mark.parametrize("shape,G,window,mask_block", SEGMENTED_SETUP_CASES,
                         ids=["glm_16k", "laguna_window", "sdar_block4"])
def test_segmented_bodies_are_built_once_and_stay_small(shape, G, window,
                                                        mask_block):
    """Three layers' prefill attention, traced twice, build the kernel's
    body ONCE; it holds two whole-tile bodies (and the init and the
    finalize) under their budgets of equations, the interior one with no
    iota, compare or select in it."""
    B, S, H, D = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, S, G, D), jnp.bfloat16)
    seg = jax.ShapeDtypeStruct((B, S), jnp.int32)

    def layers(q, k, v, seg):
        for _ in range(LAYERS):
            q = fa.flash_attention_segmented(q, k, v, seg, True,
                                             window=window,
                                             mask_block=mask_block)
        return q

    before = _fresh_account()
    first = jax.make_jaxpr(layers)(q, kv, kv, seg)
    jax.make_jaxpr(layers)(q, kv, kv, seg)
    assert _built_since(before) == {"fwd": 1, "bwd": 0, "dkv": 0, "dq": 0}
    kernels = list(_kernel_jaxprs(first.jaxpr))
    assert len(kernels) == LAYERS and len({id(b) for _, b in kernels}) == 1
    name, kernel = kernels[0]
    assert name == ("ds.flash_fwd" if window is None
                    else "ds.flash_fwd_window")
    assert _equations(kernel) <= SEGMENTED_BUDGET["kernel"], \
        _equations(kernel)
    # init, the edge body, the interior body, finalize: four `pl.when`s
    conds = [eqn.params["branches"][1].jaxpr for eqn in kernel.eqns
             if eqn.primitive.name == "cond"]
    assert len(conds) == 4
    _, edge, interior, _ = conds
    assert _equations(edge) <= SEGMENTED_BUDGET["edge"], _equations(edge)
    assert _equations(interior) <= SEGMENTED_BUDGET["interior"], \
        _equations(interior)
    used = _primitives(interior)
    assert not used & {"iota", "select_n", "eq", "ge", "lt", "le", "or",
                       "and"}, used
    assert {"iota", "select_n", "eq", "ge"} <= _primitives(edge)


# ---------------------------------------------------------------------------
# the set-up account (PR 34): a body is built once a process, and is small
# ---------------------------------------------------------------------------
#
# A kernel body is python that unrolls pairs x strips x diagonal bodies;
# tracing it and lowering it to Mosaic are host time on EVERY run, compile
# cache hit or not. PR 33 was refused for that alone: a model of 24
# unrolled layers without remat built each body 24 times, +39 s of set-up
# in the four-chip cell. No clock here: builds and equations are counted.

def _kernel_jaxprs(jaxpr):
    """(name, kernel jaxpr) of every `pallas_call` anywhere under it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"], eqn.params["jaxpr"]
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_jaxprs(sub)


def _primitives(jaxpr):
    """The names of the primitives anywhere under it."""
    return {eqn.primitive.name for eqn in jaxpr.eqns} | {
        name for eqn in jaxpr.eqns
        for sub in jax.core.jaxprs_in_params(eqn.params)
        for name in _primitives(sub)}


def _equations(jaxpr):
    return sum(1 + sum(_equations(sub)
                       for sub in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


# The three train cells' per-shard attention, and the most equations each
# kernel's body may unroll to there: about 1.3 times what this tree counts
# for the backward, less for the forward (fwd 843 / 840 / 840: the strips
# and pairs no mask touches are loops the lowering unrolls, counted once;
# written out they were 1,751 / 2,544; the fused backward 265 = dkv's 199
# + the fifth matmul, two small transposes and the slab's read and write in each of
# its five groups, and dq's init and store; as two kernels dkv 199, dq
# 182). Those are the bodies on MOVED heads ([B*H, S, D] blocks); where
# the cells' calls read the heads in place (`heads_in_place`: transposed
# blocks) the forward counts 845 / 842 / 842 (k's transpose and its
# store) and the fused backward 258 (no transpose in a group, k's and v's
# once a column). A body that grows past it is set-up every run pays:
# shrink it, or let its unrolling adapt to the shape
# (docs/long-context.md, "What a body costs the host").
BACKWARD_BUDGET = {"ds.flash_bwd": 340, "ds.flash_bwd_dkv": 260,
                   "ds.flash_bwd_dq": 240}
SETUP_CASES = [
    # shape [B, S, H, D], budget of equations a kernel
    ((1, 16384, 16, 64), {"ds.flash_fwd": 900, **BACKWARD_BUDGET}),
    ((16, 2048, 16, 64), {"ds.flash_fwd": 1000, **BACKWARD_BUDGET}),
    ((4, 2048, 16, 128), {"ds.flash_fwd": 1000, **BACKWARD_BUDGET}),
]
KERNEL_OF = {"fwd": "ds.flash_fwd", "bwd": "ds.flash_bwd",
             "dkv": "ds.flash_bwd_dkv", "dq": "ds.flash_bwd_dq"}
LAYERS = 3      # unrolled, as a model without remat calls the attention


def _layers(q, k, v):
    """At the blocks the rule gives the chip the cells run on (the CPU
    has no row of its own for 16k)."""
    from deeperspeed_tpu.ops.autotune import flash_blocks
    (bq, bk), bwd = flash_blocks(q.shape, True, device_kind="TPU v5 lite")
    x = q
    for _ in range(LAYERS):
        x = fa.flash_attention(x, k, v, True, None, bq, bk, bwd)
    return x.astype(jnp.float32)


def _layers_loss(q, k, v):
    return _layers(q, k, v).sum()


def _fresh_account():
    fa._fwd_call.cache_clear()
    fa._bwd_calls.cache_clear()
    return {kind: n for kind, (n, _) in fa._BODY_BUILDS.items()}


def _built_since(before):
    return {kind: n - before[kind]
            for kind, (n, _) in fa._BODY_BUILDS.items()}


def test_a_train_step_reads_the_heads_in_place_and_a_prefill_moves_them():
    """`dispatch_report()["flash"]["heads"]`: a traced train step of a tiny
    Pythia (two layers, two heads of 64, 2,048 tokens: two blocks) counts
    2 x layers tiled calls in place, a forward and a backward a layer, and
    none through the copies; a serving prefill's attention (the segmented
    forward, as `InferenceEngine._prefill_fn` calls it) counts the
    reverse."""
    from deeperspeed_tpu.models.gpt_neox import (GPTNeoX, GPTNeoXConfig,
                                                  causal_attention)
    layers = 2
    cfg = GPTNeoXConfig(vocab_size=256, hidden_size=128, num_layers=layers,
                        num_heads=2, max_seq_len=2048, rotary_pct=0.25)
    model = GPTNeoX(cfg, use_pallas=True)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, 2048), jnp.int32)
    before = _head_counts()
    jax.make_jaxpr(jax.value_and_grad(model.loss_fn))(params,
                                                      (tokens, tokens))
    assert _counted_since(before) == {
        "fwd": {"in_place": layers, "moved": 0},
        "bwd": {"in_place": layers, "moved": 0}}
    q = jax.ShapeDtypeStruct((1, 2048, 2, 64), jnp.bfloat16)
    before = _head_counts()
    jax.make_jaxpr(lambda q, k, v, seg: causal_attention(
        q, k, v, use_pallas=True, segment_ids=seg))(q, q, q, tokens)
    assert _counted_since(before) == {
        "fwd": {"in_place": 0, "moved": 1},
        "bwd": {"in_place": 0, "moved": 0}}


def _transposes(jaxpr):
    """The permutation of every `transpose` of a 4-D operand under it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "transpose" and \
                len(eqn.params["permutation"]) == 4:
            yield tuple(eqn.params["permutation"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _transposes(sub)


@pytest.mark.parametrize("shape", [case[0] for case in SETUP_CASES],
                         ids=["train_16k", "train_2k", "zero3_shard"])
def test_the_training_call_never_moves_a_head(shape):
    """`value_and_grad` of the training call at the train cells' shapes
    holds no `[B, S, H, D] -> [B, H, S, D]` transpose (`_to_bh`'s: a copy
    of the tensor on the chip) nor its inverse. What it holds is
    `_to_rows`' (0, 2, 3, 1) and back (0, 3, 1, 2): the layout XLA keeps
    such a tensor in, so a bitcast there (tests/test_tpu_compile.py reads
    the compiled program for that)."""
    spec = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        _layers_loss, argnums=(0, 1, 2)))(spec, spec, spec)
    found = set(_transposes(jaxpr.jaxpr))
    assert found == {(0, 2, 3, 1), (0, 3, 1, 2)}, found


@pytest.mark.parametrize("heads", ["in_place", "moved"])
@pytest.mark.parametrize("shape,budget", SETUP_CASES,
                         ids=["train_16k", "train_2k", "zero3_shard"])
def test_bodies_are_built_once_and_stay_small(shape, budget, backward,
                                              heads, monkeypatch):
    """Three unrolled layers' forward and backward, traced twice in one
    process, build each kernel body ONCE: two bodies, the forward's and
    the fused backward's (three where the backward is two kernels); and
    each body stays under its written budget of equations, on the heads
    in place (what the cells run) and on moved heads (a shape the rule
    does not admit; every masked, biased or segmented call)."""
    if heads == "moved":
        monkeypatch.setattr(fa, "heads_in_place", lambda h, g, d: False)
    spec = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kinds = ("fwd", *backward)
    before = _fresh_account()
    grad = jax.grad(_layers_loss, argnums=(0, 1, 2))
    first = jax.make_jaxpr(grad)(spec, spec, spec)
    jax.make_jaxpr(grad)(spec, spec, spec)
    assert _built_since(before) == {
        kind: int(kind in kinds) for kind in KERNEL_OF}
    kernels = list(_kernel_jaxprs(first.jaxpr))
    assert sorted(name for name, _ in kernels) == sorted(
        [KERNEL_OF[kind] for kind in kinds] * LAYERS)
    for name, body in kernels:
        assert _equations(body) <= budget[name], (name, _equations(body))
    # one traced body, bound by every layer: what lets jax lower it once
    # a module, too (its lowering cache is keyed on the equation's params)
    assert len({id(body) for _, body in kernels}) == len(kinds)
    report = importlib.import_module(
        "deeperspeed_tpu.ops").dispatch_report()["flash"]
    assert set(report["bodies_built"]) == set(KERNEL_OF)
    assert set(report["masked_tiles"]) == set(kinds)
    assert report["masked_tiles"]["fwd"] == fa._LAST_MASKED["fwd"]
    assert report["bwd_variant"] == \
        ("fused-trapezoid" if backward == ("bwd",) else "trapezoid")


def test_bodies_are_built_once_under_shard_map(backward):
    """The four-chip cell's path: 16 sequences over a 4-device
    `shard_map` (`parallel.mesh.per_shard`), 4 a shard at head dim 128:
    TWO bodies, the forward's and the fused backward's."""
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    spec = jax.ShapeDtypeStruct((16, 2048, 16, 128), jnp.bfloat16)
    sharded = jax.shard_map(_layers, mesh=mesh,
                            in_specs=(P("data"),) * 3, out_specs=P("data"),
                            check_vma=False)
    before = _fresh_account()
    grad = jax.grad(lambda *a: sharded(*a).sum(), argnums=(0, 1, 2))
    first = jax.make_jaxpr(grad)(spec, spec, spec)
    jax.make_jaxpr(grad)(spec, spec, spec)
    kinds = ("fwd", *backward)
    assert _built_since(before) == {
        kind: int(kind in kinds) for kind in KERNEL_OF}
    kernels = list(_kernel_jaxprs(first.jaxpr))
    assert len(kernels) == len(kinds) * LAYERS
    assert len({id(body) for _, body in kernels}) == len(kinds)
    for name, body in kernels:
        assert _equations(body) <= SETUP_CASES[2][1][name], name
