"""Compacted causal flash grid: the trapezoidal schedule must launch
~n(n+1)/2 (q, k) instances instead of n² (the compile-time invariant),
match the XLA reference numerically on every path, and the heads-batched
(hb > 1) single-block kernels must agree with hb = 1 exactly.

The tiled backward is one fused kernel where a sequence's dq slab fits
VMEM (`ops.autotune.flash_dq_slab_admitted`) and two kernels where it
does not: the `backward` fixture runs a test on both sides of that line.

Runs on CPU in interpret mode. The tile bodies against the fp32 reference
are `tests/test_flash_tile_bodies.py`; the backward on both sides of the
slab budget, the masked-tile counts and the segmented forward's bodies
`tests/test_flash_masked_tiles.py`; what they share
`tests/flash_grid_common.py`."""

import functools
import importlib
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.ops import autotune
from tests.flash_grid_common import (  # noqa: F401 (fixture)
    _kernel_jaxprs, backward, fa, make_qkv)


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


def _k_turns():
    return dict(importlib.import_module(
        "deeperspeed_tpu.ops").dispatch_report()["flash"]["k_turns"])


def _k_turned_since(before):
    return {rule: n - before[rule] for rule, n in _k_turns().items()}


K_SLAB_CASES = [
    # [B, S, H, D], forward blocks, causal: four q rows each; the heads'
    # k all differ, so a block left from the head before would show
    ((1, 1024, 16, 64), (256, 128), True),
    ((1, 1024, 16, 128), (256, 128), True),
    ((2, 512, 2, 64), (128, 256), True),
    ((3, 512, 1, 64), (128, 128), True),
    # the dense grid's q rows are `parallel`: it turns k every step
    ((2, 512, 2, 64), (256, 128), False),
]


@pytest.mark.parametrize(
    "shape,blocks,causal", K_SLAB_CASES,
    ids=["h16-d64-blocks-2-to-1", "h16-d128-blocks-2-to-1", "blocks-1-to-2",
         "equal-blocks", "dense-turns-every-step"])
def test_a_heads_k_is_turned_once_and_reads_as_turned_every_step(
        shape, blocks, causal, monkeypatch):
    """The by-rows forward that keeps a head's turned k in VMEM
    (`autotune.flash_k_slab_admitted`) against the same forward with the
    slab refused (the budget taken to nothing: the rule is a function of
    the shape): out, LSE and all three gradients BIT-equal, and
    `dispatch_report()["flash"]["k_turns"]` says which rule each call
    ran. A dense call turns k every step under either budget, and is
    held to the moved heads' forward."""
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q, k, v, w = (jax.random.normal(kk, shape, jnp.bfloat16) * 0.5
                  for kk in ks)
    scale = 1.0 / math.sqrt(shape[-1])

    def fwd_and_grads():
        before = _k_turns()
        out, res = fa._fwd(q, k, v, causal, scale, *blocks, in_place=True)
        grads = jax.grad(lambda *a: jnp.sum(fa.flash_attention(
            *a, causal, None, *blocks).astype(jnp.float32)
            * w.astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)
        return (out, res[-1], *grads), _k_turned_since(before)

    kept, counted = fwd_and_grads()
    rule = "once_a_head" if causal else "every_step"
    assert counted == {"once_a_head": 0, "every_step": 0, rule: 2}
    monkeypatch.setattr(autotune, "_FLASH_K_SLAB_BUDGET", 0)
    refused, counted = fwd_and_grads()
    assert counted == {"once_a_head": 0, "every_step": 2}
    for got, want, name in zip(kept, refused, ("out", "lse", "dq", "dk",
                                               "dv")):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32),
                                      err_msg=name)
    if not causal:
        moved, _ = fa._fwd(q, k, v, causal, scale, *blocks)
        np.testing.assert_allclose(np.asarray(kept[0], np.float32),
                                   np.asarray(moved, np.float32),
                                   atol=1e-2, rtol=1e-2)


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
# blocks) the forward counts 875 / 872 / 872 (the first-visit predicate,
# k's transpose and its store into the head's slab under it, and the
# block's row of the slab added to every score matmul's offset; 845 /
# 842 / 842 where the slab is refused and k is turned every step) and the
# fused backward 258 (no transpose in a group, k's and v's once a
# column). A body that grows past it is set-up every run pays:
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

# What the bodies may grow to where the kernels rotate q and k themselves
# (`flash_attention(..., rotary=...)`, the train cells' call since PR 63):
# this tree counts the forward 922 / 919 / 919 (+47: the table's slice and
# a rotation of the k block under the first-visit predicate, one of the q
# block and its store into the scratch under the row's first tile) and the
# fused backward 351 (+93: q rotated once a step into its scratch, k once
# a column with its table columns kept, dk's and dq's rotation back; with
# q rotated in each of its five groups it was 428 and built in twice the
# host time). Every row above stays as it is: with `rotary=None` the
# kernels trace the parent's jaxprs.
ROTATING_GROWTH = {"ds.flash_fwd": 60, "ds.flash_bwd": 120}


KERNEL_OF = {"fwd": "ds.flash_fwd", "bwd": "ds.flash_bwd",
             "dkv": "ds.flash_bwd_dkv", "dq": "ds.flash_bwd_dq"}


LAYERS = 3      # unrolled, as a model without remat calls the attention


def _layers(q, k, v, rotating=False):
    """At the blocks the rule gives the chip the cells run on (the CPU
    has no row of its own for 16k). `rotating`: the kernels rotate a
    quarter of each head's features, as Pythia's do."""
    from deeperspeed_tpu.models import gpt_neox
    from deeperspeed_tpu.ops.autotune import flash_blocks
    (bq, bk), bwd = flash_blocks(q.shape, True, device_kind="TPU v5 lite")
    rotary = gpt_neox._rotary_table(
        *gpt_neox.rope_inv_freq(q.shape[3], 0.25, 10000.0), q.shape[1],
        jnp.float32) if rotating else None
    x = q
    for _ in range(LAYERS):
        x = fa.flash_attention(x, k, v, True, None, bq, bk, bwd,
                               rotary=rotary)
    return x.astype(jnp.float32)


def _layers_loss(q, k, v, rotating=False):
    return _layers(q, k, v, rotating).sum()


def _fresh_account():
    fa._fwd_call.cache_clear()
    fa._bwd_calls.cache_clear()
    return {kind: n for kind, (n, _) in fa._BODY_BUILDS.items()}


def _built_since(before):
    return {kind: n - before[kind]
            for kind, (n, _) in fa._BODY_BUILDS.items()}


TINY_LAYERS = 2
TINY_TOKENS = jax.ShapeDtypeStruct((1, 2048), jnp.int32)


def _trace_tiny_train_step():
    """Loss and gradients of a tiny Pythia (two layers, two heads of 64,
    2,048 tokens: two blocks), traced."""
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
    cfg = GPTNeoXConfig(vocab_size=256, hidden_size=128,
                        num_layers=TINY_LAYERS, num_heads=2,
                        max_seq_len=2048, rotary_pct=0.25)
    model = GPTNeoX(cfg, use_pallas=True)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    jax.make_jaxpr(jax.value_and_grad(model.loss_fn))(
        params, (TINY_TOKENS, TINY_TOKENS))


def _trace_prefill_attention():
    """A serving prefill's attention (the segmented forward, as
    `InferenceEngine._prefill_fn` calls it), traced."""
    from deeperspeed_tpu.models.gpt_neox import causal_attention
    q = jax.ShapeDtypeStruct((1, 2048, 2, 64), jnp.bfloat16)
    jax.make_jaxpr(lambda q, k, v, seg: causal_attention(
        q, k, v, use_pallas=True, segment_ids=seg))(q, q, q, TINY_TOKENS)


def test_a_train_step_reads_the_heads_in_place_and_a_prefill_moves_them():
    """`dispatch_report()["flash"]["heads"]`: a traced train step counts
    2 x layers tiled calls in place, a forward and a backward a layer, and
    none through the copies; a serving prefill's attention counts the
    reverse."""
    before = _head_counts()
    _trace_tiny_train_step()
    assert _counted_since(before) == {
        "fwd": {"in_place": TINY_LAYERS, "moved": 0},
        "bwd": {"in_place": TINY_LAYERS, "moved": 0}}
    before = _head_counts()
    _trace_prefill_attention()
    assert _counted_since(before) == {
        "fwd": {"in_place": 0, "moved": 1},
        "bwd": {"in_place": 0, "moved": 0}}


def test_a_train_step_turns_a_heads_k_once():
    """`dispatch_report()["flash"]["k_turns"]`: the same traced train
    step counts one forward a layer that keeps the head's turned k
    (`once_a_head`) and none that turns it every grid step; a serving
    prefill (moved heads: no turn at all) counts under neither."""
    before = _k_turns()
    _trace_tiny_train_step()
    _trace_prefill_attention()
    assert _k_turned_since(before) == {"once_a_head": TINY_LAYERS,
                                       "every_step": 0}


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


@pytest.mark.parametrize("heads", ["in_place", "moved", "k_every_step",
                                   "rotating"])
@pytest.mark.parametrize("shape,budget", SETUP_CASES,
                         ids=["train_16k", "train_2k", "zero3_shard"])
def test_bodies_are_built_once_and_stay_small(shape, budget, backward,
                                              heads, monkeypatch):
    """Three unrolled layers' forward and backward, traced twice in one
    process, build each kernel body ONCE: two bodies, the forward's and
    the fused backward's (three where the backward is two kernels); and
    each body stays under its written budget of equations, on the heads
    in place (what the cells run), on moved heads (a shape the rule
    does not admit; every masked, biased or segmented call) and in place
    with k turned every grid step (a sequence whose k is over the slab's
    budget, `autotune.flash_k_slab_admitted`); and in place with the
    rotary of q and k inside the kernels (what the cells' models call
    since PR 63), each body a written number of equations over its row."""
    if heads == "moved":
        monkeypatch.setattr(fa, "heads_in_place", lambda h, g, d: False)
    if heads == "k_every_step":
        monkeypatch.setattr(autotune, "_FLASH_K_SLAB_BUDGET", 0)
    if heads == "rotating":
        budget = {name: n + ROTATING_GROWTH.get(name, 0)
                  for name, n in budget.items()}
    spec = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kinds = ("fwd", *backward)
    before = _fresh_account()
    grad = jax.grad(functools.partial(_layers_loss,
                                      rotating=heads == "rotating"),
                    argnums=(0, 1, 2))
    if heads == "rotating" and backward != ("bwd",):
        # the two kernels of a sequence over the slab's budget do not
        # rotate: the call is refused, and a model keeps its XLA rotary
        with pytest.raises(ValueError, match="rotates_in_kernel"):
            jax.make_jaxpr(grad)(spec, spec, spec)
        return
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
