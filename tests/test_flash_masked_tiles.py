"""The flash backward on both sides of the dq slab's budget, the count of
masked tiles against its closed form, and the segmented forward's two
whole-tile bodies (PR 49). Builders: `tests/flash_grid_common.py`.

Runs on CPU in interpret mode."""

import importlib
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.ops import autotune
from tests.flash_grid_common import (  # noqa: F401 (fixture)
    _kernel_jaxprs, _variant, backward, causal_seen, fa, make_qkv,
    masked_reference)


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
