"""The flash kernels' tile bodies (PR 33) against plain fp32 attention:
forward, dq, dk and dv for every mask a body knows, the backward as the
fused kernel and as the two (`tests/flash_grid_common.py`'s `backward`).

Runs on CPU in interpret mode."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.flash_grid_common import (  # noqa: F401 (fixture)
    _documents, _variant, backward, causal_seen, fa, make_qkv,
    masked_reference)


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
