"""Long-context kernel suite (`longctx` marker, slow lane).

Exercises the 16k/32k dispatch decisions and the segmented kernels at
multi-block depth. On CPU the Pallas kernels run in interpret mode, so
the shapes here stay modest (1k) while the DISPATCH paths are probed at
the real 16k/32k geometries (block selection is host-side and cheap).
On a TPU host, run `pytest -m longctx` to execute the same parities on
the hardware kernels at full size.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

# 16k/32k shapes: `slow` by tier-1's rule (`pyproject.toml`)
pytestmark = [pytest.mark.longctx, pytest.mark.slow]


def make_qkv(b=1, s=1024, h=1, d=64, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), jnp.float32) * 0.5
                 for k in ks)


def reference_segmented(q, k, v, seg, causal):
    B, S, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = seg[:, :, None] == seg[:, None, :]
    if causal:
        mask = mask & jnp.tril(jnp.ones((S, S), bool))[None]
    logits = jnp.where(mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(mask[:, None].any(-1, keepdims=True), probs, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


def test_segmented_deep_grid_fwd_bwd():
    """Segment masking across an 8x8 block grid with asymmetric fwd/bwd
    geometry — the shape class the 16k rows dispatch."""
    from deeperspeed_tpu.ops.pallas.flash_attention import \
        flash_attention_segmented
    q, k, v = make_qkv(s=1024)
    rng = np.random.default_rng(0)
    # 5 documents + pad tail, boundaries off the 128 grain on purpose
    bounds = [0, 200, 391, 640, 811, 960, 1024]
    seg = np.zeros((1, 1024), np.int32)
    for i in range(5):
        seg[0, bounds[i]:bounds[i + 1]] = i + 1
    seg = jnp.asarray(seg)

    def loss(q, k, v):
        return jnp.sum(flash_attention_segmented(
            q, k, v, seg, True, None, 256, 128, (128, 256)) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_segmented(q, k, v, seg, True) ** 2)

    out = flash_attention_segmented(q, k, v, seg, True, None, 256, 128,
                                    (128, 256))
    ref = reference_segmented(q, k, v, seg, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-2,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("seq", [16384, 32768])
def test_bwd_dispatch_shapes_divide(seq, monkeypatch):
    """The 16k/32k dispatch must always hand the kernels a dividing
    geometry."""
    from deeperspeed_tpu.ops.autotune import flash_blocks
    monkeypatch.delenv("DS_FLASH_BLOCKS", raising=False)
    monkeypatch.delenv("DS_FLASH_BWD_BLOCKS", raising=False)
    for blocks in flash_blocks((1, seq, 12, 64), True):
        assert seq % blocks[0] == 0 and seq % blocks[1] == 0
        assert blocks[0] % 128 == 0 and blocks[1] % 128 == 0


def test_packed_model_1k_trains():
    """Packed ragged batch through the real model stack at 1k — the
    fast-lane pin runs 128 tokens; this covers a multi-block row."""
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
    from deeperspeed_tpu.runtime.packing import (PackedDataset,
                                                 synthetic_doc_mixture)
    cfg = GPTNeoXConfig(vocab_size=128, hidden_size=64, num_layers=1,
                        num_heads=1, max_seq_len=1024)
    model = GPTNeoX(cfg, use_pallas=True)
    params = model.init_params(jax.random.PRNGKey(0))
    ds = PackedDataset(synthetic_doc_mixture(5, 12, 128, mean_len=300.0,
                                             max_len=1024), 1024)
    tok = jnp.asarray(ds.tokens[:1])
    seg = jnp.asarray(ds.segment_ids[:1])
    loss, grads = jax.value_and_grad(
        lambda p: model.loss_fn(p, (tok, tok, seg)))(params)
    assert np.isfinite(float(loss))
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat)
