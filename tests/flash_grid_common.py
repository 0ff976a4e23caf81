"""What the flash grid tests share (`tests/test_flash_causal_grid.py`,
`test_flash_tile_bodies.py`, `test_flash_masked_tiles.py`): the kernel
module, the `backward` fixture, inputs, and the fp32 references a tile
body is held to."""

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


def make_qkv(b=1, s=512, h=2, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), dtype) * 0.5
                 for k in ks)


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


def _kernel_jaxprs(jaxpr):
    """(name, kernel jaxpr) of every `pallas_call` anywhere under it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"], eqn.params["jaxpr"]
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_jaxprs(sub)
