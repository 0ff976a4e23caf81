"""ZeRO memory-helper tests (parity with reference
`tests/unit/test_zero_tiled.py` plus allocator/linear coverage for
`zero/contiguous_memory_allocator.py` and `zero/linear.py`)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.runtime.zero import (ContiguousMemoryAllocator,
                                          TiledLinear,
                                          memory_efficient_linear)


@pytest.mark.parametrize("in_f,out_f,in_splits,out_splits", [
    (32, 48, 1, 1),
    (32, 48, 4, 3),
    (33, 47, 4, 3),   # ragged: padding must not leak
    (16, 16, 16, 16),  # 1x1 tiles
])
def test_tiled_linear_matches_dense(in_f, out_f, in_splits, out_splits):
    layer = TiledLinear(in_f, out_f, in_splits=in_splits,
                        out_splits=out_splits)
    rng = jax.random.PRNGKey(0)
    w = jax.random.normal(rng, (in_f, out_f), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (out_f,), jnp.float32)
    params = layer.from_dense(w, b)

    x = jax.random.normal(jax.random.PRNGKey(2), (5, in_f), jnp.float32)
    got = layer.apply(params, x)
    want = x @ w + b
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # round trip through the tile grid
    np.testing.assert_allclose(np.asarray(layer.to_dense(params)),
                               np.asarray(w), rtol=1e-6)


def test_tiled_linear_init_grad_no_padding_leak():
    layer = TiledLinear(10, 7, in_splits=3, out_splits=2)
    params = layer.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 10), jnp.float32)

    def loss(p):
        return jnp.sum(layer.apply(p, x) ** 2)

    g = jax.grad(loss)(params)
    # grads exist, finite, and padded regions of weight stay inert
    assert np.isfinite(np.asarray(g["weight"])).all()
    dense = layer.to_dense(params)
    assert dense.shape == (10, 7)


def test_memory_efficient_linear_matches_plain():
    w = jax.random.normal(jax.random.PRNGKey(0), (12, 8), jnp.float32)
    b = jnp.ones((8,), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 12), jnp.float32)
    params = {"weight": w, "bias": b}

    def loss_remat(p, x):
        return jnp.sum(memory_efficient_linear(p, x) ** 2)

    def loss_plain(p, x):
        return jnp.sum((x @ p["weight"] + p["bias"]) ** 2)

    np.testing.assert_allclose(loss_remat(params, x), loss_plain(params, x),
                               rtol=1e-6)
    g1 = jax.grad(loss_remat)(params, x)
    g2 = jax.grad(loss_plain)(params, x)
    for a, b_ in zip(jax.tree_util.tree_leaves(g1),
                     jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-5)


class TestContiguousMemoryAllocator:
    def test_alloc_release_reuse(self):
        arena = ContiguousMemoryAllocator(100)
        a = arena.allocate_tensor(40)
        b = arena.allocate_tensor(40)
        assert arena.total_free == 20
        arena.get_tensor(a)[:] = 1.0
        arena.get_tensor(b)[:] = 2.0
        arena.release_tensor(a)
        c = arena.allocate_tensor(30)  # fits in the released hole
        assert arena.get_tensor(b).sum() == 80.0
        assert arena.get_tensor(c).shape == (30,)

    def test_defrag_preserves_contents(self):
        arena = ContiguousMemoryAllocator(100)
        ids = [arena.allocate_tensor(20) for _ in range(5)]
        for i, bid in enumerate(ids):
            arena.get_tensor(bid)[:] = float(i)
        # free blocks 0, 2 → two 20-wide holes; a 40 alloc needs defrag
        arena.release_tensor(ids[0])
        arena.release_tensor(ids[2])
        assert arena.largest_contiguous == 20
        big = arena.allocate_tensor(40)
        assert arena.get_tensor(big).shape == (40,)
        for i in (1, 3, 4):
            assert (arena.get_tensor(ids[i]) == float(i)).all(), \
                f"block {i} corrupted by defrag"

    def test_exhaustion_raises(self):
        arena = ContiguousMemoryAllocator(10)
        arena.allocate_tensor(8)
        with pytest.raises(MemoryError):
            arena.allocate_tensor(4)
