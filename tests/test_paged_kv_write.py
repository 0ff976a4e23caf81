"""The one-token step leaves the KV pools where they are (PR 25).

- `paged_kv_write`: the aliased row-write kernel (interpret mode here)
  against XLA's `pool.at[layer, page_idx, :, slot].set(row)`, for
  float32, bf16 and int8 data + scale pools, at the edges of the row's
  packed sublane group (`_write_group`: what a row's write moves since
  PR 38) and on pages the group does not divide, with inactive rows on
  the trash page 0: every slot no row names is the input's, bit for bit,
  and no other layer is touched.
- `paged_decode_attention(..., layer=l)` on stacked `[L, P, H, ps, D]`
  pools against the per-layer call on `pool[l]`, both back ends.
- The engine under a model-parallel mesh with the kernels forced: the
  write and the attention run per head shard, tokens unchanged.

CPU, tiny sizes: results, never a time. `tests/test_tpu_compile_kernels.py`
compiles the same kernels, and `tests/test_tpu_compile_serving.py` the
engine's decode program, for a v5e.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.inference import InferenceEngine
from deeperspeed_tpu.inference.kv_cache import quantize_kv
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.ops import dispatch_report
from deeperspeed_tpu.ops.pallas.decode_attention import (
    _write_group, paged_decode_attention, paged_kv_write)

L, P, H, PS = 3, 7, 4, 16


def stacked_pools(rng, d, dtype, ps=PS):
    """K and V data pools [L, P, H, ps, d] of `dtype`, with their
    [L, P, H, ps] bf16 scale pools when int8."""
    def data():
        x = rng.normal(size=(L, P, H, ps, d))
        if dtype == jnp.int8:
            return jnp.asarray(np.round(x * 40).clip(-127, 127), jnp.int8)
        return jnp.asarray(x, dtype)
    pools = [data(), data()]
    if dtype == jnp.int8:
        pools += [jnp.asarray(rng.uniform(0.01, 0.1, size=(L, P, H, ps)),
                              jnp.bfloat16) for _ in range(2)]
    return pools


def new_rows(rng, pools, b):
    rows = [jnp.asarray(rng.normal(size=(b, H, pools[0].shape[-1])),
                        jnp.float32) for _ in range(2)]
    if pools[0].dtype == jnp.int8:
        (k, ks), (v, vs) = (quantize_kv(r) for r in rows)
        return [k, v, ks, vs]
    return rows


# ---------------------------------------------------------------------------
# the write kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,page_size,group", [
    (jnp.float32, 8, 8), (jnp.float32, 24, 8), (jnp.float32, 64, 8),
    (jnp.bfloat16, 8, 8), (jnp.bfloat16, 16, 16), (jnp.bfloat16, 24, 24),
    (jnp.bfloat16, 64, 16), (jnp.int8, 32, 32), (jnp.int8, 24, 24),
    (jnp.int8, 64, 32), (jnp.int8, 96, 32)])
def test_write_group_is_the_rows_packed_sublanes(dtype, page_size, group):
    """8 sublanes of 32 bits: 8 float32, 16 bf16, 32 int8 slots, where
    that divides the page; else the page."""
    assert _write_group(page_size, dtype) == group


def group_edge_slots(ps, g):
    """Five live rows' slots (each on a page of its own): the page's
    first slot, a group's last, the next group's first, the page's last,
    and one in the middle of a group; then two inactive rows' slots on
    the trash page, in neighbouring groups where the page has two."""
    return [0, g - 1, g % ps, ps - 1, (g + g // 2) % ps], [g - 1, g % ps]


@pytest.mark.parametrize("layer", [0, L - 1])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("ps", [8, 16, 24, 64])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32, jnp.int8],
                         ids=["bf16", "fp32", "int8"])
def test_write_kernel_matches_the_scatter(dtype, ps, d, layer):
    rng = np.random.default_rng(d + layer + ps)
    pools = stacked_pools(rng, d, dtype, ps)
    g = _write_group(ps, dtype)
    live, trash = group_edge_slots(ps, g)
    page_idx = jnp.asarray([3, 5, 6, 2, 1, 0, 0], jnp.int32)
    slot = jnp.asarray(live + trash, jnp.int32)
    rows = new_rows(rng, pools, 7)
    want = paged_kv_write(pools, rows, jnp.int32(layer), page_idx, slot,
                          backend="xla")
    assert "kv_write_slots" not in dispatch_report()["decode_attention"]
    got = jax.jit(lambda *a: paged_kv_write(a[:len(pools)], a[len(pools):],
                                            jnp.int32(layer), page_idx,
                                            slot, backend="pallas"))(
        *pools, *rows)
    report = dispatch_report()["decode_attention"]
    assert report["kv_write"] == "pallas" and report["kv_write_slots"] == g
    assert len(got) == len(pools)              # K, V (and their scales)
    named = np.zeros((L, P, ps), bool)
    named[layer, np.asarray(page_idx), np.asarray(slot)] = True
    for before, w, after in zip(pools, want, got):
        assert after.dtype == before.dtype and after.shape == before.shape
        w, after, before = (np.asarray(x.astype(jnp.float32))
                            for x in (w, after, before))
        # every page but the trash page, bit for bit
        np.testing.assert_array_equal(after[:, 1:], w[:, 1:])
        # the live rows did land
        assert not np.array_equal(after[layer, 1:], before[layer, 1:])
        # every slot no row names is the input's: the rest of the row's
        # own group, its page's other groups, every other page and layer
        kept = np.moveaxis(~named, 2, 0)       # [ps, L, P]
        np.testing.assert_array_equal(np.moveaxis(after, 3, 0)[kept],
                                      np.moveaxis(before, 3, 0)[kept])
        if before.ndim == 5 and trash[0] // g != trash[1] // g:
            # two rows in neighbouring groups of one page: two blocks of
            # a data pool, so both land even there (a scale pool's plane
            # is one block: the second row's write may drop the first's)
            np.testing.assert_array_equal(after[:, 0], w[:, 0])


def test_written_rows_are_the_rows():
    rng = np.random.default_rng(7)
    pools = stacked_pools(rng, 64, jnp.bfloat16)
    page_idx = jnp.asarray([2, 4], jnp.int32)
    slot = jnp.asarray([9, 0], jnp.int32)
    rows = new_rows(rng, pools, 2)
    k, v = paged_kv_write(pools, rows, 1, page_idx, slot, backend="pallas")
    for pool, row in ((k, rows[0]), (v, rows[1])):
        for b in range(2):
            np.testing.assert_array_equal(
                np.asarray(pool[1, page_idx[b], :, slot[b]], np.float32),
                np.asarray(row[b].astype(jnp.bfloat16), np.float32))


def test_write_refuses_what_does_not_match():
    rng = np.random.default_rng(8)
    pools = stacked_pools(rng, 64, jnp.bfloat16)
    idx = jnp.zeros((2,), jnp.int32)
    rows = new_rows(rng, pools, 2)
    with pytest.raises(ValueError, match="2 pools for 1 rows"):
        paged_kv_write(pools, rows[:1], 0, idx, idx)
    with pytest.raises(ValueError, match="do not match pool"):
        paged_kv_write(pools, [r[:, :2] for r in rows], 0, idx, idx)
    with pytest.raises(ValueError, match="backend"):
        paged_kv_write(pools, rows, 0, idx, idx, backend="cuda")


# ---------------------------------------------------------------------------
# the paged kernel on stacked pools
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8],
                         ids=["fp32", "int8"])
def test_layer_indexed_attention_matches_the_per_layer_call(dtype, backend):
    rng = np.random.default_rng(11)
    d, b, n_pages = 64, 3, 3
    pools = stacked_pools(rng, d, dtype)
    scales = ({"k_scales": pools[2], "v_scales": pools[3]}
              if dtype == jnp.int8 else {})
    q = jnp.asarray(rng.normal(size=(b, H, d)), jnp.float32)
    table = jnp.asarray(rng.integers(1, P, size=(b, n_pages)), jnp.int32)
    lengths = jnp.asarray([PS * 2 + 5, 0, PS], jnp.int32)
    for layer in range(L):
        one = {k: v[layer] for k, v in scales.items()}
        want = paged_decode_attention(q, pools[0][layer], pools[1][layer],
                                      table, lengths, backend=backend,
                                      **one)
        got = jax.jit(lambda lyr: paged_decode_attention(
            q, pools[0], pools[1], table, lengths, backend=backend,
            layer=lyr, **scales))(jnp.int32(layer))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert (np.asarray(got)[1] == 0.0).all()    # the inactive row


def test_layer_and_pool_rank_must_agree():
    rng = np.random.default_rng(12)
    pools = stacked_pools(rng, 64, jnp.float32)
    q = jnp.zeros((1, H, 64), jnp.float32)
    table = jnp.ones((1, 2), jnp.int32)
    lengths = jnp.ones((1,), jnp.int32)
    with pytest.raises(ValueError, match="with a layer index"):
        paged_decode_attention(q, pools[0][0], pools[1][0], table, lengths,
                               layer=0)
    with pytest.raises(ValueError, match="without a layer index"):
        paged_decode_attention(q, pools[0], pools[1], table, lengths)


# ---------------------------------------------------------------------------
# the engine: kernels per head shard under a model-parallel mesh
# ---------------------------------------------------------------------------

def engine_config(**kw):
    # page 32: the int8 sublane tile, which a forced int8 kernel needs
    block = {"enabled": True, "page_size": 32, "num_pages": 12,
             "max_batch_size": 2, "token_budget": 128,
             "prefill_lengths": [32], "prefill_batch_sizes": [1, 2],
             "decode_batch_sizes": [2]}
    block.update(kw)
    return {"inference": block}


@pytest.mark.parametrize("kv", [None, "int8"], ids=["native", "int8"])
def test_forced_kernels_under_a_model_parallel_mesh(devices, kv):
    """mp = 2: the write kernel and the paged kernel run inside a
    `shard_map` over heads (GSPMD cannot partition a Mosaic kernel) and
    give the tokens of the XLA forms on one device."""
    from deeperspeed_tpu.parallel.mesh import build_mesh
    from deeperspeed_tpu.parallel.topology import ProcessTopology
    cfg = GPTNeoXConfig.tiny()                   # 4 heads
    model = GPTNeoX(config=cfg, use_pallas=False)
    params = model.init_params(jax.random.PRNGKey(9))
    mesh = build_mesh(ProcessTopology(axes=["data", "model"], dims=[4, 2]),
                      devices)
    extra = {"kv_cache_dtype": kv} if kv else {}
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
               for n in (6, 14)]
    ref = InferenceEngine(model, config=engine_config(kernel="xla", **extra),
                          params=params).generate(prompts, max_new_tokens=4)
    tp = InferenceEngine(model, config=engine_config(kernel="pallas",
                                                     **extra),
                         params=params, mesh=mesh)
    assert tp.mp == 2
    assert tp.generate(prompts, max_new_tokens=4) == ref
    report = dispatch_report()["decode_attention"]
    assert report["kv_write"] == report["decode"] == "pallas"
    assert tp.cache.k.sharding.spec[2] == "model" if kv is None else \
        tp.cache.k.data.sharding.spec[2] == "model"


# ---------------------------------------------------------------------------
# grouped KV heads and a window (a planned model's layers)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [None, 20, 16], ids=["full", "w20", "w16"])
@pytest.mark.parametrize("group", [1, 6, 9])
def test_grouped_heads_and_window_kernel_matches_xla(group, window):
    """G = 2 KV heads under 2 * group query heads (6 and 9 a group are
    Laguna's), window off, not page-aligned (20) and page-aligned (16):
    the kernel in interpret mode against the XLA gather, on layer 1 of
    stacked pools, with an inactive row and rows shorter and longer than
    the window."""
    rng = np.random.default_rng(0)
    G, d, ps, n_pages = 2, 16, 8, 6
    q = jnp.asarray(rng.normal(size=(4, G * group, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, 30, G, ps, d)), jnp.float32)
            for _ in range(2))
    table = jnp.asarray(rng.permutation(np.arange(1, 30))[:4 * n_pages]
                        .reshape(4, n_pages), jnp.int32)
    lengths = jnp.asarray([45, 0, 7, 24], jnp.int32)
    got, want = (paged_decode_attention(q, k, v, table, lengths, layer=1,
                                        window=window, backend=b)
                 for b in ("pallas", "xla"))
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    assert not np.asarray(got[1]).any()              # the inactive row
    # brute force for row 0: head h reads KV head h // group, the last
    # `window` positions only
    n = int(lengths[0])
    keys = np.moveaxis(np.asarray(k[1, table[0]]), 1, 0).reshape(G, -1, d)
    vals = np.moveaxis(np.asarray(v[1, table[0]]), 1, 0).reshape(G, -1, d)
    lo = 0 if window is None else max(0, n - window)
    for h in (0, G * group - 1):
        s = keys[h // group, lo:n] @ np.asarray(q[0, h]) / np.sqrt(d)
        p = np.exp(s - s.max())
        np.testing.assert_allclose(got[0, h],
                                   (p / p.sum()) @ vals[h // group, lo:n],
                                   atol=2e-6, rtol=0)


def test_pages_behind_the_window_are_never_read():
    """With a window the work list starts at the page of position
    `length - window`: table entries before it may be anything (the
    engine has given those pages back) and the step count is the live
    pages alone."""
    from deeperspeed_tpu.ops.pallas.decode_attention import decode_steps
    rng = np.random.default_rng(1)
    G, d, ps, window = 2, 16, 8, 16
    q = jnp.asarray(rng.normal(size=(3, 12, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(20, G, ps, d)), jnp.float32)
            for _ in range(2))
    table = np.arange(1, 19, dtype=np.int32).reshape(3, 6)
    lengths = jnp.asarray([45, 17, 3], jnp.int32)
    want = paged_decode_attention(q, k, v, jnp.asarray(table), lengths,
                                  window=window, backend="pallas")
    first = np.maximum(np.asarray(lengths) - window, 0) // ps
    released = table.copy()
    for b in range(3):
        released[b, :first[b]] = 0
    assert (released == 0).sum() == 3
    got = paged_decode_attention(q, k, v, jnp.asarray(released), lengths,
                                 window=window, backend="pallas")
    np.testing.assert_array_equal(got, want)
    n_steps, row, start = decode_steps(lengths, ps, 6, window)
    # rows of 45, 17, 3 tokens: pages 3..5, 0..2, 0 -> 3 + 3 + 1 steps
    assert int(n_steps) == 7 and list(np.asarray(start)) == [0, 3, 6]
    assert int(decode_steps(lengths, ps, 6)[0]) == 6 + 3 + 1


def test_attention_refuses_heads_that_do_not_group():
    q = jnp.zeros((1, 5, 16))
    pool = jnp.zeros((4, 2, 8, 16))
    with pytest.raises(ValueError, match="KV\\s+heads divide"):
        paged_decode_attention(q, pool, pool, jnp.zeros((1, 2), jnp.int32),
                               jnp.ones((1,), jnp.int32))
    with pytest.raises(ValueError, match="window"):
        paged_decode_attention(jnp.zeros((1, 4, 16)), pool, pool,
                               jnp.zeros((1, 2), jnp.int32),
                               jnp.ones((1,), jnp.int32), window=0)
