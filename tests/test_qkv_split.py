"""The fused QKV projection as three dots (`gpt_neox._split_heads_dots`).

Where the tiled flash kernels are about to read the heads in place and
the head dim is under one lane tile, a block projects its ONE `qkv_w`
leaf as three dots against its q, k and v columns
(`ops.autotune.head_projection_split`): the same arithmetic, results that
XLA writes where the kernels read them (tests/test_tpu_compile.py reads
the compiled program for that). Here, on the CPU: the two forms agree, the
rule's truth table, and the counter.

`DS_FLASH_BLOCKS` of 128 makes a sequence of 256 a tiled call (two
blocks), so the kernels run small in interpret mode."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.models import gpt_neox
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.ops import autotune, dispatch_report

fa = importlib.import_module("deeperspeed_tpu.ops.pallas.flash_attention")


@pytest.fixture
def small_blocks(monkeypatch):
    monkeypatch.setenv("DS_FLASH_BLOCKS", "128,128")
    monkeypatch.setenv("DS_FLASH_BWD_BLOCKS", "128,128")


@pytest.fixture
def fused(monkeypatch):
    """Calling it turns the rule off: today's program, whatever the shape."""
    return lambda: monkeypatch.setattr(autotune, "head_projection_split",
                                       lambda d, in_place: False)


def _split_count():
    return dispatch_report()["attention"]["head_projection"]["split"]


def _model(head_dim=64, heads=2, seq=256, use_pallas=True, **cfg):
    cfg = GPTNeoXConfig(vocab_size=128, hidden_size=heads * head_dim,
                        num_layers=2, num_heads=heads, max_seq_len=seq,
                        rotary_pct=0.25, **cfg)
    return GPTNeoX(cfg, use_pallas=use_pallas)


@pytest.mark.parametrize("remat", [None, "attn_residuals"])
@pytest.mark.parametrize("qk_norm", [False, True], ids=["plain", "qk_norm"])
@pytest.mark.parametrize("bias", [True, False], ids=["qkv_b", "no_bias"])
def test_split_loss_and_gradients_equal_the_fused_forms(
        small_blocks, fused, bias, qk_norm, remat):
    """Loss and EVERY gradient (`qkv_w`, `qkv_b`, the norms', the
    embedding's: the input's) of a two-layer model at a head dim of 64,
    bfloat16 parameters: three dots against the weight's slices and one
    dot and its split agree within bfloat16's rounding."""
    model = _model(param_dtype=jnp.bfloat16, use_bias=bias, qk_norm=qk_norm,
                   norm="layernorm" if bias else "rmsnorm")
    if remat:
        model.remat_policy = remat
    params = model.init_params(jax.random.PRNGKey(0))
    if bias:    # a zero bias would hide a wrong slice of it
        params = jax.tree_util.tree_map_with_path(
            lambda path, leaf: jax.random.normal(
                jax.random.PRNGKey(1), leaf.shape, leaf.dtype) * 0.1
            if path[-1].key == "qkv_b" else leaf, params)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 256), 0, 128)
    step = jax.value_and_grad(model.loss_fn)

    before = _split_count()
    loss, grads = jax.jit(lambda p: step(p, (tokens, tokens)))(params)
    assert _split_count() > before
    fused()
    before = _split_count()
    loss_f, grads_f = jax.jit(lambda p: step(p, (tokens, tokens)))(params)
    assert _split_count() == before

    # one `qkv_w` (and `qkv_b`) leaf a block, as a checkpoint holds it
    assert jax.tree_util.tree_structure(grads) == \
        jax.tree_util.tree_structure(params)
    assert ("qkv_b" in grads["blocks"][0]["attn"]) is bias
    assert grads["blocks"][0]["attn"]["qkv_w"].shape == (128, 3 * 128)
    np.testing.assert_allclose(float(loss), float(loss_f), rtol=2e-3)
    flat, flat_f = (jax.tree_util.tree_leaves_with_path(g)
                    for g in (grads, grads_f))
    assert [p for p, _ in flat] == [p for p, _ in flat_f]
    for (path, g), (_, g_f) in zip(flat, flat_f):
        g, g_f = (np.asarray(x, np.float32) for x in (g, g_f))
        assert np.isfinite(g).all(), path
        scale = np.abs(g_f).max() + 1e-6
        # a bfloat16 rounding or two of the leaf's largest element
        assert np.abs(g - g_f).max() <= 2e-2 * scale, \
            (jax.tree_util.keystr(path), np.abs(g - g_f).max(), scale)
        assert np.abs(g_f).max() > 0 or "bias" in jax.tree_util.keystr(path)


def test_the_slices_are_the_fused_dots_columns():
    """`_split_heads_dots` against the fused dot, its bias and `jnp.split`
    in float32: q, k and v are the same columns of the same leaf."""
    B, S, K, H, D = 2, 8, 32, 4, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (B, S, K))
    a = {"qkv_w": jax.random.normal(keys[1], (K, 3 * H * D)),
         "qkv_b": jax.random.normal(keys[2], (3 * H * D,))}
    want = jnp.split((x @ a["qkv_w"] + a["qkv_b"]).reshape(B, S, H, 3 * D),
                     3, axis=-1)
    for got, ref in zip(gpt_neox._split_heads_dots(x, a, H, D), want):
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    del a["qkv_b"]
    want = jnp.split((x @ a["qkv_w"]).reshape(B, S, H, 3 * D), 3, axis=-1)
    for got, ref in zip(gpt_neox._split_heads_dots(x, a, H, D), want):
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def _train_step(head_dim=64, seq=256, use_pallas=True):
    model = _model(head_dim=head_dim, seq=seq, use_pallas=use_pallas)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, seq), jnp.int32)
    return lambda: jax.make_jaxpr(jax.value_and_grad(model.loss_fn))(
        params, (tokens, tokens))


def _serving_prefill():
    """One block over segment ids (a serving prefill's pad rows, a packed
    batch's documents): the segmented forward, which moves its heads."""
    model = _model()
    cfg = model.config
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, 256, cfg.hidden_size), jnp.bfloat16)
    seg = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    cos_sin = gpt_neox._rotary_cache(cfg, 256)

    def prefill(bp, x, seg):
        return gpt_neox._block_core(cfg, bp, x, cos_sin, True, 1,
                                    lambda t: t, return_kv=True,
                                    segment_ids=seg)
    return lambda: jax.make_jaxpr(prefill)(params["blocks"][0], x, seg)


def _decode_step():
    model = _model()
    cfg = model.config
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((4, 1, cfg.hidden_size), jnp.bfloat16)
    cos, sin, rot = gpt_neox._rotary_cache(cfg, 256)

    def decode(bp, x):
        return gpt_neox._block_qkv(cfg, bp, x, cos[:1], sin[:1], rot,
                                   cfg.num_heads)
    return lambda: jax.make_jaxpr(decode)(params["blocks"][0], x)


# (name, a traced call, is the projection split?)
TRUTH_TABLE = [
    ("train_d64", lambda: _train_step(64), True),
    ("train_d128", lambda: _train_step(128), False),
    ("serving_prefill", _serving_prefill, False),
    ("decode_step", _decode_step, False),
    ("single_block", lambda: _train_step(64, seq=128), False),
    ("xla_fallback", lambda: _train_step(64, use_pallas=False), False),
]


@pytest.mark.parametrize("name,build,split", TRUTH_TABLE,
                         ids=[row[0] for row in TRUTH_TABLE])
def test_which_calls_split_the_projection(small_blocks, fused, name, build,
                                          split):
    """The rule's truth table: the tiled training call at a head dim under
    a lane tile splits; a head dim of 128, a serving prefill (segments),
    a decode step, a call of one block and the XLA fallback trace TODAY's
    program, the jaxpr of the rule switched off, to the character."""
    trace = build()
    before = _split_count()
    text = str(trace())
    assert _split_count() - before == (2 if split else 0)   # a layer each
    fused()
    before = _split_count()
    assert (str(trace()) != text) is split
    assert _split_count() == before


def test_head_projection_split_is_a_fact_of_its_two_arguments():
    assert autotune.head_projection_split(64, True)
    assert autotune.head_projection_split(96, True)
    assert not autotune.head_projection_split(128, True)
    assert not autotune.head_projection_split(256, True)
    assert not autotune.head_projection_split(64, False)


@pytest.mark.parametrize("shape,g,blocks,want", [
    ((16, 2048, 16, 64), 16, None, True),       # train_2k
    ((1, 16384, 16, 64), 16, None, True),       # train_16k
    ((4, 2048, 16, 128), 16, None, True),       # in place; the rule says no
    ((4, 1024, 16, 64), 16, None, False),       # one block
    ((4, 2048, 16, 64), 4, None, False),        # grouped KV heads
    ((4, 2048, 16, 80), 16, None, False),       # no kernel at this head dim
    ((4, 1, 16, 64), 16, None, False),          # a decode step
], ids=["train_2k", "train_16k", "d128", "one_block", "grouped", "d80",
        "decode"])
def test_tiled_in_place_follows_the_forwards_own_choice(shape, g, blocks,
                                                        want):
    assert fa.tiled_in_place(shape, g) is want


def test_the_report_counts_the_three_forms(small_blocks):
    """`dispatch_report()["attention"]["head_projection"]`: plain | folded
    | split, the last bumped once a traced fused projection that ran as
    three dots."""
    assert set(dispatch_report()["attention"]["head_projection"]) == \
        {"plain", "folded", "split"}
    before = dict(dispatch_report()["attention"]["head_projection"])
    _train_step(64)()
    after = dispatch_report()["attention"]["head_projection"]
    assert after["split"] - before["split"] == 2
    assert after["folded"] == before["folded"]
    assert after["plain"] == before["plain"]
    _decode_step()()
    assert dispatch_report()["attention"]["head_projection"]["plain"] == \
        after["plain"] + 1


def test_a_quantized_weight_keeps_its_kernel():
    """A serving-time `QuantizedWeight` is never sliced: `_qkv_split` says
    no whatever the call."""
    from deeperspeed_tpu.ops.pallas.quant_matmul import quantize_weight
    w = quantize_weight(jnp.ones((128, 384), jnp.bfloat16))
    shape = (2, 2048, 2, 64)
    assert gpt_neox._qkv_split({"qkv_w": jnp.ones((128, 384))}, shape,
                               None, use_pallas=True)
    assert not gpt_neox._qkv_split({"qkv_w": w}, shape, None,
                                   use_pallas=True)
    assert not gpt_neox._qkv_split({"qkv_w": jnp.ones((128, 384))}, shape,
                                   lambda *a: None, use_pallas=True)
