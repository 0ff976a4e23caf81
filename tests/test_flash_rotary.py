"""The rotary inside the training flash kernels
(`flash_attention(..., rotary=(cos, sin, rot_dim))`).

Where the training call runs its tiled forward and its fused backward on
the heads in place, the kernels take the UN-rotated projections and the
rotary's table: a q^T or k^T block is rotated where it is loaded, and dq
and dk are rotated back where they are stored, so no pass over q, k, dq or
dk is left in the step (tests/test_tpu_compile.py reads the compiled
program for that). Here, on the CPU in the interpreter: the call agrees
with `apply_rotary` followed by today's call for out, dq, dk and dv; the
rule's two halves (`flash_attention.rotates_in_kernel`, a fact of the
shape, and `gpt_neox._rotary_in_kernel`, what the block alone knows) say
no where they must; a block on either side of each boundary computes what
the rule switched off computes; and `dispatch_report()["flash"]["rotary"]`
counts the side taken.

`DS_FLASH_BLOCKS` of 128 makes a sequence of 256 a tiled call (two
blocks), so the kernels run small."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.models import gpt_neox
from deeperspeed_tpu.models.gpt_neox import (GPTNeoX, GPTNeoXConfig,
                                             LayerSpec, apply_rotary)
from deeperspeed_tpu.ops import autotune, dispatch_report

fa = importlib.import_module("deeperspeed_tpu.ops.pallas.flash_attention")


def _tables(d, rot, s):
    return gpt_neox._rotary_table(
        *gpt_neox.rope_inv_freq(d, rot / d, 10000.0), s, jnp.float32)


def _qkv(s, d, dtype, heads=2):
    keys = jax.random.split(jax.random.PRNGKey(d + s), 4)
    return [jax.random.normal(key, (1, s, heads, d), jnp.float32).astype(dtype)
            for key in keys]


def _out_and_grads(attn, q, k, v, w):
    """out and (dq, dk, dv) of `attn` under the cotangent `w`, float32."""
    def loss(q, k, v):
        return (attn(q, k, v).astype(jnp.float32)
                * w.astype(jnp.float32)).sum()
    out = attn(q, k, v)
    return [np.asarray(x, np.float32)
            for x in (out, *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))]


def _both_forms(d, rot, blocks, dtype, s=512):
    """The kernels' rotary and the XLA rotary in front of today's call, on
    the same q, k, v and cotangent."""
    q, k, v, w = _qkv(s, d, dtype)
    cos, sin, _ = _tables(d, rot, s)
    pin = (True, None, *blocks, blocks)

    def in_kernel(q, k, v):
        return fa.flash_attention(q, k, v, *pin, rotary=(cos, sin, rot))

    def in_xla(q, k, v):
        return fa.flash_attention(*apply_rotary(q, k, cos, sin, rot), v, *pin)

    return (_out_and_grads(in_kernel, q, k, v, w),
            _out_and_grads(in_xla, q, k, v, w))


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128)],
                         ids=["equal_blocks", "two_k_blocks_a_q_block"])
@pytest.mark.parametrize("d,rot", [(64, 16), (128, 32), (64, 64)],
                         ids=["d64_rot16", "d128_rot32", "d64_rot64"])
def test_the_kernels_rotary_is_the_xla_rotarys_arithmetic(d, rot, blocks):
    """out, dq, dk and dv in float32, where neither form rounds: the
    kernel's `x1 c - x2 s`, `x2 c + x1 s` on transposed blocks and its
    transpose on dq and dk are `apply_rotary` and ITS transpose."""
    before = dict(fa._ROTARY)
    got, want = _both_forms(d, rot, blocks, jnp.float32)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5, err_msg=name)
        assert np.abs(b).max() > 1e-2, name
    # a forward for out, and the rule's forward under `grad`; one XLA pair
    assert fa._ROTARY["in_kernel"] - before["in_kernel"] == 2
    assert fa._ROTARY["xla"] - before["xla"] == 2


@pytest.mark.parametrize("d,rot", [(64, 16), (128, 32)],
                         ids=["d64_rot16", "d128_rot32"])
def test_bfloat16_blocks_round_once(d, rot):
    """On bfloat16 operands the kernel rotates in float32 against float32
    tables and rounds once, where the XLA form multiplies by tables
    rounded to bfloat16: both stay within bfloat16's rounding of the
    float32 arithmetic, and of each other."""
    got, want = _both_forms(d, rot, (128, 128), jnp.bfloat16)
    exact, _ = _both_forms(d, rot, (128, 128), jnp.float32)
    for name, a, b, e in zip(("out", "dq", "dk", "dv"), got, want, exact):
        scale = np.abs(e).max()
        assert np.abs(a - b).max() <= 3e-2 * scale, name
        # the inputs' own rounding to bfloat16 is in both
        assert np.abs(a - e).max() <= 4e-2 * scale, name


@pytest.mark.parametrize("why,d,rot,s,blocks,bwd", [
    ("q_block_under_k_block", 64, 16, 512, (128, 256), (128, 128)),
    ("bwd_q_block_under_k_block", 64, 16, 512, (128, 128), (128, 256)),
    ("one_block", 64, 16, 128, (128, 128), (128, 128)),
    ("one_backward_block", 64, 16, 256, (128, 128), (256, 256)),
    ("rot_dim_not_whole_tiles", 64, 8, 512, (128, 128), (128, 128)),
    ("rot_dim_over_head_dim", 64, 128, 512, (128, 128), (128, 128)),
], ids=lambda x: x if isinstance(x, str) else None)
def test_a_call_the_kernels_cannot_rotate_raises(why, d, rot, s, blocks, bwd):
    q, k, v, _ = _qkv(s, d, jnp.float32)
    cos, sin, _ = _tables(d, min(rot, d), s)
    with pytest.raises(ValueError, match="rotates_in_kernel"):
        fa.flash_attention(q, k, v, True, None, *blocks, bwd,
                           rotary=(cos, sin, rot))


def test_per_row_positions_raise():
    q, k, v, _ = _qkv(512, 64, jnp.float32)
    cos, sin, _ = _tables(64, 16, 512)
    with pytest.raises(ValueError, match="rotates_in_kernel"):
        fa.flash_attention(q, k, v, True, None, 128, 128, (128, 128),
                           rotary=(cos[None], sin[None], 16))


@pytest.mark.parametrize("shape,g,rot,dtype,want", [
    ((16, 2048, 16, 64), 16, 16, jnp.bfloat16, True),      # train_2k
    ((1, 16384, 16, 64), 16, 16, jnp.bfloat16, True),      # train_16k
    ((4, 2048, 16, 128), 16, 32, jnp.bfloat16, True),      # the 4-chip shard
    ((1, 32768, 16, 64), 16, 64, jnp.bfloat16, True),      # largest slabs
    ((1, 32768, 16, 64), 16, 16, jnp.float32, False),      # k over its slab
    ((1, 65536, 16, 64), 16, 16, jnp.bfloat16, False),     # two-kernel bwd
    ((1, 32768, 16, 128), 16, 32, jnp.bfloat16, False),    # two-kernel bwd
    ((4, 1024, 16, 64), 16, 16, jnp.bfloat16, False),      # one block
    ((4, 2048, 16, 64), 4, 16, jnp.bfloat16, False),       # grouped KV heads
    ((4, 2048, 16, 64), 16, 24, jnp.bfloat16, False),      # half a tile over
    ((4, 2048, 16, 80), 16, 16, jnp.bfloat16, False),      # no kernel at all
    ((4, 1, 16, 64), 16, 16, jnp.bfloat16, False),         # a decode step
], ids=["train_2k", "train_16k", "zero3_shard", "largest_slabs",
        "k_slab_over", "dq_slab_over_d64", "dq_slab_over_d128", "one_block",
        "grouped", "rot_24", "d80", "decode"])
def test_rotates_in_kernel_is_a_fact_of_the_shape(shape, g, rot, dtype, want):
    assert fa.rotates_in_kernel(shape, g, rot, dtype) is want


def test_a_head_whose_k_is_turned_every_step_keeps_the_xla_rotary(
        monkeypatch):
    """The forward rotates a k block where it turns it ONCE, in the row
    that covers its positions; a head whose k is over the slab's budget
    turns a block every step, in every row: the rule says no."""
    assert fa.rotates_in_kernel((1, 2048, 2, 64), 2, 16)
    monkeypatch.setattr(autotune, "_FLASH_K_SLAB_BUDGET", 0)
    assert not fa.rotates_in_kernel((1, 2048, 2, 64), 2, 16)
    assert not fa.rotates_in_kernel((1, 2048, 2, 64), 2, 16, causal=False)


# ---------------------------------------------------------------------------
# the block: both sides of each boundary of the rule
# ---------------------------------------------------------------------------

@pytest.fixture
def small_blocks(monkeypatch):
    monkeypatch.setenv("DS_FLASH_BLOCKS", "128,128")
    monkeypatch.setenv("DS_FLASH_BWD_BLOCKS", "128,128")


@pytest.fixture
def rule_off(monkeypatch):
    """Calling it switches the rule off: the parent's program."""
    return lambda: monkeypatch.setattr(fa, "rotates_in_kernel",
                                       lambda *a, **k: False)


def _counted(fn):
    before = dict(fa._ROTARY)
    result = fn()
    return result, {side: fa._ROTARY[side] - before[side] for side in before}


def _plain(seq=256, head_dim=64, rotary_pct=0.25, **cfg):
    cfg = GPTNeoXConfig(vocab_size=128, hidden_size=2 * head_dim,
                        num_layers=1, num_heads=2, max_seq_len=seq,
                        rotary_pct=rotary_pct, **cfg)
    model = GPTNeoX(cfg, use_pallas=True)
    params = model.init_params(jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map_with_path(   # a zero bias hides its sum
        lambda path, leaf: jax.random.normal(
            jax.random.PRNGKey(1), leaf.shape, leaf.dtype) * 0.1
        if path[-1].key == "qkv_b" else leaf, params)
    return cfg, params["blocks"][0]


def _planned(attn, seq=256):
    """One layer of a plan: 2 query heads of 64 over ONE KV head."""
    spec = LayerSpec(attn=attn, heads=2, rotary_pct=0.25)
    cfg = GPTNeoXConfig(
        vocab_size=128, hidden_size=128, num_layers=1, num_heads=2,
        num_kv_heads=1, attn_head_dim=64, max_seq_len=seq, norm="rmsnorm",
        use_bias=False, use_parallel_residual=False, hidden_act="silu",
        ffn_gated=True, ffn_width=64, layer_plan=(spec,), attn_window=128)
    params = GPTNeoX(cfg, use_pallas=True).init_params(jax.random.PRNGKey(0))
    (stack,) = params["stacks"].values()
    return cfg, jax.tree_util.tree_map(lambda leaf: leaf[0], stack), spec


def _block(cfg, bp, seq=256, spec=None, grads=True, **kw):
    """Loss and gradients (or the results alone) of `_block_core` on one
    layer `bp`, as a function of nothing: traced and run at each call."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, seq, cfg.hidden_size))
    cos_sin = gpt_neox._rotary_cache(cfg, seq, spec=spec)

    def run(bp, x):
        out = gpt_neox._block_core(cfg, bp, x, cos_sin, True, 1, lambda t: t,
                                   spec=spec, **kw)
        return out if grads else jax.tree_util.tree_leaves(out)

    def loss(bp, x):
        return (run(bp, x) ** 2).mean()

    if grads:
        return lambda: jax.tree_util.tree_leaves(
            jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(bp, x))
    # a fresh function a call: never another rule's cached trace
    return lambda: jax.jit(lambda bp, x: run(bp, x))(bp, x)


def _planned_block(attn):
    cfg, bp, spec = _planned(attn)
    return _block(cfg, bp, spec=spec, grads=False)


def _ring_stand_in(q, k, v):
    return gpt_neox.causal_attention(q, k, v, use_pallas=True)


def _segments(seq=256):
    return jnp.concatenate([jnp.full((2, seq // 2), 1, jnp.int32),
                            jnp.full((2, seq // 2), 2, jnp.int32)], axis=1)


# (name, the block's call, layers' rotaries counted: in the kernels | XLA)
BOUNDARIES = [
    ("training", lambda: _block(*_plain()), "in_kernel"),
    ("training_d128_rot32", lambda: _block(*_plain(head_dim=128)),
     "in_kernel"),
    ("training_full_rotary", lambda: _block(*_plain(rotary_pct=1.0)),
     "in_kernel"),
    ("return_kv", lambda: _block(*_plain(), grads=False, return_kv=True),
     "xla"),
    ("segment_ids", lambda: _block(*_plain(), segment_ids=_segments()),
     "xla"),
    ("attn_fn", lambda: _block(*_plain(), attn_fn=_ring_stand_in), "xla"),
    ("one_block", lambda: _block(*_plain(seq=128), seq=128), "xla"),
    ("rot_dim_8", lambda: _block(*_plain(rotary_pct=0.125)), "xla"),
    ("window", lambda: _planned_block("window"), "xla"),
    ("grouped_kv_heads", lambda: _planned_block("full"), "xla"),
]


@pytest.mark.parametrize("name,build,side", BOUNDARIES,
                         ids=[row[0] for row in BOUNDARIES])
def test_a_block_on_each_side_of_the_rule(small_blocks, rule_off, name,
                                          build, side):
    """`_block_core`, loss and gradients (the results alone where the call
    has no backward): the training call counts its rotary in the kernels
    and agrees with the rule switched off (the parent's form: XLA's
    rotary, then today's kernels) in every output and gradient; across
    each boundary (the caller wants the rotated k; per-row positions; an
    `attn_fn`; a call of one block; `rot_dim` not whole sublane tiles; a
    window; grouped KV heads) the block counts an XLA rotary and computes
    the parent's numbers to the bit."""
    run = build()
    got, counted = _counted(run)
    assert counted["in_kernel" if side == "xla" else "xla"] == 0, counted
    assert counted[side] >= 1, counted
    rule_off()
    want, counted = _counted(run)
    assert counted["in_kernel"] == 0 and counted["xla"] >= 1, counted
    assert len(got) == len(want)
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all()
        if side == "xla":
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-4,
                                       atol=1e-5 * np.abs(b).max() + 1e-7)


def test_the_rule_asks_the_route_and_the_caller(small_blocks):
    """`_rotary_in_kernel`'s own terms, one at a time."""
    attn = {"qkv_w": jnp.ones((128, 384))}
    cos, _, rot = _tables(64, 16, 256)
    shape, args = (2, 256, 2, 64), (jnp.bfloat16, cos, rot)

    def says(attn=attn, cos=cos, attn_fn=None, return_kv=False, **call):
        return gpt_neox._rotary_in_kernel(
            attn, shape, args[0], cos, rot, attn_fn, return_kv,
            **dict({"use_pallas": True}, **call))

    assert says()
    assert not says(return_kv=True)
    assert not says(attn_fn=_ring_stand_in)
    assert not says(cos=jnp.stack([cos, cos]))
    assert not says(attn={"q_w": attn["qkv_w"]})
    assert not says(use_pallas=False)
    assert not says(segment_ids=_segments())
    assert not says(window=128)
    assert not says(block=4)
    assert not says(sm_scale=0.5)


def test_the_report_counts_both_sides(small_blocks):
    """`dispatch_report()["flash"]["rotary"]`: a traced train step counts
    its layers' rotaries in the kernels and none in XLA; a decode step's
    projection counts one in XLA."""
    assert set(dispatch_report()["flash"]["rotary"]) == {"in_kernel", "xla"}
    cfg = GPTNeoXConfig(vocab_size=128, hidden_size=128, num_layers=2,
                        num_heads=2, max_seq_len=256, rotary_pct=0.25)
    model = GPTNeoX(cfg, use_pallas=True)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    _, counted = _counted(lambda: jax.make_jaxpr(
        jax.value_and_grad(model.loss_fn))(params, (tokens, tokens)))
    assert counted["xla"] == 0 and counted["in_kernel"] >= 2, counted
    cos, sin, rot = gpt_neox._rotary_cache(cfg, 256)
    x = jax.ShapeDtypeStruct((4, 1, 128), jnp.float32)
    _, counted = _counted(lambda: jax.make_jaxpr(
        lambda bp, x: gpt_neox._block_qkv(cfg, bp, x, cos[:1], sin[:1], rot,
                                          2))(params["blocks"][0], x))
    assert counted == {"in_kernel": 0, "xla": 1}
