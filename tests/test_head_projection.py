"""Attention's projection to heads (`gpt_neox._heads_dot`): a plain dot
behind a barrier where the activation is the small operand, XLA's to
fold into a convolution over the heads where the weight is. The form is
a pure function of the two shapes (`autotune.head_projection_plain`);
the arithmetic is the same in both, to the bit."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deeperspeed_tpu
from deeperspeed_tpu.models import gpt_neox as neox
from deeperspeed_tpu.models.gpt_neox import GPTNeoXConfig, LayerSpec
from deeperspeed_tpu.ops import autotune, dispatch_report
from deeperspeed_tpu.ops.pallas.quant_matmul import quantize_weight
from deeperspeed_tpu.parallel.mesh import build_mesh
from deeperspeed_tpu.parallel.topology import ProcessTopology

TINY = GPTNeoXConfig.tiny()          # hidden 64, 4 heads of 16, biases
PLAN = LayerSpec(attn="full", heads=4, rotary_pct=1.0, ffn="dense")
BRANCHES = {
    "fused_bias": TINY,
    "fused_no_bias": dataclasses.replace(TINY, use_bias=False),
    "fused_qk_norm": dataclasses.replace(TINY, norm="rmsnorm",
                                         use_bias=False, qk_norm=True),
    # 4 query heads over 2 KV heads of 16: `q_w` [64, 64], `kv_w` [64, 64]
    "planned_grouped": GPTNeoXConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, attn_head_dim=16, max_seq_len=128,
        use_parallel_residual=False, norm="rmsnorm", use_bias=False,
        hidden_act="silu", ffn_gated=True, ffn_width=96,
        layer_plan=(PLAN,) * 2),
}
B, S = 2, 8                          # 16 rows under a hidden size of 64


def counts():
    return dispatch_report()["attention"]["head_projection"]


def force(monkeypatch, plain):
    monkeypatch.setattr(autotune, "head_projection_plain",
                        lambda rows, k: plain)


def block_of(cfg, dtype, seed=0):
    """(layer params, x [B, S, h], the block's rotary arguments)."""
    key, kx, kb = jax.random.split(jax.random.PRNGKey(seed), 3)
    if cfg.layer_plan:
        params = jax.tree_util.tree_map(
            lambda leaf: leaf[0], neox.init_stack_params(cfg, PLAN, 1, key))
        rot = neox._rotary_cache(cfg, S, spec=PLAN)
    else:
        params = neox.init_block_params(cfg, key)
        rot = neox._rotary_cache(cfg, S)
        if "qkv_b" in params["attn"]:
            # the init's zeros would hide a bias added on the wrong side
            params["attn"]["qkv_b"] = 0.1 * jax.random.normal(
                kb, params["attn"]["qkv_b"].shape)
    params = jax.tree_util.tree_map(lambda leaf: leaf.astype(dtype), params)
    x = jax.random.normal(kx, (B, S, cfg.hidden_size)).astype(dtype)
    return params, x, rot


def qkv_fn(cfg, rot):
    """A FRESH function every call: never a trace cached in the other
    form."""
    return lambda params, x: neox._block_qkv(cfg, params, x, *rot,
                                             cfg.num_heads)


def in_both_forms(monkeypatch, run):
    out = []
    for plain in (False, True):
        force(monkeypatch, plain)
        before = counts()
        out.append(run())
        form = "plain" if plain else "folded"
        other = "folded" if plain else "plain"
        assert counts()[form] > before[form], form
        assert counts()[other] == before[other], other
    return out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("branch", BRANCHES)
def test_both_forms_give_the_same_q_k_v_to_the_bit(monkeypatch, branch,
                                                   dtype):
    cfg = BRANCHES[branch]
    params, x, rot = block_of(cfg, dtype)
    folded, plain = in_both_forms(
        monkeypatch, lambda: jax.jit(qkv_fn(cfg, rot))(params, x))
    G = cfg.kv_heads if cfg.layer_plan else cfg.num_heads
    assert [t.shape for t in plain] == [(B, S, 4, 16)] + [(B, S, G, 16)] * 2
    for name, a, b in zip("qkv", folded, plain):
        assert a.dtype == b.dtype == dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), name)


@pytest.mark.parametrize("branch", BRANCHES)
def test_gradients_through_the_plain_form_are_the_folded_forms(monkeypatch,
                                                               branch):
    cfg = BRANCHES[branch]
    params, x, rot = block_of(cfg, jnp.float32)
    weights = [jax.random.normal(jax.random.PRNGKey(i), (B, S, 1, 16))
               for i in range(3)]

    def grads():
        fn = qkv_fn(cfg, rot)

        def loss(params, x):
            return sum((t * w).sum() for t, w in zip(fn(params, x), weights))
        return jax.jit(jax.grad(loss, argnums=(0, 1)))(params, x)

    folded, plain = in_both_forms(monkeypatch, grads)
    leaves = jax.tree_util.tree_leaves_with_path(folded)
    assert any("_w" in jax.tree_util.keystr(path) and np.abs(leaf).max() > 0
               for path, leaf in leaves)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7),
        folded, plain)


@pytest.mark.parametrize("branch", ["fused_bias", "planned_grouped"])
def test_the_plain_form_batches_under_vmap(monkeypatch, branch):
    cfg = BRANCHES[branch]
    params, x, rot = block_of(cfg, jnp.float32)
    xs = jnp.stack([x, 2.0 * x, -x])
    force(monkeypatch, True)
    fn = qkv_fn(cfg, rot)
    batched = jax.jit(jax.vmap(fn, in_axes=(None, 0)))(params, xs)
    for i in range(3):
        one = jax.jit(fn)(params, xs[i])
        for a, b in zip(batched, one):
            np.testing.assert_array_equal(a[i], b)


@pytest.mark.parametrize("dims", [(8, 1), (4, 2)],
                         ids=["data8", "data4_model2"])
def test_a_train_step_runs_the_plain_form_sharded(monkeypatch, devices,
                                                  dims):
    """The train engine's step at a shape the rule sends to the plain
    form (8 x 4 rows under a hidden size of 64), on the data mesh and
    with the QKV weight's columns split over `model`: the barrier
    differentiates and shards as the dot did, and three steps' losses are
    those of the folded form."""
    assert autotune.head_projection_plain(8 * 4, TINY.hidden_size)
    mesh = build_mesh(ProcessTopology(axes=["data", "model"],
                                      dims=list(dims)), devices)
    toks = np.random.default_rng(3).integers(
        0, TINY.vocab_size, size=(1, 8, 4), dtype=np.int32)

    def losses():
        model = neox.GPTNeoX(TINY)
        engine, *_ = deeperspeed_tpu.initialize(
            model=model, mesh=mesh,
            model_parameters=model.init_params(jax.random.PRNGKey(0)),
            config_params={
                "train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})
        return [float(engine.train_batch(batch=(toks, toks)))
                for _ in range(3)]

    before = counts()
    ruled = losses()
    assert counts()["plain"] > before["plain"]
    assert counts()["folded"] == before["folded"]
    force(monkeypatch, False)
    folded = losses()
    assert ruled[-1] < ruled[0]
    np.testing.assert_allclose(ruled, folded, rtol=1e-6)


# (rows of the activation, the weight's contracting dim, plain?)
CELL_SHAPES = {
    "pythia-1.4b.decode_32": (32, 2048, True),
    "olmoe.decode_32": (32, 2048, True),
    "laguna.decode_32": (32, 3072, True),
    "ouro.decode_16": (16, 2048, True),
    "ouro.prefill_64": (64, 2048, True),
    "ouro.prefill_128": (128, 2048, True),
    "ouro.prefill_256": (256, 2048, True),
    "decode_256_at_hidden_1024": (256, 1024, True),
    "pythia-1.4b.prefill_128": (128, 2048, True),
    "pythia-1.4b.prefill_1536": (1536, 2048, True),
    "laguna.prefill_2048": (2048, 3072, True),
    "rows_equal_to_k": (2048, 2048, False),
    "laguna.prefill_4096": (4096, 3072, False),
    "laguna.prefill_8192": (8192, 3072, False),
    "pythia-410m.train_2k": (16 * 2048, 1024, False),
    "pythia-410m.train_16k": (1 * 16384, 1024, False),
    "pythia-1.4b.train_zero3_4c": (16 * 2048, 2048, False),
    "pythia-1.4b.train_zero3_4c.one_chip": (4 * 2048, 2048, False),
}


@pytest.mark.parametrize("name", CELL_SHAPES)
def test_the_rule_at_the_cells_shapes(name):
    rows, k, plain = CELL_SHAPES[name]
    assert autotune.head_projection_plain(rows, k) is plain


def barriers(jaxpr):
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "optimization_barrier"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += barriers(sub)
    return n


@pytest.mark.parametrize("name,b,s,want", [
    ("decode_32", 32, 1, 1), ("prefill_512", 1, 512, 1),
    ("train_2k", 16, 2048, 0), ("train_16k", 1, 16384, 0)])
@pytest.mark.parametrize("branch", ["fused_bias", "planned_grouped"])
def test_the_jaxpr_holds_a_barrier_only_under_few_rows(branch, name, b, s,
                                                       want):
    """At real widths, from shapes alone: a train step's projection is
    traced as it always was (no barrier in its jaxpr: the same program),
    a decode step's and a prefill bucket's holds one a weight."""
    small = BRANCHES[branch]
    cfg = dataclasses.replace(small, hidden_size=1024, num_heads=16,
                              attn_head_dim=64 if small.layer_plan else 0)
    plan = dataclasses.replace(PLAN, heads=16)
    if cfg.layer_plan:
        cfg = dataclasses.replace(cfg, layer_plan=(plan,) * 2)
        params = jax.eval_shape(lambda: jax.tree_util.tree_map(
            lambda leaf: leaf[0],
            neox.init_stack_params(cfg, plan, 1, jax.random.PRNGKey(0))))
        weights = 2
    else:
        params = jax.eval_shape(
            lambda: neox.init_block_params(cfg, jax.random.PRNGKey(0)))
        weights = 1
    cos = jax.ShapeDtypeStruct((s, 16), jnp.float32)
    x = jax.ShapeDtypeStruct((b, s, 1024), jnp.bfloat16)
    before = counts()
    jaxpr = jax.make_jaxpr(
        lambda params, x, cos, sin: neox._block_qkv(
            cfg, params, x, cos, sin, 16, 16))(params, x, cos, cos)
    assert barriers(jaxpr.jaxpr) == want * weights
    form = "plain" if want else "folded"
    assert counts()[form] == before[form] + weights


def test_a_quantized_weight_passes_through():
    """int8 at rest: the matmul is `quant_matmul` (a kernel the reshape
    cannot enter), so no barrier and no count."""
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 192))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, 64))
    qw = quantize_weight(w)
    before = counts()
    jaxpr = jax.make_jaxpr(neox._heads_dot)(x, qw)
    assert barriers(jaxpr.jaxpr) == 0 and counts() == before
    np.testing.assert_array_equal(neox._heads_dot(x, qw), neox._wmat(x, qw))
    # the same call on the float weight is counted, and plain
    assert barriers(jax.make_jaxpr(neox._heads_dot)(x, w).jaxpr) == 1
    assert counts()["plain"] == before["plain"] + 1
