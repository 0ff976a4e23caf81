"""GLM-MoE-lite (GLM-4.7-Flash: latent attention through a head-less page
kind, a sigmoid router whose bias chooses and does not weigh, a shared
expert, a next-token-prediction block) on the normal path against the
plain reference (`benchmarks/reference/glm_moe_lite.py`), at a small size
on the CPU with the published RATIOS: hidden 64, 4 heads of nope 24 !=
rope 8 != v 32, ranks 24 (q) and 32 (kv), so a cache row of 40 features;
layer 0 dense, two expert layers of 8 experts, 2 a token, one shared;
page 8 (the least the `inference` block takes); one nextn block.

Both sides compute in float32, the program with absorbed attention
through the latent pages where the reference expands, so the tolerances
are those of float32 rounding in another order of summation.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.families import glm_moe_lite as family
from benchmarks.reference import glm_moe_lite as reference
from deeperspeed_tpu.inference import InferenceEngine
from deeperspeed_tpu.inference.kv_cache import PagedKVCache
from deeperspeed_tpu.models import gpt_neox as neox
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, LayerSpec
from deeperspeed_tpu.moe.layer import moe_ffn_dropless
from deeperspeed_tpu.ops.pallas import decode_attention
from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError
from tests.model.references import jitted, reference_rows

VOCAB, PAGE = 128, 8
# float32 rounding through three layers (and the nextn block) on logits of
# size ~1; a dropped bias or norm moves them by far more (asserted below)
ATOL = 1e-4


def conf(layers=3, nextn=1, **over):
    return {
        "family": "glm_moe_lite", "model_type": "glm4_moe_lite",
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 64,
        "intermediate_size": 160, "max_position_embeddings": 256,
        "moe_intermediate_size": 48, "topk_method": "noaux_tc",
        "norm_topk_prob": True, "num_attention_heads": 4, "n_group": 1,
        "topk_group": 1, "n_routed_experts": 8, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 2,
        "first_k_dense_replace": 1, "num_hidden_layers": layers,
        "num_key_value_heads": 4, "num_nextn_predict_layers": nextn,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-5,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 24,
        "kv_lora_rank": 32, "qk_nope_head_dim": 24, "qk_rope_head_dim": 8,
        "v_head_dim": 32, "vocab_size": VOCAB, **over}


def perturbed(params, seed=1):
    """Norm scales away from their init of 1, so a misplaced one shows."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 1000))

    def move(path, p):
        name = jax.tree_util.keystr(path)
        if "scale" in name or "_norm" in name:
            return p + 0.1 * jax.random.normal(next(keys), p.shape)
        return p
    return jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def setup():
    c = conf()
    model = family.build_model(c, "float32", {"use_pallas": False})
    params = perturbed(model.init_params(jax.random.PRNGKey(0)))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 0, VOCAB)
    return c, model, params, tokens


def engine_for(model, params, **over):
    inference = {"enabled": True, "page_size": PAGE, "num_pages": 64,
                 "max_seq_len": 128, "max_batch_size": 4,
                 "token_budget": 128, "prefill_lengths": [16, 32],
                 "prefill_batch_sizes": [1, 2],
                 "decode_batch_sizes": [4], **over}
    return InferenceEngine(model, config={"inference": inference},
                           params=params)


def with_leaf(params, stack, group, leaf, fn):
    """`params` with one leaf of one kind's stack replaced."""
    stacks = dict(params["stacks"])
    kind = dict(stacks[stack])
    kind[group] = dict(kind[group], **{leaf: fn(kind[group][leaf])})
    stacks[stack] = kind
    return dict(params, stacks=stacks)


# ---------------------------------------------------------------------------
# the model's forward against the reference
# ---------------------------------------------------------------------------

def test_the_plan_the_stacks_and_the_parameter_count(setup):
    c, model, params, _ = setup
    cfg = model.config
    assert [s.kind for s in cfg.layer_plan] == [
        "latent4.dense", "latent4.experts", "latent4.experts"]
    assert cfg.cache_layers("latent") == 3 and cfg.latent_width == 40
    attn = params["stacks"]["latent4.experts"]["attn"]
    assert {k: v.shape[1:] for k, v in attn.items()} == {
        "q_a": (64, 24), "q_a_norm": (24,), "q_b": (24, 4 * 32),
        "kv_a": (64, 40), "kv_a_norm": (32,), "kv_b": (32, 4 * 56),
        "out_w": (4 * 32, 64)}
    mlp = params["stacks"]["latent4.experts"]["mlp"]
    assert mlp["gate_bias"].shape == (2, 8)
    # a bias of zeros would hide a bias that is dropped or leaks
    assert float(jnp.abs(mlp["gate_bias"]).min()) > 0
    assert set(params["mtp"]) == {"hnorm", "enorm", "proj", "block",
                                  "final_ln"}
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == cfg.num_params() == reference.num_params(c)
    served = conf(nextn=0)
    assert family.model_config(served, "float32").num_params() == \
        reference.num_params(served) < n


def test_the_published_configuration_counts_its_published_parameters():
    """The cell's configuration: 21,763,328 parameters a layer in the
    attention and the layer's two norms, 84,677,888 in layer 0,
    635,311,424 in an expert layer (ISSUE 35)."""
    import json
    import os
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "glm-4.7-flash.json")) as f:
        c = json.load(f)
    cfg = family.model_config(c, "bfloat16", 16896)
    assert cfg._latent_params(20) + 2 * 2048 == 21763328
    assert cfg.num_params() == reference.num_params(c) == \
        2 * 154880 * 2048 + 2048 + 84677888 + 5 * 635311424 == \
        c["assumed"]["num_parameters_at_6_layers"]
    assert cfg.latent_width == 576


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_logits_agree_with_the_reference(setup, use_pallas):
    c, model, params, tokens = setup
    run = GPTNeoX(model.config, use_pallas=use_pallas)
    with jax.default_matmul_precision("highest"):
        got = jitted(run.apply)(params, tokens)
    want = jitted(reference.logits, c)(params, tokens)
    np.testing.assert_allclose(got, want,
                               atol=ATOL, rtol=0)


def test_mtp_logits_agree_with_the_reference(setup):
    c, model, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        got = jitted(model.mtp_logits)(params, tokens)
    want = jitted(reference.mtp_logits, c)(params, tokens)
    assert got.shape == want.shape == (2, 23, VOCAB)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


def test_the_tolerance_refuses_what_is_dropped_or_misplaced(setup):
    """Each of the block's distinctive parts, taken out of the PROGRAM's
    weights, moves the logits by far more than the tolerance: the
    comparison would see a norm that is skipped, a rotary part that is
    not rotated and a bias that is ignored."""
    c, model, params, tokens = setup
    want = jitted(reference.logits, c)(params, tokens)

    def worst(p):
        with jax.default_matmul_precision("highest"):
            got = jitted(model.apply)(p, tokens)
            return float(jnp.max(jnp.abs(got - want)))

    ones = with_leaf(params, "latent4.experts", "attn", "kv_a_norm",
                     jnp.ones_like)
    assert worst(ones) > 10 * ATOL
    no_bias = with_leaf(params, "latent4.experts", "mlp", "gate_bias",
                        jnp.zeros_like)
    assert worst(no_bias) > 10 * ATOL
    # the rope columns of kv_a swapped: the rotary pairing is a fact
    swapped = with_leaf(params, "latent4.dense", "attn", "kv_a",
                        lambda w: w.at[..., 32:].set(w[..., :31:-1]))
    assert worst(swapped) > 10 * ATOL


# ---------------------------------------------------------------------------
# the router: the bias chooses and does not weigh
# ---------------------------------------------------------------------------

def test_the_bias_picks_and_is_no_part_of_the_weights(setup):
    c, model, params, _ = setup
    mlp = jax.tree_util.tree_map(lambda a: a[0],
                                 params["stacks"]["latent4.experts"]["mlp"])
    m = jax.random.normal(jax.random.PRNGKey(5), (64, 64))
    with jax.default_matmul_precision("highest"):
        top_e, top_w = reference.route(c, mlp, m)
        plain_e, _ = reference.route(
            c, dict(mlp, gate_bias=jnp.zeros_like(mlp["gate_bias"])), m)
        scores = jax.nn.sigmoid(m @ mlp["gate"])
    # the test data holds tokens whose kept set the bias changes
    differ = np.any(np.sort(top_e, -1) != np.sort(plain_e, -1), axis=-1)
    assert differ.sum() >= 4
    # the kept weights are the chosen experts' own scores, renormalised
    # and scaled by 1.8: they sum to 1.8 and carry no bias
    kept = np.take_along_axis(np.asarray(scores), np.asarray(top_e), -1)
    np.testing.assert_allclose(top_w, 1.8 * kept / kept.sum(-1,
                                                            keepdims=True),
                               atol=1e-6)
    # the program's layer against the reference's, token by token
    with jax.default_matmul_precision("highest"):
        y, stats = moe_ffn_dropless(mlp, m, 2, norm_topk_prob=True,
                                    scale=1.8, score="sigmoid")
        want = reference.moe_layer(c, mlp, m, shared=False)
    np.testing.assert_allclose(y, want, atol=1e-5, rtol=0)
    # stats keep their meaning: f the share of routed pairs, P a mean of
    # scores that sum to one a token
    counts = np.bincount(np.asarray(top_e).ravel(), minlength=8)
    np.testing.assert_allclose(stats[0], counts / counts.sum(), atol=1e-6)
    assert abs(float(stats[1].sum()) - 1.0) < 1e-5


def test_the_shares_add_up_to_the_uncut_layer(setup):
    """`moe_held` over 4 disjoint ranges of the 8 experts: the parts, the
    shared expert counted once, add up to the uncut reference layer."""
    c, model, params, _ = setup
    mlp = jax.tree_util.tree_map(lambda a: a[1],
                                 params["stacks"]["latent4.experts"]["mlp"])
    m = jax.random.normal(jax.random.PRNGKey(4), (40, 64))
    with jax.default_matmul_precision("highest"):
        want = jitted(reference.moe_layer, c)(mlp, m)
        total = neox._gated_mlp(m, mlp["shared_in"], mlp["shared_out"],
                                jax.nn.silu)
        rows = 0
        for lo, hi in ((0, 2), (2, 4), (4, 6), (6, 8)):
            share = dict(mlp, w_in=mlp["w_in"][lo:hi],
                         w_out=mlp["w_out"][lo:hi])
            y, stats = jitted(moe_ffn_dropless, top_k=2, norm_topk_prob=True,
                              held=(lo, hi), scale=1.8,
                              score="sigmoid")(share, m)
            ref_share = jitted(reference.moe_layer, c, held=(lo, hi),
                               shared=False)(mlp, m)
            np.testing.assert_allclose(y, ref_share, atol=1e-5, rtol=0)
            total = total + y
            rows += float(stats[2, lo:hi].sum())
    np.testing.assert_allclose(total, want, atol=1e-5, rtol=0)
    assert rows == 40 * 2                # every routed pair is held once


# ---------------------------------------------------------------------------
# the loss and its gradient
# ---------------------------------------------------------------------------

def test_the_loss_and_four_gradients_agree_with_the_reference(setup):
    c, model, params, tokens = setup
    tokens = tokens[:1, :16]      # one row: the reference traces a row at a time
    weight = family.MTP_LOSS_WEIGHT
    assert model.config.mtp_loss_weight == weight

    def leaves(p):
        experts = p["stacks"]["latent4.experts"]
        return {"q_b": experts["attn"]["q_b"],
                "kv_b": experts["attn"]["kv_b"],
                "router": experts["mlp"]["gate"],
                "mtp_proj": p["mtp"]["proj"]}

    def put(x):
        p = with_leaf(params, "latent4.experts", "attn", "q_b",
                      lambda _: x["q_b"])
        p = with_leaf(p, "latent4.experts", "attn", "kv_b",
                      lambda _: x["kv_b"])
        p = with_leaf(p, "latent4.experts", "mlp", "gate",
                      lambda _: x["router"])
        return dict(p, mtp=dict(p["mtp"], proj=x["mtp_proj"]))

    with jax.default_matmul_precision("highest"):
        got, got_grad = jax.jit(jax.value_and_grad(
            lambda x: model.loss_fn(put(x), (tokens, tokens))))(
            leaves(params))
    want, want_grad = jax.jit(jax.value_and_grad(
        lambda x: reference.loss(c, put(x), tokens, weight)))(leaves(params))
    assert abs(float(got) - float(want)) < ATOL
    # the MTP term is there: without it the loss is smaller by about
    # weight * ln(vocab)
    plain = jitted(reference.loss, dict(c, num_nextn_predict_layers=0))(
        params, tokens, weight)
    assert float(want) - float(plain) > 0.5 * weight * np.log(VOCAB)
    for name in got_grad:
        scale = float(jnp.max(jnp.abs(want_grad[name])))
        assert scale > 0, name
        np.testing.assert_allclose(got_grad[name], want_grad[name],
                                   atol=ATOL * max(scale, 1.0), rtol=0,
                                   err_msg=name)


# ---------------------------------------------------------------------------
# serving: prefill (expanded) then decode (absorbed) through latent pages
# ---------------------------------------------------------------------------

def _served_logit_shortfall(c, params, requests):
    worst = 0.0
    for r in requests:
        lg = reference_rows(reference, c, params,
                            list(r.prompt) + list(r.generated), 512)
        at = len(r.prompt) - 1 + np.arange(len(r.generated))
        got = lg[at, np.asarray(r.generated)]
        worst = max(worst, float(np.max(lg[at].max(-1) - got)))
    return worst


@pytest.mark.parametrize("kernel", ["auto", "pallas"])
def test_prefill_then_decode_equals_the_references_full_forward(
        setup, kernel):
    """Sequences of unequal length in one batch, 17 to 30 decode steps
    each across several page edges (8), lookahead on: every greedy token
    is the reference's argmax of an EXPANDED full forward over what was
    served, up to float32 ties; `pallas` runs the latent kernels' bodies
    in interpret mode. Serving does not read the nextn block."""
    c, model, params, _ = setup
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, VOCAB, size=n).tolist()
               for n in (3, 14, 27)]
    new = [30, 22, 17]
    engine = engine_for(model, params, kernel=kernel)
    assert engine.cache.k.shape == (3, 64, PAGE, 128) and \
        engine.cache.v is None
    assert engine.cache.bytes_per_token() == 3 * 128 * 4
    with jax.default_matmul_precision("highest"):
        ids = [engine.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, new)]
        while engine.scheduler.has_work:
            engine.step()
    done = {r.request_id: r for r in engine.scheduler.pop_finished()}
    assert [len(done[i].generated) for i in ids] == new
    assert all(done[i].status == "ok" for i in ids)
    st = engine.stats
    assert st["lookahead_steps"] > 0
    assert st["decode_kv_tokens_latent"] == st["decode_kv_tokens"] > 0
    assert st["kv_page_steps_latent"] * PAGE >= st["decode_kv_tokens_latent"]
    assert st["kv_page_steps_full"] == st["kv_page_steps_window"] == 0
    assert st["moe_rows_routed"] == st["moe_rows_held"] > 0
    assert engine.cache.num_free == engine.cache.num_pages - 1
    want = "pallas" if kernel == "pallas" else "xla"
    assert decode_attention._LAST_BACKEND["decode_latent"] == want
    assert _served_logit_shortfall(c, params, done.values()) <= ATOL


def test_the_latent_kernel_body_against_its_xla_twin():
    """Interpret mode: rows of unequal length over more than one grid
    step a row (a step is 16 pages), an inactive row, 5 heads (padded to a
    sublane tile inside), layer 1 of a stacked pool whose row carries
    padding behind the 40 features."""
    L, P, W, V, B, H = 2, 48, 40, 32, 4, 5
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    pool = jax.random.normal(keys[0], (L, P, PAGE, 128)).at[..., W:].set(0)
    q = jax.random.normal(keys[1], (B, H, W))
    lengths = jnp.asarray([150, 0, 9, 64])
    table = np.zeros((B, 20), np.int32)
    table[0, :19] = np.arange(1, 20)
    table[2, :2] = [30, 31]
    table[3, :8] = np.arange(32, 40)
    args = (q, pool, jnp.asarray(table), lengths, 0.3, V, jnp.int32(1))
    want = decode_attention.paged_latent_decode(*args, backend="xla")
    got = decode_attention.paged_latent_decode(*args, backend="pallas")
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    assert not np.asarray(got[1]).any()           # the inactive row
    rows = jax.random.normal(keys[2], (B, W))
    page, slot = jnp.asarray([19, 0, 31, 39]), jnp.asarray([5, 0, 0, 7])
    a = decode_attention.paged_latent_write(pool, rows, jnp.int32(1), page,
                                            slot, backend="xla")
    b = decode_attention.paged_latent_write(pool, rows, jnp.int32(1), page,
                                            slot, backend="pallas")
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a[1, 31, 0, :W], rows[2])
    assert not np.asarray(a[1, 31, 0, W:]).any()


@pytest.mark.parametrize("ps", [8, 16, 24, 64])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32, jnp.int8],
                         ids=["bf16", "fp32", "int8"])
def test_latent_write_kernel_matches_the_scatter(dtype, ps):
    """`paged_latent_write` moves the row's packed sublane group of its
    page ([g, row]; the page where the group does not divide it), as
    `paged_kv_write` does: the kernel in interpret mode against XLA's
    scatter at the edges of a group, with two inactive rows on the trash
    page; every slot no row names is the input's, bit for bit."""
    L, P, W = 2, 7, 128
    g = decode_attention._write_group(ps, dtype)
    keys = jax.random.split(jax.random.PRNGKey(ps), 2)
    pool = (jax.random.normal(keys[0], (L, P, ps, W)) * 40).astype(dtype)
    rows = (jax.random.normal(keys[1], (7, 40)) * 40).astype(dtype)
    page = jnp.asarray([3, 5, 6, 2, 1, 0, 0], jnp.int32)
    trash = [g - 1, g % ps]
    slot = jnp.asarray([0, g - 1, g % ps, ps - 1, (g + g // 2) % ps] + trash,
                       jnp.int32)
    want = decode_attention.paged_latent_write(pool, rows, jnp.int32(1),
                                               page, slot, backend="xla")
    assert "kv_write_latent_slots" not in decode_attention._LAST_BACKEND
    got = jax.jit(lambda pool, rows: decode_attention.paged_latent_write(
        pool, rows, jnp.int32(1), page, slot, backend="pallas"))(pool, rows)
    assert decode_attention._LAST_BACKEND["kv_write_latent_slots"] == g
    assert got.dtype == pool.dtype
    got, want, before = (np.asarray(x.astype(jnp.float32))
                         for x in (got, want, pool))
    np.testing.assert_array_equal(got[:, 1:], want[:, 1:])
    np.testing.assert_array_equal(got[1, 1, (g + g // 2) % ps, :40],
                                  np.asarray(rows[4].astype(jnp.float32)))
    named = np.zeros((L, P, ps), bool)
    named[1, np.asarray(page), np.asarray(slot)] = True
    np.testing.assert_array_equal(got[~named], before[~named])
    if trash[0] // g != trash[1] // g:
        # neighbouring groups of the trash page are two blocks: both land
        np.testing.assert_array_equal(got[:, 0], want[:, 0])


def test_the_latent_page_kind_shares_the_allocator():
    cache = PagedKVCache(6, 10, 1, 64, 256, latent_width=576)
    assert cache.k.shape == (6, 10, 64, 640) and cache.v is None
    # 1,280 bytes a token and layer in the pool: the 576-wide row (1,152)
    # and the 64 features that fill its last lane tile
    assert cache.bytes_per_token() == 6 * 1280
    pages = cache.allocate(9)
    assert cache.allocate(1) is None
    cache.retain(pages[:2])
    cache.free(pages)
    assert cache.num_free == 7
    with pytest.raises(ValueError, match="int8"):
        PagedKVCache(6, 10, 1, 64, 256, dtype=jnp.int8, latent_width=576)


# ---------------------------------------------------------------------------
# what is not built raises by name
# ---------------------------------------------------------------------------

def _plan_config(**over):
    return dataclasses.replace(family.model_config(conf(), "float32"),
                               **over)


REFUSED_BLOCK = {
    "group-limited routing": (dict(moe_n_group=2), "moe_n_group"),
    "topk group": (dict(moe_topk_group=2), "moe_topk_group"),
    "router score": (dict(moe_router_score="tanh"), "moe_router_score"),
    "two nextn blocks": (dict(mtp_layers=2), "mtp_layers"),
    "no kv rank": (dict(mla_kv_rank=0), "mla_kv_rank"),
    "odd rope dim": (dict(mla_rope_dim=7), "mla_rope_dim"),
    "unequal qk and v": (dict(mla_v_dim=24), "mla_v_dim"),
    "latent gate": (dict(attn_gate="per-head"), "attn_gate"),
    "latent yarn": (dict(layer_plan=(LayerSpec(
        attn="latent", heads=4, rope=("yarn", 2.0, 64, 32, 1, 1.0)),) * 3),
        "rope="),
    "partial rotary": (dict(layer_plan=(LayerSpec(
        attn="latent", heads=4, rotary_pct=0.5),) * 3), "rotary_pct"),
}


@pytest.mark.parametrize("fields,match", REFUSED_BLOCK.values(),
                         ids=REFUSED_BLOCK.keys())
def test_a_block_the_code_does_not_compute_raises_by_name(fields, match):
    with pytest.raises((NotImplementedError, ValueError), match=match):
        GPTNeoX(_plan_config(**fields))


def test_latent_facts_without_a_plan_are_refused():
    from deeperspeed_tpu.models.gpt_neox import GPTNeoXConfig
    for fields in (dict(mla_kv_rank=32), dict(mtp_layers=1),
                   dict(moe_router_score="sigmoid")):
        with pytest.raises(NotImplementedError, match="without a layer_plan"):
            GPTNeoX(GPTNeoXConfig.tiny(**fields))


def test_the_family_refuses_a_config_it_does_not_compute():
    for key, value in (("topk_method", "greedy"), ("attention_bias", True),
                       ("rope_scaling", {"type": "yarn", "factor": 4}),
                       ("partial_rotary_factor", 0.5)):
        with pytest.raises(ValueError, match=key):
            family.model_config(conf(**{key: value}), "float32")
    with pytest.raises(ValueError, match="num_key_value_heads"):
        family.model_config(conf(num_key_value_heads=2), "float32")
    with pytest.raises(NotImplementedError, match="moe_n_group"):
        family.build_model(conf(n_group=4), "float32", {})


REFUSED_SERVING = {
    "prefix cache": (dict(prefix_cache={"enabled": True}), "prefix_cache"),
    "speculation": (dict(speculative={"enabled": True,
                                      "num_draft_tokens": 2}),
                    "speculative"),
    "handoff": (dict(disaggregation={"role": "prefill", "pool_id": "a"}),
                "handoff between pools"),
    "int8 latent pages": (dict(kv_cache_dtype="int8"), "latent pages"),
}


@pytest.mark.parametrize("over,match", REFUSED_SERVING.values(),
                         ids=REFUSED_SERVING.keys())
def test_serving_what_is_not_built_raises_by_name(setup, over, match):
    _, model, params, _ = setup
    with pytest.raises(DeepSpeedConfigError, match=match):
        engine_for(model, params, **over)


def test_a_plan_that_mixes_latent_and_full_layers_is_not_served(setup):
    _, model, _, _ = setup
    mixed = dataclasses.replace(
        model.config, mtp_layers=0, mtp_loss_weight=0.0,
        layer_plan=(LayerSpec(attn="full", heads=4),) +
        model.config.layer_plan[1:])
    with pytest.raises(DeepSpeedConfigError, match="latent layers beside"):
        engine_for(GPTNeoX(mixed, use_pallas=False), None)


def test_training_through_the_engine_is_still_refused(setup):
    import deeperspeed_tpu
    _, model, params, tokens = setup
    with pytest.raises(DeepSpeedConfigError, match="training of a planned"):
        deeperspeed_tpu.initialize(
            model=GPTNeoX(model.config, use_pallas=False),
            config_params={"train_batch_size": 8,
                           "optimizer": {"type": "Adam",
                                         "params": {"lr": 1e-3}}})
    with pytest.raises(DeepSpeedConfigError, match="training of a planned"):
        model.loss_fn(params, (tokens, tokens, jnp.ones_like(tokens)))
