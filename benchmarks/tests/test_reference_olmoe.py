"""The plain OLMoE reference against the program, at a tiny size on the
CPU: what `test_reference.py` does for GPT-NeoX. (The program's own
tests, `tests/test_olmoe.py`, hold it to this reference leaf by leaf.)

On the chip the same comparison runs at the published widths inside the
cell (`drivers/closed_loop.py`), outside the timed window; PERF.md
reports how close it came.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness

ROOT = harness.ROOT
reference = harness.load_module(ROOT, "reference", "olmoe")
family = harness.load_module(ROOT, "families", "olmoe")
PUBLISHED = harness.load_json(ROOT, "benchmarks", "configs",
                              "olmoe-1b-7b.json")
# the rehearsal's six keys (test_rehearsal.TINY), and fewer experts so
# that some get no token; `num_key_value_heads` stays the published 16
CONF = dict(PUBLISHED, hidden_size=256, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=128, vocab_size=512,
            max_position_embeddings=256, num_experts=16,
            num_experts_per_tok=4)
# float32 on both sides, the same arithmetic in another order
SAME_MATH_ATOL = 1e-4


@pytest.fixture(scope="module")
def setup():
    model = family.build_model(CONF, "float32", {"use_pallas": False})
    params = family.init_params(model, seed=0)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 1000))
    params = jax.tree_util.tree_map(
        lambda p: p + 0.05 * jax.random.normal(next(keys), p.shape)
        if p.ndim == 1 else p, params)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                                CONF["vocab_size"])
    return model, params, tokens


def test_the_family_builds_the_published_model_and_a_shrunk_one():
    model = family.build_model(PUBLISHED, "bfloat16", {"use_pallas": True})
    cfg = model.config
    assert (cfg.hidden_size, cfg.num_heads, cfg.head_dim) == (2048, 16, 128)
    assert (cfg.moe_num_experts, cfg.moe_top_k, cfg.intermediate_size) == \
        (64, 8, 1024)
    assert cfg.moe_dropless and not cfg.moe_norm_topk_prob
    assert cfg.num_layers == 6 and PUBLISHED["reduced"] == [
        "num_hidden_layers 16 -> 6"]
    assert cfg.num_params() == \
        PUBLISHED["assumed"]["num_parameters_at_6_layers"]
    # the catalog's keys, verbatim but for the depth
    catalog = {"attention_bias": False, "clip_qkv": None,
               "hidden_act": "silu", "hidden_size": 2048,
               "intermediate_size": 1024, "max_position_embeddings": 4096,
               "model_type": "olmoe", "norm_topk_prob": False,
               "num_attention_heads": 16, "num_experts": 64,
               "num_experts_per_tok": 8, "num_key_value_heads": 16,
               "rms_norm_eps": 1e-05, "rope_scaling": None,
               "rope_theta": 10000, "tie_word_embeddings": False,
               "vocab_size": 50304}
    assert {k: PUBLISHED[k] for k in catalog} == catalog
    with pytest.raises(ValueError, match="num_key_value_heads"):
        family.model_config(dict(PUBLISHED, num_key_value_heads=4),
                            "bfloat16")
    with pytest.raises(ValueError, match="hidden_act"):
        family.model_config(dict(PUBLISHED, hidden_act="gelu"), "bfloat16")


def test_logits_agree_with_the_program(setup):
    model, params, tokens = setup
    ours = np.asarray(model.apply(params, tokens))
    theirs = np.asarray(reference.logits(CONF, params, tokens))
    assert np.abs(ours - theirs).max() <= SAME_MATH_ATOL
    # a reference that ignored the rotary embedding or the mask would
    # still be a smooth function of the same weights
    shuffled = np.asarray(reference.logits(CONF, params, tokens[:, ::-1]))
    assert np.abs(ours - shuffled[:, ::-1]).max() > 100 * SAME_MATH_ATOL
    at = reference.logits_at(CONF, params, tokens,
                             jnp.asarray([[3, 15], [0, 7]]))
    assert np.abs(np.asarray(at)[0, 1] - theirs[0, 15]).max() <= 1e-6


def test_loss_and_gradient_agree_with_the_program(setup):
    model, params, tokens = setup
    labels = np.full(tokens.shape, reference.IGNORE_INDEX, np.int32)
    labels[0, :10] = np.asarray(tokens)[0, :10]
    labels = jnp.asarray(labels)
    ours = float(model.loss_fn(params, (tokens, labels)))
    theirs = float(reference.loss(CONF, params, tokens, labels))
    assert abs(ours - theirs) <= 1e-5 * abs(theirs)
    g_ours = jax.grad(model.loss_fn)(params, (tokens, tokens))
    g_theirs = jax.grad(
        lambda p: reference.loss(CONF, p, tokens, tokens))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_ours),
                    jax.tree_util.tree_leaves(g_theirs)):
        scale = np.abs(np.asarray(b)).max()
        assert np.abs(np.asarray(a) - np.asarray(b)).max() <= 1e-3 * scale


def test_prefill_then_decode_through_the_cache_agrees(setup):
    """The server's tokens (segmented prefill, then one token a step
    through the paged cache) against the reference's one full pass, by
    the cell's own check."""
    from deeperspeed_tpu.inference import InferenceEngine
    closed_loop = harness.load_module(ROOT, "drivers", "closed_loop")
    model, params, _ = setup
    engine = InferenceEngine(model, params=params, config={"inference": {
        "enabled": True, "page_size": 16, "num_pages": 64,
        "max_batch_size": 4, "token_budget": 256,
        "prefill_lengths": [128, 256], "kernel": "pallas"}})
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, CONF["vocab_size"], size=n).tolist()
               for n in (5, 40, 100, 130)]
    ids = [engine.submit(p, max_new_tokens=8) for p in prompts]
    done = {}
    while engine.scheduler.has_work:
        engine.step()
        done.update({r.request_id: r
                     for r in engine.scheduler.pop_finished()})
    check = closed_loop.check_served(reference, CONF, params,
                                     [done[i] for i in ids], 256, 8, 1e-3)
    assert check["checked_tokens"] == 32 and check["reference_finite"]
    assert check["max_logit_shortfall"] <= 1e-3
    assert check["exact_match_share"] >= 0.95


def test_flops_per_token_count_active_parameters_by_hand():
    """At 6 layers: attention 4 * 2048^2, the router 2048 * 64 and 8
    experts of 3 * 2048 * 1024 a layer, and the head."""
    layer = 4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024
    assert layer == 16_777_216 + 131_072 + 50_331_648
    assert reference.matmul_params(PUBLISHED) == \
        6 * layer + 50304 * 2048 == 506_462_208
    assert reference.train_flops_per_token(PUBLISHED, 4096) == \
        6 * 506_462_208 + 6 * 6 * 2048 * 4096
    # of all the matmul parameters at 6 layers, under a fifth are active
    model = family.build_model(PUBLISHED, "bfloat16", {"use_pallas": True})
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    matrices = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(shapes) if l.ndim >= 2)
    held = matrices - 50304 * 2048            # less the input embedding
    assert 0.18 < reference.matmul_params(PUBLISHED) / held < 0.20
