"""Laguna (a PLANNED model: window and full layers with their own head
counts over grouped KV heads, a per-head attention gate, a held share of
the experts with a shared one) on the normal path against the plain
reference (`benchmarks/reference/laguna.py`), at a small size on the CPU:
hidden 64, head dim 16, 2 KV heads under 4 query heads on full layers and
6 on window layers, window 16, page 8 (the least the `inference` block
takes), 8 experts of which 4 held, 3 a token, 5 layers in the published
order (full + dense, three window + experts, full + experts).

Both sides compute in float32 unless a test says otherwise, so the
tolerances are those of float32 rounding in another order of summation,
each written where it is used with what it would refuse.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.families import laguna as family
from benchmarks.reference import laguna as reference
from deeperspeed_tpu.inference import InferenceEngine
from deeperspeed_tpu.inference.kv_cache import PagedKVCache
from deeperspeed_tpu.inference.scheduler import (ContinuousBatchingScheduler,
                                                 Request)
from deeperspeed_tpu.models import gpt_neox as neox
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig, LayerSpec
from deeperspeed_tpu.moe.layer import moe_ffn_dropless
from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError
from tests.model.references import jitted, reference_rows

VOCAB, WINDOW, PAGE = 128, 16, 8
# float32 rounding through five layers on logits of size ~1; a bf16 router
# or a dropped gate moves them by far more (asserted below)
LOGITS_ATOL = 1e-4
# bf16 weights and activations against the float32 reference on the same
# (bf16-rounded) weights: activations carry 8 bits through five layers
BF16_ATOL = 6e-2

_YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
         "original_max_position_embeddings": 8192, "beta_slow": 1,
         "beta_fast": 32, "attention_factor": 1.4852030263919618,
         "partial_rotary_factor": 0.5}


def conf(held="0-3", layers=5, experts=8):
    lo, hi = (int(t) for t in held.split("-"))
    types = ["full_attention"] + ["sliding_attention"] * 3
    return {
        "family": "laguna", "model_type": "laguna", "vocab_size": VOCAB,
        "hidden_size": 64, "intermediate_size": 96,
        "num_hidden_layers": layers, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16,
        "max_position_embeddings": 256, "attention_bias": False,
        "rms_norm_eps": 1e-6, "num_experts": hi + 1 - lo,
        "num_experts_published": experts, "held_experts": held,
        "num_experts_per_tok": 3, "moe_intermediate_size": 32,
        "shared_expert_intermediate_size": 32, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head",
        "sliding_window": WINDOW,
        "rope_parameters": {
            "full_attention": dict(_YARN),
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        "layer_types": (types * 3)[:layers],
        "moe_apply_router_weight_on_input": False,
        "mlp_layer_types": (["dense"] + ["sparse"] * 11)[:layers],
        "gating_types": ["per_head"] * layers,
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": ([4, 6, 6, 6] * 3)[:layers],
        "moe_router_logit_softcapping": 0}


def perturbed(params, seed=1):
    """Norm scales away from their init of 1, so a misplaced one shows."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 1000))

    def move(path, p):
        if "scale" in jax.tree_util.keystr(path):
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
    inference = {"enabled": True, "page_size": PAGE, "num_pages": 128,
                 "max_seq_len": 128, "max_batch_size": 4,
                 "token_budget": 128, "prefill_lengths": [16, 32],
                 "prefill_batch_sizes": [1, 2],
                 "decode_batch_sizes": [4], **over}
    return InferenceEngine(model, config={"inference": inference},
                           params=params)


# ---------------------------------------------------------------------------
# the program's forward against the reference
# ---------------------------------------------------------------------------

def test_the_plan_and_the_parameter_stacks(setup):
    c, model, params, _ = setup
    cfg = model.config
    assert [s.kind for s in cfg.layer_plan] == [
        "full4.dense", "window6.experts", "window6.experts",
        "window6.experts", "full4.experts"]
    assert [(s.kind, first, at, n) for s, first, at, n in cfg.plan_runs()] \
        == [("full4.dense", 0, 0, 1), ("window6.experts", 1, 0, 3),
            ("full4.experts", 4, 0, 1)]
    assert cfg.head_dim == 16 and cfg.kv_heads == 2
    assert cfg.cache_layers("full") == 2 and cfg.cache_layers("window") == 3
    stack = params["stacks"]["window6.experts"]
    assert stack["attn"]["q_w"].shape == (3, 64, 6 * 16)
    assert stack["attn"]["kv_w"].shape == (3, 64, 2 * 2 * 16)
    assert stack["mlp"]["gate"].shape == (3, 64, 8)      # scores all 8
    assert stack["mlp"]["w_in"].shape == (3, 4, 64, 64)  # holds 4
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == cfg.num_params() == reference.num_params(c)
    assert cfg.num_params(held=False) == reference.num_params(c, held=False)
    assert cfg.num_params(held=False) - n == 4 * 4 * 3 * 64 * 32


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_logits_agree_with_the_reference(setup, use_pallas):
    c, model, params, tokens = setup
    run = GPTNeoX(model.config, use_pallas=use_pallas)
    with jax.default_matmul_precision("highest"):
        got = jitted(run.apply)(params, tokens)
    want = jitted(reference.logits, c)(params, tokens)
    np.testing.assert_allclose(got, want, atol=LOGITS_ATOL, rtol=0)


def test_bfloat16_weights_agree_at_a_written_tolerance(setup):
    c, model, params, tokens = setup
    bf16 = jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16), params)
    run = GPTNeoX(dataclasses.replace(model.config,
                                      param_dtype=jnp.bfloat16),
                  use_pallas=False)
    got = jitted(run.apply)(bf16, tokens)
    want = jitted(reference.logits, c)(bf16, tokens)
    err = float(jnp.max(jnp.abs(got - want)))
    assert LOGITS_ATOL < err < BF16_ATOL, err


def test_the_tolerance_refuses_a_bf16_router_and_a_dropped_gate(setup):
    c, model, params, tokens = setup
    want = jitted(reference.logits, c)(params, tokens)

    def worst(p):
        with jax.default_matmul_precision("highest"):
            got = jitted(model.apply)(p, tokens)
            return float(jnp.max(jnp.abs(got - want)))

    def edit(stack, group, leaf, fn):
        stacks = dict(params["stacks"])
        kind = dict(stacks[stack])
        kind[group] = dict(kind[group], **{leaf: fn(kind[group][leaf])})
        stacks[stack] = kind
        return dict(params, stacks=stacks)

    # the router's weights rounded to bf16: some token's k-th expert moves
    router = edit("window6.experts", "mlp", "gate",
                  lambda g: (g * 40).astype(jnp.bfloat16).astype(g.dtype)
                  / 40 + 3e-3)
    assert worst(router) > 10 * LOGITS_ATOL
    # gate weights of zero: sigmoid(0) = 0.5 on every head, not the gate
    no_gate = edit("full4.dense", "attn", "gate_w", jnp.zeros_like)
    assert worst(no_gate) > 10 * LOGITS_ATOL


def test_yarn_inv_freq_against_the_closed_form():
    """rot = 64 of 128, theta 500000, factor 128, original 8192, beta 32 /
    1: the correction range is [floor(c(32)), ceil(c(1))] = [9, 18]
    (c(b) = 64 ln(8192 / (2 pi b)) / (2 ln 500000)); below 9 the plain
    frequency, above 18 the plain over 128, between them the ramp."""
    import math
    inv, factor, rot = neox.rope_inv_freq(
        128, 0.5, 500000.0, ("yarn", 128, 8192, 32, 1, 1.4852030263919618))
    assert rot == 64 and factor == 1.4852030263919618
    assert math.isclose(factor, 0.1 * math.log(128) + 1.0, rel_tol=1e-12)
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    c = [64 * math.log(8192 / (b * 2 * math.pi)) / (2 * math.log(500000))
         for b in (32, 1)]
    low, high = math.floor(c[0]), math.ceil(c[1])
    assert (low, high) == (9, 18)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    want = plain / 128 * ramp + plain * (1 - ramp)
    np.testing.assert_allclose(inv, want, rtol=2e-6)
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=2e-6)
    np.testing.assert_allclose(inv[18:], plain[18:] / 128, rtol=2e-6)
    # the reference computes the same, and the plain kind is untouched
    rope = dict(_YARN)
    ref_inv, ref_factor = reference.yarn_inv_freq(rope, 128)
    np.testing.assert_allclose(ref_inv, inv, rtol=2e-6)
    assert ref_factor == factor
    plain_inv, one, rot = neox.rope_inv_freq(128, 1.0, 10000.0)
    np.testing.assert_allclose(plain_inv, 10000.0 ** (-np.arange(0, 128, 2)
                                                      / 128), rtol=2e-6)
    assert (one, rot) == (1.0, 128)


# ---------------------------------------------------------------------------
# the expert share
# ---------------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """held = 0-3 plus held = 4-7, the shared expert counted once, is the
    uncut reference's layer: the program's layer on each share against
    the reference holding all 8."""
    whole = conf(held="0-7")
    model = family.build_model(whole, "float32", {"use_pallas": False})
    params = model.init_params(jax.random.PRNGKey(3))
    mlp = jax.tree_util.tree_map(lambda a: a[1],
                                 params["stacks"]["window6.experts"]["mlp"])
    m = jax.random.normal(jax.random.PRNGKey(4), (40, 64))
    with jax.default_matmul_precision("highest"):
        want = jitted(reference.moe_layer, whole)(mlp, m)
        shared = neox._gated_mlp(m, mlp["shared_in"], mlp["shared_out"],
                                 jax.nn.silu)
        total, rows = shared, 0
        for lo, hi in ((0, 4), (4, 8)):
            share = dict(mlp, w_in=mlp["w_in"][lo:hi],
                         w_out=mlp["w_out"][lo:hi])
            y, stats = jitted(moe_ffn_dropless, top_k=3, norm_topk_prob=True,
                              held=(lo, hi), scale=2.5)(share, m)
            total = total + y
            rows += float(stats[2, lo:hi].sum())
            ref_share = jitted(reference.moe_layer, whole, held=(lo, hi),
                               shared=False)(mlp, m)
            np.testing.assert_allclose(y, ref_share, atol=1e-5, rtol=0)
    np.testing.assert_allclose(total, want, atol=1e-5, rtol=0)
    assert rows == 40 * 3                # every routed pair is held once
    # all held: the third row is not there, the result is the whole sum
    with jax.default_matmul_precision("highest"):
        y, stats = moe_ffn_dropless(mlp, m, 3, norm_topk_prob=True,
                                    scale=2.5)
    assert stats.shape == (2, 8)
    np.testing.assert_allclose(y + shared, want, atol=1e-5, rtol=0)


# ---------------------------------------------------------------------------
# serving: prefill then decode through the paged caches
# ---------------------------------------------------------------------------

def _served_logit_shortfall(c, params, requests):
    """Worst shortfall of a served token's reference logit under the
    reference's best, teacher-forced over prompt + served tokens."""
    worst = 0.0
    for r in requests:
        lg = reference_rows(reference, c, params,
                            list(r.prompt) + list(r.generated), 128)
        at = len(r.prompt) - 1 + np.arange(len(r.generated))
        got = lg[at, np.asarray(r.generated)]
        worst = max(worst, float(np.max(lg[at].max(-1) - got)))
    return worst


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
def test_prefill_then_decode_equals_the_references_full_forward(
        setup, temperature):
    """Contexts that cross the window (16), page edges (8) and several
    released pages, a batch of mixed lengths, lookahead on: every greedy
    token is the reference's argmax of a full forward over what was
    served (sampled: the streams are the same engine's, and every page
    is returned)."""
    c, model, params, _ = setup
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, VOCAB, size=n).tolist()
               for n in (3, 9, 14, 27)]
    new = [60, 41, 22, 9]
    engine = engine_for(model, params, temperature=temperature)
    with jax.default_matmul_precision("highest"):
        ids = [engine.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, new)]
        while engine.scheduler.has_work:
            engine.step()
    done = {r.request_id: r for r in engine.scheduler.pop_finished()}
    assert [len(done[i].generated) for i in ids] == new
    assert all(done[i].status == "ok" for i in ids)
    st = engine.stats
    assert st["lookahead_steps"] > 0 and st["window_pages_released"] >= 8
    assert st["decode_kv_tokens_window"] < st["decode_kv_tokens"]
    assert 0 < st["kv_page_steps_window"] < st["kv_page_steps_full"]
    assert 0 < st["moe_rows_held"] < st["moe_rows_routed"]
    # pools drain to zero: nothing leaks
    assert engine.cache.num_free == engine.cache.num_pages - 1
    assert engine.window_cache.num_free == engine.window_cache.num_pages - 1
    if temperature == 0.0:
        # float32 both sides: the served token is the argmax up to ties
        # of float32 rounding (1e-4)
        assert _served_logit_shortfall(c, params, done.values()) \
            <= LOGITS_ATOL


def test_preempt_and_resume_reprefills_both_cache_kinds(setup):
    """A full-kind pool too small for the batch: the youngest is evicted
    (both kinds' pages back), re-prefilled from its whole context, and
    the streams are those of an engine that never preempted."""
    c, model, params, _ = setup
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, VOCAB, size=n).tolist() for n in (14, 13, 12)]

    def serve(**over):
        engine = engine_for(model, params, **over)
        with jax.default_matmul_precision("highest"):
            ids = [engine.submit(p, max_new_tokens=24) for p in prompts]
            while engine.scheduler.has_work:
                engine.step()
        done = {r.request_id: r for r in engine.scheduler.pop_finished()}
        return engine, [done[i].generated for i in ids]

    roomy, want = serve(max_seq_len=64)
    tight, got = serve(num_pages=12, max_seq_len=64)
    assert roomy.stats["evictions"] == 0 and tight.stats["evictions"] > 0
    assert got == want
    for engine in (roomy, tight):
        assert engine.cache.num_free == engine.cache.num_pages - 1
        assert engine.window_cache.num_free == \
            engine.window_cache.num_pages - 1


# ---------------------------------------------------------------------------
# the allocator of the window kind
# ---------------------------------------------------------------------------

def test_window_pages_are_released_and_tables_are_by_kind():
    full = PagedKVCache(2, 40, 2, PAGE, 16)
    window = PagedKVCache(3, 13, 2, PAGE, 16)
    sched = ContinuousBatchingScheduler(
        full, max_seq_len=128, token_budget=64, max_batch_size=4,
        prefill_lengths=[16, 32, 64], prefill_batch_sizes=[1],
        decode_batch_sizes=[4], window_cache=window, window=WINDOW)
    req = Request(prompt=list(range(1, 44)), max_new_tokens=30)
    sched.add_request(req, now=0.0)
    plan = sched.schedule(now=0.0)
    assert plan.prefills == [req] and plan.prefill_len == 64
    # full kind: the whole bucket; window kind: from the page of position
    # 43 - 16 + 1 = 28 (page 3) to the page of position 43 (page 5)
    assert len(req.pages) == 8
    assert [bool(p) for p in req.window_pages] == [False] * 3 + [True] * 3
    sched.complete_prefill(req, 7)
    held = []
    while req.status is None:
        plan = sched.schedule(now=0.0)
        assert plan.decodes == [req]
        pos = req.cached
        live = [i for i, p in enumerate(req.window_pages) if p]
        # exactly the pages from the window's first to the position's
        assert live == list(range(max(0, pos - WINDOW + 1) // PAGE,
                                  pos // PAGE + 1))
        assert len(live) <= WINDOW // PAGE + 1
        held.append(len(live))
        sched.complete_decode(req, 7)
    # the last decode ran at position 71: pages 3 .. 6 went back meanwhile
    assert sched.window_pages_released == (71 - WINDOW + 1) // PAGE - 3 == 4
    assert max(held) == 3 and window.num_free == 12 \
        and full.num_free == 39                                # drained


def test_a_window_pool_that_runs_dry_evicts_and_never_leaks():
    full = PagedKVCache(2, 60, 2, PAGE, 16)
    window = PagedKVCache(3, 2 * 3 + 1, 2, PAGE, 16)   # two sequences' worth
    sched = ContinuousBatchingScheduler(
        full, max_seq_len=64, token_budget=64, max_batch_size=4,
        prefill_lengths=[16], prefill_batch_sizes=[1],
        decode_batch_sizes=[4], window_cache=window, window=WINDOW)
    reqs = [Request(prompt=list(range(1, 11)), max_new_tokens=20)
            for _ in range(3)]
    for r in reqs:
        sched.add_request(r, now=0.0)
    evicted = 0
    for _ in range(200):
        if not sched.has_work:
            break
        plan = sched.schedule(now=0.0)
        evicted += len(plan.evicted)
        for r in plan.prefills:
            sched.complete_prefill(r, 5)
        for r in plan.decodes:
            if r.status is None and r.state == "running":
                sched.complete_decode(r, 5)
    assert evicted > 0
    assert not sched.has_work and all(r.status == "ok" for r in reqs)
    assert all(len(r.generated) == 20 for r in reqs)
    assert window.num_free == 6 and full.num_free == 59


# ---------------------------------------------------------------------------
# what is not built raises by name
# ---------------------------------------------------------------------------

def _plan_config(**over):
    c = conf()
    return dataclasses.replace(family.model_config(c, "float32"), **over)


REFUSED_BLOCK = {
    "tanh router": (dict(moe_router_score="tanh"), "moe_router_score"),
    "per-feature gate": (dict(attn_gate="per-feature"), "attn_gate"),
    "qk norm": (dict(qk_norm=True), "qk_norm"),
    "parallel residual": (dict(use_parallel_residual=True),
                          "use_parallel_residual"),
    "layernorm": (dict(norm="layernorm"), "norm="),
    "biases": (dict(use_bias=True), "use_bias"),
    "capacity router": (dict(moe_dropless=False), "moe_dropless|dropless"),
    "plan length": (dict(num_layers=4), "layer_plan names 5"),
    "no window": (dict(attn_window=0), "attn_window"),
    "kv heads": (dict(num_kv_heads=3), "KV heads"),
    "held range": (dict(moe_held=(4, 12)), "moe_held"),
    "rope": (dict(layer_plan=(LayerSpec(heads=4, rope=("ntk", 2.0)),) * 5),
             "rope"),
    "kinds": (dict(layer_plan=(LayerSpec(attn="linear", heads=4),) * 5),
              "full . window"),
}


@pytest.mark.parametrize("fields,match", REFUSED_BLOCK.values(),
                         ids=REFUSED_BLOCK.keys())
def test_a_planned_block_the_code_does_not_compute_raises_by_name(
        fields, match):
    with pytest.raises((NotImplementedError, ValueError), match=match):
        GPTNeoX(_plan_config(**fields))


def test_planned_facts_without_a_plan_are_refused():
    with pytest.raises(NotImplementedError, match="without a layer_plan"):
        GPTNeoX(GPTNeoXConfig.tiny(num_kv_heads=2))
    with pytest.raises(NotImplementedError, match="without a layer_plan"):
        GPTNeoX(GPTNeoXConfig.tiny(attn_window=8))


def test_the_family_refuses_a_config_it_does_not_compute():
    with pytest.raises(ValueError, match="gating"):
        family.model_config(dict(conf(), gating="per-element"), "float32")
    with pytest.raises(ValueError, match="rope_type"):
        c = conf()
        c["rope_parameters"]["sliding_attention"]["rope_type"] = "linear"
        family.model_config(c, "float32")
    with pytest.raises(ValueError, match="held_experts"):
        family.model_config(dict(conf(), held_experts="0-5"), "float32")


REFUSED_SERVING = {
    "prefix cache": (dict(prefix_cache={"enabled": True}), "prefix_cache"),
    "speculation": (dict(speculative={"enabled": True,
                                      "num_draft_tokens": 2}),
                    "speculative"),
    "handoff": (dict(disaggregation={"role": "prefill", "pool_id": "a"}),
                "handoff between pools"),
    "int8 kv": (dict(kv_cache_dtype="int8"), "int8"),
}


@pytest.mark.parametrize("over,match", REFUSED_SERVING.values(),
                         ids=REFUSED_SERVING.keys())
def test_serving_what_is_not_built_raises_by_name(setup, over, match):
    _, model, params, _ = setup
    with pytest.raises(DeepSpeedConfigError, match=match):
        engine_for(model, params, **over)


def test_a_model_parallel_mesh_is_refused(setup, devices):
    from jax.sharding import Mesh
    _, model, params, _ = setup
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                ("data", "model"))
    with pytest.raises(DeepSpeedConfigError, match="mp > 1"):
        InferenceEngine(model, config={"inference": {
            "enabled": True, "page_size": PAGE, "num_pages": 64,
            "max_seq_len": 128}}, params=params, mesh=mesh)


def test_training_a_planned_model_raises_by_name(setup):
    import deeperspeed_tpu
    _, model, params, tokens = setup
    with pytest.raises(DeepSpeedConfigError, match="training of a planned"):
        model.loss_fn(params, (tokens, tokens))
    with pytest.raises(DeepSpeedConfigError, match="training of a planned"):
        deeperspeed_tpu.initialize(
            model=GPTNeoX(model.config, use_pallas=False),
            config_params={"train_batch_size": 8,
                           "optimizer": {"type": "Adam",
                                         "params": {"lr": 1e-3}}})
    with pytest.raises(NotImplementedError, match="no\\s+backward"):
        from deeperspeed_tpu.ops.pallas.flash_attention import \
            flash_attention_segmented
        q = jnp.ones((1, 128, 4, 64))
        kv = jnp.ones((1, 128, 2, 64))
        seg = jnp.ones((1, 128), jnp.int32)
        jax.grad(lambda q: flash_attention_segmented(
            q, kv, kv, seg, True, window=16).sum())(q)


def test_the_engine_holds_the_weights_once(setup):
    """The engine runs from the model's own tree: every block leaf it
    holds IS the caller's array."""
    _, model, params, _ = setup
    engine = engine_for(model, params)
    mine = jax.tree_util.tree_leaves(params["stacks"])
    theirs = jax.tree_util.tree_leaves(engine.params_stacked)
    assert len(mine) == len(theirs)
    assert all(a is b for a, b in zip(mine, theirs))
    assert engine.params["embed"]["wte"] is params["embed"]["wte"]
