"""qwen3_next (Qwen3-Next-80B-A3B's architecture: a PLANNED model of Gated
DeltaNet layers beside output-gated softmax attention, every FFN routed
experts with a gated shared expert, a share of the experts held) on the
normal path against the plain reference
(`benchmarks/reference/qwen3_next.py`), at a small size on the CPU: hidden
64, 4 query heads over 2 KV heads of 16, 2 key and 4 value heads of 16,
16 experts of width 32 of which 8 are held, top 4, page 8, 6 layers in the
published order (gdn, gdn, gdn, full, gdn, gdn).

Both sides compute in float32, so the tolerances are those of float32
rounding in another order of summation, each written where it is used
with what it would refuse.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.families import qwen3_next as family
from benchmarks.reference import qwen3_next as reference
from deeperspeed_tpu.inference import InferenceEngine
from deeperspeed_tpu.models import gpt_neox as neox
from deeperspeed_tpu.ops.pallas import gdn as gdn_ops
from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError
from tests.model.references import jitted, reference_rows

VOCAB, PAGE = 128, 8
# float32 rounding through six layers on logits of size ~0.8; a gate left
# out, a norm scaled by w where it is 1 + w or a key head serving the wrong
# value heads moves them by far more (asserted below)
LOGITS_ATOL = 2e-5
# a cached row or a recurrent state against the reference's, relative:
# float32 rounding through the layers before it
STATE_RTOL = 1e-4


def conf(**over):
    return {"family": "qwen3_next", "model_type": "qwen3_next",
            "decoder_sparse_step": 1, "full_attention_interval": 4,
            "head_dim": 16, "hidden_act": "silu", "hidden_size": 64,
            "intermediate_size": 128, "linear_conv_kernel_dim": 4,
            "linear_key_head_dim": 16, "linear_num_key_heads": 2,
            "linear_num_value_heads": 4, "linear_value_head_dim": 16,
            "max_position_embeddings": 256, "mlp_only_layers": [],
            "moe_intermediate_size": 32, "norm_topk_prob": True,
            "num_attention_heads": 4, "num_experts": 8,
            "num_experts_published": 16, "held_experts": "0-7",
            "num_experts_per_tok": 4, "num_hidden_layers": 6,
            "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
            "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 1e7,
            "shared_expert_intermediate_size": 32,
            "tie_word_embeddings": False, "use_sliding_window": False,
            "vocab_size": VOCAB, **over}


def perturbed(params, seed=1):
    """Norm scales (w of 1 + w, zero at init; the delta rule's w_n, one),
    the decay's leaves and the small gates away from their init, so that a
    misplaced or dropped one shows."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 1000))

    def move(path, p):
        name = jax.tree_util.keystr(path)
        if p.ndim <= 2 and "A_log" not in name and "wte" not in name:
            return p + 0.1 * jax.random.normal(next(keys), p.shape)
        return p
    return jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def setup():
    c = conf()
    model = family.build_model(c, "float32", {"use_pallas": True,
                                              "max_seq_len": 128})
    params = perturbed(model.init_params(jax.random.PRNGKey(0)))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 50), 0, VOCAB)
    return c, model, params, tokens


def engine_for(model, params, **over):
    inference = {"enabled": True, "page_size": PAGE, "num_pages": 4 * 8 + 3,
                 "max_seq_len": 64, "max_batch_size": 4,
                 "token_budget": 64, "prefill_lengths": [16, 32],
                 "prefill_batch_sizes": [1],
                 "decode_batch_sizes": [4], **over}
    return InferenceEngine(model, config={"inference": inference},
                           params=params)


def serve(engine, prompts, new, after=None):
    """Serve `prompts` (`after`: {index: the step it is submitted at}),
    return the finished requests in order."""
    after = after or {}
    ids, step = {}, 0
    with jax.default_matmul_precision("highest"):
        while len(ids) < len(prompts) or engine.scheduler.has_work:
            for i, (p, n) in enumerate(zip(prompts, new)):
                if i not in ids and after.get(i, 0) <= step:
                    ids[i] = engine.submit(p, max_new_tokens=n)
            engine.step()
            step += 1
    done = {r.request_id: r for r in engine.scheduler.pop_finished()}
    return [done[ids[i]] for i in range(len(prompts))]


def shortfall(c, params, requests):
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


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# ---------------------------------------------------------------------------
# the delta rule's kernels against the recurrence
# ---------------------------------------------------------------------------

def _operands(B, S, n_k=2, n_v=4, d=16, seed=0, one_key=False):
    r = np.random.default_rng(seed)
    q, k = (r.standard_normal((B, S, n_k, d)) for _ in range(2))
    if one_key:                 # a run of one token: every key the same
        k[:] = k[:, :1]
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(d)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.standard_normal((B, S, n_v, d))
    g = -np.exp(r.uniform(np.log(1e-3), np.log(1.6), (B, S, n_v)))
    beta = r.uniform(0.05, 0.95, (B, S, n_v))
    return [jnp.asarray(t, jnp.float32) for t in (q, k, v, g, beta)]


def _recurrence(q, k, v, g, beta):
    """`reference.delta_rule` a row of a batch, a key head's q and k
    repeated for the value heads it serves."""
    rep = v.shape[2] // q.shape[2]

    def row(q, k, v, g, beta):
        return reference.delta_rule(jnp.repeat(q, rep, 1),
                                    jnp.repeat(k, rep, 1), v, g, beta)

    return jax.vmap(row)(q, k, v, g, beta)


@pytest.mark.parametrize("rows, one_key", [
    (128, False), (50, False), (100, False), (130, False), (5, False),
    (128, True)])
def test_the_chunked_delta_rule_equals_the_recurrence(rows, one_key):
    """Lengths that are and are not whole chunks of 64 (the rows that fill
    the last chunk move nothing), and a run of ONE key, where the inverse's
    alternating series over 64 rows would cancel (the kernel builds it from
    blocks of 16): outputs and the final state to float32 rounding."""
    args = _operands(2, rows, seed=rows, one_key=one_key)
    with jax.default_matmul_precision("highest"):
        o, state = jax.jit(gdn_ops.gdn_chunk)(*args)
        want_o, want_s = jax.jit(_recurrence)(*args)
    assert o.shape == want_o.shape
    # outputs of size ~0.4, states of size ~1
    assert float(jnp.abs(o - want_o).max()) < 5e-6
    assert float(jnp.abs(state - want_s).max()) < 5e-6


def test_padding_rows_leave_the_chunk_walks_state_as_it_was():
    """g = beta = 0 behind row 37: the state after 128 rows is the state
    after 37, exactly what a longer bucket must give."""
    q, k, v, g, beta = _operands(1, 128, seed=7)
    live = (jnp.arange(128) < 37)[None, :, None]
    g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    with jax.default_matmul_precision("highest"):
        _, padded = jax.jit(gdn_ops.gdn_chunk)(q, k, v, g, beta)
        _, bare = jax.jit(gdn_ops.gdn_chunk)(
            *(t[:, :37] for t in (q, k, v, g, beta)))
    assert float(jnp.abs(padded - bare).max()) < 2e-6


def test_the_step_after_the_chunk_walk_equals_the_recurrence():
    """A prompt through `gdn_chunk`, its state into a slot of a stacked
    pool, then 9 tokens through `gdn_step` in place: outputs and state are
    the recurrence's over all 41 rows; other slots and layers untouched;
    an inactive row (g = beta = 0, the trash slot) moves nothing."""
    B, S, new = 2, 32, 9
    q, k, v, g, beta = _operands(B, S + new, seed=3)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = jax.jit(_recurrence)(q, k, v, g, beta)
        _, state = jax.jit(gdn_ops.gdn_chunk)(
            *(t[:, :S] for t in (q, k, v, g, beta)))
        marker = jnp.full((4, 16, 16), 7.0)
        pool = jnp.zeros((3, 5, 4, 16, 16)).at[:, 4].set(marker)
        conv = jnp.zeros((3, 5, 3, 1, 128))
        slots = jnp.asarray([3, 1, 0])
        pool = pool.at[1, slots[:B]].set(state)
        step = jax.jit(gdn_ops.gdn_step)
        for t in range(S, S + new):
            # a third, inactive row rides along
            row = [jnp.concatenate([x[:, t], jnp.zeros_like(x[:1, t])])
                   for x in (q, k, v, g, beta)]
            tail = jnp.full((3, 3, 128), float(t)).at[2].set(0.0)
            o, (conv, pool) = step((conv, pool), tail, slots, 1, *row)
            assert float(jnp.abs(o[:B] - want_o[:, t]).max()) < 5e-6
    # the rows handed in lie in their slots, of that layer alone
    assert np.all(np.asarray(conv[1, slots[:B]]) == S + new - 1)
    assert not np.any(np.asarray(conv[0])) and not np.any(np.asarray(conv[2]))
    assert float(jnp.abs(pool[1, slots[:B]] - want_s).max()) < 5e-6
    assert not np.any(np.asarray(pool[1, 0]))           # the trash slot
    assert np.all(np.asarray(pool[:, 4]) == 7.0)
    assert not np.any(np.asarray(pool[0, :4])) and \
        not np.any(np.asarray(pool[2, :4]))


# ---------------------------------------------------------------------------
# the program's forward against the reference
# ---------------------------------------------------------------------------

def test_the_plan_the_stacks_and_the_parameter_count(setup):
    c, model, params, _ = setup
    cfg = model.config
    assert [s.attn for s in cfg.layer_plan] == \
        ["gdn", "gdn", "gdn", "full", "gdn", "gdn"] == \
        reference.layer_kinds(c)
    assert [(s.attn, n) for s, _, _, n in cfg.plan_runs()] == \
        [("gdn", 3), ("full", 1), ("gdn", 2)]
    assert set(params["stacks"]) == {"gdn0.experts", "full4.experts"}
    assert cfg.cache_layers("state") == 5 and cfg.cache_layers("full") == 1
    assert cfg.state_shapes == ((3, 1, 128), (4, 16, 16))
    leaves = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert cfg.num_params() == reference.num_params(c) == leaves
    # the published model at this depth holds all 16 experts a layer
    assert cfg.num_params(held=False) - leaves == 6 * 8 * 3 * 64 * 32


def test_the_published_configuration_counts_its_parameters():
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs",
        "qwen3-next-80b-a3b.json")
    with open(path) as f:
        c = json.load(f)
    cfg = family.model_config(c, "bfloat16", 9216)
    cfg.check_block()
    assert cfg.num_params() == reference.num_params(c) == \
        c["assumed"]["num_parameters"] == 5_675_228_608
    assert cfg.state_shapes == ((3, 8, 1024), (32, 128, 128))
    assert cfg.moe_held == (0, 256) and cfg.moe_num_experts == 512


def test_logits_agree_with_the_reference(setup):
    c, model, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        got = jitted(model.apply)(params, tokens)
    want = jitted(reference.logits, c)(params, tokens)
    assert float(jnp.abs(got - want).max()) < LOGITS_ATOL


def _edit(params, stack, leaf, fn, group="attn"):
    stacks = dict(params["stacks"])
    layer = dict(stacks[stack])
    layer[group] = dict(layer[group], **{leaf: fn(layer[group][leaf])})
    stacks[stack] = layer
    return dict(params, stacks=stacks)


@pytest.mark.parametrize("stack, group, leaf, fn", [
    # the attention gate left out (sigmoid(-inf) != 1: a gate of 0.5)
    ("full4.experts", "attn", "gate_w", jnp.zeros_like),
    # q's head norm scaled by w, not 1 + w
    ("full4.experts", "attn", "q_norm", lambda w: w - 1.0),
    # the shared expert's gate left out
    ("gdn0.experts", "mlp", "shared_gate", jnp.zeros_like),
    # the output norm of the delta rule scaled by 1 + w
    ("gdn0.experts", "attn", "norm", lambda w: w + 1.0),
    # the decay's bias dropped
    ("gdn0.experts", "attn", "dt_bias", jnp.zeros_like),
    # a key head serving the other value heads
    ("gdn0.experts", "attn", "in_w",
     lambda w: w.at[:, :, :32].set(w[:, :, 15::-1].repeat(2, -1))),
])
def test_the_tolerance_refuses_a_wrong_fact(setup, stack, group, leaf, fn):
    c, model, params, tokens = setup
    wrong = _edit(params, stack, leaf, fn, group)
    with jax.default_matmul_precision("highest"):
        got = jitted(model.apply)(wrong, tokens)
    want = jitted(reference.logits, c)(params, tokens)
    assert float(jnp.abs(got - want).max()) > 50 * LOGITS_ATOL


def test_the_two_shares_add_up_to_the_uncut_layer(setup):
    """Shares (0, E/2) and (E/2, E), the gated shared expert counted once,
    are the uncut layer: by the reference, and by the program's layer
    holding each share against the reference's uncut one."""
    c, model, params, _ = setup
    whole_c = conf(num_experts=16, held_experts="0-15")
    whole = family.build_model(whole_c, "float32", {"use_pallas": True,
                                                    "max_seq_len": 128})
    wp = perturbed(whole.init_params(jax.random.PRNGKey(5)), seed=6)
    mlp = jax.tree_util.tree_map(lambda a: a[0],
                                 wp["stacks"]["gdn0.experts"]["mlp"])
    m = jax.random.normal(jax.random.PRNGKey(8), (24, 64))
    with jax.default_matmul_precision("highest"):
        uncut = reference.moe_layer(whole_c, mlp, m)
        low = reference.moe_layer(whole_c, mlp, m, held=(0, 8))
        high = reference.moe_layer(whole_c, mlp, m, held=(8, 16),
                                   shared=False)
        assert float(jnp.abs(low + high - uncut).max()) < 1e-6
        # the program, a share at a time: its own slice of the experts
        from deeperspeed_tpu.moe.layer import moe_ffn_dropless
        parts = []
        for lo, hi in ((0, 8), (8, 16)):
            share = dict(mlp, w_in=mlp["w_in"][lo:hi],
                         w_out=mlp["w_out"][lo:hi])
            y, _ = moe_ffn_dropless(
                share, m, 4, norm_topk_prob=True, activation=jax.nn.silu,
                held=(lo, hi), score="softmax")
            parts.append(y)
        shared = jax.nn.sigmoid(m @ mlp["shared_gate"]) * \
            reference._gated(m, mlp["shared_in"], mlp["shared_out"])
    assert float(jnp.abs(parts[0] + parts[1] + shared - uncut).max()) < 2e-6
    # and a share is not the whole: the other half's part is real
    assert float(jnp.abs(high).max()) > 1e-4


# ---------------------------------------------------------------------------
# the engine: pages, slots, the plan's walk
# ---------------------------------------------------------------------------

PROMPTS, NEW = (11, 20, 5, 3), (12, 9, 14, 6)


def test_prefill_then_decode_equals_the_references_full_forward(setup):
    """Prompts shorter than their buckets (11 in 16, 20 in 32), page edges
    (8), a batch of mixed ages with one request that joins mid-way,
    lookahead on: every served token is the reference's argmax of a full
    forward over what was served, up to float32 rounding of its logit."""
    c, model, params, _ = setup
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, VOCAB, size=n).tolist() for n in PROMPTS]
    engine = engine_for(model, params)
    done = serve(engine, prompts, NEW, after={3: 4})
    assert [len(r.generated) for r in done] == list(NEW)
    assert all(r.status == "ok" for r in done)
    st = engine.stats
    assert st["lookahead_steps"] > 0
    assert st["state_bytes"] == engine.state_cache.bytes_per_sequence() == \
        5 * (3 * 128 * 4 + 4 * 16 * 16 * 4)
    assert st["state_slot_steps"] == st["decode_tokens"]
    assert st["gdn_state_updates"] == 5 * st["decode_tokens"]
    assert st["gdn_prefill_tokens"] == 5 * sum(PROMPTS)
    assert st["kv_bytes_per_token_full"] == 2 * 1 * 2 * 16 * 4
    # the held share: half the routed pairs, about; at least one expert a
    # routing layer-step and never more than the 8 held
    assert 0 < st["moe_rows_held"] < st["moe_rows_routed"]
    layer_steps = 6 * st["decode_steps"]
    assert layer_steps <= st["moe_experts_touched"] <= 8 * layer_steps
    # pools and slots drain: nothing leaks
    assert engine.cache.num_free == engine.cache.num_pages - 1
    assert engine.state_cache.num_free == 4 and \
        engine.state_cache.in_use == 0
    assert shortfall(c, params, done) <= LOGITS_ATOL


def test_a_request_that_joins_a_running_batch_is_served_what_it_is_alone(
        setup):
    c, model, params, _ = setup
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, VOCAB, size=n).tolist() for n in (9, 14, 7)]
    alone = serve(engine_for(model, params), prompts[2:], [12])[0]
    joined = serve(engine_for(model, params), prompts, [15, 15, 12],
                   after={2: 5})[2]
    assert joined.generated == alone.generated


def _live_request(engine, prompt, new, steps):
    rid = engine.submit(prompt, max_new_tokens=new)
    with jax.default_matmul_precision("highest"):
        for _ in range(steps):
            engine.step()
    return next(r for r in engine.scheduler.running if r.request_id == rid)


@pytest.mark.parametrize("bucket", [16, 32])
def test_the_state_after_a_padded_prefill_is_the_bare_prompts(setup, bucket):
    """A prompt of 11 through a bucket of 16 or 32: the slot holds the
    convolution rows and the matrix states after the prompt's LAST REAL
    token, whatever the bucket, and after 7 decode steps those after the
    last token fed; the pages hold the full layer's rows."""
    c, model, params, _ = setup
    prompt = np.random.default_rng(3).integers(1, VOCAB, size=11).tolist()
    engine = engine_for(model, params, prefill_lengths=[bucket])
    req = _live_request(engine, prompt, 20, 1)
    assert req.cached + req.pending in (11, 12)
    states = jitted(reference.states, c)
    for steps in (0, 7):
        with jax.default_matmul_precision("highest"):
            for _ in range(steps):
                engine.step()
        # the tokens that went through the model: those cached, and the
        # one a decode in flight took (read back the step before)
        fed = req.cached + req.pending
        row = np.zeros(64, np.int32)
        row[:fed] = (list(prompt) + list(req.generated))[:fed]
        want = states(params, jnp.asarray(row), fed)
        slot = req.state_slot
        assert relative(np.asarray(engine.state_cache.conv[:, slot]).reshape(
            want["conv"].shape), want["conv"]) < STATE_RTOL
        assert relative(engine.state_cache.ssm[:, slot],
                        want["state"]) < STATE_RTOL
        pages = np.asarray(req.pages)
        got = np.concatenate([
            np.moveaxis(np.asarray(pool[0, pages]), 1, 2).reshape(-1, 32)
            for pool in (engine.cache.k, engine.cache.v)], axis=-1)[:fed]
        assert relative(got, want["full"][0, :fed]) < STATE_RTOL


def test_a_slot_handed_on_starts_from_zero(setup):
    """One slot, two requests in turn: the second is served what a fresh
    engine serves it, though the slot held the first's state."""
    c, model, params, _ = setup
    rng = np.random.default_rng(4)
    first, second = (rng.integers(1, VOCAB, size=n).tolist()
                     for n in (13, 6))
    engine = engine_for(model, params, max_batch_size=1,
                        decode_batch_sizes=[1], num_pages=8 + 3)
    assert engine.state_cache.num_slots == 2
    serve(engine, [first], [8])
    assert np.any(np.asarray(engine.state_cache.ssm[:, 1]))
    got = serve(engine, [second], [8])[0]
    want = serve(engine_for(model, params), [second], [8])[0]
    assert got.generated == want.generated
    assert shortfall(c, params, [got]) <= LOGITS_ATOL


# ---------------------------------------------------------------------------
# what is not computed raises by name
# ---------------------------------------------------------------------------

def _cfg(**over):
    return dataclasses.replace(
        family.model_config(conf(), "float32", 128), **over)


@pytest.mark.parametrize("fields, match", [
    ({"gdn_conv": 1}, "at least 2 taps"),
    ({"gdn_value_heads": 3}, "multiple of the key heads"),
    ({"loop_steps": 2}, "a gdn layer with loop_steps=2"),
    ({"generation_block": 4, "mask_token_id": 1}, "generation_block=4"),
    ({"use_bias": True, "norm": "layernorm", "norm_unit_offset": False},
     "a gdn layer with use_bias=True"),
    ({"attn_gate": "per-feature"}, "attn_gate 'per-feature'"),
    ({"moe_shared_width": 0}, "moe_shared_gate without a shared expert"),
])
def test_a_plan_the_code_does_not_compute_raises_by_name(fields, match):
    with pytest.raises((ValueError, NotImplementedError), match=match):
        _cfg(**fields).check_block()


def test_the_facts_need_a_plan_and_the_family_holds_the_file_to_its_block():
    with pytest.raises(NotImplementedError, match="gdn_key_heads=2"):
        _cfg(layer_plan=()).check_block()
    plan = _cfg().layer_plan
    with pytest.raises(ValueError, match="without a gdn layer"):
        _cfg(layer_plan=(plan[3],) * 6).check_block()
    with pytest.raises(NotImplementedError, match="a gdn layer with window"):
        _cfg(layer_plan=plan[:5] + (dataclasses.replace(
            plan[3], attn="window"),), attn_window=8).check_block()
    with pytest.raises(ValueError, match="use_sliding_window"):
        family.model_config(conf(use_sliding_window=True), "float32")
    with pytest.raises(ValueError, match="held_experts"):
        family.model_config(conf(held_experts="0-3"), "float32")


@pytest.mark.parametrize("over, match", [
    ({"prefix_cache": {"enabled": True}}, "prefix_cache"),
    ({"kv_cache_dtype": "int8"}, "recurrent-state cache kind"),
    ({"num_pages": 4 * 8}, "recurrent-state cache kind"),
])
def test_serving_what_is_not_built_raises_by_name(setup, over, match):
    _, model, params, _ = setup
    with pytest.raises((DeepSpeedConfigError, ValueError), match=match):
        engine_for(model, params, **over)


def test_packed_rows_raise_by_name(setup):
    _, model, params, tokens = setup
    with pytest.raises(NotImplementedError, match="gdn layer"):
        neox._forward_hidden_planned(
            model.config, params, tokens, True, jnp.ones_like(tokens))
