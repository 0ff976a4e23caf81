"""phi4flash (Phi-4-mini-flash-reasoning's architecture: a PLANNED model of
Mamba-1, window and ONE full differential-attention layer, whose second
half is Gated Memory Units on the last Mamba layer's scan output and
cross attention over the full layer's K and V) on the normal path against
the plain reference (`benchmarks/reference/phi4flash.py`), at a small
size on the CPU: hidden 64, 8 heads of 8 (4 pairs of 16 over 2 KV heads),
window 16, page 8, inner 128, state 16, 8 layers in the published order
(ssm, window, ssm, window, ssm, full, gmu, cross).

Both sides compute in float32, so the tolerances are those of float32
rounding in another order of summation, each written where it is used
with what it would refuse.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.families import phi4flash as family
from benchmarks.reference import phi4flash as reference
from deeperspeed_tpu.inference import InferenceEngine
from deeperspeed_tpu.inference.kv_cache import PagedKVCache, StateCache
from deeperspeed_tpu.inference.scheduler import (ContinuousBatchingScheduler,
                                                 Request)
from deeperspeed_tpu.models import gpt_neox as neox
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig, LayerSpec
from deeperspeed_tpu.ops.pallas import ssm as ssm_ops
from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError
from tests.model.references import jitted, reference_rows

VOCAB, WINDOW, PAGE = 128, 16, 8
# float32 rounding through eight layers on logits of size ~0.5; a dropped
# bias, a lam0 of the wrong layer or a memory taken before the skip moves
# them by far more (asserted below)
LOGITS_ATOL = 1e-5
# a cached row or a recurrent state against the reference's, relative:
# float32 rounding through the layers before it
STATE_RTOL = 1e-4


def conf(layers=8):
    return {"family": "phi4flash", "model_type": "phi4flash",
            "hidden_act": "silu", "hidden_size": 64,
            "intermediate_size": 96, "layer_norm_eps": 1e-5,
            "max_position_embeddings": 256, "mb_per_layer": 2,
            "num_attention_heads": 8, "num_hidden_layers": layers,
            "num_key_value_heads": 4, "sliding_window": WINDOW,
            "tie_word_embeddings": True, "mlp_bias": False,
            "lm_head_bias": False, "vocab_size": VOCAB, "embd_pdrop": 0,
            "resid_pdrop": 0}


def perturbed(params, seed=1):
    """Norm scales and biases, every projection's bias, the convolution
    and the skip away from their init (1 or 0), so that a misplaced or
    dropped one shows."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 1000))

    def move(path, p):
        name = jax.tree_util.keystr(path)
        if p.ndim <= 2 and "lam0" not in name and "A_log" not in name \
                and "wte" not in name:
            return p + 0.1 * jax.random.normal(next(keys), p.shape)
        return p
    return jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def setup():
    c = conf()
    model = family.build_model(c, "float32", {"use_pallas": False})
    params = perturbed(model.init_params(jax.random.PRNGKey(0)))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 40), 0, VOCAB)
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
                            list(r.prompt) + list(r.generated), 512)
        at = len(r.prompt) - 1 + np.arange(len(r.generated))
        got = lg[at, np.asarray(r.generated)]
        worst = max(worst, float(np.max(lg[at].max(-1) - got)))
    return worst


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# ---------------------------------------------------------------------------
# the program's forward against the reference
# ---------------------------------------------------------------------------

def test_the_plan_the_stacks_and_the_parameter_count(setup):
    c, model, params, _ = setup
    cfg = model.config
    assert [s.attn for s in cfg.layer_plan] == reference.layer_kinds(c) == [
        "ssm", "window", "ssm", "window", "ssm", "full", "gmu", "cross"]
    assert cfg.head_dim == 16 and cfg.kv_heads == 2
    assert cfg.attn_scale == 1 / np.sqrt(8)
    # ONE cache layer of the full kind: the cross layer keeps none
    assert cfg.cache_layers("full") == 1 and cfg.cache_layers("window") == 2
    assert cfg.cache_layers("state") == 3 and cfg.last_row_from == 5
    assert set(params["stacks"]) == set(reference.stack_names(c).values())
    assert "embed_out" not in params                  # a tied head
    cross = params["stacks"]["cross4.dense"]["attn"]
    assert sorted(cross) == ["lam0", "lam_k1", "lam_k2", "lam_q1", "lam_q2",
                             "out_b", "out_w", "q_b", "q_w", "subln"]
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    lam0s = sum(1 for s in cfg.layer_plan
                if s.attn in ("window", "full", "cross"))
    assert n - lam0s == cfg.num_params() == reference.num_params(c)


def test_the_published_configuration_counts_its_parameters():
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs",
        "phi-4-mini-flash.json")
    with open(path) as f:
        published = json.load(f)
    cfg = family.model_config(published, "bfloat16")
    assert cfg.num_params() == reference.num_params(published) == \
        published["assumed"]["num_parameters"] == 3_852_562_944
    kinds = [s.attn for s in cfg.layer_plan]
    assert [kinds.count(k) for k in ("ssm", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "ssm" and kinds[17] == "full"
    assert (cfg.ssm_inner, cfg.ssm_state, cfg.ssm_conv, cfg.ssm_dt_rank) \
        == (5120, 16, 4, 160)
    assert (cfg.num_heads, cfg.kv_heads, cfg.head_dim) == (20, 10, 128)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_logits_agree_with_the_reference(setup, use_pallas):
    c, model, params, tokens = setup
    run = GPTNeoX(model.config, use_pallas=use_pallas)
    with jax.default_matmul_precision("highest"):
        got = jitted(run.apply)(params, tokens)
    want = jitted(reference.logits, c)(params, tokens)
    np.testing.assert_allclose(got, want, atol=LOGITS_ATOL, rtol=0)


def _edit(params, stack, leaf, fn, group="attn"):
    stacks = dict(params["stacks"])
    kind = dict(stacks[stack])
    kind[group] = dict(kind[group], **{leaf: fn(kind[group][leaf])})
    stacks[stack] = kind
    return dict(params, stacks=stacks)


WRONG = {
    "lam0 of the next layer": ("window4.dense", "lam0", lambda v: jnp.asarray(
        [neox.diff_lambda_init(2), neox.diff_lambda_init(4)], v.dtype)),
    "no bias on k and v": ("window4.dense", "kv_b", jnp.zeros_like),
    "no bias on the output projection": ("full4.dense", "out_b",
                                         jnp.zeros_like),
    "no skip in the memory": ("ssm0.dense", "D", jnp.zeros_like),
    "a gmu's gate is not silu of zero": ("gmu0.dense", "in_w",
                                         jnp.zeros_like),
}


@pytest.mark.parametrize("stack,leaf,fn", WRONG.values(), ids=WRONG.keys())
def test_the_tolerance_refuses_a_wrong_fact(setup, stack, leaf, fn):
    c, model, params, tokens = setup
    want = jitted(reference.logits, c)(params, tokens)
    with jax.default_matmul_precision("highest"):
        got = jitted(model.apply)(_edit(params, stack, leaf, fn), tokens)
    assert float(jnp.max(jnp.abs(got - want))) > 10 * LOGITS_ATOL


def test_lam0_follows_the_layers_index(setup):
    c, model, params, _ = setup
    cfg = model.config
    for name, (spec, layers) in cfg.plan_kinds().items():
        if spec.attn in ("ssm", "gmu"):
            assert "lam0" not in params["stacks"][name]["attn"]
            continue
        got = params["stacks"][name]["attn"]["lam0"]
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(
            got, [0.8 - 0.6 * np.exp(-0.3 * i) for i in layers], rtol=1e-6)
        assert [reference.lam0(i) for i in layers] == \
            [neox.diff_lambda_init(i) for i in layers]
    assert cfg.plan_kinds()["full4.dense"][1] == [5]


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("window", [None, WINDOW], ids=["full", "window"])
def test_the_zero_padded_route_equals_the_four_softmax_definition(
        setup, window, use_pallas):
    """Two query heads a pair, [q1 | 0] and [0 | q2], over the KV head's
    [k1 | k2] and whole-width v on the attention every layer uses, against
    the reference's four softmax-weighted sums: float32 rounding."""
    c, model, params, _ = setup
    cfg = model.config
    layer = 1 if window else 5
    name = "window4.dense" if window else "full4.dense"
    p = jax.tree_util.tree_map(lambda a: a[0], params["stacks"][name])["attn"]
    a = jax.random.normal(jax.random.PRNGKey(7), (128, 64))
    with jax.default_matmul_precision("highest"):
        k, v = reference.keys_values(c, p, a)
        want = reference.diff_attention(c, layer, p, a, k, v, window)
        q = (a @ p["q_w"] + p["q_b"]).reshape(1, 128, 4, 16)
        out = neox.causal_attention(
            neox.diff_queries(q), k.reshape(1, 128, 2, 16), v[None],
            use_pallas=use_pallas, window=window, sm_scale=cfg.attn_scale)
        got = neox.diff_combine(cfg, p, out).reshape(128, -1) @ p["out_w"] \
            + p["out_b"]
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)


# ---------------------------------------------------------------------------
# the scan: kernel against XLA, the step against the walk
# ---------------------------------------------------------------------------

def _scan_operands(B=2, S=16, d=1024, N=4):
    def rnd(k, *s):
        return jax.random.normal(jax.random.PRNGKey(k), s, jnp.float32)
    return (jax.nn.softplus(rnd(0, B, S, d)), rnd(1, B, S, d),
            rnd(2, B, S, N), rnd(3, B, S, N), -jnp.exp(0.3 * rnd(4, N, d)),
            rnd(5, d))


@pytest.mark.parametrize("d", [1024, 96], ids=["registers", "one-row"])
def test_the_scan_and_the_step_kernels_agree_with_xla(d):
    dt, x, Bm, Cm, A, D = _scan_operands(d=d)
    s_x, h_x = ssm_ops.ssm_scan(dt, x, Bm, Cm, A, D, backend="xla")
    s_p, h_p = ssm_ops.ssm_scan(dt, x, Bm, Cm, A, D, backend="pallas")
    np.testing.assert_allclose(s_p, s_x, atol=2e-5)
    np.testing.assert_allclose(h_p, h_x, atol=2e-5)
    assert h_p.shape == (2, 4, *ssm_ops.state_tile(d))
    pool = jax.random.normal(jax.random.PRNGKey(6),
                             (3, 5, 4, *ssm_ops.state_tile(d)))
    slots, layer = jnp.asarray([2, 4]), jnp.int32(1)
    conv = jax.random.normal(jax.random.PRNGKey(8),
                             (3, 5, 3, *ssm_ops.state_tile(d)))
    tail = jax.random.normal(jax.random.PRNGKey(9), (2, 3, d))
    args = (tail, slots, layer, dt[:, 0], x[:, 0], Bm[:, 0], Cm[:, 0], A, D)
    s_x, (conv_x, pool_x) = ssm_ops.ssm_step((conv, pool), *args,
                                             backend="xla")
    s_p, (conv_p, pool_p) = ssm_ops.ssm_step((conv, pool), *args,
                                             backend="pallas")
    np.testing.assert_allclose(s_p, s_x, atol=2e-5)
    np.testing.assert_allclose(pool_p, pool_x, atol=2e-5)
    np.testing.assert_array_equal(conv_p, conv_x)
    np.testing.assert_array_equal(
        np.asarray(conv_p[1, 4]).reshape(3, d), tail[1])
    # the other slots and layers are where they were
    untouched = np.ones(pool.shape[:2], bool)
    untouched[1, [2, 4]] = False
    np.testing.assert_array_equal(np.asarray(pool_p)[untouched],
                                  np.asarray(pool)[untouched])


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_a_row_whose_step_is_zero_moves_no_state(backend):
    dt, x, Bm, Cm, A, D = _scan_operands()
    real = jnp.arange(16)[None, :] < jnp.asarray([[11], [16]])
    _, h_pad = ssm_ops.ssm_scan(jnp.where(real[..., None], dt, 0.0), x, Bm,
                                Cm, A, D, backend=backend)
    _, h_bare = ssm_ops.ssm_scan(dt[:1, :11], x[:1, :11], Bm[:1, :11],
                                 Cm[:1, :11], A, D, backend="xla")
    np.testing.assert_allclose(h_pad[0], h_bare[0], atol=2e-5)


def test_decodes_memory_and_state_equal_the_full_forwards(setup):
    """One token at a time through `ssm_token` from a zeroed slot: each
    step's memory m_t, and the state left at the end, are `ssm_mixer`'s
    over the whole sequence."""
    c, model, params, _ = setup
    cfg = model.config
    p = jax.tree_util.tree_map(lambda a: a[1],
                               params["stacks"]["ssm0.dense"])["attn"]
    a = jax.random.normal(jax.random.PRNGKey(5), (1, 20, 64))
    with jax.default_matmul_precision("highest"):
        out, (tail, h, mem) = neox.ssm_mixer(cfg, p, a, use_pallas=False)
        cache = StateCache(3, 4, *cfg.state_shapes, jnp.float32)
        state, slots = (cache.conv, cache.ssm), jnp.asarray([3])
        for t in range(20):
            o_t, state, m_t = neox.ssm_token(
                cfg, p, a[:, t:t + 1], state, slots, jnp.int32(2),
                jnp.asarray([True]), backend="xla")
            np.testing.assert_allclose(m_t[:, 0], mem[:, t], atol=1e-5)
            np.testing.assert_allclose(o_t[:, 0], out[:, t], atol=1e-5)
    np.testing.assert_allclose(state[0][2, 3], tail[0], atol=1e-6)
    assert state[0].shape == (3, 4, 3, 1, 128)
    np.testing.assert_allclose(state[1][2, 3], h[0], atol=1e-5)
    assert not np.any(np.asarray(state[1][:2])) and \
        not np.any(np.asarray(state[1][2, :3]))
    # an inactive row moves nothing
    _, same, _ = neox.ssm_token(cfg, p, a[:, :1], state, slots,
                                jnp.int32(2), jnp.asarray([False]),
                                backend="xla")
    np.testing.assert_array_equal(same[0], state[0])
    np.testing.assert_array_equal(same[1], state[1])


# ---------------------------------------------------------------------------
# the engine: prefill, then decode through the full, window and state kinds
# ---------------------------------------------------------------------------

PROMPTS, NEW = (11, 20, 5, 3), (22, 16, 28, 8)


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_prefill_then_decode_equals_the_references_full_forward(
        setup, kernel):
    """Prompts shorter than their buckets (11 in 16, 20 in 32), contexts
    that cross the window (16), page edges (8) and released pages, a
    batch of mixed ages with one request that joins mid-way, lookahead
    on: every served token is the reference's argmax of a full forward
    over what was served, up to float32 rounding of its logit."""
    c, model, params, _ = setup
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, VOCAB, size=n).tolist() for n in PROMPTS]
    new = NEW if kernel == "xla" else (6, 5, 7, 3)
    if kernel == "pallas":
        model = GPTNeoX(model.config, use_pallas=True)
    engine = engine_for(model, params, kernel=kernel)
    done = serve(engine, prompts, new, after={3: 4})
    assert [len(r.generated) for r in done] == list(new)
    assert all(r.status == "ok" for r in done)
    st = engine.stats
    assert st["lookahead_steps"] > 0
    # the cross half of a prefill runs on the last row alone
    assert st["prefill_rows"] == 16 + 32 + 16 + 16
    assert st["prefill_rows_cross"] == st["prefill_requests"] == 4
    assert st["state_bytes"] == engine.state_cache.bytes_per_sequence() == \
        3 * (3 * 128 * 4 + 16 * 128 * 4)
    assert st["state_slot_steps"] == st["decode_tokens"]
    assert st["kv_bytes_per_token_full"] == 2 * 1 * 2 * 16 * 4
    assert st["kv_bytes_per_token_window"] == 2 * 2 * 2 * 16 * 4
    # pools and slots drain: nothing leaks
    assert engine.cache.num_free == engine.cache.num_pages - 1
    assert engine.window_cache.num_free == engine.window_cache.num_pages - 1
    assert engine.state_cache.num_free == 4 and \
        engine.state_cache.in_use == 0
    assert shortfall(c, params, done) <= LOGITS_ATOL


def test_a_request_that_joins_a_running_batch_is_served_what_it_is_alone(
        setup):
    c, model, params, _ = setup
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, VOCAB, size=n).tolist() for n in (9, 14, 7)]
    alone = serve(engine_for(model, params), prompts[2:], [20])[0]
    joined = serve(engine_for(model, params), prompts, [25, 25, 20],
                   after={2: 9})[2]
    assert joined.generated == alone.generated


def _live_request(engine, prompt, new, steps):
    rid = engine.submit(prompt, max_new_tokens=new)
    with jax.default_matmul_precision("highest"):
        for _ in range(steps):
            engine.step()
    return next(r for r in engine.scheduler.running if r.request_id == rid)


def _held_state(engine, request):
    slot = request.state_slot
    return (np.asarray(engine.state_cache.conv[:, slot]).reshape(
        engine.state_cache.num_layers, -1),
            np.asarray(engine.state_cache.ssm[:, slot]).reshape(
                engine.state_cache.num_layers, 16, -1))


@pytest.mark.parametrize("bucket", [16, 32])
def test_the_state_after_a_padded_prefill_is_the_bare_prompts(setup, bucket):
    """A prompt of 11 through a bucket of 16 or 32: the slot holds the
    convolution rows and the scan state after the prompt's LAST REAL
    token, whatever the bucket, and after 12 decode steps those after
    the last token fed; the pages hold the full layer's and the window
    layers' rows."""
    c, model, params, _ = setup
    prompt = np.random.default_rng(3).integers(1, VOCAB, size=11).tolist()
    engine = engine_for(model, params, prefill_lengths=[bucket])
    req = _live_request(engine, prompt, 30, 1)
    assert req.cached + req.pending in (11, 12)
    for steps in (0, 12):
        with jax.default_matmul_precision("highest"):
            for _ in range(steps):
                engine.step()
        # the tokens that went through the model: those cached, and the
        # one a decode in flight took (read back the step before)
        fed = req.cached + req.pending
        row = np.zeros(64, np.int32)
        row[:fed] = (list(prompt) + list(req.generated))[:fed]
        want = reference.states(c, params, jnp.asarray(row), fed)
        conv, ssm = _held_state(engine, req)
        assert relative(conv, want["conv"].reshape(3, -1)) < STATE_RTOL
        assert relative(ssm, want["ssm"]) < STATE_RTOL
        full = engine.cache.k[0, np.asarray(req.pages)]
        got = np.moveaxis(np.asarray(full), 1, 2).reshape(-1, 2 * 16)[:fed]
        assert relative(got, want["full"][0, :fed, :32]) < STATE_RTOL


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
    serve(engine, [first], [12])
    assert np.any(np.asarray(engine.state_cache.ssm[:, 1]))
    got = serve(engine, [second], [12])[0]
    want = serve(engine_for(model, params), [second], [12])[0]
    assert got.generated == want.generated
    assert shortfall(c, params, [got]) <= LOGITS_ATOL


def test_the_allocators_give_a_request_every_kind_or_none():
    full = PagedKVCache(1, 20, 2, PAGE, 16)
    window = PagedKVCache(2, 13, 2, PAGE, 16)
    state = StateCache(3, 3, (3, 1, 128), (16, 1, 128), jnp.float32)
    sched = ContinuousBatchingScheduler(
        full, max_seq_len=64, token_budget=64, max_batch_size=4,
        prefill_lengths=[16], prefill_batch_sizes=[1],
        decode_batch_sizes=[4], window_cache=window, window=WINDOW,
        state_cache=state)
    reqs = [Request(prompt=[1] * 9, max_new_tokens=4) for _ in range(3)]
    for r in reqs:
        sched.add_request(r)
    for _ in range(3):
        plan = sched.schedule()
        for r in plan.prefills:
            sched.complete_prefill(r, 1)
    # two slots beside the trash slot: the third request waits, and holds
    # no page of either kind
    assert [r.state_slot for r in reqs] == [1, 2, 0]
    assert reqs[2].pages == [] and reqs[2].window_pages == []
    assert full.num_free == 19 - 4 and state.num_free == 0
    sched._finish(reqs[0], "ok")
    assert state.num_free == 1 and reqs[0].state_slot == 0
    assert sched.schedule().prefills == [reqs[2]] and reqs[2].state_slot == 1
    with pytest.raises(ValueError, match="double free of state slot"):
        state.free(2) or state.free(2)


# ---------------------------------------------------------------------------
# what is not built raises by name
# ---------------------------------------------------------------------------

def _plan_config(**over):
    return dataclasses.replace(family.model_config(conf(), "float32"),
                               **over)


def _plan(*kinds):
    return tuple(LayerSpec(attn=k, heads=0 if k in ("ssm", "gmu") else 4,
                           rotary_pct=0.0) for k in kinds)


REFUSED_BLOCK = {
    "a cross layer before the full layer": (
        dict(num_layers=3, layer_plan=_plan("ssm", "cross", "full")),
        "cross layer behind 0 full layers"),
    "a cross layer behind two full layers": (
        dict(num_layers=3, layer_plan=_plan("full", "full", "cross"),
             ssm_inner=0, ssm_state=0, ssm_conv=0, ssm_dt_rank=0),
        "cross layer behind 2 full layers"),
    "a gmu layer without a memory": (
        dict(num_layers=2, layer_plan=_plan("gmu", "ssm")),
        "gmu layer with no ssm layer"),
    "an ssm layer without its sizes": (dict(ssm_state=0), "ssm_state"),
    "ssm sizes without an ssm layer": (
        dict(num_layers=2, layer_plan=_plan("window", "full")),
        "without an ssm layer"),
    "heads on an ssm layer": (
        dict(num_layers=1, layer_plan=(LayerSpec(attn="ssm", heads=4),)),
        "has\\s+no heads"),
    "differential attention with a rotary": (
        dict(layer_plan=tuple(dataclasses.replace(s, rotary_pct=1.0)
                              for s in family.layer_plan(conf()))),
        "attn_diff with"),
    "differential attention with a head norm": (dict(qk_norm="head"),
                                                "attn_diff with"),
    "layernorm without its biases": (dict(use_bias=False), "use_bias"),
    "biases without layernorm": (dict(norm="rmsnorm"), "norm="),
    "a loop": (dict(loop_steps=2), "loop_steps"),
    "a next-token block": (dict(mtp_layers=1), "mtp_layers"),
    "an unknown mixer": (
        dict(layer_plan=_plan(*["linear"] * 8)), "full . window"),
}


@pytest.mark.parametrize("fields,match", REFUSED_BLOCK.values(),
                         ids=REFUSED_BLOCK.keys())
def test_a_plan_the_code_does_not_compute_raises_by_name(fields, match):
    with pytest.raises((NotImplementedError, ValueError), match=match):
        GPTNeoX(_plan_config(**fields))


def test_the_facts_need_a_plan_and_the_family_holds_the_file_to_its_block():
    with pytest.raises(NotImplementedError, match="without a layer_plan"):
        GPTNeoX(GPTNeoXConfig.tiny(attn_diff=True))
    with pytest.raises(NotImplementedError, match="without a layer_plan"):
        GPTNeoX(GPTNeoXConfig.tiny(ssm_inner=128))
    with pytest.raises(ValueError, match="mlp_bias"):
        family.model_config(dict(conf(), mlp_bias=True), "float32")
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        family.model_config(dict(conf(), tie_word_embeddings=False),
                            "float32")
    with pytest.raises(ValueError, match="multiple of 4"):
        family.model_config(conf(layers=6), "float32")


# what the planned models before this one were refused is refused still
STILL_REFUSED = {
    "layernorm alone": (dict(norm="layernorm"), "norm="),
    "biases alone": (dict(use_bias=True), "use_bias"),
    "a norm over all of q": (dict(qk_norm=True), "qk_norm"),
    "a parallel residual": (dict(use_parallel_residual=True),
                            "use_parallel_residual"),
    "an ungated FFN": (dict(ffn_gated=False), "ffn_gated"),
    "a window layer without a window": (
        dict(layer_plan=(LayerSpec(attn="window", heads=4),) * 2),
        "attn_window"),
}


@pytest.mark.parametrize("fields,match", STILL_REFUSED.values(),
                         ids=STILL_REFUSED.keys())
def test_an_earlier_planned_blocks_refusals_still_fire(fields, match):
    plain = dict(vocab_size=VOCAB, hidden_size=64, num_layers=2, num_heads=4,
                 max_seq_len=64, norm="rmsnorm", use_bias=False,
                 use_parallel_residual=False, ffn_gated=True,
                 hidden_act="silu",
                 layer_plan=(LayerSpec(attn="full", heads=4),) * 2)
    GPTNeoX(GPTNeoXConfig(**plain))
    with pytest.raises((NotImplementedError, ValueError), match=match):
        GPTNeoX(GPTNeoXConfig(**{**plain, **fields}))


REFUSED_SERVING = {
    "prefix cache": (dict(prefix_cache={"enabled": True}), "prefix_cache"),
    "speculation": (dict(speculative={"enabled": True,
                                      "num_draft_tokens": 2}),
                    "speculative"),
    "handoff": (dict(disaggregation={"role": "prefill", "pool_id": "a"}),
                "handoff between pools"),
    "int8 kv": (dict(kv_cache_dtype="int8"), "int8"),
    "pools a request could be evicted from": (
        dict(num_pages=4 * 8), "recurrent-state cache kind"),
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
            "max_seq_len": 64}}, params=params, mesh=mesh)


def test_a_dry_pool_raises_and_never_drops_a_state():
    full = PagedKVCache(1, 4, 2, PAGE, 16)
    sched = ContinuousBatchingScheduler(
        full, max_seq_len=64, token_budget=64, max_batch_size=4,
        prefill_lengths=[16], prefill_batch_sizes=[1],
        decode_batch_sizes=[4],
        state_cache=StateCache(3, 5, (3, 1, 128), (16, 1, 128), jnp.float32))
    req = Request(prompt=[1] * 16, max_new_tokens=30)
    sched.add_request(req)
    sched.complete_prefill(sched.schedule().prefills[0], 1)
    for _ in range(8):
        sched.schedule()
        sched.complete_decode(req, 1)
    with pytest.raises(RuntimeError, match="recurrent-state cache kind"):
        sched.schedule()
    for bad in (dict(spec_tokens=2), dict(block=4)):
        with pytest.raises(ValueError, match="recurrent-state"):
            ContinuousBatchingScheduler(
                PagedKVCache(1, 9, 2, PAGE, 16), max_seq_len=64,
                token_budget=64, max_batch_size=4, prefill_lengths=[16],
                prefill_batch_sizes=[1], decode_batch_sizes=[4],
                state_cache=StateCache(3, 5, (3, 1, 128), (16, 1, 128), jnp.float32),
                **bad)


def test_training_and_packed_rows_raise_by_name(setup):
    import deeperspeed_tpu
    _, model, params, tokens = setup
    with pytest.raises(DeepSpeedConfigError, match="state-space layer"):
        model.loss_fn(params, (tokens, tokens))
    with pytest.raises(DeepSpeedConfigError, match="training of a planned"):
        deeperspeed_tpu.initialize(
            model=GPTNeoX(model.config, use_pallas=False),
            config_params={"train_batch_size": 8,
                           "optimizer": {"type": "Adam",
                                         "params": {"lr": 1e-3}}})
    with pytest.raises(NotImplementedError, match="packed rows"):
        neox._forward_hidden_planned(
            model.config, params, tokens, False,
            jnp.ones(tokens.shape, jnp.int32))
