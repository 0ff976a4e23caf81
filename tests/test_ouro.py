"""Ouro (ByteDance's looped language model: a stack of layers run
`total_ut_steps` times over the same weights, a KV cache a pass, a norm
on each sublayer's output, the final norm after every pass, an exit gate)
on the normal path against the plain reference
(`benchmarks/reference/ouro.py`), at a small size on the CPU: hidden 64,
2 heads of 32, 3 layers, T = 3, page 8 (the least the `inference` block
takes).

Both sides compute in float32, so the tolerances are those of float32
rounding in another order of summation (a paged cache against a full
forward, a scan against a loop).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.families import ouro as family
from benchmarks.reference import ouro as reference
from deeperspeed_tpu.inference import InferenceEngine
from deeperspeed_tpu.inference.kv_cache import PagedKVCache
from deeperspeed_tpu.models import gpt_neox as neox
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig, LayerSpec
from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError
from tests.model.references import jitted, reference_rows

VOCAB, PAGE, LAYERS, T = 128, 8, 3, 3
# float32 rounding through 3 x 3 layers on logits of size ~1 (measured
# 6e-7 on the forward, 2e-6 through the cache); a dropped norm, bias or
# pass moves them by 1e-2 and more (asserted below)
ATOL = 1e-4


def conf(**over):
    return {
        "family": "ouro", "model_type": "ouro", "head_dim": 32,
        "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 96,
        "layer_types": ["full_attention"] * LAYERS,
        "max_position_embeddings": 128, "num_attention_heads": 2,
        "num_hidden_layers": LAYERS, "num_key_value_heads": 2,
        "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "total_ut_steps": T,
        "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": VOCAB, **over}


def perturbed(params, seed=1):
    """Norm scales away from their init of 1 and the gate's bias away
    from 0, so that a misplaced or dropped one shows."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 100))

    def move(path, p):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return p + 0.1 * jax.random.normal(next(keys), p.shape)
        if "loop_exit" in name and "'b'" in name:
            return p - 0.7
        if "loop_exit" in name:
            return p * 20.0         # gates spread over (0.1, 0.9)
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
    inference = {"enabled": True, "page_size": PAGE, "num_pages": 24,
                 "max_seq_len": 64, "max_batch_size": 4,
                 "token_budget": 64, "prefill_lengths": [16, 32],
                 "prefill_batch_sizes": [1, 2],
                 "decode_batch_sizes": [4], **over}
    return InferenceEngine(model, config={"inference": inference},
                           params=params)


# ---------------------------------------------------------------------------
# the model's forward against the reference
# ---------------------------------------------------------------------------

def test_forward_logits_equal_the_references(setup):
    c, model, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        got = jitted(model.apply)(params, tokens)
    want = jitted(reference.logits, c)(params, tokens)
    assert float(jnp.abs(want).max()) > 0.3
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    # at the published threshold of 1 every token reads the last pass
    exits = jitted(reference.exit_passes, c)(params, tokens)
    assert (np.asarray(exits) == T).all()


@pytest.mark.parametrize("what", ["attn out norm", "mlp out norm",
                                  "final norm", "one pass fewer"])
def test_each_fact_of_the_loop_moves_the_logits(setup, what):
    """What the tolerance is tight enough to see: either norm on a
    sublayer's output, the final norm's scale (applied after EVERY
    pass), and the number of passes."""
    c, model, params, tokens = setup
    want = jitted(reference.logits, c)(params, tokens)
    stack = dict(params["stacks"]["full2.dense"])
    cfg = model.config
    if what == "attn out norm":
        stack["ln_attn_out"] = {"scale": jnp.ones((LAYERS, 64))}
    elif what == "mlp out norm":
        stack["ln_mlp_out"] = {"scale": jnp.ones((LAYERS, 64))}
    elif what == "one pass fewer":
        cfg = dataclasses.replace(cfg, loop_steps=T - 1)
    other = dict(params, stacks={"full2.dense": stack})
    if what == "final norm":
        other["final_ln"] = {"scale": jnp.ones((64,))}
    with jax.default_matmul_precision("highest"):
        got = jitted(neox.forward, cfg, use_pallas=False)(other, tokens)
    assert float(jnp.abs(got - want).max()) > 100 * ATOL


@pytest.mark.parametrize("threshold", [0.5])
def test_the_exit_gate_picks_the_references_pass(setup, threshold):
    """Per token: `t*` and the logits of `z_{t*}`. Under threshold 1 the
    perturbed gate spreads the tokens over the passes (else the test
    would show nothing)."""
    c, model, params, tokens = setup
    c = conf(early_exit_threshold=threshold)
    looped = family.build_model(c, "float32", {"use_pallas": False})
    want = np.asarray(jitted(reference.exit_passes, c)(params, tokens))
    z = jnp.stack([jitted(reference.passes, c)(params, row) for row in tokens],
                  axis=1)                                   # [T, B, S, h]
    _, got = neox.loop_exit(looped.config, params, z)
    np.testing.assert_array_equal(got, want)
    assert len(set(want.ravel().tolist())) == T
    with jax.default_matmul_precision("highest"):
        want = jitted(reference.logits, c)(params, tokens)
        np.testing.assert_allclose(jitted(looped.apply)(params, tokens),
                                   want,
                                   atol=ATOL, rtol=0)


def test_one_pass_without_output_norms_is_todays_planned_model(setup):
    """`loop_steps` 1 and no norm on a sublayer's output: the same tree
    (no gate, no `ln_*_out`), and the planned model's forward and the
    engine's tokens bit for bit."""
    _, model, _, tokens = setup
    plain = dataclasses.replace(model.config, loop_steps=1,
                                sublayer_out_norm=False)
    params = neox.init_params(plain, jax.random.PRNGKey(0))
    assert "loop_exit" not in params
    assert set(params["stacks"]["full2.dense"]) == {"ln_attn", "ln_mlp",
                                                    "attn", "mlp"}
    assert plain.num_params() == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    assert plain.cache_layers("full") == LAYERS

    def unlooped_forward(params, tokens):
        # the planned forward as it was before the loop: the layers once,
        # the final norm, the head
        x = params["embed"]["wte"][tokens]
        rotary = neox.plan_rotary(plain, tokens.shape[1])
        for spec, bp in neox.plan_layer_params(plain, params["stacks"]):
            x = neox.block_hidden(neox._block_core(
                plain, bp, x, rotary[spec.attn], False, mp=1,
                reduce_fn=lambda t: t, spec=spec))
        x = neox.norm(plain, params["final_ln"], x)
        return jnp.einsum("bsh,vh->bsv", x, params["embed_out"]["wte"],
                          preferred_element_type=jnp.float32)

    np.testing.assert_array_equal(
        neox.forward(plain, params, tokens, use_pallas=False),
        unlooped_forward(params, tokens))
    engine = engine_for(GPTNeoX(plain, use_pallas=False), params)
    assert engine.loop_steps == 1 and engine.cache.k.shape[0] == LAYERS
    out = engine.generate([[5, 9, 2, 77, 31]], max_new_tokens=12)[0]
    lg = unlooped_forward(params, jnp.asarray([[5, 9, 2, 77, 31] + out]))[0]
    assert out == np.asarray(lg[4:-1].argmax(-1)).tolist()
    assert "loop_exit_hist" not in engine.serve_stats()
    assert engine.stats["loop_passes"] == engine.stats["decode_steps"] + 1


def test_parameters_are_counted_once_and_cache_layers_a_pass(setup):
    c, model, params, _ = setup
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert model.config.num_params() == reference.num_params(c) == n
    assert model.config.cache_layers("full") == T * LAYERS
    assert model.config.cache_layers("window") == 0
    # the published model: 2,667,974,657 parameters, 192 cache layers
    published = family.model_config(dict(
        conf(), hidden_size=2048, num_hidden_layers=48,
        layer_types=["full_attention"] * 48, num_attention_heads=16,
        num_key_value_heads=16, head_dim=128, intermediate_size=5632,
        vocab_size=49152, total_ut_steps=4,
        max_position_embeddings=65536), "bfloat16")
    assert published.num_params() == 48 * 51388416 + 2 * 100663296 + \
        2048 + 2049 == 2667974657
    assert published.cache_layers("full") == 192
    cache = PagedKVCache(published.cache_layers("full"), 3, 16, 64, 128)
    assert cache.bytes_per_token() == 1572864


# ---------------------------------------------------------------------------
# serving: prefill then decode through a paged cache a pass
# ---------------------------------------------------------------------------

def _served_logits(c, params, request):
    """(the reference's logits at every served position, the served
    tokens)."""
    lg = reference_rows(reference, c, params,
                        list(request.prompt) + list(request.generated), 512)
    at = len(request.prompt) - 1 + np.arange(len(request.generated))
    return lg[at], np.asarray(request.generated)


@pytest.mark.parametrize("kernel,threshold", [("pallas", 0.5)])
def test_prefill_then_decode_equals_the_references_full_forward(
        setup, kernel, threshold):
    """Sequences of unequal length in one batch, 11 to 20 decode steps
    each across page edges (8), lookahead on. Logits, not tokens:
    every served token's reference logit is within float32 rounding of
    the reference's best at that position, over a full forward of what
    was served. `pallas` runs the paged kernels' bodies in interpret
    mode (the XLA twins serve the next test's request); at threshold 0.5
    the head reads each token's own pass, and the engine's count of
    tokens by exit pass is the reference's."""
    _, _, params, _ = setup
    c = conf(early_exit_threshold=threshold)
    model = family.build_model(c, "float32", {"use_pallas": False})
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, VOCAB, size=n).tolist() for n in (3, 14, 27)]
    new = [20, 14, 11]
    engine = engine_for(model, params, kernel=kernel)
    assert engine.cache.k.shape == (T * LAYERS, 24, 2, PAGE, 32)
    with jax.default_matmul_precision("highest"):
        ids = [engine.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, new)]
        while engine.scheduler.has_work:
            engine.step()
    done = {r.request_id: r for r in engine.scheduler.pop_finished()}
    assert [len(done[i].generated) for i in ids] == new
    assert all(done[i].status == "ok" for i in ids)
    hist = np.zeros(T, int)
    for r in done.values():
        lg, served = _served_logits(c, params, r)
        short = lg.max(-1) - lg[np.arange(len(served)), served]
        assert float(short.max()) <= ATOL
        row = jnp.asarray(list(r.prompt) + list(r.generated))[None]
        t_star = np.asarray(jitted(reference.exit_passes, c)(params, row))[0]
        hist += np.bincount(t_star[len(r.prompt) - 1:-1] - 1, minlength=T)
    stats = engine.serve_stats()
    assert stats["loop_exit_hist"] == hist.tolist()
    assert sum(hist) == sum(new) and (threshold < 1) == (hist[-1] < sum(new))
    assert stats["kv_bytes_per_token"] == T * LAYERS * 2 * 2 * 32 * 4
    # T passes a dispatched program: the decode steps, and the prefills
    # (two prompts may share one)
    prefills = stats["loop_passes"] / T - stats["decode_steps"]
    assert prefills in (2, 3)
    assert stats["lookahead_steps"] > 0
    assert stats["kv_page_steps_full"] * PAGE >= stats["decode_kv_tokens"]
    assert engine.cache.num_free == engine.cache.num_pages - 1


def test_each_pass_keeps_its_own_rows_in_the_cache(setup):
    """Every pass's cached K and V of every layer against the
    reference's rows of THAT pass (`cache_rows`: pass t of layer l at
    index (t - 1) L + l), for a request prefilled (a bucket of 16) and
    decoded over two page edges. A cache index that mixes passes, or a
    pass that reads or writes another's rows, fails: two passes' rows of
    one layer differ by far more than the tolerance (asserted)."""
    c, model, params, _ = setup
    engine = engine_for(model, params)
    prompt = np.random.default_rng(3).integers(1, VOCAB, size=11).tolist()
    with jax.default_matmul_precision("highest"):
        rid = engine.submit(prompt, max_new_tokens=20)
        request = None
        while engine.scheduler.has_work:
            engine.step()
            request = next((r for r in engine.scheduler.running
                            if r.request_id == rid), request)
            if len(request.generated) >= 14:
                break
    tokens = list(prompt) + list(request.generated)[:-1]
    n = len(tokens)
    assert n == 3 * PAGE            # the decode's writes crossed two page edges
    pages = np.asarray(request.pages, np.int32)

    def held(pool):                 # [L, P, H, page, D] -> [L, n, H * D]
        rows = jnp.moveaxis(pool[:, pages], 2, 3)
        return rows.reshape(pool.shape[0], -1, 64)[:, :n]

    got = jnp.concatenate([held(engine.cache.k), held(engine.cache.v)], -1)
    want = jitted(reference.cache_rows, c)(params, jnp.asarray(tokens))
    assert want.shape == (T * LAYERS, n, 2 * 2 * 32)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    for layer in range(LAYERS):
        for a in range(T):
            for b in range(a + 1, T):
                assert float(jnp.abs(want[a * LAYERS + layer] -
                                     want[b * LAYERS + layer]).max()) > 0.01


def test_a_bucket_under_the_flash_block_is_padded_not_sent_to_xla():
    """A 64-token prefill bucket at a head dim the flash forward takes
    (64; the cell's is 128): the kernel's least block is 128 rows, so the
    engine pads the attention's q, k, v and segment ids up to one block
    and gives the real rows back (`_prefill_fn.attention`). The kernel
    ran (not XLA: `serve_xla_fallbacks` counts that on the chip), and
    every pass's cached rows and the first token are the reference's: a
    pad key a real query could see, or a real row cut off, moves them."""
    from deeperspeed_tpu.ops.pallas.flash_attention import _LAST_BACKEND
    c = conf(head_dim=64, hidden_size=128, intermediate_size=192)
    model = family.build_model(c, "float32", {"use_pallas": True})
    params = perturbed(model.init_params(jax.random.PRNGKey(0)))
    engine = engine_for(model, params, prefill_lengths=[64],
                        max_seq_len=128, token_budget=128)
    prompt = np.random.default_rng(5).integers(1, VOCAB, size=41).tolist()
    _LAST_BACKEND.pop("attention", None)
    with jax.default_matmul_precision("highest"):
        rid = engine.submit(prompt, max_new_tokens=2)
        engine.step()
        request = next(r for r in engine.scheduler.running
                       if r.request_id == rid)
        pages = np.asarray(request.pages, np.int32)
        engine.drain()
    assert _LAST_BACKEND["attention"] == "pallas"
    assert ("prefill", 1, 64) in engine._compiled
    n = len(prompt)

    def held(pool):                 # [L, P, H, page, D] -> [L, n, H * D]
        rows = jnp.moveaxis(pool[:, pages], 2, 3)
        return rows.reshape(pool.shape[0], -1, 128)[:, :n]

    got = jnp.concatenate([held(engine.cache.k), held(engine.cache.v)], -1)
    want = jitted(reference.cache_rows, c)(params, jnp.asarray(prompt))
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    lg = np.asarray(jitted(reference.logits, c)(
        params, jnp.asarray(prompt)[None]))
    first = request.generated[0]
    assert float(lg[0, -1].max() - lg[0, -1, first]) <= ATOL


def test_the_weights_are_held_once_and_the_pools_carried(setup):
    """The engine runs from the model's own tree by reference (no second
    copy of the stack), and the compiled decode step takes each weight
    ONCE whatever the passes: the pass loop is one `while` whose body is
    traced once, not `loop_steps` copies of the stack's walk."""
    _, model, params, _ = setup
    engine = engine_for(model, params)
    assert engine.params_stacked is engine.params["stacks"]
    w = engine.params_stacked["full2.dense"]["mlp"]["in_w"]
    assert w.shape[0] == LAYERS

    def decode_text(engine):
        args = (engine.params, engine.params_stacked,
                jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32),
                {"full": jnp.zeros((4, engine.n_pages_max), jnp.int32)},
                engine._pools(), engine._next_rng(), engine._carry,
                jnp.full((4,), -1, jnp.int32))
        return engine._decode_fn(4).lower(*args).as_text()

    text = decode_text(engine)
    shorter = engine_for(GPTNeoX(dataclasses.replace(
        model.config, loop_steps=T - 1), use_pallas=False), params)
    # the same matmuls in the program's text whatever the passes
    assert text.count("dot_general") == \
        decode_text(shorter).count("dot_general") > 0
    # the pools go in donated and come out: input and output alias
    assert "tf.aliasing_output" in text or "jax.buffer_donor" in text


# ---------------------------------------------------------------------------
# what is not built raises by name
# ---------------------------------------------------------------------------

def _plan_config(**over):
    return dataclasses.replace(family.model_config(conf(), "float32"),
                               **over)


REFUSED_BLOCK = {
    "no passes": (dict(loop_steps=0), "loop_steps"),
    "threshold over 1": (dict(loop_exit_threshold=1.5),
                         "loop_exit_threshold"),
    "threshold 0": (dict(loop_exit_threshold=0.0), "loop_exit_threshold"),
    "a gate on one pass": (dict(loop_steps=1, loop_exit_threshold=0.5),
                           "one pass has no exit gate"),
    "loop with a nextn block": (dict(mtp_layers=1), "mtp_layers"),
    "output norm on experts": (dict(
        layer_plan=(LayerSpec(attn="full", heads=2, ffn="experts"),) * 3,
        moe_num_experts=4, moe_top_k=2, moe_dropless=True),
        "sublayer_out_norm"),
}


@pytest.mark.parametrize("fields,match", REFUSED_BLOCK.values(),
                         ids=REFUSED_BLOCK.keys())
def test_a_block_the_code_does_not_compute_raises_by_name(fields, match):
    with pytest.raises((NotImplementedError, ValueError), match=match):
        GPTNeoX(_plan_config(**fields))


@pytest.mark.parametrize("fields", [dict(loop_steps=2),
                                    dict(sublayer_out_norm=True),
                                    dict(loop_exit_threshold=0.5)])
def test_loop_facts_without_a_plan_are_refused(fields):
    with pytest.raises(NotImplementedError, match="without a layer_plan"):
        GPTNeoX(GPTNeoXConfig.tiny(**fields))


@pytest.mark.parametrize("key,value", [
    ("hidden_act", "gelu"), ("tie_word_embeddings", True),
    ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("use_sliding_window", True), ("attention_bias", True),
    ("mlp_bias", True), ("num_key_value_heads", 1),
    ("layer_types", ["full_attention", "sliding_attention",
                     "full_attention"])])
def test_the_family_refuses_a_config_it_does_not_compute(key, value):
    with pytest.raises(ValueError, match=key):
        family.model_config(conf(**{key: value}), "float32")


def test_the_homogeneous_generate_path_refuses_the_loop(setup):
    _, model, params, tokens = setup
    with pytest.raises(NotImplementedError, match="loop_steps=3"):
        model.generate(params, tokens[:, :4], max_new_tokens=2)


REFUSED_SERVING = {
    "prefix cache": (dict(prefix_cache={"enabled": True}), "prefix_cache"),
    "speculation": (dict(speculative={"enabled": True,
                                      "num_draft_tokens": 2}),
                    "speculative"),
    "handoff": (dict(disaggregation={"role": "prefill", "pool_id": "a"}),
                "handoff between pools"),
    "int8 pages": (dict(kv_cache_dtype="int8"), "looped model"),
}


@pytest.mark.parametrize("over,match", REFUSED_SERVING.values(),
                         ids=REFUSED_SERVING.keys())
def test_serving_what_is_not_built_raises_by_name(setup, over, match):
    _, model, params, _ = setup
    with pytest.raises(DeepSpeedConfigError, match=match) as err:
        engine_for(model, params, **over)
    assert "loop_steps=3" in str(err.value)


def test_training_and_pipeline_are_still_refused(setup):
    """`_refuse_planned_training` (what `initialize` and `loss_fn` call)
    names the loop; the pipeline's layer list knows one homogeneous
    block."""
    _, model, params, tokens = setup
    with pytest.raises(DeepSpeedConfigError, match="looped over its"):
        model._refuse_planned_training("initialize")
    with pytest.raises(DeepSpeedConfigError, match="training of a planned"):
        model.loss_fn(params, (tokens, tokens))
    with pytest.raises((DeepSpeedConfigError, NotImplementedError)):
        neox.to_layer_specs(model.config)
