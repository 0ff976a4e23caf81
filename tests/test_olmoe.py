"""OLMoE on the normal path against the plain reference
(`benchmarks/reference/olmoe.py`), at a small size on the CPU: RMSNorm,
QK-norm, SiLU-gated experts behind a router that drops nothing, through
the shared block, the sort dispatch, the ragged grouped matmul, the
train engine and the serving engine.

Both sides compute in float32 here, so the tolerances are those of
float32 rounding in another order of summation, each written where it is
used with what it would refuse. Router near-ties (two correct
computations choosing different experts at the k-th probability):
float32 on both sides leaves none at these seeds. `ROUTER_GAP` below
measures it: the smallest gap between the k-th and (k+1)-th probability
over every token and layer is far above the 1e-6 the two sides differ by,
so no token is excluded (the test says how many would be: 0).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.reference import olmoe as reference
from deeperspeed_tpu.models import gpt_neox as neox
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.moe.layer import dropless_geometry, moe_ffn_dropless
from tests.model.references import jitted, reference_rows

# the public config.json's keys at a small size: 16 experts of which 2 a
# token, so that with 24 tokens some experts get none
CONF = dict(hidden_act="silu", attention_bias=False, clip_qkv=None,
            rope_scaling=None, tie_word_embeddings=False,
            num_key_value_heads=4, vocab_size=512, hidden_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=256, rope_theta=10000,
            rms_norm_eps=1e-5, intermediate_size=64, num_experts=16,
            num_experts_per_tok=2, norm_topk_prob=False,
            router_aux_loss_coef=0.01)
# float32 rounding through two layers on logits of size ~1; a bf16
# matmul pass moves them by 7.5e-3 (asserted below)
LOGITS_ATOL = 1e-4
# relative to a leaf's largest gradient entry: float32 rounding through
# the backward pass; a bf16 pass would be ~1e-2
GRAD_RTOL = 1e-3


def config(**over):
    c = dict(CONF, **over)
    return GPTNeoXConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        max_seq_len=c["max_position_embeddings"], rotary_pct=1.0,
        rotary_emb_base=c["rope_theta"], layernorm_eps=c["rms_norm_eps"],
        use_parallel_residual=False, norm="rmsnorm", use_bias=False,
        qk_norm=True, hidden_act="silu", ffn_gated=True,
        ffn_width=c["intermediate_size"], moe_num_experts=c["num_experts"],
        moe_top_k=c["num_experts_per_tok"], moe_dropless=True,
        moe_norm_topk_prob=c["norm_topk_prob"],
        moe_aux_loss_coef=c["router_aux_loss_coef"])


def perturbed(params, seed=1):
    """Norm scales away from their init of 1, so a misplaced one shows."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 1000))
    return jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(next(keys), p.shape)
        if p.ndim == 1 else p, params)


@pytest.fixture(scope="module")
def setup():
    model = GPTNeoX(config(), use_pallas=False)
    params = perturbed(model.init_params(jax.random.PRNGKey(0)))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0,
                                CONF["vocab_size"])
    return model, params, tokens


def routed(params, tokens):
    """The reference's router decisions: per layer (probs, chosen)."""
    with jax.default_matmul_precision("highest"):
        return reference._forward(CONF, params, tokens)[1]


def test_some_expert_gets_no_token_and_no_router_near_tie(setup):
    _, params, tokens = setup
    k = CONF["num_experts_per_tok"]
    empty, gaps = 0, []
    for probs, chosen in routed(params, tokens):
        empty += int((np.asarray(chosen).sum(0) == 0).sum())
        top = np.sort(np.asarray(probs), axis=-1)[:, ::-1]
        gaps.append(top[:, k - 1] - top[:, k])
    assert empty >= 4, "the case must hold experts without a token"
    gaps = np.concatenate(gaps)
    excluded = int((gaps < 1e-5).sum())
    assert excluded == 0, f"{excluded} tokens lie at a router near-tie"


@pytest.mark.parametrize("norm_topk_prob", [False, True],
                         ids=["weights_as_they_are", "weights_renormalised"])
def test_logits_agree_with_the_reference(setup, norm_topk_prob):
    _, params, tokens = setup
    model = GPTNeoX(config(norm_topk_prob=norm_topk_prob), use_pallas=False)
    ours = np.asarray(jitted(model.apply)(params, tokens))
    theirs = np.asarray(jitted(
        reference.logits, dict(CONF, norm_topk_prob=norm_topk_prob))(
        params, tokens))
    assert np.abs(ours - theirs).max() <= LOGITS_ATOL
    other = np.asarray(jitted(
        reference.logits, dict(CONF, norm_topk_prob=not norm_topk_prob))(
        params, tokens))
    assert np.abs(ours - other).max() > 100 * LOGITS_ATOL


def test_the_tolerance_refuses_a_bfloat16_pass(setup):
    """The same program on weights rounded to bfloat16, computing in
    bfloat16, is 100 times further from the reference than allowed."""
    model, params, tokens = setup
    low = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16) if p.ndim >= 2 else p, params)
    ours = np.asarray(jitted(model.apply)(low, tokens), np.float32)
    theirs = np.asarray(jitted(reference.logits, CONF)(params, tokens))
    assert np.abs(ours - theirs).max() > 20 * LOGITS_ATOL


def test_loss_with_its_aux_term_agrees_and_the_aux_term_counts(setup):
    model, params, tokens = setup
    ours = float(jitted(model.loss_fn)(params, (tokens, tokens)))
    theirs = float(jitted(reference.loss, CONF)(params, tokens, tokens))
    # two float32 scalars of size ~6 from the same arithmetic
    assert abs(ours - theirs) <= 1e-5 * abs(theirs)
    no_aux = float(jitted(reference.loss,
                          dict(CONF, router_aux_loss_coef=0.0))(
        params, tokens, tokens))
    assert abs(theirs - no_aux) > 1e-3      # ~0.01 * (about 1)


def _leaf_paths(tree):
    return [("/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path), leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]]


def test_gradient_of_every_leaf_agrees_with_the_reference(setup):
    """Router, gate/up/down of every expert (an expert without a token
    included: its gradient is exactly zero on both sides), the four
    norms' scales of each block, the embeddings and the head."""
    model, params, tokens = setup
    ours = jax.jit(jax.grad(model.loss_fn))(params, (tokens, tokens))
    theirs = jax.jit(jax.grad(
        lambda p: reference.loss(CONF, p, tokens, tokens)))(params)
    names = []
    for (name, a), (_, b) in zip(_leaf_paths(ours), _leaf_paths(theirs)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.abs(b).max() > 0, f"{name}: the reference's is all zero"
        assert np.abs(a - b).max() <= GRAD_RTOL * np.abs(b).max(), name
        names.append(name)
    for want in ("mlp/gate", "mlp/w_in", "mlp/w_out", "ln_attn/scale",
                 "ln_mlp/scale", "attn/q_norm/scale", "attn/k_norm/scale",
                 "final_ln/scale"):
        assert any(want in n for n in names), want
    # per expert: an expert no token chose has a zero gradient, not an
    # unvisited block of the dw kernel's output
    for layer, (_, chosen) in enumerate(routed(params, tokens)):
        unused = np.asarray(chosen).sum(0) == 0
        for key in ("w_in", "w_out"):
            g = np.asarray(ours["blocks"][layer]["mlp"][key])
            ref = np.asarray(theirs["blocks"][layer]["mlp"][key])
            assert not g[unused].any() and not ref[unused].any()
            for e in np.flatnonzero(~unused):
                assert np.abs(g[e] - ref[e]).max() <= \
                    GRAD_RTOL * np.abs(ref).max(), (layer, key, e)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_dropless_layer_kernel_path_and_padding_rows(setup, backend):
    """The layer alone, through the interpreted kernel and through the
    XLA fallback: a padded row is routed nowhere, comes out zero, and
    leaves every real token's result as it is alone."""
    _, params, _ = setup
    mlp = params["blocks"][0]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(5), (11, CONF["hidden_size"]))
    alone, stats = moe_ffn_dropless(mlp, x, 2, gmm_backend=backend)
    probs, weights = reference.router(CONF, params["blocks"][0], x)
    with jax.default_matmul_precision("highest"):
        want = reference._experts(CONF, params["blocks"][0], x, weights)
    assert np.abs(np.asarray(alone) - np.asarray(want)).max() <= 1e-5
    np.testing.assert_allclose(np.asarray(stats[1]),
                               np.asarray(probs).mean(0), atol=1e-6)
    np.testing.assert_allclose(np.asarray(stats[0]),
                               np.asarray(weights > 0).sum(0) / 22.0,
                               atol=1e-6)
    # the same tokens among 5 padding rows (garbage in them)
    padded = jnp.concatenate([x[:4], 9.0 * jnp.ones((5, x.shape[1])), x[4:]])
    mask = jnp.asarray([True] * 4 + [False] * 5 + [True] * 7)
    y, stats_p = moe_ffn_dropless(mlp, padded, 2, token_mask=mask,
                                  gmm_backend=backend)
    assert not np.asarray(y[4:9]).any()
    np.testing.assert_allclose(np.asarray(y[mask]), np.asarray(alone),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(stats_p), np.asarray(stats),
                               atol=1e-6)


def test_padded_tokens_do_not_move_the_real_tokens_hidden_states(setup):
    """A sequence shorter than its bucket (segment id 0 on the tail, as
    the serving prefill marks it): the real positions' hidden states are
    those of the sequence alone."""
    model, params, tokens = setup
    alone = neox.forward_hidden(model.config, params, tokens[:1],
                                use_pallas=False)[0]
    padded = jnp.concatenate([tokens[:1], tokens[1:, :9]], axis=1)
    seg = jnp.concatenate([jnp.ones((1, 12), jnp.int32),
                           jnp.zeros((1, 9), jnp.int32)], axis=1)
    out = neox.forward_hidden(model.config, params, padded, use_pallas=False,
                              segment_ids=seg)[0]
    assert np.abs(np.asarray(out[:, :12]) - np.asarray(alone)).max() <= 1e-5


def _serve(model, params, prompts, max_new=6, **over):
    from deeperspeed_tpu.inference import InferenceEngine
    engine = InferenceEngine(model, params=params, config={"inference": dict({
        "enabled": True, "page_size": 16, "num_pages": 64,
        "max_batch_size": 4, "token_budget": 256,
        "prefill_lengths": [64, 128], "prefill_batch_sizes": [1],
        "decode_batch_sizes": [4], "kernel": "pallas"}, **over)})
    ids = [engine.submit(p, max_new_tokens=max_new) for p in prompts]
    done = {}
    while engine.scheduler.has_work:
        engine.step()
        done.update({r.request_id: r
                     for r in engine.scheduler.pop_finished()})
    return [done[i] for i in ids], engine


def _shortfall(params, request):
    """Worst (best logit - the served token's logit) of the reference's
    one full pass over prompt + served tokens."""
    lg = reference_rows(reference, CONF, params,
                        list(request.prompt) + list(request.generated), 512)
    n_p = len(request.prompt)
    at = lg[n_p - 1:n_p - 1 + len(request.generated)]
    return float((at.max(-1) -
                  at[np.arange(len(at)), request.generated]).max())


def test_prefill_then_decode_through_the_paged_cache_agrees(setup):
    """`InferenceEngine` (segmented prefill, then a token a step through
    the paged cache with the interpreted kernels) against ONE full pass
    of the reference: logits, not tokens. A prompt shorter than its
    bucket, and a decode batch of 4 with inactive rows, give what the
    same request gives alone."""
    model, params, _ = setup
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, CONF["vocab_size"], size=n).tolist()
               for n in (5, 40, 64, 70)]
    together, engine = _serve(model, params, prompts)
    for r in together:
        # float32 through the cache against float32 in one pass: the
        # served token is the reference's best but for rounding
        assert _shortfall(params, r) <= 1e-3
    assert engine.stats["moe_rows_prefill"] == (5 + 40 + 64 + 70) * 2 * 2
    assert engine.stats["moe_rows_decode"] == 4 * 5 * 2 * 2
    rows = {t: dropless_geometry(t, 2, 16)[0] for t in (4, 64, 128)}
    # buffers, padding included: four prefills of one request each in
    # their buckets, and a whole number of decode steps at batch 4
    steps, rest = divmod(engine.stats["moe_buffer_rows"]
                         - 2 * (3 * rows[64] + rows[128]), 2 * rows[4])
    assert rest == 0 and 5 <= steps <= 20
    # alone: the decode batch of 4 has 3 inactive rows, and the prompt
    # of 5 lies in a bucket of 64
    for i in (0, 3):
        alone, _ = _serve(model, params, [prompts[i]])
        assert alone[0].generated == together[i].generated
        assert _shortfall(params, alone[0]) <= 1e-3


def test_a_capacity_routed_moe_is_still_refused_by_the_server():
    from deeperspeed_tpu.inference import InferenceEngine
    from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError
    model = GPTNeoX(GPTNeoXConfig.tiny(moe_num_experts=4, moe_top_k=2),
                    use_pallas=False)
    with pytest.raises(DeepSpeedConfigError, match="batch neighbours"):
        InferenceEngine(model, config={"inference": {"enabled": True}})


def test_int8_weights_with_an_moe_are_refused_by_name(setup):
    from deeperspeed_tpu.inference import InferenceEngine
    from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError
    model, params, _ = setup
    with pytest.raises(DeepSpeedConfigError, match="quantization.weights"):
        InferenceEngine(model, params=params, config={
            "inference": {"enabled": True},
            "quantization": {"weights": "int8"}})


def test_one_train_batch_step_through_initialize(devices):
    """`deeperspeed_tpu.initialize` / `train_batch` on the tiny model: the
    step's loss (CE + the aux term) is the reference's on the weights the
    engine started from."""
    import deeperspeed_tpu
    model = GPTNeoX(config(), use_pallas=False)
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=None,
        config_params={"train_batch_size": 8, "steps_per_print": 1000,
                       "optimizer": {"type": "Adam",
                                     "params": {"lr": 1e-3}}},
        rng=jax.random.PRNGKey(0))
    start = jax.device_get(engine.state.params)
    toks = np.random.default_rng(0).integers(
        0, CONF["vocab_size"], (1, 8, 16), np.int32)
    loss = float(engine.train_batch(batch=(toks, toks)))
    want = float(jitted(reference.loss, CONF)(
        start, jnp.asarray(toks[0]), jnp.asarray(toks[0])))
    assert np.isfinite(loss)
    # the engine means the loss over 8 data shards of one sequence each
    # where the reference takes one mean; the aux term is computed per
    # shard's tokens: equal to float32 rounding only for CE, so the aux
    # term (0.01 * about 1) is held to 2% of itself
    assert abs(loss - want) <= 2e-4 * abs(want)
    after = jax.device_get(engine.state.params)
    moved = [np.abs(np.asarray(a) - np.asarray(b)).max() > 0
             for a, b in zip(jax.tree_util.tree_leaves(after["blocks"][0]),
                             jax.tree_util.tree_leaves(start["blocks"][0]))]
    assert all(moved)


# --- what the description refuses, by name --------------------------------

REFUSED = {
    "gated_dense_mlp": (dict(ffn_gated=True), "ffn_gated"),
    "ungated_dropless": (dict(moe_num_experts=4, moe_dropless=True),
                         "gated"),
    "biased_dropless": (dict(moe_num_experts=4, moe_dropless=True,
                             ffn_gated=True), "no biases"),
    "dropless_without_experts": (dict(moe_dropless=True), "moe_num_experts"),
    "capacity_router_top_8": (dict(moe_num_experts=16, moe_top_k=8),
                              "top-1 / top-2"),
    "capacity_router_silu": (dict(moe_num_experts=4, hidden_act="silu"),
                             "top-1 / top-2"),
    "unknown_norm": (dict(norm="scalenorm"), "norm must be"),
    "unknown_activation": (dict(hidden_act="relu"), "hidden_act must be"),
    "quantized_ffn_with_the_new_block": (
        dict(moe_num_experts=4, moe_dropless=True, ffn_gated=True,
             use_bias=False, ffn_quant_recipe="int8"), "quantization.ffn"),
    "jitter_with_dropless": (
        dict(moe_num_experts=4, moe_dropless=True, ffn_gated=True,
             use_bias=False, moe_jitter_eps=0.1), "jitter"),
}


@pytest.mark.parametrize("fields,match", REFUSED.values(), ids=REFUSED.keys())
def test_a_block_the_code_does_not_compute_raises_by_name(fields, match):
    with pytest.raises((ValueError, NotImplementedError), match=match):
        GPTNeoX(GPTNeoXConfig.tiny(**fields), use_pallas=False)


def test_parallel_layouts_refuse_the_new_block(devices):
    from jax.sharding import Mesh
    model = GPTNeoX(config(), use_pallas=False)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    for axes, what in ((("data", "model"), "tensor parallel"),
                       (("data", "expert"), "expert parallelism")):
        mesh = Mesh(np.asarray(devices[:4]).reshape(2, 2), axes)
        with pytest.raises(NotImplementedError, match=what):
            model.param_specs(shapes, mesh)
    with pytest.raises(NotImplementedError, match="aux loss|pipeline"):
        neox.to_layer_specs(model.config)
    dense = GPTNeoX(dataclasses.replace(
        GPTNeoXConfig.tiny(), norm="rmsnorm", use_bias=False, qk_norm=True),
        use_pallas=False)
    mesh = Mesh(np.asarray(devices[:4]).reshape(2, 2), ("data", "model"))
    with pytest.raises(NotImplementedError, match="tensor parallelism"):
        dense.param_specs(jax.eval_shape(dense.init_params,
                                         jax.random.PRNGKey(0)), mesh)
    with pytest.raises(NotImplementedError, match="pipeline"):
        dense.to_pipe_spmd(mesh, 2)
    with pytest.raises(NotImplementedError, match="capacity router"):
        model.apply_ds_config(type("C", (), {"moe_params": {
            "num_experts": 4}})())


def test_a_dense_block_with_rmsnorm_no_bias_and_silu_runs(setup):
    """The description's other facts on the dense path: RMSNorm, no
    biases, SiLU, an FFN width that is no multiple of the hidden size."""
    cfg = dataclasses.replace(GPTNeoXConfig.tiny(), norm="rmsnorm",
                              use_bias=False, hidden_act="silu",
                              ffn_width=96, qk_norm=True)
    model = GPTNeoX(cfg, use_pallas=False)
    params = model.init_params(jax.random.PRNGKey(0))
    leaves = sum(int(np.prod(l.shape))
                 for l in jax.tree_util.tree_leaves(params))
    assert leaves == cfg.num_params()
    assert params["blocks"][0]["mlp"]["in_w"].shape == (64, 96)
    assert "in_b" not in params["blocks"][0]["mlp"]
    assert "bias" not in params["final_ln"]
    tokens = jnp.arange(16, dtype=jnp.int32)[None]
    loss = model.loss_fn(params, (tokens, tokens))
    assert np.isfinite(float(loss))
    assert model.generate(params, tokens[:, :4], 3).shape == (1, 3)
