"""The plain reference against the program, at a tiny size on the CPU.

On the chip the same comparison runs at the published widths inside every
cell (`drivers/`), outside the timed window; PERF.md reports how close
it came.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness

ROOT = harness.ROOT
reference = harness.load_module(ROOT, "reference", "gpt_neox")
family = harness.load_module(ROOT, "families", "gpt_neox")

CONF = dict(harness.load_json(ROOT, "benchmarks", "configs",
                              "pythia-410m.json"),
            hidden_size=256, num_hidden_layers=3, num_attention_heads=4,
            intermediate_size=1024, vocab_size=512,
            max_position_embeddings=256)
# The program's MLP uses the tanh form of GELU, the published model the
# erf form; they differ by up to 5e-4 per activation. With the tanh form
# named in the configuration the two float32 passes are the same
# arithmetic in another order: 1e-4 on logits of size ~1 is float32
# rounding through three layers. With the published erf form the
# departure shows: measured 2e-3 here, held to 1e-2.
SAME_MATH_ATOL = 1e-4
GELU_FORM_ATOL = 1e-2


@pytest.fixture(scope="module")
def setup():
    model = family.build_model(CONF, "float32", {"use_pallas": False})
    params = family.init_params(model, seed=0)
    # biases and norms away from their init of 0 and 1, so that a
    # misplaced one shows
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 1000))
    params = jax.tree_util.tree_map(
        lambda p: p + 0.05 * jax.random.normal(next(keys), p.shape)
        if p.ndim == 1 else p, params)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 128), 0,
                                CONF["vocab_size"])
    return model, params, tokens


def test_logits_agree_with_the_program(setup):
    model, params, tokens = setup
    ours = np.asarray(model.apply(params, tokens))
    tanh = np.asarray(reference.logits(dict(CONF, hidden_act="gelu_new"),
                                       params, tokens))
    erf = np.asarray(reference.logits(CONF, params, tokens))
    assert np.abs(ours - tanh).max() <= SAME_MATH_ATOL
    assert np.abs(ours - erf).max() <= GELU_FORM_ATOL
    # a reference that ignored the rotary embedding or the mask would
    # still be a smooth function of the same weights: be sure it is not
    # that close by accident
    shuffled = np.asarray(reference.logits(CONF, params, tokens[:, ::-1]))
    assert np.abs(ours - shuffled[:, ::-1]).max() > 10 * GELU_FORM_ATOL


def test_loss_agrees_with_the_program_and_ignores_masked_targets(setup):
    model, params, tokens = setup
    labels = np.full(tokens.shape, reference.IGNORE_INDEX, np.int32)
    labels[0, :64] = np.asarray(tokens)[0, :64]
    ours = float(model.loss_fn(params, (tokens, jnp.asarray(labels))))
    theirs = float(reference.loss(dict(CONF, hidden_act="gelu_new"), params,
                                  tokens, jnp.asarray(labels)))
    assert abs(ours - theirs) <= 1e-5 * abs(theirs)
    # only the loss-carrying part matters, by causality and row
    # independence: what lets a cell check a whole step on a small part
    part = float(reference.loss(dict(CONF, hidden_act="gelu_new"), params,
                                tokens[:1, :64], jnp.asarray(labels)[:1, :64]))
    assert abs(part - theirs) <= 1e-5 * abs(theirs)


def test_gradient_agrees_with_the_program(setup):
    model, params, tokens = setup
    conf = dict(CONF, hidden_act="gelu_new")
    ours = jax.grad(model.loss_fn)(params, (tokens, tokens))
    theirs = jax.grad(lambda p: reference.loss(conf, p, tokens, tokens))(
        params)
    for a, b in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(theirs)):
        scale = np.abs(np.asarray(b)).max()
        assert np.abs(np.asarray(a) - np.asarray(b)).max() <= 1e-3 * scale


def test_prefill_then_decode_through_the_cache_agrees(setup):
    """The server's tokens (segmented prefill, then one token a step
    through the paged cache) against the reference's one full pass."""
    from deeperspeed_tpu.inference import InferenceEngine
    closed_loop = harness.load_module(ROOT, "drivers", "closed_loop")
    model, params, _ = setup
    conf = dict(CONF, hidden_act="gelu_new")
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
    check = closed_loop.check_served(reference, conf, params,
                                     [done[i] for i in ids], 256, 8, 1e-3)
    assert check["checked_tokens"] == 32 and check["reference_finite"]
    assert check["max_logit_shortfall"] <= 1e-3
    assert check["exact_match_share"] >= 0.95


def test_flops_per_token_counts_matmul_parameters_and_causal_attention():
    model = family.build_model(CONF, "float32", {"use_pallas": False})
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    matrices = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(shapes) if l.ndim == 2)
    embedding = CONF["vocab_size"] * CONF["hidden_size"]
    assert reference.matmul_params(CONF) == matrices - embedding
    # against bench.py's 6 * num_params + 12 * L * h * s at 125M / 2k
    small = dict(hidden_size=768, intermediate_size=3072,
                 num_hidden_layers=12, vocab_size=50304)
    cfg = dataclasses.replace(model.config, hidden_size=768, num_layers=12,
                              num_heads=12, vocab_size=50304)
    generous = 6 * cfg.num_params() + 12 * 12 * 768 * 2048
    ratio = reference.train_flops_per_token(small, 2048) / generous
    assert 0.70 < ratio < 0.72
    # attention's share of the model flops, as PERF.md states it
    for seq, share in ((2048, 0.125), (16384, 0.533)):
        full = dict(harness.load_json(ROOT, "benchmarks", "configs",
                                      "pythia-410m.json"))
        attn = 6 * full["num_hidden_layers"] * full["hidden_size"] * seq
        assert abs(attn / reference.train_flops_per_token(full, seq)
                   - share) < 0.005


def test_kernel_costs_against_counts_by_hand():
    from benchmarks import kernel_costs as kc
    peaks = harness.load_json(ROOT, "benchmarks", "peaks.json")["TPU v5 lite"]
    # one head, 4 positions, head dim 2, dense: QK^T is 4*4*2 multiply-adds
    assert kc.flash_fwd(1, 1, 4, 2, causal=False)[0] == 2 * 2 * 4 * 4 * 2
    assert kc.flash_bwd(1, 1, 4, 2)[0] == 2.5 * kc.flash_fwd(1, 1, 4, 2)[0]
    # flash forward at pythia-410m, 16 x 2048: compute-bound on a v5e
    flops, bytes_ = kc.flash_fwd(16, 16, 2048, 64)
    assert kc.least_seconds(flops, bytes_, peaks)[1] == "compute"
    # paged decode reads the cache once: memory-bound
    flops, bytes_ = kc.paged_decode([470] * 32, 16, 128)
    assert bytes_ >= 2 * 32 * 470 * 16 * 128 * 2
    assert kc.least_seconds(flops, bytes_, peaks)[1] == "memory"
    assert kc.ce_head(10, 4, 8)[0] == 3 * kc.ce_head(10, 4, 8, False)[0]
    assert kc.adam_update(1000)[1] == 1000 * (4 + 12 + 12 + 2)
