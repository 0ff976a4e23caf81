"""BERT family tests (reference: `tests/unit/modeling.py` fixtures +
the BingBertSquad / bert-pretraining workloads)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deeperspeed_tpu
from deeperspeed_tpu.models.bert import (BertConfig, BertModel,
                                         BertForPreTraining,
                                         BertForQuestionAnswering,
                                         to_layer_specs)

# passes and fits tier-1's rule (`pyproject.toml`, `slow`); `slow` for the
# whole run's budget alone, with the mechanisms no cell runs (ROADMAP D19)
pytestmark = pytest.mark.slow


def _pretrain_batch(cfg, bs=4, seq=32, seed=0):
    rng = np.random.default_rng(seed)
    input_ids = rng.integers(0, cfg.vocab_size, (bs, seq)).astype(np.int32)
    token_type = (np.arange(seq)[None, :] >= seq // 2).astype(np.int32) * \
        np.ones((bs, 1), np.int32)
    mask = np.ones((bs, seq), np.int32)
    mlm_labels = np.full((bs, seq), -1, np.int32)
    mlm_labels[:, ::5] = rng.integers(0, cfg.vocab_size,
                                      (bs, (seq + 4) // 5))
    nsp = rng.integers(0, 2, (bs,)).astype(np.int32)
    return input_ids, token_type, mask, mlm_labels, nsp


def test_bert_encoder_shapes():
    cfg = BertConfig.tiny()
    model = BertModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jnp.zeros((2, 16), jnp.int32)
    seq = model.encode(params, ids)
    assert seq.shape == (2, 16, cfg.hidden_size)
    pooled = model.pool(params, seq)
    assert pooled.shape == (2, cfg.hidden_size)


def test_bert_pretraining_loss_decreases():
    cfg = BertConfig.tiny()
    model = BertForPreTraining(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=params,
        config_params={"train_batch_size": 4 * jax.device_count(),
                       "optimizer": {"type": "Adam",
                                     "params": {"lr": 1e-3}},
                       "steps_per_print": 1000})
    batch = _pretrain_batch(cfg, bs=4 * jax.device_count())
    stacked = tuple(np.expand_dims(b, 0) for b in batch)
    losses = [float(engine.train_batch(batch=stacked)) for _ in range(10)]
    assert losses[-1] < losses[0] - 0.5, losses


def test_bert_mlm_decoder_tied_to_word_embeddings():
    cfg = BertConfig.tiny()
    model = BertForPreTraining(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = _pretrain_batch(cfg, bs=2, seq=16)

    grads = jax.grad(lambda p: model.loss_fn(p, batch))(params)
    # tied decoder → MLM loss gradient reaches the word embedding table
    wg = np.asarray(grads["embeddings"]["word"])
    assert np.abs(wg).sum() > 0


def test_bert_qa_loss():
    cfg = BertConfig.tiny()
    model = BertForQuestionAnswering(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    bs, seq = 4, 32
    batch = (rng.integers(0, cfg.vocab_size, (bs, seq)).astype(np.int32),
             np.zeros((bs, seq), np.int32),
             np.ones((bs, seq), np.int32),
             rng.integers(0, seq, (bs,)).astype(np.int32),
             rng.integers(0, seq, (bs,)).astype(np.int32))
    loss = model.loss_fn(params, batch)
    assert np.isfinite(float(loss))
    g = jax.grad(lambda p: model.loss_fn(p, batch))(params)
    assert np.isfinite(np.asarray(g["qa"]["w"])).all()


def test_bert_tp_param_specs():
    from deeperspeed_tpu.parallel.mesh import build_mesh
    from deeperspeed_tpu.parallel.topology import ProcessTopology

    cfg = BertConfig.tiny()
    model = BertForPreTraining(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    n = jax.device_count()
    if n < 2:
        pytest.skip("needs multi-device mesh")
    topo = ProcessTopology(axes=["data", "model"], dims=[n // 2, 2])
    mesh = build_mesh(topo, jax.devices()[:n])
    specs = model.param_specs(params, mesh)
    # same tree structure
    jax.tree_util.tree_map(lambda a, b: None, params, specs)
    from jax.sharding import PartitionSpec as P
    assert specs["layers"][0]["attn_qkvw"] == P(None, "model")
    assert specs["layers"][0]["attn_ow"] == P("model", None)
    assert specs["embeddings"]["word"] == P("model", None)


def test_bert_pipeline_specs():
    cfg = BertConfig.tiny()
    specs = to_layer_specs(cfg)
    assert len(specs) == cfg.num_layers + 2  # embeddings + layers + head
    # build each layer and push a batch through manually; the mask rides
    # along as (hidden, attention_mask) between stages
    rng = jax.random.PRNGKey(0)
    ids = jnp.zeros((2, 16), jnp.int32)
    mask = np.ones((2, 16), np.int32)
    mask[:, 12:] = 0
    x = (ids, jnp.asarray(mask))
    for i, spec in enumerate(specs):
        layer = spec.build()
        p = layer.init(jax.random.fold_in(rng, i), x)
        x = layer.apply(p, x)
    mlm_logits, nsp_logits = x
    assert mlm_logits.shape == (2, 16, cfg.vocab_size)
    assert nsp_logits.shape == (2, 2)


def test_bert_pipeline_mask_changes_output():
    """Padding must be masked in every pipeline stage (parity with
    BertModel.encode)."""
    cfg = BertConfig.tiny()
    specs = to_layer_specs(cfg, with_head=False)
    rng = jax.random.PRNGKey(0)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 16)).astype(np.int32))
    mask = np.ones((2, 16), np.int32)
    mask[:, 8:] = 0

    def run(mask_arr):
        x = (ids, None if mask_arr is None else jnp.asarray(mask_arr))
        for i, spec in enumerate(specs):
            layer = spec.build()
            p = layer.init(jax.random.fold_in(rng, i), x)
            x = layer.apply(p, x)
        return np.asarray(x[0], np.float32)

    full = run(None)
    masked = run(mask)
    # the unpadded positions see different context when padding is masked
    assert np.abs(full[:, :8] - masked[:, :8]).max() > 1e-4


def test_gpt_neox_tied_pipeline_head_uses_embedding():
    from deeperspeed_tpu.models.gpt_neox import GPTNeoXConfig
    from deeperspeed_tpu.models.gpt_neox import to_layer_specs as neox_specs
    from deeperspeed_tpu.runtime.pipe import PipelineModule

    cfg = GPTNeoXConfig.tiny(tie_word_embeddings=True)
    module = PipelineModule(layers=neox_specs(cfg, use_pallas=False),
                            num_stages=1)
    ids = jnp.zeros((2, 16), jnp.int32)
    params = module.init_params(jax.random.PRNGKey(0), example_input=ids)
    assert "embed" in params["tied"]
    logits = module.forward_range(params, ids, 0, module.num_layers())
    assert logits.shape == (2, 16, cfg.vocab_size)
    # grads flow into the tied table from both the lookup and the head
    g = jax.grad(lambda p: jnp.sum(
        module.forward_range(p, ids, 0,
                             module.num_layers()).astype(jnp.float32)))(
        params)
    assert np.abs(np.asarray(g["tied"]["embed"]["wte"])).sum() > 0


def test_bert_activation_capture_through_engine():
    cfg = BertConfig.tiny()
    model = BertForPreTraining(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=params,
        config_params={"train_batch_size": 4 * jax.device_count(),
                       "optimizer": {"type": "Adam",
                                     "params": {"lr": 1e-3}},
                       "steps_per_print": 1000})
    batch = _pretrain_batch(cfg, bs=4 * jax.device_count())
    stacked = tuple(np.expand_dims(b, 0) for b in batch)
    engine.train_batch(batch=stacked, layers_to_hook=["transformerlayer"])
    acts = engine.get_hooked_activations()
    assert sorted(acts) == [1, 2]
    assert acts[1].shape[-1] == cfg.hidden_size
