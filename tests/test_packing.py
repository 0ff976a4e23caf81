"""Document packing + segment-aware attention stack (fast lane).

Covers the PR-7 long-context pipeline end to end on CPU interpret mode:
`runtime/packing.py` (greedy bin-packing, segment metadata, label
masking, effective-token accounting), the segmented flash fwd/dkv/dq
kernels vs an XLA segment-masked reference, segment-aware ring /
zigzag / Ulysses sequence parallelism vs single-device, the
packed-vs-padded model pin (packing changes the loss ONLY via removed
cross-document attention), the config plumb, and the block-sparse
attention engine selection.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from deeperspeed_tpu.runtime.packing import (
    PAD_SEGMENT_ID, PackedDataset, count_effective_targets,
    mask_cross_document_labels, pack_documents, packed_batch_token_stats,
    segment_relative_positions, synthetic_doc_mixture)
from tests.model.references import jitted


# ---------------------------------------------------------------------------
# packing module
# ---------------------------------------------------------------------------

def docs_fixture():
    rng = np.random.default_rng(0)
    return [rng.integers(1, 100, n, dtype=np.int32)
            for n in (40, 30, 20, 65, 7, 130)]


def test_pack_documents_preserves_tokens():
    docs = docs_fixture()
    tok, seg = pack_documents(docs, 64)
    # every non-pad token appears exactly as often as in the corpus
    packed = np.sort(tok[seg != PAD_SEGMENT_ID])
    corpus = np.sort(np.concatenate(docs))
    np.testing.assert_array_equal(packed, corpus)


def test_pack_documents_segment_structure():
    tok, seg = pack_documents(docs_fixture(), 64)
    assert tok.shape == seg.shape and tok.shape[1] == 64
    for row in seg:
        nz = row[row != PAD_SEGMENT_ID]
        # ids are 1-based and non-decreasing (contiguous segments — the
        # kernels' block-skip min/max test relies on this)
        assert nz.size == 0 or nz.min() >= 1
        assert (np.diff(row.astype(np.int64)) >= 0).sum() >= 0  # defined
        assert (np.diff(nz.astype(np.int64)) >= 0).all()
        # pads only at the tail
        pad_at = np.nonzero(row == PAD_SEGMENT_ID)[0]
        assert pad_at.size == 0 or pad_at[0] == row.size - pad_at.size


def test_pack_documents_splits_long_docs():
    doc = np.arange(1, 151, dtype=np.int32)   # 150 tokens, window 64
    tok, seg = pack_documents([doc], 64)
    packed = tok[seg != PAD_SEGMENT_ID]
    np.testing.assert_array_equal(np.sort(packed), np.sort(doc))
    # pieces are window-sized: no segment exceeds 64
    for row in seg:
        for sid in np.unique(row[row != 0]):
            assert (row == sid).sum() <= 64


def test_pack_documents_drop_tail():
    # one full-ish doc and one tiny one that lands alone in a tail row
    docs = [np.ones(60, np.int32), np.ones(10, np.int32)]
    tok_keep, _ = pack_documents(docs, 64, drop_tail=False)
    tok_drop, _ = pack_documents(docs, 64, drop_tail=True)
    assert tok_keep.shape[0] == 2
    assert tok_drop.shape[0] == 1   # the <50%-occupancy row is dropped


def test_pack_documents_empty():
    tok, seg = pack_documents([], 64)
    assert tok.shape == (0, 64) and seg.shape == (0, 64)


def test_packed_dataset_triples_and_occupancy():
    ds = PackedDataset(docs_fixture(), 64)
    tok, lab, seg = ds[0]
    np.testing.assert_array_equal(tok, lab)
    assert 0.0 < ds.occupancy() <= 1.0
    assert len(ds) == ds.tokens.shape[0]


def test_segment_relative_positions_values():
    seg = np.array([[1, 1, 1, 2, 2, 0, 0, 0]], np.int32)
    want = np.array([[0, 1, 2, 0, 1, 0, 1, 2]], np.int32)
    np.testing.assert_array_equal(segment_relative_positions(seg), want)
    # jnp path matches the numpy path
    got_j = segment_relative_positions(jnp.asarray(seg))
    np.testing.assert_array_equal(np.asarray(got_j), want)


def test_mask_cross_document_labels():
    seg = np.array([[1, 1, 2, 2, 2, 0, 0]], np.int32)
    lab = np.arange(7, dtype=np.int32)[None]
    out = mask_cross_document_labels(lab, seg)
    # position 0 masked, cross-doc boundary (2) masked, pad entry (5)
    # and the pad-run continuation: seg[5]=0 != seg[4] -> masked;
    # seg[6]=0 == seg[5]=0 but IS pad -> masked
    want = np.array([[-100, 1, -100, 3, 4, -100, -100]], np.int32)
    np.testing.assert_array_equal(out, want)
    out_j = mask_cross_document_labels(jnp.asarray(lab), jnp.asarray(seg))
    np.testing.assert_array_equal(np.asarray(out_j), want)


def test_count_effective_targets_is_mask_complement():
    _, seg = pack_documents(docs_fixture(), 64)
    lab = np.ones_like(seg)
    eff = count_effective_targets(seg)
    masked = mask_cross_document_labels(lab, seg)
    # the first column is never a target position in the count
    assert eff == int((masked[:, 1:] != -100).sum())


def test_packed_batch_token_stats():
    _, seg = pack_documents(docs_fixture(), 64)
    tok = np.ones_like(seg)
    stats = packed_batch_token_stats((tok, tok, seg))
    assert stats == (count_effective_targets(seg),
                     seg.shape[0] * (seg.shape[1] - 1))
    assert packed_batch_token_stats((tok, tok)) is None
    assert packed_batch_token_stats(tok) is None


def test_synthetic_doc_mixture_deterministic():
    a = synthetic_doc_mixture(7, 16, 100, mean_len=50.0)
    b = synthetic_doc_mixture(7, 16, 100, mean_len=50.0)
    assert len(a) == 16
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# segmented flash kernels vs XLA reference
# ---------------------------------------------------------------------------

def reference_segmented(q, k, v, seg, causal):
    B, S, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = seg[:, :, None] == seg[:, None, :]             # [B, S, S]
    if causal:
        mask = mask & jnp.tril(jnp.ones((S, S), bool))[None]
    logits = jnp.where(mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(mask[:, None].any(-1, keepdims=True), probs, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


def make_qkv(b=2, s=256, h=2, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), dtype) * 0.5
                 for k in ks)


def make_seg(b=2, s=256, n_docs=3, seed=1, pad=32):
    """Random contiguous segment layout with a pad tail."""
    rng = np.random.default_rng(seed)
    seg = np.zeros((b, s), np.int32)
    for r in range(b):
        cuts = np.sort(rng.choice(np.arange(1, s - pad), n_docs - 1,
                                  replace=False))
        bounds = np.concatenate([[0], cuts, [s - pad]])
        for i in range(n_docs):
            seg[r, bounds[i]:bounds[i + 1]] = i + 1
    return jnp.asarray(seg)


@pytest.mark.parametrize("causal", [True, False])
def test_segmented_flash_forward_parity(causal):
    from deeperspeed_tpu.ops.pallas.flash_attention import \
        flash_attention_segmented
    q, k, v = make_qkv()
    seg = make_seg()
    out = flash_attention_segmented(q, k, v, seg, causal)
    ref = reference_segmented(q, k, v, seg, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("bwd_blocks", [None, (128, 128)])
def test_segmented_flash_backward_parity(bwd_blocks):
    from deeperspeed_tpu.ops.pallas.flash_attention import \
        flash_attention_segmented
    q, k, v = make_qkv(seed=3)
    seg = make_seg(seed=4)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention_segmented(
            q, k, v, seg, True, None, 128, 128, bwd_blocks) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_segmented(q, k, v, seg, True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-3,
                                   err_msg=f"d{name} mismatch")


def test_segmented_single_segment_matches_unsegmented():
    from deeperspeed_tpu.ops.pallas.flash_attention import (
        flash_attention, flash_attention_segmented)
    q, k, v = make_qkv(b=1, seed=5)
    seg = jnp.ones((1, q.shape[1]), jnp.int32)
    out_seg = flash_attention_segmented(q, k, v, seg, True)
    out = flash_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out_seg), np.asarray(out),
                               atol=2e-6, rtol=2e-6)


def test_segmented_no_cross_document_leak():
    """Perturbing document 2's tokens must not change document 1's
    outputs — the direct statement of intra-document attention."""
    from deeperspeed_tpu.ops.pallas.flash_attention import \
        flash_attention_segmented
    q, k, v = make_qkv(b=1, s=256, seed=6)
    seg = jnp.asarray(np.repeat([1, 2], 128)[None].astype(np.int32))
    out = flash_attention_segmented(q, k, v, seg, True)
    k2 = k.at[:, 128:].add(1.0)
    v2 = v.at[:, 128:].add(-0.5)
    out2 = flash_attention_segmented(q, k2, v2, seg, True)
    np.testing.assert_allclose(np.asarray(out[:, :128]),
                               np.asarray(out2[:, :128]),
                               atol=1e-6, rtol=1e-6)
    # and doc 2's outputs DID change (the perturbation was visible)
    assert not np.allclose(np.asarray(out[:, 128:]),
                           np.asarray(out2[:, 128:]), atol=1e-3)


def test_causal_attention_xla_fallback_segmented():
    """The models' XLA fallback path applies the identical segment
    semantics as the Pallas kernel."""
    from deeperspeed_tpu.models.gpt_neox import causal_attention
    q, k, v = make_qkv(seed=7)
    seg = make_seg(seed=8)
    out_xla = causal_attention(q, k, v, use_pallas=False,
                               segment_ids=seg)
    out_pallas = causal_attention(q, k, v, use_pallas=True,
                                  segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out_xla),
                               np.asarray(out_pallas),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# segment-aware sequence parallelism
# ---------------------------------------------------------------------------

@pytest.fixture
def seq_mesh(devices):
    return Mesh(np.asarray(devices), ("seq",))


def _sp_case(mesh, mode, balance, causal=True, seed=10):
    from deeperspeed_tpu.parallel.sequence import SequenceParallel
    q, k, v = make_qkv(b=2, s=128, h=8, d=16, seed=seed)
    seg = make_seg(b=2, s=128, n_docs=3, seed=seed + 1, pad=16)
    sp = SequenceParallel(mesh, axis="seq", mode=mode, causal=causal,
                          balance=balance)
    # under one jit, as every caller in the package holds it: called bare,
    # each operation of each ring step is dispatched on its own to 8 devices
    out = jax.jit(lambda q, k, v, seg: sp(q, k, v, segment_ids=seg))(
        q, k, v, seg)
    ref = reference_segmented(q, k, v, seg, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_sp_segmented_parity(seq_mesh):
    _sp_case(seq_mesh, "ring", balance=False)


def test_ring_sp_segmented_noncausal(seq_mesh):
    _sp_case(seq_mesh, "ring", balance=False, causal=False, seed=20)


def test_zigzag_sp_segmented_parity(seq_mesh):
    _sp_case(seq_mesh, "ring", balance=True, seed=30)


def test_ulysses_sp_segmented_parity(seq_mesh):
    _sp_case(seq_mesh, "ulysses", balance=None, seed=40)


def test_ring_sp_segmented_grads(seq_mesh):
    from deeperspeed_tpu.parallel.sequence import SequenceParallel
    q, k, v = make_qkv(b=1, s=128, h=8, d=16, seed=50)
    seg = make_seg(b=1, s=128, n_docs=2, seed=51, pad=16)
    sp = SequenceParallel(seq_mesh, axis="seq", mode="ring",
                          causal=True, balance=True)
    g_sp = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(sp(q, k, v, segment_ids=seg) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(reference_segmented(q, k, v, seg,
                                                    True) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(g_sp, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-3,
                                   err_msg=f"d{name} mismatch")


def test_sp_unsegmented_unchanged(seq_mesh):
    """segment_ids=None keeps the pre-PR behavior bit-for-bit."""
    from deeperspeed_tpu.parallel.sequence import SequenceParallel
    q, k, v = make_qkv(b=1, s=128, h=8, d=16, seed=60)
    sp = SequenceParallel(seq_mesh, axis="seq", mode="ring", causal=True)
    out_a = jax.jit(sp)(q, k, v)
    out_b = jax.jit(lambda q, k, v: sp(q, k, v, segment_ids=None))(q, k, v)
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))


# ---------------------------------------------------------------------------
# the packed-vs-padded model pin
# ---------------------------------------------------------------------------

def tiny_neox(seq):
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
    cfg = GPTNeoXConfig(vocab_size=97, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=seq)
    model = GPTNeoX(cfg, use_pallas=False)
    return model, model.init_params(jax.random.PRNGKey(0))


def test_packed_vs_padded_loss_pin():
    """Same documents packed into one row vs padded one-per-row: the
    per-token losses (and thus the masked mean over the identical target
    set) must match — packing may change the loss ONLY via removed
    cross-document attention, which the segment masks remove."""
    S = 128
    model, params = tiny_neox(S)
    rng = np.random.default_rng(2)
    docs = [rng.integers(1, 97, n, dtype=np.int32) for n in (50, 40, 30)]

    tok_p, seg_p = pack_documents(docs, S)
    assert tok_p.shape[0] == 1      # all three fit one row
    packed_loss = jitted(model.loss_fn)(
        params, (jnp.asarray(tok_p), jnp.asarray(tok_p),
                 jnp.asarray(seg_p)))

    # padded: one doc per row, each its own single-segment batch
    tok_d = np.zeros((3, S), np.int32)
    seg_d = np.zeros((3, S), np.int32)
    for i, d in enumerate(docs):
        tok_d[i, :d.size] = d
        seg_d[i, :d.size] = 1
    padded_loss = jitted(model.loss_fn)(
        params, (jnp.asarray(tok_d), jnp.asarray(tok_d),
                 jnp.asarray(seg_d)))

    # identical target sets (non-pad, non-cross-doc) on both sides
    assert count_effective_targets(seg_p) == count_effective_targets(seg_d)
    np.testing.assert_allclose(float(packed_loss), float(padded_loss),
                               atol=1e-5, rtol=1e-5)


def test_packed_vs_padded_hidden_pin():
    """Stronger form: per-position hidden states of a packed document
    equal the same document's hidden states padded alone (positions are
    intra-document by construction)."""
    from deeperspeed_tpu.models.gpt_neox import forward_hidden
    S = 128
    model, params = tiny_neox(S)
    rng = np.random.default_rng(3)
    d1 = rng.integers(1, 97, 48, dtype=np.int32)
    d2 = rng.integers(1, 97, 40, dtype=np.int32)

    tok_p, seg_p = pack_documents([d1, d2], S)
    hid_p = forward_hidden(model.config, params, jnp.asarray(tok_p),
                           use_pallas=False,
                           segment_ids=jnp.asarray(seg_p))
    # d1 occupies the first 48 positions of the packed row
    tok_a = np.zeros((1, S), np.int32)
    tok_a[0, :48] = d1
    seg_a = np.zeros((1, S), np.int32)
    seg_a[0, :48] = 1
    hid_a = forward_hidden(model.config, params, jnp.asarray(tok_a),
                           use_pallas=False,
                           segment_ids=jnp.asarray(seg_a))
    np.testing.assert_allclose(np.asarray(hid_p[0, :48]),
                               np.asarray(hid_a[0, :48]),
                               atol=2e-5, rtol=2e-5)


def test_gpt2_packed_vs_padded_loss_pin():
    """GPT-2 plumb: learned wpe gathered at intra-document positions."""
    from deeperspeed_tpu.models.gpt2 import GPT2, GPT2Config
    S = 64
    cfg = GPT2Config(vocab_size=97, hidden_size=32, num_layers=2,
                     num_heads=2, max_seq_len=S)
    model = GPT2(cfg, use_pallas=False)
    params = model.init_params(jax.random.PRNGKey(1))
    rng = np.random.default_rng(4)
    docs = [rng.integers(1, 97, n, dtype=np.int32) for n in (30, 25)]

    tok_p, seg_p = pack_documents(docs, S)
    packed_loss = jitted(model.loss_fn)(
        params, (jnp.asarray(tok_p), jnp.asarray(tok_p),
                 jnp.asarray(seg_p)))
    tok_d = np.zeros((2, S), np.int32)
    seg_d = np.zeros((2, S), np.int32)
    for i, d in enumerate(docs):
        tok_d[i, :d.size] = d
        seg_d[i, :d.size] = 1
    padded_loss = jitted(model.loss_fn)(
        params, (jnp.asarray(tok_d), jnp.asarray(tok_d),
                 jnp.asarray(seg_d)))
    np.testing.assert_allclose(float(packed_loss), float(padded_loss),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# model/config plumbing
# ---------------------------------------------------------------------------

def test_loss_fn_requires_segments_when_packing_enabled():
    import dataclasses
    model, params = tiny_neox(64)
    model.config = dataclasses.replace(model.config, use_segment_ids=True)
    tok = jnp.zeros((1, 64), jnp.int32)
    with pytest.raises(ValueError, match="segment_ids"):
        model.loss_fn(params, (tok, tok))


def test_packing_block_sets_use_segment_ids():
    from deeperspeed_tpu.runtime.config import DeepSpeedConfig
    model, _ = tiny_neox(64)
    cfg = DeepSpeedConfig({"train_batch_size": 8,
                           "packing": {"enabled": True}})
    assert cfg.packing_params == {"pad_id": 0, "drop_tail": False}
    model.apply_ds_config(cfg)
    assert model.config.use_segment_ids


def test_engine_pack_dataset_uses_config_knobs():
    """packing.pad_id / packing.drop_tail are consumed by
    engine.pack_dataset — the config block, not PackedDataset defaults,
    decides the packed rows."""
    import deeperspeed_tpu
    model, params = tiny_neox(64)
    eng, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=params,
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "packing": {"enabled": True, "pad_id": 7, "drop_tail": True},
        })
    docs = [np.arange(40, dtype=np.int32), np.arange(3, dtype=np.int32)]
    ds = eng.pack_dataset(docs)
    # pad positions carry the configured pad_id
    assert (ds.tokens[ds.segment_ids == PAD_SEGMENT_ID] == 7).all()
    # drop_tail=True dropped the under-half-full tail row
    ref = PackedDataset(docs, 64, pad_id=7, drop_tail=True)
    assert len(ds) == len(ref)
    np.testing.assert_array_equal(ds.tokens, ref.tokens)
    # explicit seq_len override still threads the config knobs
    assert eng.pack_dataset(docs, seq_len=48).seq_len == 48
    # without the packing block, pack_dataset refuses
    from deeperspeed_tpu.runtime.config import DeepSpeedConfigError
    model2, params2 = tiny_neox(64)
    eng2, *_ = deeperspeed_tpu.initialize(
        model=model2, model_parameters=params2,
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        })
    with pytest.raises(DeepSpeedConfigError, match="packing"):
        eng2.pack_dataset(docs)


def test_packing_block_validation():
    from deeperspeed_tpu.runtime.config import (DeepSpeedConfig,
                                                DeepSpeedConfigError)
    base = {"train_batch_size": 8}
    with pytest.raises(DeepSpeedConfigError, match="Unknown 'packing'"):
        DeepSpeedConfig({**base, "packing": {"enable": True}})
    with pytest.raises(DeepSpeedConfigError, match="boolean"):
        DeepSpeedConfig({**base, "packing": {"enabled": "yes"}})
    with pytest.raises(DeepSpeedConfigError, match="pad_id"):
        DeepSpeedConfig({**base, "packing": {"enabled": True,
                                             "pad_id": -1}})
    with pytest.raises(DeepSpeedConfigError, match="boolean"):
        DeepSpeedConfig({**base, "packing": {"enabled": True,
                                             "drop_tail": 3}})
    # disabled block parses and clears the params
    cfg = DeepSpeedConfig({**base, "packing": {"enabled": False,
                                               "pad_id": 5}})
    assert cfg.packing_params is False


def test_packing_plus_sparse_attention_rejected():
    from deeperspeed_tpu.runtime.config import (DeepSpeedConfig,
                                                DeepSpeedConfigError)
    with pytest.raises(DeepSpeedConfigError, match="sparse_attention"):
        DeepSpeedConfig({"train_batch_size": 8,
                         "packing": {"enabled": True},
                         "sparse_attention": {"mode": "fixed"}})


def test_bert_rejects_packing_block():
    from deeperspeed_tpu.models.bert import BertConfig, BertModel
    from deeperspeed_tpu.runtime.config import DeepSpeedConfig
    cfg = DeepSpeedConfig({"train_batch_size": 8,
                           "packing": {"enabled": True}})
    model = BertModel(BertConfig(vocab_size=64, hidden_size=32,
                                 num_layers=1, num_heads=2,
                                 intermediate_size=64,
                                 max_position_embeddings=64))
    with pytest.raises(NotImplementedError, match="packing"):
        model.apply_ds_config(cfg)


def test_offload_stream_rejects_packing():
    import dataclasses
    model, params = tiny_neox(64)
    model.config = dataclasses.replace(model.config, use_segment_ids=True)
    with pytest.raises(NotImplementedError, match="param-offload"):
        model.stream_plan()


# ---------------------------------------------------------------------------
# block-sparse engine selection
# ---------------------------------------------------------------------------

def test_attention_engine_validation():
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
    with pytest.raises(ValueError, match="attention_engine"):
        GPTNeoX(GPTNeoXConfig(vocab_size=64, hidden_size=32,
                              num_layers=1, num_heads=2, max_seq_len=64,
                              attention_engine="triton"))


def test_make_sparse_attention_defaults_unidirectional():
    """A minimal JSON block without an explicit `attention` key must
    work on a causal LM: the parse leaves the key None (unset) and the
    sparse engine defaults it to unidirectional — only an EXPLICIT
    bidirectional request is the hard error."""
    from deeperspeed_tpu.models.gpt_neox import (GPTNeoXConfig,
                                                 make_sparse_attention)
    from deeperspeed_tpu.runtime.config import DeepSpeedConfig
    cfg = GPTNeoXConfig(vocab_size=64, hidden_size=128, num_layers=1,
                        num_heads=2, max_seq_len=256)
    ds = DeepSpeedConfig({"train_batch_size": 8,
                          "sparse_attention": {"mode": "fixed"}})
    assert ds.sparse_attention["attention"] is None
    fn = make_sparse_attention(cfg, ds.sparse_attention)
    q = jnp.zeros((1, 256, 2, 64), jnp.float32)
    assert fn(q, q, q).shape == q.shape


def test_sparsity_config_unset_attention_keeps_reference_default():
    """The same unset-`attention` parse feeds the reference
    SparseSelfAttention path with the constructor default intact
    (bidirectional) — the unidirectional default is causal-LM only."""
    from deeperspeed_tpu.ops.sparse_attention.sparsity_config import \
        sparsity_config_from_dict
    from deeperspeed_tpu.runtime.config import DeepSpeedConfig
    ds = DeepSpeedConfig({"train_batch_size": 8,
                          "sparse_attention": {"mode": "fixed"}})
    sc = sparsity_config_from_dict(ds.sparse_attention)
    assert sc.attention == "bidirectional"


def test_gpt2_rejects_sparse_attention_block():
    """GPT-2 (and BERT, same shared helper) must fail LOUDLY on a
    sparse_attention config — accepting it would silently train dense
    attention the config said to replace."""
    from deeperspeed_tpu.models.gpt2 import GPT2, GPT2Config
    from deeperspeed_tpu.runtime.config import DeepSpeedConfig
    model = GPT2(GPT2Config(vocab_size=64, hidden_size=32, num_layers=1,
                            num_heads=2, max_seq_len=64),
                 use_pallas=False)
    ds = DeepSpeedConfig({"train_batch_size": 8,
                          "sparse_attention": {"mode": "fixed"}})
    with pytest.raises(NotImplementedError, match="sparse_attention"):
        model.apply_ds_config(ds)


def test_make_sparse_attention_rejects_bidirectional():
    from deeperspeed_tpu.models.gpt_neox import (GPTNeoXConfig,
                                                 make_sparse_attention)
    cfg = GPTNeoXConfig(vocab_size=64, hidden_size=128, num_layers=1,
                        num_heads=2, max_seq_len=256)
    with pytest.raises(ValueError, match="unidirectional"):
        make_sparse_attention(cfg, {"mode": "fixed",
                                    "attention": "bidirectional"})


def test_make_sparse_attention_rejects_segments():
    from deeperspeed_tpu.models.gpt_neox import (GPTNeoXConfig,
                                                 make_sparse_attention)
    cfg = GPTNeoXConfig(vocab_size=64, hidden_size=128, num_layers=1,
                        num_heads=2, max_seq_len=256)
    fn = make_sparse_attention(cfg, {"mode": "fixed", "block": 128,
                                     "num_local_blocks": 2})
    q = jnp.zeros((1, 256, 2, 64), jnp.float32)
    with pytest.raises(NotImplementedError, match="segment"):
        fn(q, q, q, segment_ids=jnp.zeros((1, 256), jnp.int32))


def test_sparse_engine_loss_runs():
    """attention_engine='sparse' trains end-to-end on a small shape:
    the engine selects the masked dense-flash arm here (dense-ish
    layout), exercising the full config->engine->kernel path."""
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
    cfg = GPTNeoXConfig(vocab_size=97, hidden_size=128, num_layers=1,
                        num_heads=2, max_seq_len=256,
                        attention_engine="sparse")
    model = GPTNeoX(cfg, use_pallas=False)
    params = model.init_params(jax.random.PRNGKey(0))
    tok = jnp.asarray(np.random.default_rng(0).integers(
        0, 97, (1, 256), np.int32))
    loss = model.loss_fn(params, (tok, tok))
    assert np.isfinite(float(loss))


def test_sparse_engine_config_plumb():
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
    from deeperspeed_tpu.runtime.config import DeepSpeedConfig
    ds = DeepSpeedConfig({
        "train_batch_size": 8,
        "sparse_attention": {"mode": "fixed", "block": 128,
                             "num_local_blocks": 2,
                             "attention": "unidirectional"}})
    model = GPTNeoX(GPTNeoXConfig(vocab_size=97, hidden_size=128,
                                  num_layers=1, num_heads=2,
                                  max_seq_len=256))
    model.apply_ds_config(ds)
    assert model.config.attention_engine == "sparse"
    assert model._attn_fn is not None


# ---------------------------------------------------------------------------
# transformer-kernel (BERT-family) segment plumb
# ---------------------------------------------------------------------------

def test_transformer_layer_segmented_matches_additive_mask():
    from deeperspeed_tpu.ops.transformer import (
        DeepSpeedTransformerConfig, DeepSpeedTransformerLayer)
    cfg = DeepSpeedTransformerConfig(
        batch_size=2, hidden_size=128, heads=2, intermediate_size=256,
        attn_dropout_ratio=0.0, hidden_dropout_ratio=0.0,
        num_hidden_layers=1, initializer_range=0.02,
        pre_layer_norm=True, training=False)
    layer = DeepSpeedTransformerLayer(cfg)
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 128),
                          jnp.float32) * 0.3
    seg = make_seg(b=2, s=256, n_docs=2, seed=9, pad=32)
    out_seg = layer.apply(params, x, segment_ids=seg)
    # reference: the same pairwise mask as an additive attention mask
    pair = jnp.where(seg[:, None, :, None] == seg[:, None, None, :],
                     0.0, -1e30)
    out_mask = layer.apply(params, x, attention_mask=pair)
    np.testing.assert_allclose(np.asarray(out_seg), np.asarray(out_mask),
                               atol=2e-4, rtol=2e-4)


def test_bert_encode_segmented_no_leak():
    """Perturbing doc 2 leaves doc 1's encoder output unchanged."""
    from deeperspeed_tpu.models.bert import BertConfig, BertModel
    model = BertModel(BertConfig(vocab_size=64, hidden_size=32,
                                 num_layers=1, num_heads=2,
                                 intermediate_size=64,
                                 max_position_embeddings=64))
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    ids = rng.integers(1, 64, (1, 64), np.int32)
    seg = np.repeat([1, 2], 32)[None].astype(np.int32)
    out = model.encode(params, jnp.asarray(ids),
                       segment_ids=jnp.asarray(seg))
    ids2 = ids.copy()
    ids2[0, 32:] = (ids2[0, 32:] + 7) % 63 + 1
    out2 = model.encode(params, jnp.asarray(ids2),
                        segment_ids=jnp.asarray(seg))
    np.testing.assert_allclose(np.asarray(out[:, :32]),
                               np.asarray(out2[:, :32]),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# telemetry effective-token scalars
# ---------------------------------------------------------------------------

class _FakeMonitor:
    def __init__(self):
        self.events = []

    def record(self, samples, scalars):
        self.events.append((samples, dict(scalars)))


def test_telemetry_effective_token_scalars():
    from deeperspeed_tpu.runtime.telemetry import Telemetry

    class Eng:
        global_samples = 8
        checkpoint_manager = None

    mon = _FakeMonitor()
    tel = Telemetry(monitor=mon, goodput=True, mfu=False, spans=False)
    tel.on_step_start(0)
    tel.on_step_end(Eng(), verdict="ok", tokens=(300, 1000))
    tel.on_step_start(1)
    tel.on_step_end(Eng(), verdict="ok", tokens=(200, 1000))
    tel.close()
    scalars = mon.events[-1][1]
    assert scalars["Train/Samples/tokens_per_sec"] > 0
    assert scalars["Train/Samples/effective_tokens_per_sec"] > 0
    np.testing.assert_allclose(
        scalars["Train/Goodput/effective_token_fraction"], 0.25)
    # ratio of the per-step rates matches the per-step token ratio
    np.testing.assert_allclose(
        scalars["Train/Samples/effective_tokens_per_sec"] /
        scalars["Train/Samples/tokens_per_sec"], 0.2)


def test_telemetry_no_token_scalars_when_unpacked():
    from deeperspeed_tpu.runtime.telemetry import Telemetry

    class Eng:
        global_samples = 8
        checkpoint_manager = None

    mon = _FakeMonitor()
    tel = Telemetry(monitor=mon, goodput=True, mfu=False, spans=False)
    tel.on_step_start(0)
    tel.on_step_end(Eng(), verdict="ok", tokens=None)
    tel.close()
    scalars = mon.events[-1][1]
    assert "Train/Samples/tokens_per_sec" not in scalars
    assert "Train/Goodput/effective_token_fraction" not in scalars


def test_null_telemetry_accepts_tokens():
    from deeperspeed_tpu.runtime.telemetry import NULL_TELEMETRY
    NULL_TELEMETRY.on_step_end(None, verdict="ok", tokens=(1, 2))


# ---------------------------------------------------------------------------
# engine integration: packed triple through initialize + train_batch
# ---------------------------------------------------------------------------

def test_engine_trains_packed_batch():
    import deeperspeed_tpu
    model, params = tiny_neox(64)
    eng, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=params,
        config_params={
            "train_batch_size": 8,
            "gradient_accumulation_steps": 1,
            "steps_per_print": 10_000,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "packing": {"enabled": True},
            "telemetry": {"enabled": True, "goodput": True,
                          "mfu": False, "spans": False},
        })
    assert model.config.use_segment_ids   # apply_ds_config plumb ran
    ds = eng.pack_dataset(synthetic_doc_mixture(11, 48, 97, mean_len=30.0,
                                                max_len=64))
    assert ds.seq_len == 64               # inferred from config.max_seq_len
    tok = ds.tokens[:8][None]
    seg = ds.segment_ids[:8][None]
    loss0 = eng.train_batch(batch=(tok, tok, seg))
    loss1 = eng.train_batch(batch=(tok, tok, seg))
    assert np.isfinite(float(loss0)) and np.isfinite(float(loss1))
    assert float(loss1) < float(loss0)   # it actually learns the batch
    frac = eng.telemetry.goodput  # telemetry ran
    assert eng.telemetry._tokens_total > 0
    assert 0 < eng.telemetry._tokens_effective < \
        eng.telemetry._tokens_total
