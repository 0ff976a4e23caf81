"""The cache kinds beside pages of K and V, compiled for a described TPU
v5e (`tests/tpu_compile_common.py` says how): the recurrent state
(phi4flash's scan state, qwen3_next's delta-rule matrices), the latent row
(GLM-4.7-Flash) and the chunk-pooled page (EvaByte), each kind's kernels
and its serving programs.
"""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.ops import dispatch_report
from tests.tpu_compile_common import (  # noqa: F401 (fixtures)
    assert_kernel, BF16, decode_attention, eva, gdn, INSTRUCTION,
    kernel_names, on_chip, pool_shaped_moves, ssm, stacked, v5e_2x2)

# ---------------------------------------------------------------------------
# a recurrent-state cache kind (phi4flash) at its published widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows", [1024, 128])
def test_ssm_scan_compiles(on_chip, rows):
    f32 = jnp.float32
    text = on_chip(
        lambda *a: ssm.ssm_scan(*a, backend="pallas"),
        ((1, rows, 5120), f32), ((1, rows, 5120), f32), ((1, rows, 16), f32),
        ((1, rows, 16), f32), ((16, 5120), f32), ((5120,), f32))
    assert kernel_names(text) == {"ds.ssm_scan"}


def test_ssm_step_compiles(on_chip):
    f32 = jnp.float32
    text = on_chip(
        lambda conv, pool, *a: ssm.ssm_step((conv, pool), *a,
                                            backend="pallas"),
        ((9, 97, 3, 8, 640), BF16), ((9, 97, 16, 8, 640), f32),
        ((96, 3, 5120), BF16), ((96,), jnp.int32), ((), jnp.int32),
        ((96, 5120), f32), ((96, 5120), f32), ((96, 16), f32),
        ((96, 16), f32), ((16, 5120), f32), ((5120,), f32))
    assert kernel_names(text) == {"ds.ssm_step"}


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_state_kind_serving_programs_compile_and_carry_every_pool(
        on_chip, v5e_2x2, program):
    """The engine's decode and prefill programs for phi4flash's block at
    the published widths (hidden 2560, 20 pairs of 128 over 10 KV heads,
    window 512, inner 5120, state 16, MLP 10240; a small vocabulary),
    eight layers in the published order (ssm, window, ssm, window, ssm,
    full, gmu, cross), compiled for the described v5e from shapes alone:
    every kernel runs under its own name, nothing falls to XLA, and no
    instruction's result has the shape of a page pool or of a state
    pool (a decode step moves rows and states, never a pool)."""
    from jax.sharding import SingleDeviceSharding
    from benchmarks.families import phi4flash as family
    from deeperspeed_tpu.inference import InferenceEngine
    conf = {"hidden_act": "silu", "hidden_size": 2560,
            "intermediate_size": 10240, "layer_norm_eps": 1e-5,
            "max_position_embeddings": 262144, "mb_per_layer": 2,
            "num_attention_heads": 40, "num_hidden_layers": 8,
            "num_key_value_heads": 20, "sliding_window": 512,
            "tie_word_embeddings": True, "mlp_bias": False,
            "lm_head_bias": False, "vocab_size": 1024, "embd_pdrop": 0,
            "resid_pdrop": 0}
    model = family.build_model(conf, "bfloat16",
                               {"use_pallas": True, "max_seq_len": 3072})
    params = jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, leaf.dtype),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    batch, page, seqlen = 96, 64, 1024
    engine = InferenceEngine(model, params=params, config={"inference": {
        "enabled": True, "page_size": page, "num_pages": batch * 48 + 17,
        "max_seq_len": 3072, "max_batch_size": batch,
        "token_budget": seqlen + batch, "prefill_lengths": [seqlen],
        "prefill_batch_sizes": [1], "decode_batch_sizes": [batch]}})
    assert engine.cache.k.shape == (1, batch * 48 + 17, 10, page, 128)
    assert engine.window_cache.k.shape == (2, batch * 9 + 1, 10, page, 128)
    assert engine.state_cache.ssm.shape == (3, batch + 1, 16, 8, 640)
    assert engine.state_cache.conv.shape == (3, batch + 1, 3, 8, 640)
    one_chip = SingleDeviceSharding(v5e_2x2[0])

    def shape_of(leaf):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=one_chip)

    def ints(*shape):
        return shape_of(np.zeros(shape, np.int32))

    def tables(rows, width):
        return dict({kind: ints(rows, width) for kind in engine.caches},
                    state=ints(rows))

    shapes = functools.partial(jax.tree_util.tree_map, shape_of)
    carry = ()
    if program == "decode":
        fn = engine._decode_fn(batch)
        inputs = (ints(batch), ints(batch),
                  tables(batch, engine.n_pages_max))
        carry = (ints(batch), ints(batch))
        kernels = {"ds.paged_decode", "ds.paged_decode_window",
                   "ds.paged_decode_cross", "ds.kv_write", "ds.ssm_step"}
    else:
        fn = engine._prefill_fn(1, seqlen)
        inputs = (ints(1, seqlen), ints(1), tables(1, seqlen // page))
        kernels = {"ds.flash_fwd_window", "ds.ssm_scan", "ds.paged_decode",
                   "ds.paged_decode_cross"}
    text = fn.lower(
        shapes(engine.params), shapes(engine.params_stacked), *inputs,
        shapes(engine._pools()), shape_of(jax.random.PRNGKey(0)),
        *carry).compile().as_text()
    assert kernel_names(text) == kernels
    assert "ds.attn_xla" not in text and "ds.paged_decode_xla" not in text
    for name in ("ds.ssm_in", "ds.ssm_out", "ds.gmu", "ds.attn_diff"):
        assert name in text, name
    if program == "decode":
        for pool in (engine.cache.k, engine.window_cache.k):
            assert not pool_shaped_moves(text, pool.shape)
        assert not pool_shaped_moves(text, engine.state_cache.ssm.shape,
                                     "f32")
        assert not pool_shaped_moves(text, engine.state_cache.conv.shape)


# ---------------------------------------------------------------------------
# a matrix state a head (qwen3_next's Gated DeltaNet) at its published widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows", [1024, 100])
def test_gdn_chunk_compiles(on_chip, rows):
    f32 = jnp.float32
    text = on_chip(
        gdn.gdn_chunk, ((1, rows, 16, 128), f32), ((1, rows, 16, 128), f32),
        ((1, rows, 32, 128), f32), ((1, rows, 32), f32),
        ((1, rows, 32), f32))
    assert kernel_names(text) == {"ds.gdn_chunk"}


def test_gdn_step_compiles(on_chip):
    f32 = jnp.float32
    text = on_chip(
        lambda conv, pool, tail, slots, *a: gdn.gdn_step(
            (conv, pool), tail, slots, 2, *a),
        ((5, 65, 3, 8, 1024), BF16), ((5, 65, 32, 128, 128), f32),
        ((64, 3, 8192), BF16), ((64,), jnp.int32), ((64, 16, 128), f32),
        ((64, 16, 128), f32), ((64, 32, 128), f32), ((64, 32), f32),
        ((64, 32), f32))
    assert kernel_names(text) == {"ds.gdn_step"}


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_gdn_serving_programs_compile_and_carry_every_pool(
        on_chip, v5e_2x2, program):
    """The engine's decode and prefill programs for qwen3_next's block at
    the published widths (hidden 2048, 16 query heads over 2 KV heads of
    256, 16 key and 32 value heads of 128, experts of width 512 with a
    gated shared one; 16 of 32 experts and a small vocabulary), six layers
    in the published order (gdn, gdn, gdn, full, gdn, gdn), compiled for
    the described v5e from shapes alone: every kernel runs under its own
    name, nothing falls to XLA, and no instruction's result has the shape
    of the page pool or of a state pool (a decode step moves rows and
    states, never a pool)."""
    from jax.sharding import SingleDeviceSharding
    from benchmarks.families import qwen3_next as family
    from deeperspeed_tpu.inference import InferenceEngine
    conf = {"decoder_sparse_step": 1, "full_attention_interval": 4,
            "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
            "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
            "linear_key_head_dim": 128, "linear_num_key_heads": 16,
            "linear_num_value_heads": 32, "linear_value_head_dim": 128,
            "max_position_embeddings": 262144, "mlp_only_layers": [],
            "moe_intermediate_size": 512, "norm_topk_prob": True,
            "num_attention_heads": 16, "num_experts": 16,
            "num_experts_published": 32, "held_experts": "0-15",
            "num_experts_per_tok": 10, "num_hidden_layers": 6,
            "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
            "rms_norm_eps": 1e-6, "rope_scaling": None,
            "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
            "tie_word_embeddings": False, "use_sliding_window": False,
            "vocab_size": 1024}
    model = family.build_model(conf, "bfloat16",
                               {"use_pallas": True, "max_seq_len": 9216})
    params = jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, leaf.dtype),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    batch, page, seqlen = 8, 64, 1024
    engine = InferenceEngine(model, params=params, config={"inference": {
        "enabled": True, "page_size": page, "num_pages": batch * 144 + 17,
        "max_seq_len": 9216, "max_batch_size": batch,
        "token_budget": seqlen + batch, "prefill_lengths": [seqlen],
        "prefill_batch_sizes": [1], "decode_batch_sizes": [batch]}})
    assert engine.cache.k.shape == (1, batch * 144 + 17, 2, page, 256)
    assert engine.state_cache.ssm.shape == (5, batch + 1, 32, 128, 128)
    assert engine.state_cache.conv.shape == (5, batch + 1, 3, 8, 1024)
    one_chip = SingleDeviceSharding(v5e_2x2[0])

    def shape_of(leaf):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=one_chip)

    def ints(*shape):
        return shape_of(np.zeros(shape, np.int32))

    def tables(rows, width):
        return {"full": ints(rows, width), "state": ints(rows)}

    shapes = functools.partial(jax.tree_util.tree_map, shape_of)
    carry = ()
    if program == "decode":
        fn = engine._decode_fn(batch)
        inputs = (ints(batch), ints(batch),
                  tables(batch, engine.n_pages_max))
        carry = (ints(batch + 2), ints(batch))
        kernels = {"ds.paged_decode", "ds.kv_write", "ds.gdn_step",
                   "ds.grouped_matmul"}
    else:
        fn = engine._prefill_fn(1, seqlen)
        inputs = (ints(1, seqlen), ints(1), tables(1, seqlen // page))
        kernels = {"ds.flash_fwd", "ds.gdn_chunk", "ds.grouped_matmul"}
    text = fn.lower(
        shapes(engine.params), shapes(engine.params_stacked), *inputs,
        shapes(engine._pools()), shape_of(jax.random.PRNGKey(0)),
        *carry).compile().as_text()
    assert kernel_names(text) == kernels
    assert "ds.attn_xla" not in text and "ds.paged_decode_xla" not in text
    for name in ("ds.gdn_in", "ds.gdn_out", "ds.attn_gate",
                 "ds.moe_shared"):
        assert name in text, name
    if program == "decode":
        assert not pool_shaped_moves(text, engine.cache.k.shape)
        assert not pool_shaped_moves(text, engine.state_cache.ssm.shape,
                                     "f32")
        # (the convolution rows' pool is small, 16 MB at 65 slots, and the
        # compiler may stage it in on-chip memory round a layer loop: an
        # async copy each way, which this check would name)


# ---------------------------------------------------------------------------
# latent attention (GLM-4.7-Flash) at its published widths
# ---------------------------------------------------------------------------

LATENT_POOL = ((6, 289, 64, 640), BF16)     # a 576-wide row in whole lanes


# ---------------------------------------------------------------------------
# a chunk-pooled cache kind (EvaByte) at its published widths
# ---------------------------------------------------------------------------

def test_eva_summarize_compiles(on_chip):
    """A decode step's pooling at EvaByte's shapes (24 rows, 32 heads of
    128, chunk 16, page 64, 8 layers x 1,001 pages): the pools stay in
    HBM and alias the outputs, a closing row's four [32, 16, 128] tiles
    are moved by the kernel itself."""
    pools = stacked(8, 1001, 32, 64, 128, False)
    B = 24
    ints = [((), jnp.int32)] + [((B,), jnp.int32)] * 4 + [((B,), jnp.bool_)]
    heads = [((32, 128), BF16)] * 2

    def summarize(layer, src_page, src_slot, dst_page, dst_slot, closing,
                  phi, mu, *pools):
        return eva.eva_summarize(pools, phi, mu, layer, src_page, src_slot,
                                 closing, dst_page, dst_slot, 16,
                                 128 ** -0.5, backend="pallas")

    text = on_chip(summarize, *ints, *heads, *pools)
    assert_kernel(text)
    assert kernel_names(text) == {"ds.eva_summarize"}


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_eva_serving_programs_compile_and_leave_the_pool_in_place(
        on_chip, v5e_2x2, program):
    """The engine's decode and prefill programs for EvaByte's block at the
    published widths (hidden 4096, 32 heads of 128, SwiGLU 11,008,
    vocabulary 320 x 8 heads, window 2,048, chunk 16; two layers) at the
    cell's shapes (24 rows, page 64, 1,001 pages, a bucket of 4,096: two
    windows), compiled for the described v5e from shapes alone. The decode
    step is the row write, the pooling and the ORDINARY paged kernel, and
    no instruction of it produces an array of the pool's shape: no copy
    around the pooling's read. The prefill runs the flash forward, keeps
    under its temporaries the rows of ONE window a layer (not the
    bucket's), and neither program re-lays out a weight a decode step
    would stream."""
    from jax.sharding import SingleDeviceSharding
    from deeperspeed_tpu.inference import InferenceEngine
    from deeperspeed_tpu.models.gpt_neox import (GPTNeoX, GPTNeoXConfig,
                                                 LayerSpec)
    layers, pages, batch, page_size, seqlen = 2, 1001, 24, 64, 4096
    cfg = GPTNeoXConfig(
        vocab_size=320, hidden_size=4096, num_layers=layers, num_heads=32,
        max_seq_len=20480, layernorm_eps=1e-5, use_parallel_residual=False,
        tie_word_embeddings=False, norm="rmsnorm", use_bias=False,
        hidden_act="silu", ffn_gated=True, ffn_width=11008,
        layer_plan=(LayerSpec(attn="eva", heads=32, rotary_pct=1.0,
                              rotary_base=1e5, ffn="dense"),) * layers,
        eva_window=2048, eva_chunk=16, norm_unit_offset=True,
        num_pred_heads=8, param_dtype=BF16)
    model = GPTNeoX(cfg, use_pallas=True)
    params = jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, BF16),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    engine = InferenceEngine(model, params=params, config={"inference": {
        "enabled": True, "page_size": page_size,
        # the engine's own pool stays small: the programs take the pools
        # as arguments, and those are shapes of 1,001 pages
        "num_pages": 20480 // page_size + 1, "max_seq_len": 20480,
        "max_batch_size": batch, "token_budget": seqlen + batch,
        "prefill_lengths": [2048, seqlen], "prefill_batch_sizes": [1],
        "decode_batch_sizes": [batch]}})
    assert engine.n_pages_max == 2 * 10 + 32
    one_chip = SingleDeviceSharding(v5e_2x2[0])

    def shape_of(leaf, shape=None):
        return jax.ShapeDtypeStruct(shape or leaf.shape, leaf.dtype,
                                    sharding=one_chip)

    def ints(*shape):
        return shape_of(np.zeros(shape, np.int32))

    shapes = functools.partial(jax.tree_util.tree_map, shape_of)
    pools = jax.tree_util.tree_map(
        lambda leaf: shape_of(leaf, (layers, pages) + leaf.shape[2:]),
        engine._pools())
    carry = ()
    if program == "decode":
        fn = engine._decode_fn(batch)
        inputs = (ints(batch), ints(batch),
                  {"eva": ints(batch, engine.n_pages_max),
                   "eva_pending": ints(batch, 2)})
        carry = (ints(batch), ints(batch))
        kernels = {"ds.kv_write", "ds.eva_summarize", "ds.paged_decode"}
    else:
        fn = engine._prefill_fn(1, seqlen)
        inputs = (ints(1, seqlen), ints(1),
                  {"eva": ints(1, 2048 // page_size),
                   "eva_pooled": ints(1, seqlen // 16 // page_size)})
        kernels = {"ds.flash_fwd"}
    compiled = fn.lower(
        shapes(engine.params), shapes(engine.params_stacked), *inputs, pools,
        shape_of(jax.random.PRNGKey(0)), *carry).compile()
    text = compiled.as_text()
    assert kernels == set(re.findall(
        r"%(ds\.[a-z0-9_]+)[.\d]* = .*tpu_custom_call", text))
    assert "ds.attn_xla" not in text and "ds.paged_decode_xla" not in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    if program == "decode":
        assert not pool_shaped_moves(text, (layers, pages, 32, 64, 128))
        assert not pool_shaped_moves(text, (1, pages, 32, 64, 128))
        weight = re.compile(
            r"bf16\[(?:\d,)?(?:4096,(?:4096|8192|22016)|11008,4096)\]")
        assert not [line[:120] for line in text.splitlines()
                    if (m := INSTRUCTION.match(line)) and m["op"] == "copy"
                    and weight.search(m["type"])]
        assert temp < 16 * 2 ** 20
    else:
        assert "ds.eva_prefill" in text and "ds.eva_summarize" in text
        # q, k, v and the MLP's halves of 4,096 rows, not a bucket's K and
        # V of every layer
        assert temp < 2 ** 30


def test_latent_paged_decode_compiles_and_reads_the_pool_where_it_lies(
        on_chip):
    """The absorbed kernel at the cell's decode shapes: 20 query heads of
    512 + 64 over ONE [64, 640] tile a page, batch 32, a table of 264
    pages, the layer a traced scalar. The pool's row is whole lane tiles:
    with a 576-wide row the chip's own layout puts another dim innermost
    and the call is handed a COPY of the pool (seen here before the first
    chip run, PR 35)."""
    def decode(q, table, lengths, layer, pool):
        return decode_attention.paged_latent_decode(
            q, pool, table, lengths, 1 / 16, 512, layer, backend="pallas")

    text = on_chip(decode, ((32, 20, 576), BF16), ((32, 264), jnp.int32),
                   ((32,), jnp.int32), ((), jnp.int32), LATENT_POOL)
    assert re.search(r"%ds\.paged_decode_latent[.\d]* = .*tpu_custom_call",
                     text)
    assert not pool_shaped_moves(text, LATENT_POOL[0])
    assert decode_attention.latent_row_width(576) == 640


def test_latent_row_write_compiles(on_chip):
    def write(pool, rows, layer, page_idx, slot):
        return decode_attention.paged_latent_write(
            pool, rows, layer, page_idx, slot, backend="pallas")

    text = on_chip(write, LATENT_POOL, ((32, 576), BF16), ((), jnp.int32),
                   ((32,), jnp.int32), ((32,), jnp.int32))
    assert re.search(r"%ds\.kv_write[.\d]* = .*tpu_custom_call", text)
    # the row's sublane group of its [64, 640] page: a [16, 640] block
    assert dispatch_report()["decode_attention"]["kv_write_latent_slots"] \
        == 16


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_latent_serving_programs_compile_and_leave_the_pool_in_place(
        on_chip, v5e_2x2, program):
    """The engine's decode and prefill programs for GLM-4.7-Flash's block
    at the published widths (hidden 2048, 20 heads of 192 + 64 / 256,
    ranks 768 and 512, dense width 10,240, experts of width 1,536, 4 a
    token, a sigmoid router with its bias, a shared expert; 8 experts
    and a small vocabulary), layer 0 dense and two expert layers,
    compiled for the described v5e from shapes alone: the latent kernel,
    the row write and the grouped matmul are there, and no instruction
    of the decode step but them produces an array of the pool's shape."""
    from jax.sharding import SingleDeviceSharding
    from deeperspeed_tpu.inference import InferenceEngine
    from deeperspeed_tpu.models.gpt_neox import (GPTNeoX, GPTNeoXConfig,
                                                 LayerSpec)
    latent = dict(attn="latent", heads=20, rotary_pct=1.0, rotary_base=1e6)
    batch, seqlen, page_size = 32, 2048, 64
    cfg = GPTNeoXConfig(
        vocab_size=1024, hidden_size=2048, num_layers=3, num_heads=20,
        num_kv_heads=20, max_seq_len=4096, use_parallel_residual=False,
        norm="rmsnorm", use_bias=False, hidden_act="silu", ffn_gated=True,
        ffn_width=10240,
        layer_plan=(LayerSpec(ffn="dense", **latent),
                    *(LayerSpec(ffn="experts", **latent),) * 2),
        attn_head_dim=256, mla_q_rank=768, mla_kv_rank=512,
        mla_nope_dim=192, mla_rope_dim=64, mla_v_dim=256,
        moe_num_experts=8, moe_top_k=4, moe_dropless=True,
        moe_norm_topk_prob=True, moe_router_score="sigmoid",
        moe_expert_width=1536, moe_shared_width=1536,
        moe_routing_scale=1.8)
    model = GPTNeoX(cfg, use_pallas=True)
    params = jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, BF16),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    engine = InferenceEngine(model, params=params, config={"inference": {
        "enabled": True, "page_size": page_size,
        "num_pages": 4096 // page_size + 1, "max_batch_size": batch,
        "token_budget": 4096, "prefill_lengths": [seqlen],
        "prefill_batch_sizes": [1], "decode_batch_sizes": [batch]}})
    pool = engine.cache.k
    assert pool.shape == (3, 65, page_size, 640) and engine.cache.v is None
    one_chip = SingleDeviceSharding(v5e_2x2[0])

    def shape_of(leaf):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=one_chip)

    def ints(*shape):
        return shape_of(np.zeros(shape, np.int32))

    shapes = functools.partial(jax.tree_util.tree_map, shape_of)
    carry = ()
    if program == "decode":
        fn = engine._decode_fn(batch)
        inputs = (ints(batch), ints(batch),
                  {kind: ints(batch, engine.n_pages_max)
                   for kind in engine.caches})
        carry = (ints(batch), ints(batch))
        kernels = ("ds.paged_decode_latent", "ds.kv_write",
                   "ds.grouped_matmul")
    else:
        fn = engine._prefill_fn(1, seqlen)
        inputs = (ints(1, seqlen), ints(1),
                  {kind: ints(1, seqlen // page_size)
                   for kind in engine.caches})
        kernels = ("ds.flash_fwd", "ds.grouped_matmul")
    text = fn.lower(
        shapes(engine.params), shapes(engine.params_stacked), *inputs,
        shapes(engine._pools()), shape_of(jax.random.PRNGKey(0)),
        *carry).compile().as_text()
    for name in kernels:
        assert re.search(rf"%{name}[.\d]* = .*tpu_custom_call", text), name
    if program == "decode":
        # the step's only writer of the pool is the row-write kernel
        # (prefill's whole-page scatter is XLA's, and writes it)
        assert not pool_shaped_moves(text, pool.shape)
