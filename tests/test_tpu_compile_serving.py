"""The serving programs at published widths, compiled for a described TPU
v5e (`tests/tpu_compile_common.py` says how): each holds its weights once
and leaves its pools where they lie.
"""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.tpu_compile_common import (  # noqa: F401 (fixtures)
    attention_weight_relayouts, BF16, CARRIES, INSTRUCTION, on_chip,
    pool_shaped_moves, v5e_2x2)

@pytest.mark.parametrize("kv", [None, "int8"], ids=["bf16", "int8"])
def test_decode_program_leaves_the_pools_in_place(on_chip, v5e_2x2, kv):
    """The engine's real decode program at Pythia-1.4b's widths (hidden
    2048, 16 heads of 128; two layers, a small vocabulary), 401 pages of
    64, batch 32, compiled for the described v5e from shapes alone.

    Apart from what only carries a pool (parameters, tuples, bitcasts,
    the loop) and the two kernels' own custom calls, no instruction's
    result has the shape of a pool or of one layer's pool: no copy of a
    donated pool, no slicing a layer out of the stack or stacking it
    back, no layout change around a scatter. The program's temporaries
    stay under one layer's pool. Int8 pages: the data pools are held to
    the same; their scale pools (1/64 of the bytes) get one layout
    change a program from the compiler, because the chip's own layout
    of a `[.., 16, 64]` bf16 array is not row-major (PERF.md, section 7).

    The QKV weight is read where it lies in the stack: 32 rows under a
    hidden size of 2048 keep the projection a plain dot, so no copy has
    the shape of a layer's attention weight and no convolution under
    `ds.attn` runs over a window of heads (PERF.md, section 6, PR 40).
    """
    from jax.sharding import SingleDeviceSharding
    from deeperspeed_tpu.inference import InferenceEngine
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
    layers, pages, page_size, batch = 2, 401, 64, 32
    cfg = GPTNeoXConfig(vocab_size=1024, hidden_size=2048,
                        num_layers=layers, num_heads=16, max_seq_len=2048,
                        rotary_pct=0.25)
    model = GPTNeoX(cfg, use_pallas=True)
    params = jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, BF16),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    block = {"enabled": True, "page_size": page_size,
             # the engine's own pools stay small: the program takes the
             # pools as arguments, and those are shapes of 401 pages
             "num_pages": 2048 // page_size + 1, "max_batch_size": batch,
             "token_budget": 2048, "prefill_lengths": [128],
             "prefill_batch_sizes": [1], "decode_batch_sizes": [batch]}
    if kv:
        block["kv_cache_dtype"] = kv
    engine = InferenceEngine(model, params=params,
                             config={"inference": block})
    one_chip = SingleDeviceSharding(v5e_2x2[0])

    def shape_of(leaf, shape=None):
        return jax.ShapeDtypeStruct(shape or leaf.shape, leaf.dtype,
                                    sharding=one_chip)

    def pool_of(pool):
        return jax.tree_util.tree_map(
            lambda leaf: shape_of(leaf, (layers, pages) + leaf.shape[2:]),
            pool)

    compiled = engine._decode_fn(batch).lower(
        jax.tree_util.tree_map(shape_of, engine.params),
        jax.tree_util.tree_map(shape_of, engine.params_stacked),
        shape_of(np.zeros((batch,), np.int32)),
        shape_of(np.zeros((batch,), np.int32)),
        {"full": shape_of(np.zeros((batch, engine.n_pages_max), np.int32))},
        pool_of(engine._pools()),
        shape_of(jax.random.PRNGKey(0)),
        # the in-flight decode's tokens and each row's place in them
        shape_of(np.zeros((batch,), np.int32)),
        shape_of(np.zeros((batch,), np.int32))).compile()
    text = compiled.as_text()
    for name in ("ds.kv_write", "ds.paged_decode"):
        assert re.search(rf"%{name}[.\d]* = .*tpu_custom_call", text), name

    tile = f"{pages},16,{page_size},128]"
    pool_shaped = re.compile(
        rf"{'s8' if kv else 'bf16'}\[(?:{layers},|1,)?{re.escape(tile)}")
    moved = []
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m and pool_shaped.search(m["type"]) and m["op"] not in CARRIES \
                and "tpu_custom_call" not in line:
            moved.append((m["op"], m["type"][:60]))
    assert not moved, moved
    assert not attention_weight_relayouts(text)
    layer_pool = pages * 16 * page_size * 128 * (1 if kv else 2)
    assert compiled.memory_analysis().temp_size_in_bytes < layer_pool


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_moe_serving_programs_leave_the_experts_in_place(on_chip, v5e_2x2,
                                                         program):
    """The engine's decode and prefill programs for an OLMoE block at the
    published widths (hidden 2048, 16 heads of 128, experts of width
    1024, 8 a token; two layers, 16 experts, a small vocabulary),
    compiled for the described v5e from shapes alone. The layer loop
    does not slice a layer's experts out of the stacked weights (0.8 GB
    a layer at 64 experts: a third of the device's time when a scan did
    it): but for what only carries them, and the grouped matmul's own
    calls, no instruction's result has the experts' shape."""
    from jax.sharding import SingleDeviceSharding
    from deeperspeed_tpu.inference import InferenceEngine
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
    layers, experts, batch, seqlen, page_size = 2, 16, 32, 256, 64
    cfg = GPTNeoXConfig(
        vocab_size=1024, hidden_size=2048, num_layers=layers, num_heads=16,
        max_seq_len=2048, rotary_pct=1.0, use_parallel_residual=False,
        norm="rmsnorm", use_bias=False, qk_norm=True, hidden_act="silu",
        ffn_gated=True, ffn_width=1024, moe_num_experts=experts,
        moe_top_k=8, moe_dropless=True)
    model = GPTNeoX(cfg, use_pallas=True)
    params = jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, BF16),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    engine = InferenceEngine(model, params=params, config={"inference": {
        "enabled": True, "page_size": page_size,
        "num_pages": 2048 // page_size + 1, "max_batch_size": batch,
        "token_budget": 2048, "prefill_lengths": [seqlen],
        "prefill_batch_sizes": [1], "decode_batch_sizes": [batch]}})
    one_chip = SingleDeviceSharding(v5e_2x2[0])

    def shape_of(leaf):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=one_chip)

    def ints(*shape):
        return shape_of(np.zeros(shape, np.int32))

    carry = ()
    if program == "decode":
        fn = engine._decode_fn(batch)
        inputs = (ints(batch), ints(batch),
                  {"full": ints(batch, engine.n_pages_max)})
        carry = (ints(batch), ints(batch))   # in-flight tokens, row of each
    else:
        fn = engine._prefill_fn(1, seqlen)
        inputs = (ints(1, seqlen), ints(1),
                  {"full": ints(1, seqlen // page_size)})
    text = fn.lower(
        jax.tree_util.tree_map(shape_of, engine.params),
        jax.tree_util.tree_map(shape_of, engine.params_stacked), *inputs,
        jax.tree_util.tree_map(shape_of, engine._pools()),
        shape_of(jax.random.PRNGKey(0)), *carry).compile().as_text()
    calls = re.findall(r"%ds\.grouped_matmul[.\d]* = .*tpu_custom_call", text)
    assert len(calls) >= 2, "gate-and-up and down: two kernel calls a layer"
    expert_shaped = re.compile(
        rf"bf16\[(?:{layers},|1,)?{experts},(?:2048,2048|1024,2048)\]")
    moved = []
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m and expert_shaped.search(m["type"]) and \
                m["op"] not in CARRIES and "tpu_custom_call" not in line:
            moved.append((m["op"], m["type"][:60]))
    assert not moved, moved


def _homogeneous_engine_holds_the_weights_once():
    """A homogeneous model (`blocks`: a list of layers) behind the same
    walk: the engine stacks its layers once and keeps no `blocks`, so
    what the construction leaves live is the weights ONCE beside the
    pools (the caller here keeps no tree of its own; with the placed list
    kept beside the stack it was the block weights twice)."""
    from deeperspeed_tpu.inference import InferenceEngine
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
    layers = 4
    cfg = GPTNeoXConfig(vocab_size=256, hidden_size=256, num_layers=layers,
                        num_heads=4, max_seq_len=256, param_dtype=BF16)
    model = GPTNeoX(cfg, use_pallas=False)
    before = {id(a): a for a in jax.live_arrays()}
    engine = InferenceEngine(
        model, params=model.init_params(jax.random.PRNGKey(0)),
        config={"inference": {
            "enabled": True, "page_size": 16, "num_pages": 17,
            "max_batch_size": 2, "token_budget": 256}})

    def nbytes(tree):
        return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree))

    assert "blocks" not in engine.params
    (stack,) = engine.params_stacked.values()
    assert all(leaf.shape[0] == layers
               for leaf in jax.tree_util.tree_leaves(stack))
    held = nbytes(engine.params) + nbytes(stack) + nbytes(engine._pools())
    live = sum(a.nbytes for a in jax.live_arrays() if id(a) not in before)
    # the rotary tables, the carried tokens: small beside a layer
    assert held <= live < held + nbytes(stack) // layers, (live, held)


def _block_programs_hold_the_weights_once(v5e_2x2, program):
    """The engine's block-pass and prefill programs for SDAR's block at
    the published widths (hidden 2048, 32 query heads over 4 KV heads of
    128 with a norm a head, 128 experts of width 768, 8 a token, the whole
    vocabulary of 151,936; two layers) at the cell's shapes (32 sequences
    x 2 slots of 4 rows, page 64, 1,601 pages, a window of 3,072, a bucket
    of 2,048), compiled for the described v5e from shapes alone. The paged
    kernel runs under the block pass's name and the row writes are there;
    no instruction produces an array of the pool's or of the experts'
    shape, none re-lays out an attention weight, and the engine's stack
    is the caller's array: the weights are held once."""
    from jax.sharding import SingleDeviceSharding
    from deeperspeed_tpu.inference import InferenceEngine
    from deeperspeed_tpu.models.gpt_neox import (GPTNeoX, GPTNeoXConfig,
                                                 LayerSpec)
    batch, seqlen, page_size, layers, block = 32, 2048, 64, 2, 4
    cfg = GPTNeoXConfig(
        vocab_size=151936, hidden_size=2048, num_layers=layers,
        num_heads=32, num_kv_heads=4, max_seq_len=3072,
        use_parallel_residual=False, norm="rmsnorm", use_bias=False,
        qk_norm="head", hidden_act="silu", ffn_gated=True, ffn_width=768,
        layernorm_eps=1e-6, attn_head_dim=128,
        layer_plan=(LayerSpec(attn="full", heads=32, rotary_pct=1.0,
                              rotary_base=1e6, ffn="experts"),) * layers,
        moe_num_experts=128, moe_top_k=8, moe_dropless=True,
        moe_norm_topk_prob=True, moe_expert_width=768,
        generation_block=block, mask_token_id=151669)
    model = GPTNeoX(cfg, use_pallas=True)
    params = jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, BF16),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    engine = InferenceEngine(model, params=params, config={"inference": {
        "enabled": True, "page_size": page_size, "num_pages": 1601,
        "max_seq_len": 3072, "max_batch_size": batch,
        "token_budget": 2048 + batch * block, "prefill_lengths": [seqlen],
        "prefill_batch_sizes": [1], "decode_batch_sizes": [batch]}})
    pool = engine.cache.k
    assert pool.shape == (layers, 1601, 4, page_size, 128)
    assert engine.params_stacked is engine.params["stacks"]
    one_chip = SingleDeviceSharding(v5e_2x2[0])

    def shape_of(leaf):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=one_chip)

    def ints(*shape):
        return shape_of(np.zeros(shape, np.int32))

    shapes = functools.partial(jax.tree_util.tree_map, shape_of)
    carry = ()
    if program == "block_decode":
        fn = engine._decode_fn(batch)
        # a row's two slots: their state, their ends
        inputs = (ints(batch, 4 * block + 1), ints(batch, 2),
                  {kind: ints(batch, engine.n_pages_max)
                   for kind in engine.caches})
        carry = (ints(batch, 4 * block + 1), ints(batch))
        kernels = ("ds.paged_decode_block", "ds.kv_write",
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
    assert "ds.attn_xla" not in text and "ds.paged_decode_xla" not in text
    assert not re.search(r"%ds\.paged_decode[.\d]* = ", text)
    expert_shaped = re.compile(
        rf"bf16\[(?:\d,)?128,(?:2048,1536|768,2048)\]")
    moved = [line[:120] for line in text.splitlines()
             if (m := INSTRUCTION.match(line)) and
             expert_shaped.search(m["type"]) and m["op"] not in CARRIES
             and "tpu_custom_call" not in line]
    assert not moved, moved
    if program == "block_decode":
        assert "ds.unmask" in text
        assert not pool_shaped_moves(text, pool.shape)
        # 128 rows under a hidden size of 2048 keep the projections to
        # heads plain: no copy of a layer's q, k/v or output weight (the
        # dots over [32, 4, 2048] rows are convolutions of window 1, which
        # `attention_weight_relayouts` would take for the folded form)
        weight = re.compile(
            r"bf16\[(?:\d,)?(?:2048,4096|2048,1024|4096,2048)\]")
        assert not [line[:120] for line in text.splitlines()
                    if (m := INSTRUCTION.match(line)) and m["op"] == "copy"
                    and weight.search(m["type"])]
        assert "window={size=1}" in text and not re.search(
            r"window=\{size=(?!1\})\d+\}.*ds\.attn", text)
    else:
        # no head in a block model's prefill: nothing of the vocabulary's
        # width is computed
        assert "ds.lm_head" not in text and \
            not re.search(r"f32\[[\d,]*151936\]", text)


@pytest.mark.parametrize("program", ["decode", "prefill", "block_decode",
                                     "block_prefill", "homogeneous"])
def test_planned_serving_programs_compile_and_hold_the_weights_once(
        on_chip, v5e_2x2, program):
    """The engine's decode and prefill programs for Laguna's block at the
    published widths (hidden 3072, head dim 128, 8 KV heads under 48 / 72
    query heads, window 512, dense width 12288, experts of width 1024, 10
    a token of 256 scored, a shared expert; 16 experts held and a small
    vocabulary), five layers in the published order, compiled for the
    described v5e from shapes alone. Both attention kernels run under
    both names, no instruction's result has the shape of a kind's
    experts, and the engine's stacks are the caller's arrays. `block_*`:
    the same for a block-generating model
    (`_block_programs_hold_the_weights_once`); `homogeneous`: a model of
    one layer kind walks the same way and its weights too are held once
    (`_homogeneous_engine_holds_the_weights_once`)."""
    if program == "homogeneous":
        return _homogeneous_engine_holds_the_weights_once()
    if program.startswith("block_"):
        return _block_programs_hold_the_weights_once(v5e_2x2, program)
    from jax.sharding import SingleDeviceSharding
    from deeperspeed_tpu.inference import InferenceEngine
    from deeperspeed_tpu.models.gpt_neox import (GPTNeoX, GPTNeoXConfig,
                                                 LayerSpec)
    yarn = ("yarn", 128, 8192, 32, 1, 1.4852030263919618)
    full = dict(attn="full", heads=48, rotary_pct=0.5, rotary_base=5e5,
                rope=yarn)
    window = dict(attn="window", heads=72, rotary_pct=1.0, rotary_base=1e4)
    held, batch, seqlen, page_size = 16, 32, 1024, 64
    cfg = GPTNeoXConfig(
        vocab_size=1024, hidden_size=3072, num_layers=5, num_heads=48,
        max_seq_len=2048, use_parallel_residual=False, norm="rmsnorm",
        use_bias=False, hidden_act="silu", ffn_gated=True, ffn_width=12288,
        layernorm_eps=1e-6,
        layer_plan=(LayerSpec(ffn="dense", **full),
                    *(LayerSpec(ffn="experts", **window),) * 3,
                    LayerSpec(ffn="experts", **full)),
        attn_head_dim=128, num_kv_heads=8, attn_window=512,
        attn_gate="per-head", moe_num_experts=256, moe_top_k=10,
        moe_dropless=True, moe_norm_topk_prob=True, moe_expert_width=1024,
        moe_shared_width=1024, moe_routing_scale=2.5, moe_held=(0, held))
    model = GPTNeoX(cfg, use_pallas=True)
    params = jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, BF16),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    engine = InferenceEngine(model, params=params, config={"inference": {
        "enabled": True, "page_size": page_size,
        "num_pages": 2048 // page_size + 1, "max_batch_size": batch,
        "token_budget": 2048, "prefill_lengths": [seqlen],
        "prefill_batch_sizes": [1], "decode_batch_sizes": [batch]}})
    assert all(a is b for a, b in zip(
        jax.tree_util.tree_leaves(params["stacks"]),
        jax.tree_util.tree_leaves(engine.params_stacked)))
    assert engine.window_cache.num_pages == batch * 9 + 1
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
        carry = (ints(batch + 1), ints(batch))
        kernels = ("ds.paged_decode", "ds.paged_decode_window",
                   "ds.kv_write", "ds.grouped_matmul")
    else:
        fn = engine._prefill_fn(1, seqlen)
        inputs = (ints(1, seqlen), ints(1),
                  {kind: ints(1, seqlen // page_size)
                   for kind in engine.caches})
        kernels = ("ds.flash_fwd", "ds.flash_fwd_window",
                   "ds.grouped_matmul")
    text = fn.lower(
        shapes(engine.params), shapes(engine.params_stacked), *inputs,
        shapes(engine._pools()), shape_of(jax.random.PRNGKey(0)),
        *carry).compile().as_text()
    for name in kernels:
        assert re.search(rf"%{name}[.\d]* = .*tpu_custom_call", text), name
    expert_shaped = re.compile(
        rf"bf16\[(?:\d,)?{held},(?:3072,2048|1024,3072)\]")
    moved = []
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m and expert_shaped.search(m["type"]) and \
                m["op"] not in CARRIES and "tpu_custom_call" not in line:
            moved.append((m["op"], m["type"][:60]))
    assert not moved, moved


# ---------------------------------------------------------------------------
# a looped model (Ouro-2.6B) at its published widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("program,seqlen", [
    ("decode", 256), ("prefill", 256), ("prefill", 128), ("prefill", 64)])
def test_looped_serving_programs_compile_and_carry_the_pools(
        on_chip, v5e_2x2, program, seqlen):
    """The engine's decode and prefill programs for Ouro's block at the
    published widths (hidden 2048, 16 heads of 128, a gated MLP of width
    5,632, a norm on each sublayer's output; three layers and a small
    vocabulary) run 4 times over the same weights, compiled for the
    described v5e from shapes alone: the paged kernel and the row write
    (prefill: the flash forward) are there ONCE, in the body of the pass
    loop, the pool has 12 cache layers, and no instruction of the decode
    step but the row write produces an array of the pool's shape: the
    pools ride the pass loop and the layer scan as carried state. A
    64-token prefill bucket, half the flash forward's least block, still
    runs the kernel (the engine pads its attention up to one block).
    Neither program copies the q or the k/v weight stack into another
    layout (folded into the dot, the reshape to heads costs a copy of
    the WHOLE loop-invariant stack a step: 1.21 GB at 48 layers): 16 to
    256 rows under a hidden size of 2048 keep the projections plain."""
    from jax.sharding import SingleDeviceSharding
    from deeperspeed_tpu.inference import InferenceEngine
    from deeperspeed_tpu.models.gpt_neox import (GPTNeoX, GPTNeoXConfig,
                                                 LayerSpec)
    batch, page_size, layers, passes = 16, 64, 3, 4
    cfg = GPTNeoXConfig(
        vocab_size=1024, hidden_size=2048, num_layers=layers, num_heads=16,
        num_kv_heads=16, max_seq_len=640, use_parallel_residual=False,
        norm="rmsnorm", use_bias=False, hidden_act="silu", ffn_gated=True,
        ffn_width=5632, layernorm_eps=1e-6, attn_head_dim=128,
        layer_plan=(LayerSpec(attn="full", heads=16, rotary_pct=1.0,
                              rotary_base=1e6, ffn="dense"),) * layers,
        sublayer_out_norm=True, loop_steps=passes)
    model = GPTNeoX(cfg, use_pallas=True)
    params = jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, BF16),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    engine = InferenceEngine(model, params=params, config={"inference": {
        "enabled": True, "page_size": page_size, "num_pages": 81,
        "max_seq_len": 640, "max_batch_size": batch, "token_budget": 272,
        "prefill_lengths": [seqlen], "prefill_batch_sizes": [1],
        "decode_batch_sizes": [batch]}})
    pool = engine.cache.k
    assert pool.shape == (passes * layers, 81, 16, page_size, 128)
    assert engine.params_stacked is engine.params["stacks"]
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
        # the tokens and, behind them, each row's exit pass
        carry = (ints(2 * batch), ints(batch))
        kernels = ("ds.paged_decode", "ds.kv_write")
    else:
        fn = engine._prefill_fn(1, seqlen)
        inputs = (ints(1, seqlen), ints(1),
                  {kind: ints(1, seqlen // page_size)
                   for kind in engine.caches})
        kernels = ("ds.flash_fwd",)
    text = fn.lower(
        shapes(engine.params), shapes(engine.params_stacked), *inputs,
        shapes(engine._pools()), shape_of(jax.random.PRNGKey(0)),
        *carry).compile().as_text()
    for name in kernels:
        calls = re.findall(rf"%{name}[.\d]* = .*tpu_custom_call", text)
        assert len(calls) == 1, (name, len(calls))
    assert "ds.loop/ds.layers" in text and "ds.loop_exit" in text
    assert "ds.attn_xla" not in text
    assert not attention_weight_relayouts(text)
    if program == "decode":
        assert not pool_shaped_moves(text, pool.shape)
