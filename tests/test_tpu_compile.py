"""Every Pallas kernel, compiled for a TPU v5e from this CPU sandbox.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (`jax.experimental.topologies`). Interpret mode
has no tiling rules, so a kernel can pass every CPU test and still be
refused by Mosaic — `paged_decode_attention_pallas` was, from PR 8 to
PR 22. Each case lowers one kernel at a shape the main path really runs,
with `_interpret` forced off, compiles it for `v5e:2x2` and asserts that
the compiled text holds a `tpu_custom_call`. Nothing runs: a compile
that passes says nothing about results or times.

The persistent compile cache is turned off around the compiles — an
executable compiled for a described chip is written to the cache but
cannot be read back without one.
"""

import functools
import importlib
import os
import re

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# `ops.pallas` re-exports several functions under their module's own
# name (`flash_attention`, `grouped_matmul`, ...): fetch modules by path
(fa, decode_attention, block_sparse_attention, grouped_matmul, quant_matmul,
 optimizer, ssm, eva) = KERNEL_MODULES = tuple(
    importlib.import_module(f"deeperspeed_tpu.ops.pallas.{name}")
    for name in ("flash_attention", "decode_attention",
                 "block_sparse_attention", "grouped_matmul", "quant_matmul",
                 "optimizer", "ssm", "eva"))
from deeperspeed_tpu.ops import dispatch_report  # noqa: E402

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four described chips of a v5e 2x2 host; skipped where the
    topology cannot be described (no libtpu)."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"cannot describe a v5e topology here: {e}")


@pytest.fixture
def on_chip(monkeypatch, v5e_2x2):
    """`compile_for_chip(fn, *shape_dtypes, sharding=one chip)` →
    compiled text, with every kernel module's `_interpret` forced off
    and the compile cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    for mod in KERNEL_MODULES:
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(v5e_2x2[0])

    def compile_for_chip(fn, *args, sharding=one_chip):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
                for shape, dtype in args]
        # a fresh lambda: never a trace cached in interpret mode
        return jax.jit(lambda *a: fn(*a)).lower(*args).compile().as_text()

    yield compile_for_chip
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


def assert_kernel(text, at_least=1):
    assert text.count("tpu_custom_call") >= at_least, \
        "the compiled program holds no Mosaic kernel"


def kernel_names(text):
    """The `ds.*` kernel scope each Mosaic call of the program lies in."""
    return {m for line in text.splitlines() if "tpu_custom_call" in line
            for m in re.findall(r"/(ds\.[a-z0-9_]+)/pallas_call", line)}


def qkv(b, s, h, d):
    return [((b, s, h, d), BF16)] * 3


def _equations_under(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations_under(sub)


def loss_of(fn):
    """Scalar fp32 loss of an attention callable, for the backward."""
    return lambda *a: fn(*a).astype(jnp.float32).sum()


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

# (name, [B, S, H, D], causal): the trainer's shape, the long-context
# shape, and the single-block / dense-grid variants of the same kernels
FLASH_SHAPES = [
    ("train_2x2048", (2, 2048, 12, 64), True),
    ("smoke_8x2048", (8, 2048, 12, 64), True),
    ("long_1x16384", (1, 16384, 12, 64), True),
    ("single_block_4x1024", (4, 1024, 12, 64), True),
    ("dense_grid_2x2048", (2, 2048, 16, 64), False),
]


@pytest.mark.parametrize("name,shape,causal", FLASH_SHAPES,
                         ids=[s[0] for s in FLASH_SHAPES])
def test_flash_forward_compiles(on_chip, name, shape, causal):
    assert_kernel(on_chip(
        lambda q, k, v: fa.flash_attention(q, k, v, causal), *qkv(*shape)))


@pytest.mark.parametrize("name,shape,causal", FLASH_SHAPES,
                         ids=[s[0] for s in FLASH_SHAPES])
def test_flash_backward_compiles(on_chip, name, shape, causal):
    grad = jax.grad(loss_of(
        lambda q, k, v: fa.flash_attention(q, k, v, causal)),
        argnums=(0, 1, 2))
    # forward + the backward, one kernel tiled or on a single block
    assert_kernel(on_chip(grad, *qkv(*shape)), at_least=2)


# Everything `ops.autotune.flash_blocks` can return for a v5e at 8k tokens
# and over: each row of its table, and the fallback (asked for under a
# device kind that has no row) at head dim 64 and 128, causal and not.
# PR 23's cell met a candidate on the chip that did not compile; this is
# the check that would have met it here.
def _long_flash_cases():
    from deeperspeed_tpu.ops.autotune import (FLASH_LONG_SEQ_BLOCKS,
                                              flash_blocks)
    cases = [(f"row_d{d}_{'causal' if causal else 'full'}",
              (1, 16384, 16, d), causal, kind)
             for kind, d, causal in FLASH_LONG_SEQ_BLOCKS
             if kind == "TPU v5 lite"]
    cases += [(f"fallback_d{d}_{'causal' if causal else 'full'}",
               (1, 16384, 16, d), causal, "no row")
              for d in (64, 128) for causal in (True, False)]
    return [pytest.param(shape, causal, flash_blocks(shape, causal, kind),
                         id=name) for name, shape, causal, kind in cases]


@pytest.mark.parametrize("shape,causal,blocks", _long_flash_cases())
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_long_sequence_flash_geometry_compiles(on_chip, shape, causal,
                                               blocks, grad):
    (bq, bk), bwd = blocks

    def attn(q, k, v):
        return fa.flash_attention(q, k, v, causal, None, bq, bk, bwd)

    if grad:
        text = on_chip(jax.grad(loss_of(attn), argnums=(0, 1, 2)),
                       *qkv(*shape))
        assert fa._LAST_BLOCKS["dkv"] == fa._LAST_BLOCKS["dq"] == bwd
        # 16k tokens at head dim 64 and 128: the slab is admitted
        assert fa._LAST_BLOCKS["bwd_variant"].startswith("fused-")
    else:
        text = on_chip(attn, *qkv(*shape))
    assert fa._LAST_BLOCKS["fwd"] == (bq, bk)
    assert_kernel(text, at_least=2 if grad else 1)


# The tiled kernels at the shapes the benchmark's cells run them (per
# chip): a tile body Mosaic refuses (VMEM, a relayout it cannot do in a
# strip) fails here and not in the cell. (name, [B, S, H, D], fwd blocks,
# bwd blocks; None = `flash_blocks`' own.)
CELL_FLASH_SHAPES = [
    ("train_16k", (1, 16384, 16, 64), (1024, 512), (1024, 1024)),
    ("train_2k", (16, 2048, 16, 64), None, None),
    ("train_zero3_4c", (4, 2048, 16, 128), None, None),
]


@pytest.mark.parametrize("name,shape,fwd,bwd", CELL_FLASH_SHAPES,
                         ids=[c[0] for c in CELL_FLASH_SHAPES])
def test_flash_compiles_at_the_train_cells_shapes(on_chip, name, shape, fwd,
                                                  bwd):
    """The forward and the fused backward, each with its masked and its
    unmasked body; the backward's dq slab ([16, 64, 1024] float32 at 16k)
    under the VMEM limit the call asks for. The forward's loops over
    pairs and strips are ones the lowering unrolls: a loop left rolled
    would compile too, and cut a tile's body into basic blocks that
    overlap nothing."""
    bq, bk = fwd or (None, None)

    def attn(q, k, v):
        return fa.flash_attention(q, k, v, True, None, bq, bk, bwd)

    assert_kernel(on_chip(attn, *qkv(*shape)))
    loops = [eqn for eqn in _equations_under(jax.make_jaxpr(attn)(
        *(jax.ShapeDtypeStruct(*a) for a in qkv(*shape))).jaxpr)
        if eqn.primitive.name in ("scan", "while")]
    assert loops and all(eqn.primitive.name == "scan" and
                         eqn.params["unroll"] == eqn.params["length"]
                         for eqn in loops), loops
    text = on_chip(jax.grad(loss_of(attn), argnums=(0, 1, 2)), *qkv(*shape))
    assert_kernel(text, at_least=2)
    assert fa._LAST_BLOCKS["bwd_variant"] == "fused-trapezoid"
    assert {"ds.flash_fwd", "ds.flash_bwd"} <= kernel_names(text)
    assert not {"ds.flash_bwd_dkv", "ds.flash_bwd_dq"} & kernel_names(text)
    for kind in ("fwd", "bwd"):
        masked, launched = fa._LAST_MASKED[kind]
        assert 0 < masked < launched      # both bodies are in the kernel


# The heads where the program holds them (PR 53). XLA lays a
# `[B, S, H, D]` tensor of a train step out with the SEQUENCE minor
# (physically [B, H, D, S]: the rotary fusions write q and k so and read
# their gradients so), and the tiled kernels take q^T, k^T, v^T, dO^T as
# [B, H*D, S] (`flash_attention.heads_in_place`): between the model and a
# kernel there is then no copy of a tensor, where [B*H, S, D] operands
# cost eight a layer (7.4% of `train_2k`'s step, more around them).

def _copies(text, elements):
    """The `copy` instructions of a compiled program, and the `transpose`s
    that permute anything, whose result holds `elements` elements: a
    tensor of the attention."""
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?\S+ = \w+\[([0-9,]*)\]\S* "
                     r"(copy|transpose)\(", line)
        if not m or not m.group(1) or np.prod(
                [int(n) for n in m.group(1).split(",")]) != elements:
            continue
        dims = re.search(r"dimensions=\{([0-9,]*)\}", line)
        if m.group(2) == "copy" or dims.group(1).split(",") != sorted(
                dims.group(1).split(",")):
            found.append(line.strip()[:120])
    return found


@pytest.mark.parametrize("name,shape,fwd,bwd", CELL_FLASH_SHAPES,
                         ids=[c[0] for c in CELL_FLASH_SHAPES])
def test_flash_call_moves_no_tensor_at_the_train_cells_shapes(on_chip, name,
                                                              shape, fwd, bwd):
    """Forward and gradients of the training call from tensors that lie
    as a train step's do ([B, H, D, S]; the model's [B, S, H, D] is their
    transpose): the compiled program is the two kernels and a reduction
    (delta), with no copy and no transpose of a tensor. The same call on
    the moved heads copies its operands and results."""
    bq, bk = fwd or (None, None)
    b, s, h, d = shape
    held = [((b, h, d, s), BF16)] * 4

    def call(attention):
        def run(qT, kT, vT, wT):
            def loss(*a):
                out = attention(*(x.transpose(0, 3, 1, 2) for x in a))
                return (out.transpose(0, 2, 3, 1).astype(jnp.float32)
                        * wT.astype(jnp.float32)).sum()
            return jax.grad(loss, argnums=(0, 1, 2))(qT, kT, vT)
        return run

    def in_place(q, k, v):
        return fa.flash_attention(q, k, v, True, None, bq, bk, bwd)

    text = on_chip(call(in_place), *held)
    assert kernel_names(text) == {"ds.flash_fwd", "ds.flash_bwd"}
    assert not _copies(text, b * s * h * d), _copies(text, b * s * h * d)

    @jax.custom_vjp
    def moved(q, k, v):
        return moved_fwd(q, k, v)[0]

    def moved_fwd(q, k, v):
        (fq, fk), _ = fa._resolve_blocks(q.shape, True, bq, bk, bwd)
        return fa._fwd(q, k, v, True, 1.0 / np.sqrt(d), fq, fk)

    def moved_bwd(res, g):
        _, blocks = fa._resolve_blocks(g.shape, True, bq, bk, bwd)
        return fa._bwd(True, None, *blocks, res, g)

    moved.defvjp(moved_fwd, moved_bwd)
    # (eight a layer in the model; alone, XLA folds some into others)
    assert len(_copies(on_chip(call(moved), *held), b * s * h * d)) >= 4


def _one_layer_step(batch, seq, heads, hidden, remat):
    """(loss and gradients of one Pythia layer, its arguments' shapes): a
    train cell's layer at the cell's batch a chip, bfloat16 parameters."""
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
    cfg = GPTNeoXConfig(vocab_size=1024, hidden_size=hidden, num_layers=1,
                        num_heads=heads, max_seq_len=seq, rotary_pct=0.25,
                        param_dtype=BF16)
    model = GPTNeoX(cfg, use_pallas=True)
    if remat:
        model.remat_policy = remat
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten(params)

    def step(tokens, *leaves):
        params = jax.tree_util.tree_unflatten(treedef, leaves)
        return jax.value_and_grad(model.loss_fn)(params, (tokens, tokens))

    return step, [((batch, seq), jnp.int32),
                  *((leaf.shape, leaf.dtype) for leaf in leaves)]


def _heads_moved():
    return {kind: n["moved"] for kind, n in
            dispatch_report()["flash"]["heads"].items()}


def _projections():
    return dict(dispatch_report()["attention"]["head_projection"])


# (name, batch, seq, heads, hidden, remat policy, the QKV projection's form)
TRAIN_LAYERS = [
    ("train_2k", 16, 2048, 16, 1024, "attn_residuals", "split"),
    ("train_16k", 1, 16384, 16, 1024, "attn_residuals", "split"),
    ("train_zero3_4c_shard", 4, 2048, 16, 2048, None, "folded"),
]


@pytest.mark.parametrize("name,batch,seq,heads,hidden,remat,form",
                         TRAIN_LAYERS, ids=[c[0] for c in TRAIN_LAYERS])
def test_train_step_copies_no_attention_tensor(on_chip, name, batch, seq,
                                               heads, hidden, remat, form):
    """Loss and gradients of one layer at a train cell's batch a chip,
    compiled for the described v5e: no `copy` or `transpose` has the size
    of q, k, v, out or a gradient of one. At 16 heads of 64 (Pythia-410m,
    16 x 2,048 and 1 x 16,384 tokens, the cells' remat policy) none has
    the size of the three together either: the QKV projection runs as
    three dots against the weight's slices
    (`autotune.head_projection_split`), whose results XLA writes where
    the flash kernels read them, where one fused dot's result was copied
    whole, once a pass, before its split (PERF.md section 6, PR 59). At 16
    heads of 128 (`train_zero3_4c`'s shard of Pythia-1.4b, no remat) XLA
    lays a head dim of a whole lane tile the other way, three dots would
    cost six copies of `B*S*hidden`, and the rule keeps the ONE fused dot
    and its one copy."""
    step, args = _one_layer_step(batch, seq, heads, hidden, remat)
    moved_before, projections = _heads_moved(), _projections()
    text = on_chip(step, *args)
    assert kernel_names(text) >= {"ds.flash_fwd", "ds.flash_bwd"}
    moved = _copies(text, batch * seq * hidden)
    assert not moved, moved
    whole = _copies(text, 3 * batch * seq * hidden)
    assert len(whole) == (0 if form == "split" else 1), whole
    assert moved_before == _heads_moved()
    assert _projections() == {**projections, form: projections[form] + 1}


# Both sides of `ops.autotune.flash_dq_slab_admitted`, at the blocks the
# rule gives a v5e: the largest slabs it admits (8 MiB: 32k tokens at head
# dim 64, 16k at 128) run the fused backward, and the next sequence up
# takes the two kernels. (name, [B, S, H, D], fused)
SLAB_SHAPES = [
    ("largest_slab_d64", (1, 32768, 16, 64), True),
    ("largest_slab_d128", (1, 16384, 16, 128), True),
    ("over_budget_d128", (1, 32768, 16, 128), False),
    ("over_budget_d64", (1, 65536, 16, 64), False),
]


@pytest.mark.parametrize("name,shape,fused", SLAB_SHAPES,
                         ids=[c[0] for c in SLAB_SHAPES])
def test_flash_backward_compiles_on_both_sides_of_the_slab_budget(
        on_chip, name, shape, fused):
    from deeperspeed_tpu.ops.autotune import (flash_blocks,
                                              flash_dq_slab_admitted)
    assert flash_dq_slab_admitted(shape[1], shape[3]) is fused
    (bq, bk), bwd = flash_blocks(shape, True, "TPU v5 lite")

    def attn(q, k, v):
        return fa.flash_attention(q, k, v, True, None, bq, bk, bwd)

    text = on_chip(jax.grad(loss_of(attn), argnums=(0, 1, 2)), *qkv(*shape))
    backward = {"ds.flash_bwd"} if fused else \
        {"ds.flash_bwd_dkv", "ds.flash_bwd_dq"}
    assert kernel_names(text) == {"ds.flash_fwd"} | backward
    assert fa._LAST_BLOCKS["bwd_variant"] == \
        ("fused-trapezoid" if fused else "trapezoid")


def test_segmented_prefill_compiles_at_the_serve_cells_bucket(on_chip):
    """The Pythia and OLMoE cells' largest prefill bucket: one row of
    1,536 tokens, 16 heads of 128, pad rows masked through segment ids."""
    shape = (1, 1536, 16, 128)
    assert_kernel(on_chip(
        lambda q, k, v, s: fa.flash_attention_segmented(q, k, v, s, True),
        *qkv(*shape), ((1, 1536), jnp.int32)))


# the serving prefill buckets (`InferenceEngine._prefill_fn` masks pad
# rows through segment ids) and the packed-training shape
@pytest.mark.parametrize("shape", [(4, 128, 12, 64), (4, 1024, 12, 64),
                                   (2, 2048, 12, 64)],
                         ids=["prefill_128", "prefill_1024", "packed_2048"])
def test_segmented_flash_compiles(on_chip, shape):
    seg = ((shape[0], shape[1]), jnp.int32)
    assert_kernel(on_chip(
        lambda q, k, v, s: fa.flash_attention_segmented(q, k, v, s, True),
        *qkv(*shape), seg))
    grad = jax.grad(loss_of(
        lambda q, k, v, s: fa.flash_attention_segmented(q, k, v, s, True)),
        argnums=(0, 1, 2))
    assert_kernel(on_chip(grad, *qkv(*shape), seg), at_least=2)


def test_flash_key_bias_and_dropout_compile(on_chip):
    """The BERT-side variants: per-key additive bias, and in-kernel
    attention dropout from a seed."""
    shape = (4, 512, 16, 64)
    kbias = ((4, 512), jnp.float32)
    grad = jax.grad(loss_of(
        lambda q, k, v, b: fa.flash_attention_kbias(q, k, v, b)),
        argnums=(0, 1, 2))
    assert_kernel(on_chip(grad, *qkv(*shape), kbias), at_least=2)
    grad = jax.grad(loss_of(
        lambda q, k, v, b, s: fa.flash_attention_train(
            q, k, v, b, s, dropout_rate=0.1)), argnums=(0, 1, 2))
    assert_kernel(on_chip(grad, *qkv(*shape), kbias, ((1,), jnp.int32)),
                  at_least=2)


def test_masked_flash_compiles(on_chip):
    """Dense-flash iteration under a block-activity map (the sparse
    engine's arm above the density crossover)."""
    n = 2048 // 128
    layout = np.tril(np.ones((n, n), np.int32))[None].repeat(12, axis=0)
    kernel = fa.make_masked_flash_attention(layout, causal=True)
    grad = jax.grad(loss_of(kernel), argnums=(0, 1, 2))
    assert_kernel(on_chip(grad, *qkv(2, 2048, 12, 64)), at_least=2)


# ---------------------------------------------------------------------------
# paged decode attention (the serving engine's every decode step)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("page_size,quant", [(16, False), (64, False),
                                             (32, True), (64, True)],
                         ids=["bf16_page16", "bf16_page64", "int8_page32",
                              "int8_page64"])
def test_paged_decode_compiles(on_chip, page_size, quant):
    B, H, D, P = 8, 12, 64, 513
    n_pages = 2048 // page_size
    pool = ((P, H, page_size, D), jnp.int8 if quant else BF16)
    args = [((B, H, D), BF16), pool, pool, ((B, n_pages), jnp.int32),
            ((B,), jnp.int32)]
    if quant:
        args += [((P, H, page_size), BF16)] * 2
    assert decode_attention.paged_decode_supported(D, page_size, quant)

    def decode(q, k, v, table, lengths, *scales):
        return decode_attention.paged_decode_attention_pallas(
            q, k, v, table, lengths, 0.125, *scales)

    assert_kernel(on_chip(decode, *args))


def stacked(layers, pages, heads, page_size, head_dim, quant):
    """(shape, dtype) of the engine's stacked K and V pools, then of the
    int8 pages' scale pools."""
    pool = ((layers, pages, heads, page_size, head_dim),
            jnp.int8 if quant else BF16)
    scale = ((layers, pages, heads, page_size), BF16)
    return [pool, pool] + [scale, scale] * quant


# (page table's width, layers, pages) of the two serve cells' pools: batch
# 32, 16 heads, pages of 64
SERVE_CELLS = {"pythia-1.4b.serve_closed32": (32, 24, 401),
               "olmoe-1b-7b.serve_fewshot32": (64, 6, 801)}
# the cells' own contexts: a mean of 440 tokens over 32 rows; of 1,060
# over 19 live rows beside 13 inactive ones
CELL_CONTEXTS = {"pythia-1.4b.serve_closed32": [440] * 32,
                 "olmoe-1b-7b.serve_fewshot32": [1060] * 19 + [0] * 13}


def layer_indexed_decode(table_width, layers, pages, head_dim, quant):
    """(callable, argument shapes) of one paged decode call on stacked
    pools at a serve cell's shapes, the layer a traced scalar."""
    B, H, page_size = 32, 16, 64
    args = [((B, H, head_dim), BF16), ((B, table_width), jnp.int32),
            ((B,), jnp.int32), ((), jnp.int32)]

    def decode(q, table, lengths, layer, k, v, *scales):
        return decode_attention.paged_decode_attention_pallas(
            q, k, v, table, lengths, 0.125, *scales, layer=layer)

    return decode, args + stacked(layers, pages, H, page_size, head_dim,
                                  quant)


@pytest.mark.parametrize("cell", SERVE_CELLS)
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_layer_indexed_paged_decode_compiles(on_chip, head_dim, quant, cell):
    """The paged kernel on the stacked pools, the layer a traced scalar,
    at both serve cells' shapes (head dim 128 as they run it; 64 is
    Pythia-410m served)."""
    decode, args = layer_indexed_decode(*SERVE_CELLS[cell], head_dim, quant)
    assert_kernel(on_chip(decode, *args))


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_paged_decode_is_one_call_over_live_pages(on_chip, cell):
    """A decode call at a serve cell's shapes is ONE Mosaic custom call
    named `ds.paged_decode` (the roofline metric divides by the mean time
    of one), whose grid has no (batch, head, page) product. A step is
    what `step_geometry` says of the call's own shape: here a SPAN OF TWO
    pages of all 16 heads (1 MiB of K and V, the bytes at which a step's
    fixed cost is amortised; 8 KV heads take 4 pages a step, 4 take 8),
    all rows against all slots, and as many steps as the rows have live
    spans — at most batch x half the table's width, which only a batch of
    full tables reaches."""
    table_width, layers, pages = SERVE_CELLS[cell]
    decode, args = layer_indexed_decode(table_width, layers, pages, 128,
                                        False)
    text = on_chip(decode, *args)
    calls = re.findall(r"^\s*%?([\w.\-]+) = .*tpu_custom_call", text, re.M)
    assert len(calls) == 1 and calls[0].startswith("ds.paged_decode"), calls

    jaxpr = jax.make_jaxpr(decode)(
        *[jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in args])
    (grid,) = [eqn.params["grid_mapping"].grid for eqn in jaxpr.eqns
               if eqn.primitive.name == "pallas_call"]
    assert decode_attention.step_geometry(
        16, 64, 128, BF16, table_width=table_width) == (16, 2)
    assert dispatch_report()["decode_attention"]["decode_scores"] == \
        "collapsed"
    # (head groups, steps): one group, and a step count read at run time
    assert len(grid) == 2 and grid[0] == 1 and not isinstance(grid[1], int)
    B = 32
    worst, _, _ = decode_attention.decode_steps(
        jnp.full((B,), table_width * 64, jnp.int32), 64, table_width,
        pages=2)
    assert int(worst) == B * table_width // 2
    lengths = CELL_CONTEXTS[cell]
    steps, _, _ = decode_attention.decode_steps(
        jnp.asarray(lengths, jnp.int32), 64, table_width, pages=2)
    # a step a live span, one for an inactive row: 128 of 1,024 pages'
    # worth; 184 of 2,048
    assert int(steps) == sum(max(1, -(-n // 128)) for n in lengths)
    assert int(steps) * 8 <= B * table_width


@pytest.mark.parametrize("quant,head_dim,run", [
    (False, 64, 1), (False, 128, 1), (True, 64, 1), (True, 128, 1),
    (False, 128, 4)],
    ids=["bf16-64", "bf16-128", "int8-64", "int8-128", "bf16-128-run4"])
def test_kv_write_compiles(on_chip, head_dim, quant, run):
    """The aliased row write: K and V (and for int8 pages their scale
    pools) in one call, the row's packed sublane group of its page a
    batch row: a [H, 16, D] block of a bf16 pool, [H, 32, D] of an int8
    one, and the scale pool's whole [H, page] plane. `run` 4: a block
    pass's 4 rows a sequence (SDAR's shapes: 4 KV heads, 1,601 pages),
    still one group a batch row."""
    B, H = 32, 16 if run == 1 else 4
    pools = stacked(24 if run == 1 else 6, 401 if run == 1 else 1601, H, 64,
                    head_dim, quant)
    rows = [((B, H) + ((run,) if run > 1 else ()) + shape[4:], dtype)
            for shape, dtype in pools]
    index = [((), jnp.int32), ((B,), jnp.int32), ((B,), jnp.int32)]

    def write(layer, page_idx, slot, *leaves):
        return decode_attention.paged_kv_write_pallas(
            leaves[:len(pools)], leaves[len(pools):], layer, page_idx, slot)

    text = on_chip(write, *index, *pools, *rows)
    assert_kernel(text)
    assert "ds.kv_write" in text
    g = 32 if quant else 16
    assert dispatch_report()["decode_attention"]["kv_write_slots"] == g
    jaxpr = jax.make_jaxpr(write)(
        *[jax.ShapeDtypeStruct(shape, dtype)
          for shape, dtype in (*index, *pools, *rows)])
    (maps,) = [eqn.params["grid_mapping"].block_mappings
               for eqn in jaxpr.eqns if eqn.primitive.name == "pallas_call"]
    blocks = [m.block_aval.shape for m in maps]
    # rows, pools in, pools out: K, V (and their scales) each
    n = len(pools)
    assert blocks[n:2 * n] == blocks[2 * n:] == \
        [(H, g, head_dim)] * 2 + [(H, 64)] * (n - 2)


INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (?P<type>.*?) (?P<op>[a-z][a-z\-]*)\(")
# what may carry a pool without moving it
CARRIES = ("parameter", "tuple", "get-tuple-element", "bitcast", "while")


ATTN_WEIGHT = re.compile(r"bf16\[(?:\d+,)?2048,(?:6144|4096|2048)\]")


def attention_weight_relayouts(text):
    """What the reshape to heads costs a program when XLA folds it into
    the projection's dot (`gpt_neox._heads_dot`): every `copy` whose
    result has the shape of a layer's attention weight or of a stack of
    them (hidden 2048: `qkv_w` [.., 2048, 6144], `kv_w` [.., 2048, 4096],
    `q_w` / `out_w` [.., 2048, 2048]), and every convolution over a
    window of heads under `ds.attn`."""
    found = []
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if not m:
            continue
        if m["op"] == "copy" and ATTN_WEIGHT.search(m["type"]):
            found.append(("copy", m["type"][:60]))
        if m["op"] == "convolution" and "window={size=" in line \
                and "ds.attn" in line:
            found.append(("convolution", m["type"][:60]))
    return found


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


# ---------------------------------------------------------------------------
# a planned model (Laguna-S-2.1) at its published widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("heads,window,G", [(48, None, 8), (72, 512, 8),
                                            (128, None, 4), (256, None, 4)],
                         ids=["full_48", "window_72", "block_4x32",
                              "block_slots_4x64"])
def test_grouped_window_paged_decode_compiles(on_chip, heads, window, G):
    """The paged kernel at Laguna's decode shapes: 48 (full) or 72
    (window 512) query heads over 8 KV heads of 128, batch 32, a table of
    136 pages of 64, the layer a traced scalar. And at a block pass's
    (SDAR): a block's 4 rows x 8 query heads as a group of 32 rows under
    each of 4 KV heads, a table of 48 pages, a pool of 1,601; and at its
    two slots, 64 rows a KV head, the first half's end prefetched beside
    the row's. Each is ONE Mosaic call (a pool rides once a page of the
    step: still one call)."""
    B, D, ps = 32, 128, 64
    pool = ((2, 289, G, ps, D) if G == 8 else (6, 1601, G, ps, D), BF16)
    name = "ds.paged_decode_block" if G == 4 else \
        "ds.paged_decode" if window is None else "ds.paged_decode_window"

    def decode(q, table, lengths, layer, k, v):
        return decode_attention.paged_decode_attention(
            q, k, v, table, lengths, D ** -0.5, backend="pallas",
            layer=layer, window=window, block_pass=G == 4,
            first_lengths=lengths - 4 if heads == 256 else None)

    text = on_chip(decode, ((B, heads, D), BF16),
                   ((B, 136 if G == 8 else 48), jnp.int32),
                   ((B,), jnp.int32), ((), jnp.int32), pool, pool)
    # ONE custom call under the kind's name, the pools read where they lie
    assert text.count("tpu_custom_call") == 1
    assert kernel_names(text) == {name}
    assert not pool_shaped_moves(text, pool[0])
    # a step is 1 MiB of K and V (8 pages of 4 KV heads, 4 of 8), and a
    # KV head's query group meets its own slots alone
    step = dispatch_report()["decode_attention"]
    assert (step["decode_heads_per_step"], step["decode_pages_per_step"],
            step["decode_scores"]) == (G, 32 // G, "per_head")


@pytest.mark.parametrize("heads,window,block", [
    (48, None, 0), (72, 512, 0), (32, None, 4)],
    ids=["full_48", "window_72", "block_causal_32"])
def test_grouped_window_flash_forward_compiles(on_chip, heads, window,
                                               block):
    """The segmented forward at Laguna's prefill shapes: one row of 8,192
    tokens, 48 or 72 query heads over 8 KV heads of 128. And under the
    block-causal mask at SDAR's: a bucket of 2,048 tokens, 32 query heads
    over 4 KV heads of 128, blocks of 4."""
    S, G, D = (2048, 4, 128) if block else (8192, 8, 128)

    def prefill(q, k, v, seg):
        return fa.flash_attention_segmented(q, k, v, seg, True,
                                            window=window, mask_block=block)

    assert_kernel(on_chip(prefill, ((1, S, heads, D), BF16),
                          ((1, S, G, D), BF16), ((1, S, G, D), BF16),
                          ((1, S), jnp.int32)))


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


def pool_shaped_moves(text, shape, dtype="bf16"):
    """Instructions that produce an array of the pool's shape and are
    neither a kernel nor a way of carrying it."""
    shaped = re.compile(dtype + r"\[" + ",".join(map(str, shape)) + r"\]")
    moved = []
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m and shaped.search(m["type"]) and m["op"] not in CARRIES and \
                "tpu_custom_call" not in line:
            moved.append((m["op"], m["type"][:60]))
    return moved


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


@pytest.mark.parametrize("tokens,masked", [(16384, (16, 136)),
                                           (8192, (8, 36))])
def test_flash_forward_compiles_at_head_dim_256(on_chip, tokens, masked):
    """The expanded prefill's attention at the latent cell's two largest
    buckets: one row of 16,384 or 8,192 tokens, 20 heads of 192 + 64 for
    q.k and 256 for v, segmented: both whole-tile bodies of the kernel at
    (1024, 1024), the diagonal's tiles alone counted as masked."""
    def prefill(q, k, v, seg):
        return fa.flash_attention_segmented(q, k, v, seg, True)

    qkv = ((1, tokens, 20, 256), BF16)
    text = on_chip(prefill, qkv, qkv, qkv, ((1, tokens), jnp.int32))
    assert_kernel(text)
    assert kernel_names(text) == {"ds.flash_fwd"}
    report = dispatch_report()["flash"]
    assert report["fwd"] == (1024, 1024)
    assert report["masked_tiles"]["fwd"] == masked


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


# ---------------------------------------------------------------------------
# grouped matmul, int8 weight matmul, fused Adam
# ---------------------------------------------------------------------------

def test_grouped_matmul_compiles(on_chip):
    """8 experts, 768 → 3072, span 512: forward, and the backward's dx
    (the same kernel against wᵀ) and dw kernels."""
    E, K, N, span = 8, 768, 3072, 512
    args = [((E * span, K), BF16), ((E, K, N), BF16), ((E,), jnp.int32)]
    assert grouped_matmul.grouped_matmul_supported(K, N, span)

    def gmm(x, w, sizes):
        return grouped_matmul.grouped_matmul(x, w, sizes, span,
                                             backend="pallas")

    assert_kernel(on_chip(gmm, *args))
    grad = jax.grad(lambda x, w, s: gmm(x, w, s).astype(jnp.float32).sum(),
                    argnums=(0, 1))
    assert_kernel(on_chip(grad, *args), at_least=2)


@pytest.mark.parametrize("tokens", [32, 256, 1024, 1536],
                         ids=["decode_32", "prefill_256", "prefill_1024",
                              "prefill_1536"])
def test_ragged_grouped_matmul_compiles_at_olmoe_shapes(on_chip, tokens):
    """The dropless layout at OLMoE-1B-7B's widths: 64 experts, 8 a
    token, the fused gate-and-up projection 2048 -> 2048 and the down
    projection 1024 -> 2048, at a decode step's 256 rows (4 a group on
    average, a 16-row tile) and at the prefill buckets' rows."""
    from deeperspeed_tpu.moe.layer import dropless_geometry
    E, k, h, inter = 64, 8, 2048, 1024
    rows, bm = dropless_geometry(tokens, k, E)
    assert rows % bm == 0 and rows >= tokens * k + E
    assert grouped_matmul.grouped_matmul_supported(h, 2 * inter, bm)
    maps = [((rows // bm,), jnp.int32)] * 2

    def ffn(x, w_in, w_out, tile_expert, tile_rows):
        hmid = grouped_matmul.ragged_matmul(x, w_in, tile_expert,
                                            tile_rows, bm, backend="pallas")
        hmid = jax.nn.silu(hmid[:, :inter]) * hmid[:, inter:]
        return grouped_matmul.ragged_matmul(hmid, w_out, tile_expert,
                                            tile_rows, bm, backend="pallas")

    args = [((rows, h), BF16), ((E, h, 2 * inter), BF16),
            ((E, inter, h), BF16), *maps]
    assert_kernel(on_chip(ffn, *args), at_least=2)
    if tokens == 256:
        # backward: dx over w's [N, K] slabs (no transposed copy) and dw
        grad = jax.grad(lambda *a: ffn(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))
        # (the last forward call is dead code under a sum)
        assert_kernel(on_chip(grad, *args), at_least=5)


@pytest.mark.parametrize(
    "tokens,k,e_all,held,h,inter",
    [(32, 8, 64, None, 2048, 1024), (8192, 10, 256, (0, 128), 3072, 1024),
     (16384, 4, 64, None, 2048, 1536)],
    ids=["olmoe_decode_32", "laguna_prefill_8192", "glm_prefill_16384"])
def test_dropless_layer_moves_integers_by_index_once(on_chip, tokens, k,
                                                     e_all, held, h, inter):
    """The whole dropless layer at a decode step's rows and at the two
    largest prefills of the serving cells (81,920 and 65,536 pairs): the
    ragged layout's plan is counted (`moe.layer.dropless_plan`), so
    beyond the router's `top_k` the program the chip's compiler emits
    holds ONE sort (the buffer's rows, for `src`), no scatter, and the
    two grouped matmuls."""
    from deeperspeed_tpu.moe.layer import moe_ffn_dropless
    E = held[1] - held[0] if held else e_all

    def count(op, text):
        return len(re.findall(rf" {op}\(", text))

    def layer(x, gate, w_in, w_out, mask):
        return moe_ffn_dropless({"gate": gate, "w_in": w_in, "w_out": w_out},
                                x, k, norm_topk_prob=True, token_mask=mask,
                                gmm_backend="pallas", held=held)

    def routed(x, gate):                 # what `top_k` alone compiles to
        return jax.lax.top_k(jax.nn.softmax(
            x.astype(jnp.float32) @ gate, axis=-1), k)

    args = [((tokens, h), BF16), ((h, e_all), jnp.float32)]
    text = on_chip(layer, *args, ((E, h, 2 * inter), BF16),
                   ((E, inter, h), BF16), ((tokens,), jnp.bool_))
    assert_kernel(text, at_least=2)
    sorts = count("sort", text) - count("sort", on_chip(routed, *args))
    assert (sorts, count("scatter", text)) == (1, 0)


@pytest.mark.parametrize("m,k,n", [(8, 768, 3072), (256, 768, 3072),
                                   (8, 6144, 24576)],
                         ids=["decode_768x3072", "prefill_768x3072",
                              "decode_6144x24576"])
def test_quant_matmul_compiles(on_chip, m, k, n):
    def qmm(x, qval, scale):
        return quant_matmul.quant_matmul_pallas(
            x, quant_matmul.QuantizedWeight(qval, scale))

    assert_kernel(on_chip(qmm, ((m, k), BF16), ((k, n), jnp.int8),
                          ((n,), jnp.float32)))


def test_fused_adam_compiles(on_chip):
    n = 4 * 1024 * 1024
    flat = ((n,), jnp.float32)
    adam = functools.partial(optimizer.fused_adam_flat.__wrapped__,
                             adam_w=True, bias_correction=True)
    assert_kernel(on_chip(adam, ((n,), BF16), flat, flat, flat,
                          ((), jnp.float32), ((), jnp.int32)))


# ---------------------------------------------------------------------------
# block-sparse attention
# ---------------------------------------------------------------------------

def test_block_sparse_compiles(on_chip):
    """The LUT block-skipping kernels (forward, dkv, dq) under a causal
    local + global layout at seq 2048."""
    n = 2048 // 128
    rows = np.arange(n)
    layout = (np.abs(rows[:, None] - rows[None, :]) <= 2) | \
        (rows[None, :] == 0)
    layout = np.tril(layout).astype(np.int32)[None].repeat(12, axis=0)
    kernel = block_sparse_attention.BlockSparseAttention(
        layout, block=128, causal=True)
    assert_kernel(on_chip(kernel, *qkv(2, 2048, 12, 64)))
    grad = jax.grad(loss_of(kernel), argnums=(0, 1, 2))
    assert_kernel(on_chip(grad, *qkv(2, 2048, 12, 64)), at_least=3)


# ---------------------------------------------------------------------------
# the CE head: no kernel of ours, but what XLA makes of it is the cost
# ---------------------------------------------------------------------------

def _written_to_hbm(text, shape):
    """The instructions OUTSIDE any fused computation (a while body's or
    the entry's own: their results lie in HBM) whose result holds an
    array of `shape`."""
    fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
    found, nested = [], False
    for line in text.splitlines():
        header = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(", line)
        if header:
            nested = header.group(1) in fused
        elif not nested and re.match(
                r"\s*(?:ROOT )?%\S+ = [^=]*?" + re.escape(shape)
                + r"[^=]*? \w[\w\-]*\(", line) and \
                " get-tuple-element(" not in line:
            found.append(line.strip()[:140])
    return found


def test_ce_head_runs_three_matmuls_and_writes_the_tile_once(on_chip):
    """Loss and gradients of the head alone at `train_2k`'s shape
    ([16, 2048, 1024] against 50,304 words), compiled for the described
    v5e. A chunk (the scan's body, in the text once) runs exactly three
    convolutions over the vocabulary under `ds.ce_head`: the logits tile,
    `dx` and `dW`. A fourth is a forward recomputed for the backward,
    which is what `jax.checkpoint` round the body cost until PR 56. None
    lies under `rematted_computation`, and the float32 [4096, 50304]
    tile is written to HBM once a chunk, by the first matmul's fusion."""
    from deeperspeed_tpu.models.gpt_neox import fused_lm_head_loss
    batch, seq, hidden, vocab = 16, 2048, 1024, 50304

    def step(x, wte, labels):
        return jax.value_and_grad(
            lambda x, w: fused_lm_head_loss(x, w, labels), (0, 1))(x, wte)

    before = dispatch_report()["ce_head"]
    text = on_chip(step, ((batch, seq, hidden), BF16),
                   ((vocab, hidden), BF16), ((batch, seq), jnp.int32))
    after = dispatch_report()["ce_head"]
    assert after["loss_and_grads"] == before["loss_and_grads"] + 1
    head = [line for line in text.splitlines() if "ds.ce_head" in line]
    # (the label's logit is a row-dot, which XLA makes a reduction: every
    # convolution of the head has the vocabulary as one of its dims)
    matmuls = [line.strip()[:100] for line in head
               if " convolution(" in line]
    assert len(matmuls) == 3, \
        "a fourth matmul over the vocabulary is a recomputed forward:\n" \
        + "\n".join(matmuls)
    assert not [line for line in head if "rematted_computation" in line]
    tiles = _written_to_hbm(text, f"f32[4096,{vocab}]")
    assert len(tiles) == 1 and " fusion(" in tiles[0], tiles


# ---------------------------------------------------------------------------
# four chips: GSPMD cannot partition a Mosaic kernel
# ---------------------------------------------------------------------------

def test_attention_runs_per_shard_under_a_mesh(on_chip, v5e_2x2):
    """Traced under a multi-device mesh, a Pallas call is refused by the
    TPU compiler unless it sits in a `shard_map` — which the attention
    dispatcher does once the tracing engine has declared its mesh
    (`DeepSpeedEngine._jit`); the four-chip smoke run stands on this."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deeperspeed_tpu.models.gpt_neox import causal_attention
    mesh = Mesh(np.asarray(v5e_2x2), ("data",))
    batch_sharded = NamedSharding(mesh, P("data"))
    shape = qkv(8, 2048, 12, 64)
    grad = jax.grad(loss_of(causal_attention), argnums=(0, 1, 2))

    with pytest.raises(NotImplementedError,
                       match="cannot be automatically partitioned"):
        on_chip(grad, *shape, sharding=batch_sharded)

    def declared(*a):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return grad(*a)

    text = on_chip(declared, *shape, sharding=batch_sharded)
    assert_kernel(text, at_least=2)
