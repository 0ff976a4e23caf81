"""The training step and its kernels, compiled for a described TPU v5e
(`tests/tpu_compile_common.py` says how): the flash kernels at the train
cells' shapes, one layer's step, the CE head, and a mesh of four chips.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.ops import dispatch_report
from tests.tpu_compile_common import (  # noqa: F401 (fixtures)
    assert_kernel, BF16, fa, kernel_names, loss_of, on_chip, qkv, v5e_2x2)

def _equations_under(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations_under(sub)


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


ENTRY_LINE = re.compile(
    r"^\s*(?:ROOT )?%?(?P<name>[\w.\-]+) = (?P<type>.*?) "
    r"(?P<op>[a-z][a-z\-]*)\((?P<args>[^)]*)\)")
# what hands a tensor on as it lies
PASSES_ON = {"bitcast", "get-tuple-element", "copy-start", "copy-done"}


def _entry(text):
    """{name: (op, operand names, the last part of its `op_name`, elements
    of its first array)} of a compiled program's ENTRY computation."""
    found = {}
    for line in text[text.index("\nENTRY "):].splitlines():
        m = ENTRY_LINE.match(line)
        if not m:
            continue
        dims = re.search(r"\w+\[([0-9,]*)\]", m.group("type"))
        scope = re.search(r'op_name="([^"]*)"', line)
        found[m.group("name")] = (
            m.group("op"), re.findall(r"%([\w.\-]+)", m.group("args")),
            scope.group(1).rsplit("/", 1)[-1] if scope else "",
            int(np.prod([int(n) for n in dims.group(1).split(",")]))
            if dims and dims.group(1) else 1)
    return found


def _flash_neighbours(text, elements):
    """What a compiled train step's flash kernels read their q, k, v (and
    dO) FROM and what reads the backward's dq, dk, dv: [(kernel, side,
    the neighbour's op, its `op_name`'s last part)] over every operand and
    result of `elements` elements, through whatever passes a tensor on as
    it lies; the kernels' own results among their operands (out) left
    out."""
    entry = _entry(text)
    users = {}
    for name, (_, operands, _, _) in entry.items():
        for operand in operands:
            users.setdefault(operand, []).append(name)

    def source(name):
        op, operands, _, _ = entry[name]
        return source(operands[0]) if op in PASSES_ON else name

    def readers(name):
        for user in users.get(name, []):
            if entry[user][0] in PASSES_ON:
                yield from readers(user)
            else:
                yield user

    found = []
    for name, (op, operands, scope, _) in entry.items():
        if op != "custom-call" or scope != "pallas_call":
            continue
        kernel = name.rsplit(".", 1)[0]
        for operand in operands:
            if entry[operand][3] == elements:
                src = source(operand)
                if entry[src][0] != "custom-call":
                    found.append((kernel, "reads", *entry[src][::2]))
        if kernel == "ds.flash_bwd":
            found += [(kernel, "read_by", *entry[user][::2])
                      for user in readers(name)
                      if entry[user][3] != 1]
    return found


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
    and its one copy.

    And at the two one-chip shapes NO ELEMENTWISE PASS stands between the
    projections and the kernels (PERF.md section 6, PR 63): the kernels
    rotate q and k themselves (`flash_attention.rotates_in_kernel`), so
    the forward's q, k and v, and the recomputed ones the backward reads,
    are the projections' dot fusions' own results, dO is the output
    projection's gradient dot's, and what reads dq, dk and dv is the
    weight-gradient dots and the bias gradient's reductions, in the
    forward, the recomputation and the backward alike. The rotary's
    `concatenate` / `neg` / `slice` / `add_any` fusions of the parent
    would each be named here."""
    step, args = _one_layer_step(batch, seq, heads, hidden, remat)
    moved_before, projections = _heads_moved(), _projections()
    rotary = dict(dispatch_report()["flash"]["rotary"])
    text = on_chip(step, *args)
    assert kernel_names(text) >= {"ds.flash_fwd", "ds.flash_bwd"}
    moved = _copies(text, batch * seq * hidden)
    assert not moved, moved
    whole = _copies(text, 3 * batch * seq * hidden)
    assert len(whole) == (0 if form == "split" else 1), whole
    assert moved_before == _heads_moved()
    assert _projections() == {**projections, form: projections[form] + 1}
    counted = dispatch_report()["flash"]["rotary"]
    assert counted["xla"] == rotary["xla"]
    assert counted["in_kernel"] > rotary["in_kernel"]
    if form == "split":
        around = _flash_neighbours(text, batch * seq * hidden)
        assert {row[:2] for row in around} == {
            ("ds.flash_fwd", "reads"), ("ds.flash_bwd", "reads"),
            ("ds.flash_bwd", "read_by")}, around
        # q, k, v; q, k, v recomputed and dO; of each of dq, dk, dv the
        # weight's and the input's gradient dots and the bias's reduction
        assert len(around) == 3 + 4 + 9, around
        passes = [row for row in around if (row[2], row[3]) not in {
            ("fusion", "dot_general"), ("reduce", "reduce_sum")}]
        assert not passes, passes


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


# Both sides of `ops.autotune.flash_k_slab_admitted`: the by-rows forward
# keeps a head's turned k in VMEM ([S, D] of the input's dtype, a 64-wide
# minor dim laid out as a whole lane tile) at the compiler's DEFAULT limit
# at the train cells' shapes and up to the largest slabs the rule admits
# (8 MiB: 32k tokens of bfloat16 at head dim 64 and 128), and the next
# sequence up compiles the form that turns its k block every grid step.
# (name, [B, S, H, D], forward blocks or None = `flash_blocks`' own, kept)
K_SLAB_SHAPES = [
    ("train_16k", (1, 16384, 16, 64), (1024, 512), True),
    ("train_zero3_4c", (4, 2048, 16, 128), None, True),
    ("largest_slab_d64", (1, 32768, 16, 64), None, True),
    ("largest_slab_d128", (1, 32768, 16, 128), None, True),
    ("over_budget_d64", (1, 65536, 16, 64), None, False),
]


@pytest.mark.parametrize("name,shape,blocks,kept", K_SLAB_SHAPES,
                         ids=[c[0] for c in K_SLAB_SHAPES])
def test_flash_forward_compiles_on_both_sides_of_the_k_slab_budget(
        on_chip, name, shape, blocks, kept):
    from deeperspeed_tpu.ops.autotune import (flash_blocks,
                                              flash_k_slab_admitted)
    assert flash_k_slab_admitted(shape[1], shape[3], 2, True) is kept
    bq, bk = blocks or flash_blocks(shape, True, "TPU v5 lite")[0]
    before = dict(dispatch_report()["flash"]["k_turns"])
    text = on_chip(lambda q, k, v: fa.flash_attention(
        q, k, v, True, None, bq, bk), *qkv(*shape))
    assert kernel_names(text) == {"ds.flash_fwd"}
    assert {rule: n - before[rule] for rule, n in
            dispatch_report()["flash"]["k_turns"].items()} == {
        "once_a_head": int(kept), "every_step": int(not kept)}


# The kernels that rotate q and k themselves (`flash_attention(...,
# rotary=...)`) at the largest the rule admits (the train cells' shapes
# compile inside `test_train_step_copies_no_attention_tensor`'s steps):
# the longest head (its k slab and dq slab both at their budgets) with the
# smallest and the widest table, the whole head rotated.
# (name, [B, S, H, D], rot_dim)
ROTATING_SHAPES = [
    ("largest_slabs_d64", (1, 32768, 16, 64), 16),
    ("largest_slabs_d64_whole_head", (1, 32768, 16, 64), 64),
    ("largest_dq_slab_d128_whole_head", (1, 16384, 16, 128), 128),
]


@pytest.mark.parametrize("name,shape,rot", ROTATING_SHAPES,
                         ids=[c[0] for c in ROTATING_SHAPES])
def test_rotating_kernels_compile(on_chip, name, shape, rot):
    """Forward and fused backward with the rotary inside, at the blocks
    the rule gives a v5e: two Mosaic kernels, one table operand
    `[2 x rot, S]` each."""
    from deeperspeed_tpu.models import gpt_neox
    from deeperspeed_tpu.ops.autotune import flash_blocks
    assert fa.rotates_in_kernel(shape, shape[2], rot)
    (bq, bk), bwd = flash_blocks(shape, True, "TPU v5 lite")
    b, s, h, d = shape
    rotary = gpt_neox._rotary_table(
        *gpt_neox.rope_inv_freq(d, rot / d, 10000.0), s, jnp.float32)

    def attn(q, k, v):
        return fa.flash_attention(q, k, v, True, None, bq, bk, bwd,
                                  rotary=rotary)

    text = on_chip(jax.grad(loss_of(attn), argnums=(0, 1, 2)), *qkv(*shape))
    assert kernel_names(text) == {"ds.flash_fwd", "ds.flash_bwd"}
    assert text.count(f"f32[{2 * rot},{s}]{{1,0}}") >= 2


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
