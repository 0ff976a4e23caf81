"""Kernel geometry, compiled for a described TPU v5e
(`tests/tpu_compile_common.py` says how): paged decode and the cache
write at the serve cells' shapes, grouped and windowed attention, the
grouped and int8 matmuls, fused Adam, block-sparse attention.
"""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.ops import dispatch_report
from tests.tpu_compile_common import (  # noqa: F401 (fixtures)
    assert_kernel, BF16, block_sparse_attention, decode_attention, fa,
    grouped_matmul, INSTRUCTION, kernel_names, loss_of, on_chip, optimizer,
    pool_shaped_moves, qkv, quant_matmul, stacked, v5e_2x2)

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


DROPLESS_SHAPES = {          # tokens, k, the router's experts, held, H, I
    "olmoe_decode_32": (32, 8, 64, None, 2048, 1024),
    "laguna_prefill_8192": (8192, 10, 256, (0, 128), 3072, 1024),
    "glm_prefill_16384": (16384, 4, 64, None, 2048, 1536),
    "qwen3next_prefill_4096": (4096, 10, 512, (0, 256), 2048, 512)}
_DROPLESS_TEXTS = {}        # one compile a shape for the two guards below


def _dropless_layer_text(on_chip, shape):
    """The whole dropless layer at one of `DROPLESS_SHAPES`, compiled
    for the described v5e: (text, the inputs of x and the router)."""
    from deeperspeed_tpu.moe.layer import moe_ffn_dropless
    tokens, k, e_all, held, h, inter = DROPLESS_SHAPES[shape]
    E = held[1] - held[0] if held else e_all

    def layer(x, gate, w_in, w_out, mask):
        return moe_ffn_dropless({"gate": gate, "w_in": w_in, "w_out": w_out},
                                x, k, norm_topk_prob=True, token_mask=mask,
                                gmm_backend="pallas", held=held)

    args = [((tokens, h), BF16), ((h, e_all), jnp.float32)]
    if shape not in _DROPLESS_TEXTS:
        _DROPLESS_TEXTS[shape] = on_chip(
            layer, *args, ((E, h, 2 * inter), BF16), ((E, inter, h), BF16),
            ((tokens,), jnp.bool_))
    assert_kernel(_DROPLESS_TEXTS[shape], at_least=2)
    return _DROPLESS_TEXTS[shape], args


@pytest.mark.parametrize("shape", ["olmoe_decode_32", "laguna_prefill_8192",
                                   "glm_prefill_16384"])
def test_dropless_layer_moves_integers_by_index_once(on_chip, shape):
    """The whole dropless layer at a decode step's rows and at the two
    largest prefills of the serving cells (81,920 and 65,536 pairs): the
    ragged layout's plan is counted (`moe.layer.dropless_plan`), so
    beyond the router's `top_k` the program the chip's compiler emits
    holds ONE sort (the buffer's rows, for `src`), no scatter, and the
    two grouped matmuls."""
    k = DROPLESS_SHAPES[shape][1]

    def count(op, text):
        return len(re.findall(rf" {op}\(", text))

    def routed(x, gate):                 # what `top_k` alone compiles to
        return jax.lax.top_k(jax.nn.softmax(
            x.astype(jnp.float32) @ gate, axis=-1), k)

    text, args = _dropless_layer_text(on_chip, shape)
    sorts = count("sort", text) - count("sort", on_chip(routed, *args))
    assert (sorts, count("scatter", text)) == (1, 0)


@pytest.mark.parametrize("shape", sorted(DROPLESS_SHAPES))
def test_dropless_layer_moves_each_routed_row_once_each_way(on_chip, shape):
    """Between the router and the result the compiled layer holds, at
    the buffer's size `[R, H]` and the pairs' `[T * k, H]`, exactly TWO
    gathers (the fill from x and its zero row, the combine's
    `out[pair_row]`) and ONE reduce (the weighted sum over the choices):
    no `select` over the buffer (the grouped matmul masks a tile's
    padding rows itself, and the fill gathers them as zeros), and no
    `copy` or `reshape` of the gathered rows to `[T, k, H]` (token-major
    pairs put k on the tiled second-minor dimension: a relayout of every
    row at k = 10 and k = 4; choice-major, `[k, T, H]` is a bitcast)."""
    from deeperspeed_tpu.moe.layer import dropless_geometry
    tokens, k, e_all, held, h, _ = DROPLESS_SHAPES[shape]
    R, _ = dropless_geometry(tokens, k, held[1] - held[0] if held else e_all)
    text, _ = _dropless_layer_text(on_chip, shape)
    assert _row_moves(text, tokens, k, R, h) == {
        "gathers": sorted([f"bf16[{R},{h}]", f"bf16[{tokens * k},{h}]"]),
        "reduces": 1, "passes": []}


def _row_moves(text, tokens, k, rows, h):
    """What moves the routed rows in a compiled dropless layer: the
    gathers whose result has the buffer's size `[R, H]` or the pairs'
    `[T * k, H]`, the reduces to `[T, H]`, and the PASSES that should
    not be there: a `select` or `copy` of that size or of the gathered
    rows as `[T, k, H]` / `[k, T, H]` anywhere, and a `reshape` of them
    that stands alone in the entry computation (inside a gather's fusion
    a reshape re-types the rows in place; alone it relays them out)."""
    sized = re.compile(rf"bf16\[(?:{rows},{h}|{tokens * k},{h}|"
                       rf"{tokens},{k},{h}|{k},{tokens},{h})\]")
    gathers, reduces, passes, in_entry = [], 0, [], False
    for line in text.splitlines():
        if line.startswith("ENTRY "):
            in_entry = True
        elif line.startswith("}"):
            in_entry = False
        m = INSTRUCTION.match(line)
        if not m:
            continue
        shape = sized.match(m["type"])
        if m["op"] == "reduce" and m["type"].startswith(f"bf16[{tokens},{h}]"):
            reduces += 1
        if not shape:
            continue
        if m["op"] == "gather":
            gathers.append(shape[0])
        elif m["op"] in ("select", "copy") or \
                (m["op"] == "reshape" and in_entry):
            passes.append((m["op"], shape[0]))
    return {"gathers": sorted(gathers), "reduces": reduces, "passes": passes}


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
