"""Paged decode attention as a Pallas TPU kernel.

The serving engine (`deeperspeed_tpu.inference`) keeps each sequence's
K/V history in fixed-size PAGES of a preallocated pool instead of one
contiguous [B, S_max, H, D] buffer — admission never has to find a
contiguous region, eviction frees exact pages, and memory scales with
tokens actually resident rather than worst-case sequence length. Decode
then needs the 1-query-row variant of the flash forward: for every
in-flight sequence, one new query attends over all its cached tokens,
reading K/V THROUGH the page table.

Contract (shared by kernel and XLA fallback):

- ``q`` [B, H, D]: one query row per sequence (the token being decoded).
- ``k_pages``/``v_pages`` [P, H, page_size, D]: the pooled cache for ONE
  layer, head-major so a model-parallel mesh shards the heads and each
  shard runs this kernel on its local heads unchanged (attention is
  head-independent). With ``layer`` (a traced int32 scalar) they are the
  engine's STACKED pools [L, P, H, page_size, D] and the kernel reads
  layer ``layer`` of them where they lie: the layer index rides as a
  third scalar-prefetch operand and is the first coordinate of the pool
  blocks, so no layer's pool is ever sliced out of the stack.
- ``page_table`` [B, NP] int32: page ids of sequence b's pages in
  position order. Entries past the sequence's live pages are don't-care
  (the scheduler pads with page 0 — the pool's reserved trash page);
  their loads are masked and contribute nothing.
- ``lengths`` [B] int32: tokens valid for attention — INCLUDING the one
  being decoded (its K/V must already be written to its page). A length
  of 0 marks an inactive (padding) batch row; its output is exact zero.

Mechanics: a grid step moves a SPAN of one row's live pages, of as many
of the call's KV heads as fit, and its geometry follows the call's own
shape (`step_geometry`: KV heads, query rows a KV head, a page's bytes,
the pool's dtype, the table's width; no option). A page of one layer is
a contiguous [H, page_size, D] tile of the pool, so a K or V block is
``(None, None, Hb, page_size, D)`` with ``Hb`` the largest divisor of the
head count whose step fits a fixed share of VMEM (H itself at every
shape served so far), and a step takes the fewest pages whose K and V
reach the bytes at which its fixed cost is amortised (`_STEP_MIN_BYTES`:
two pages of 16 heads of 128, four of 8, eight of 4). The pools ride once a
page of the step, each operand's index map resolving its own page. The
grid is ``(H / Hb, steps)`` where ``steps`` is traced: `decode_steps`
lists one step a span of live pages, rows in order, and rides as scalar
prefetch (`pltpu.PrefetchScalarGridSpec`) beside the page table, the
lengths and the layer, so the index maps resolve step → (row, page) and
the page-table indirection at DMA-issue time. Pages past a row's last
span, and whatever of the table's width no row uses, get no grid step
and no DMA; a slot of a row's last span past its length names the page
its operand already holds (no second fetch: the table a span reads has
such slots resolved before the call, `span_table`) and is masked; an
inactive row keeps one step, which names the trash page and writes its
zeros. The step dimension is ``arbitrary`` (it carries the online
softmax: scratch m, l, acc, reset at a row's first step, written out as
``acc / l`` at its last). Inside a step the span's pages are joined
along their slots and the scores take one of two forms, by the query rows
a KV head: one row a head (and int8 pages) meets ALL the step's slots as
one [Hb * S, D] operand (`_page_update`); a GROUP of rows a head (grouped
KV heads, a block pass) meets its own head's slots in a head-batched
matmul (`_group_update`). A block pass of two slots (`first_lengths`)
prefetches a second length a row: the first half of a group's rows end
there, the second half at the row's own, K and V read once for both. No
backward exists: decode is inference.

The decoded token's own K/V row gets into its page through
`paged_kv_write`: on a TPU a second small kernel that ALIASES the stacked
pool (`input_output_aliases`) and rewrites, per batch row, the row's
packed sublane GROUP of its page in place (`_write_group`: the 16 slots
of a bf16 page that share the row's sublanes, 32 of an int8 page, 8 of a
float32 one; never the page's 64), so a decode step moves the pages it
attends over and never a pool.

Off a TPU the kernel runs in interpreter mode (slow, test-only) and
`paged_decode_attention` defaults to the XLA form there, a gather +
masked softmax with identical semantics. Which one ran is recorded in
`_LAST_BACKEND`; XLA chosen on a TPU is logged by name. `paged_kv_write`
dispatches the same way (off a TPU: XLA's ``.at[].set``).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import scopes
from ...compat import CompilerParams
from .flash_attention import LANES, NEG_INF, _interpret, note_xla_on_tpu

_DIMSEM = CompilerParams(dimension_semantics=("parallel", "arbitrary"))

# Test/bench observability: backend ("pallas"/"xla") of the most recent
# paged_decode_attention call — the serving tests pin which path ran.
_LAST_BACKEND = {}
_DISPATCH_LOGGED = False
# how the kernel's grid step engaged at the last call traced: KV heads and
# pages of a row a step, and the score form (absent under XLA)
_STEP_KEYS = ("decode_heads_per_step", "decode_pages_per_step",
              "decode_scores")


def _log_first_dispatch():
    """One structured log line at the first paged-decode dispatch (see
    flash_attention._log_first_dispatch; `ops.dispatch_report()` is the
    query interface)."""
    global _DISPATCH_LOGGED
    if _DISPATCH_LOGGED:
        return
    _DISPATCH_LOGGED = True
    from ...utils.logging import logger
    logger.info("ops.dispatch decode_attention first dispatch: "
                f"backend={_LAST_BACKEND.get('decode')}")


def paged_decode_supported(head_dim, page_size, quantized=False):
    """Mosaic constraints for the real-TPU kernel: MXU-friendly head
    dim, sublane-aligned page size (int8 pools need the int8 sublane
    tile, 32). Interpret mode (CPU tests) has no tiling rules."""
    if _interpret():
        return True
    align = 32 if quantized else 8
    return head_dim in (64, 128, 256) and page_size % align == 0


def _auto_backend(op, head_dim, page_size, quant):
    """The dispatchers' default: the kernel on a TPU where
    `paged_decode_supported`, XLA otherwise — off a TPU, where it is the
    expected stand-in, or noted by name where a TPU runs it."""
    if not _interpret() and paged_decode_supported(head_dim, page_size,
                                                   quantized=quant):
        return "pallas"
    note_xla_on_tpu(
        op, f"head dim {head_dim}, page size {page_size}, int8 pools "
            f"{quant}: the kernel needs a head dim of 64/128/256 and a "
            f"page size that is a multiple of {32 if quant else 8}")
    return "xla"


# VMEM one grid step may fill with its page tiles (K and V, and int8
# pages' scales, each double-buffered) and the float32 tiles it computes
# with: a quarter of the 16 MiB a Mosaic kernel gets by default, so the
# compiler's own temporaries always have room
_STEP_VMEM_BYTES = 4 * 2 ** 20

# K and V bytes a grid step should move. A step's DMAs are one step ahead
# and no more (a block has one or two buffers), so each step pays their
# latency over its bytes' time, about 0.3 us on a v5e whatever the tile,
# beside the scalar core's walk through every operand's index map. Timed
# alone on a v5e (PERF.md section 6, PR 44): 16 heads of 128 (a page of K
# and V 512 KiB) 182 us a call at one page a step, 154 at two, 160 at
# four; 4 KV heads (128 KiB) 246 / 152 / 114 / 102 at 1 / 2 / 4 / 8;
# 8 KV heads (256 KiB) 818 / 569 / 469 at 1 / 2 / 4.
_STEP_MIN_BYTES = 2 ** 20

# the most pages of a row one step takes (operands a pool: the latent
# kernel's 16 is the most this file has timed)
_STEP_MAX_PAGES = 16


def scores_per_head(group, pool_dtype):
    """Whether a step scores each KV head's `group` query rows against
    that head's own slots alone (`_group_update`) or all the step's rows
    against all its slots at once (`_page_update`). A group makes full
    vector registers of its own; one row a head does not, and int8 pages'
    [Hb, slots] scale tiles lie as the collapsed form's columns do."""
    return group > 1 and jnp.dtype(pool_dtype) != jnp.int8


def step_geometry(heads, page_size, head_dim, pool_dtype, group=1,
                  table_width=None):
    """A grid step's geometry from the call's own shape: ``(heads,
    pages)``, the KV heads whose K and V it moves and the pages of a row
    it moves of them.

    Heads: the largest divisor of `heads` (the call's own KV heads, so a
    model-parallel shard's H / mp; `group` query rows read each) whose
    one-page step fits `_STEP_VMEM_BYTES`. One page of all heads is
    contiguous in the pool ([H, page_size, D]), so the answer is H
    wherever it fits (16 heads of 128 at page 64: 1.1 MiB) and the grid
    has no head dimension to speak of; wider shapes split the heads and
    no other path exists.

    Pages: the fewest whose K and V tiles reach `_STEP_MIN_BYTES` (8 at
    4 KV heads of 128, 4 at 8, 2 at 16: bf16 pages of 64), no more than
    fit `_STEP_VMEM_BYTES`, than `table_width` (the page table's) or
    than `_STEP_MAX_PAGES`."""
    pool_dtype = jnp.dtype(pool_dtype)
    quant = pool_dtype == jnp.int8
    per_head = scores_per_head(group, pool_dtype)

    def step_bytes(hb, pages=1):
        slots = hb * pages * page_size
        tiles = 2 * slots * head_dim * pool_dtype.itemsize      # K and V
        # scores, probabilities: a row meets its own head's slots, or all
        work = 2 * group * slots * 4 * (1 if per_head else hb)
        if quant:
            tiles += 2 * slots * 2                 # their bf16 scale tiles
            work += 2 * slots * head_dim * 4       # K and V widened
        if pages > 1:
            work += tiles                          # the pages joined
        return 2 * tiles + work

    # an int8 pool's [Hb, page_size] scale block has the heads on its
    # sublanes: Mosaic takes it whole or in bf16 sublane tiles of 16
    tiles = [hb for hb in range(1, heads + 1)
             if heads % hb == 0 and (not quant or hb % 16 == 0 or hb == heads)]
    hb = max([hb for hb in tiles
              if step_bytes(hb) <= _STEP_VMEM_BYTES] or tiles[:1])
    most = min(-(-_STEP_MIN_BYTES // (2 * hb * page_size * head_dim *
                                      pool_dtype.itemsize)),
               table_width or _STEP_MAX_PAGES, _STEP_MAX_PAGES)
    return hb, max([n for n in range(1, most + 1)
                    if step_bytes(hb, n) <= _STEP_VMEM_BYTES] or [1])


def _fold(s, live, m, l, acc, sm_scale, weigh):
    """A step's score tile `s` (its LAST axis the step's slots, `live`
    those that count) folded into the running softmax: the new
    (m, l, acc). ``weigh(prob)`` is the step's probabilities times V."""
    s = jnp.where(live, s * sm_scale, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    # a live step has a live slot in every row, so m_new is finite and
    # l stays an exact count of live probability mass
    prob = jnp.where(live, jnp.exp(s - m_new), 0.0)
    l_new = alpha * l + jnp.sum(prob, axis=-1, keepdims=True)
    return m_new, l_new, acc * alpha + weigh(prob)


def _page_update(q, k, v, k_scale, v_scale, m, l, acc, first_pos, length,
                 sm_scale, window=None):
    """A step's slots of `Hb` heads folded into the running softmax, ALL
    ROWS AGAINST ALL SLOTS (the form of one query row a head, and of int8
    pages): returns the new (m, l, acc).

    ``q`` [Hb * r, D]: the `r` query rows of each of the step's `Hb` KV
    heads, a KV head's rows together (r = 1 where every query head has
    its own); ``k``/``v`` [Hb, S, D]: the step's S slots a head (one page
    as it lies in the pool, or a span of pages joined); ``k_scale``/
    ``v_scale`` [Hb, S] for int8 pages, else None; ``m``/``l``
    [Hb * r, 1] and ``acc`` [Hb * r, D] float32. With ``window`` a slot
    is live only if it lies among the last `window` positions before
    `length`.

    The tile is read as ONE [Hb * S, D] operand (a free collapse of its
    leading dims): every head's query meets every head's keys in a
    single [Hb * r, Hb * S] matmul and each row keeps its own head's S
    columns (the others are masked to -inf, their probabilities exact
    zeros, so the second matmul adds nothing of another head's V). The
    MXU does Hb times the needed work, which at one query row a head is
    still nothing beside the tile's bytes, and in exchange a step is two
    matmuls and one softmax over full vector registers instead of 2 * Hb
    one-row matmuls and Hb eighth-filled softmaxes (timed: 0.357 against
    0.454 ms, PR 27). At a GROUP of rows a head the exchange turns: Hb
    times the softmax's registers are other heads' columns, and
    `_group_update` is the form. Int8 pages: the per-slot scales fold
    into the score / probability columns — ``q·(k·s) == (q·k)·s`` — and
    the math runs float32."""
    hb, ps, d = k.shape
    r = q.shape[0] // hb
    if k_scale is not None:
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jax.lax.dot_general(
        q, k.reshape(hb * ps, d), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)             # [Hb * r, Hb * S]
    # column c of query row h is slot c - (h // r) * S of its KV head's
    # tile, if in [0, S)
    slot = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) - \
        (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // r) * ps
    live = (slot >= 0) & (slot < ps) & (first_pos + slot < length)
    if window is not None:
        live = live & (first_pos + slot >= length - window)

    def own_columns(scale):
        # [Hb, S] -> [Hb * r, Hb * S]: a query row's KV head's scales
        # under each head's columns; only its own (the live ones) are used
        return jnp.tile(jnp.repeat(scale.astype(jnp.float32), r, axis=0),
                        (1, hb))

    if k_scale is not None:
        s = s * own_columns(k_scale)

    def weigh(prob):
        if v_scale is not None:
            prob = prob * own_columns(v_scale)
        else:
            prob = prob.astype(v.dtype)
        return jax.lax.dot_general(
            prob, v.reshape(hb * ps, d), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [Hb * r, D]

    return _fold(s, live, m, l, acc, sm_scale, weigh)


def _group_update(q, k, v, m, l, acc, first_pos, length, sm_scale,
                  window=None, first_length=None):
    """`_page_update` for a GROUP of query rows a KV head, EACH HEAD'S
    ROWS AGAINST ITS OWN SLOTS: ``q`` [Hb, r, D] meets ``k``/``v``
    [Hb, S, D] in a head-batched matmul, so the float32 score tile is
    [Hb, r, S] with every column live but the tail past `length` (and
    what lies before a `window`), and no head mask exists. ``m``/``l``
    [Hb, r, 1], ``acc`` [Hb, r, D]. With ``first_length`` the group is a
    block pass's TWO SLOTS: the first half of its rows (the earlier
    block's) end at `first_length`, the second half at `length`."""
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                 # [Hb, r, S]
    pos = first_pos + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    if first_length is not None:
        length = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) <
            s.shape[1] // 2, first_length, length)
    live = pos < length
    if window is not None:
        live = live & (pos >= length - window)

    def weigh(prob):
        return jax.lax.dot_general(
            prob.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)             # [Hb, r, D]

    return _fold(s, live, m, l, acc, sm_scale, weigh)


def _first_page(length, page_size, window):
    """The first page a row of `length` tokens attends over: page 0, or
    with a `window` the page that holds position `length - window`."""
    if window is None:
        return 0
    return jnp.maximum(length - window, 0) // page_size


def _decode_kernel(row_ref, start_ref, pt_ref, len_ref, lyr_ref, *refs,
                   sm_scale, page_size, pages, window, per_head, two_slots):
    """Grid step `t` of a head group: `pages` consecutive live pages of
    one batch row — each page's K and V tiles of `Hb` heads ([Hb, ps, D],
    an operand a page) joined into the step's [Hb, pages * ps, D] — meet
    the row's queries, [Hb * r, D] (`_page_update`) or, `per_head`,
    [Hb, r, D] (`_group_update`). `pt_ref` and `lyr_ref` (the page table
    and the layer of the stacked pools) are read by the index maps alone.
    Int8 pools bring their pages' [Hb, ps] scale tiles behind the data's,
    resolved through the same maps. A slot past the row's length holds a
    page the maps chose for costing nothing, and is masked. `two_slots`:
    one more prefetched operand leads `refs`, each row's `first_length`
    (`_group_update`)."""
    first_ref = None
    if two_slots:
        first_ref, *refs = refs
    q_ref, *tile_refs, o_ref, m_scr, l_scr, acc_scr = refs
    t = pl.program_id(1)
    b = row_ref[t]
    length = len_ref[b]
    first_pos = ((t - start_ref[b]) * pages +
                 _first_page(length, page_size, window)) * page_size

    def span(refs):
        # one pool's pages of the step, joined along their slots
        tiles = [r[...] for r in refs]
        return jnp.concatenate(tiles, axis=1) if pages > 1 else tiles[0]

    @pl.when(t == start_ref[b])
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(first_pos < length)         # not an inactive row's one step
    def _compute():
        k, v, *scales = [span(tile_refs[i:i + pages])
                         for i in range(0, len(tile_refs), pages)]
        carry = m_scr[..., :1], l_scr[..., :1], acc_scr[:]
        if per_head:
            m, l, acc = _group_update(
                q_ref[...], k, v, *carry, first_pos, length, sm_scale,
                window, first_ref[b] if two_slots else None)
        else:
            m, l, acc = _page_update(q_ref[...], k, v,
                                     *scales or (None, None), *carry,
                                     first_pos, length, sm_scale, window)
        m_scr[:] = jnp.broadcast_to(m, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l, l_scr.shape)
        acc_scr[:] = acc

    @pl.when(first_pos + pages * page_size >= length)  # the row's last step
    def _finalize():
        l = l_scr[..., :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        # inactive rows (length 0) never accumulated: acc == 0 → out 0
        o_ref[...] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def decode_steps(lengths, page_size, table_width, window=None, pages=1):
    """The kernel's work list: one grid step a span of `pages` LIVE pages,
    rows in order (a row of length 0 keeps one step, which writes its
    zeros; a row's last step may hold fewer live pages than the span);
    with a `window`, a page is live only from the one that holds position
    ``length - window`` on, so a row has at most ``window / page_size +
    1`` of them. Returns ``(n_steps, row, start)``: the traced step count
    (at most ``B * ceil(table_width / pages)``), ``row`` of that many
    entries — the batch row of step `t`; entries at and past `n_steps`
    are never read — and ``start`` [B], each row's first step, so step
    `t` starts at page ``(t - start[row[t]]) * pages`` of its row's live
    pages."""
    B = lengths.shape[0]
    live = -(-lengths // page_size) - _first_page(lengths, page_size, window)
    steps = jnp.maximum(-(-live // pages), 1)
    ends = jnp.cumsum(steps)
    row = jnp.searchsorted(
        ends, jnp.arange(B * -(-table_width // pages), dtype=jnp.int32),
        side="right", method="compare_all")
    return ends[-1], jnp.minimum(row, B - 1).astype(jnp.int32), ends - steps


def span_table(page_table, lengths, page_size, pages, window=None):
    """The page table as the kernel's steps read it, [B, table width +
    pages - 1]: a slot past its row's length names the page its operand
    held a step ago if that was the row's own (a block index that repeats
    is not fetched again), else the trash page (an inactive row's one
    step: a run of them fetches it once). Resolved here, once a call and
    elementwise, so that an operand's index map is one lookup (a map that
    also walked length -> live -> held was 90 bundles of scalar code an
    operand and step in the compiler's listing, a lookup 40)."""
    table = jnp.pad(page_table.astype(jnp.int32), ((0, 0), (0, pages - 1)))
    page = jnp.arange(table.shape[1], dtype=jnp.int32)
    held = jnp.pad(table, ((0, 0), (pages, 0)))[:, :table.shape[1]]
    first = jnp.broadcast_to(_first_page(lengths, page_size, window),
                             lengths.shape)
    return jnp.where(page * page_size < lengths[:, None], table, jnp.where(
        page - pages >= first[:, None], held, 0))


def _layer_operand(layer):
    """The layer index as the [1] int32 array a scalar-prefetch operand
    has to be."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def paged_decode_attention_pallas(q, k_pages, v_pages, page_table, lengths,
                                  sm_scale, k_scales=None, v_scales=None,
                                  layer=None, window=None,
                                  name="ds.paged_decode",
                                  first_lengths=None):
    B, H, D = q.shape
    quant = k_scales is not None
    if layer is None:
        # one layer's pools: a stack of one (a bitcast), read at layer 0
        layer = 0
        k_pages, v_pages = k_pages[None], v_pages[None]
        if quant:
            k_scales, v_scales = k_scales[None], v_scales[None]
    G, page_size = k_pages.shape[2], k_pages.shape[3]
    table_width = page_table.shape[1]
    r = H // G              # query rows a KV head (1: each its own)
    hb, pages = step_geometry(G, page_size, D, k_pages.dtype, group=r,
                              table_width=table_width)
    per_head = scores_per_head(r, k_pages.dtype)
    _LAST_BACKEND.update(zip(_STEP_KEYS, (
        hb, pages, "per_head" if per_head else "collapsed")))
    # the rows ride as [B, G / Hb, Hb * r, D] (a KV head's r queries lie
    # together), or a group a head as [B, G / Hb, Hb, r, D], so that a
    # block's last two dims are the array's own whatever Hb is; `None`
    # dims are squeezed out of the kernel's refs
    rows = (hb, r, D) if per_head else (hb * r, D)

    # (a block pass of two slots prefetches `first_lengths` behind these)
    def row_block(g, t, row, start, pt, ln, lyr, *_):
        return (row[t], g) + (0,) * len(rows)

    def page_block(j):
        def block(g, t, row, start, pt, ln, lyr, *_):
            b = row[t]
            page = (t - start[b]) * pages + j + \
                _first_page(ln[b], page_size, window)
            return lyr[0], pt[b, page], g, 0, 0     # `pt` is `span_table`
        return block

    row_spec = pl.BlockSpec((None, None, *rows), row_block)
    in_specs = [row_spec] + [
        pl.BlockSpec((None, None, hb, page_size, D), page_block(j))
        for _ in range(2) for j in range(pages)]
    args = [q.reshape(B, G // hb, *rows)] + [k_pages] * pages + \
        [v_pages] * pages
    if quant:
        # the scale pool rides the SAME scalar-prefetch maps that resolve
        # the data pool's page indirection — one page id, two DMAs
        in_specs += [
            pl.BlockSpec((None, None, hb, page_size),
                         lambda *a, block=page_block(j): block(*a)[:-1])
            for _ in range(2) for j in range(pages)]
        # scale pools stay at their storage dtype (bf16) on the wire;
        # the kernel widens each tile in VMEM — a whole-pool fp32
        # cast here would materialize a pool-sized copy every step
        args += [k_scales] * pages + [v_scales] * pages
    slots = () if first_lengths is None else (
        first_lengths.astype(jnp.int32),)
    with scopes.scope(name):
        lengths = lengths.astype(jnp.int32)
        n_steps, row, start = decode_steps(lengths, page_size, table_width,
                                           window, pages)
        out = pl.pallas_call(
            functools.partial(_decode_kernel, sm_scale=sm_scale,
                              page_size=page_size, pages=pages,
                              window=window, per_head=per_head,
                              two_slots=bool(slots)),
            out_shape=jax.ShapeDtypeStruct((B, G // hb, *rows), q.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5 + len(slots),
                # the step dimension carries the online softmax; its
                # extent is this call's own count of live spans
                grid=(G // hb, n_steps),
                in_specs=in_specs,
                out_specs=row_spec,
                scratch_shapes=[
                    pltpu.VMEM((*rows[:-1], LANES), jnp.float32),
                    pltpu.VMEM((*rows[:-1], LANES), jnp.float32),
                    pltpu.VMEM(rows, jnp.float32),
                ],
            ),
            compiler_params=_DIMSEM,
            interpret=_interpret(), name=name,
        )(row, start,
          span_table(page_table, lengths, page_size, pages, window),
          lengths, _layer_operand(layer), *slots, *args)
    return out.reshape(B, H, D)


@scopes.scoped("ds.paged_decode_xla")
def paged_decode_attention_xla(q, k_pages, v_pages, page_table, lengths,
                               sm_scale, k_scales=None, v_scales=None,
                               layer=None, window=None, first_lengths=None):
    """Pure-XLA reference/fallback: gather the sequence's pages back
    into a contiguous [B, G, S_max, D] view and run a masked softmax.
    Identical semantics to the kernel, including exact-zero outputs for
    inactive (length 0) rows, the int8 dequant at the gather, grouped KV
    heads, the `window` and a block pass's `first_lengths`. With
    ``layer`` the gather indexes that layer of the stacked pools."""
    B, H, D = q.shape
    out_dtype = q.dtype
    G, page_size = k_pages.shape[-3], k_pages.shape[-2]
    NP = page_table.shape[1]

    def rows(pool, *tail):
        pages = (pool[page_table] if layer is None
                 else pool[layer, page_table])      # [B, NP, G, ps, ...]
        return jnp.moveaxis(pages, 2, 1).reshape(B, G, NP * page_size,
                                                 *tail)

    k = rows(k_pages, D)
    v = rows(v_pages, D)
    if k_scales is not None:
        ks = rows(k_scales)
        vs = rows(v_scales)
        k = k.astype(jnp.float32) * ks[..., None]
        v = v.astype(jnp.float32) * vs[..., None]
        q = q.astype(jnp.float32)
    # query head h reads KV head h // (H / G)
    s = jnp.einsum("bgrd,bgsd->bgrs", q.reshape(B, G, H // G, D), k,
                   preferred_element_type=jnp.float32) * sm_scale
    pos = jnp.arange(NP * page_size, dtype=jnp.int32)[None, :]
    live = pos < lengths[:, None]
    if window is not None:
        live = live & (pos >= lengths[:, None] - window)
    live = live[:, None, None, :]
    if first_lengths is not None:
        # the first half of a KV head's rows: the earlier slot's
        first = jnp.arange(H // G)[:, None] < H // G // 2
        live = jnp.where(first, pos < first_lengths[:, None, None, None],
                         live)
    s = jnp.where(live, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    prob = jnp.exp(s - m)
    prob = jnp.where(s <= NEG_INF * 0.5, 0.0, prob)
    l = jnp.sum(prob, axis=-1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bgrs,bgsd->bgrd", (prob / l_safe).astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, D).astype(out_dtype)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths,
                           sm_scale=None, backend=None, k_scales=None,
                           v_scales=None, layer=None, window=None,
                           block_pass=False, cross=False,
                           first_lengths=None):
    """One decode step of paged attention: ``out[b, h] = softmax(q[b, h]
    · K[b]) · V[b]`` with K/V read through ``page_table[b]`` and masked
    at ``lengths[b]``.

    ``k_scales``/``v_scales`` [P, page_size... = [P, H, page_size]]
    mark int8 pools (`inference.kv_cache.QuantizedPages`): the kernel
    dequantizes each page tile at the DMA boundary through the same
    page-table LUT; the fallback dequantizes at the gather. Kernel and
    fallback agree to float tolerance either way.

    ``layer`` (a traced int32 scalar, or None): with it the pools (and
    scale pools) are the engine's stacked ``[L, P, H, page_size, ...]``
    arrays and the call attends over layer ``layer`` of them in place;
    None keeps the one-layer contract above.

    The pools may hold FEWER heads than ``q``: with G KV heads under H
    query heads, query head h reads KV head ``h // (H / G)``. ``window``
    (a static int, or None) keeps a row's last `window` positions only:
    the kernel's work list then starts at the page that holds position
    ``length - window``, so earlier pages are neither read nor need a
    live entry in the page table (the serving engine has given them
    back), and the call runs under the scope `ds.paged_decode_window`.
    ``block_pass``: the call is a block pass's
    (`InferenceEngine._plan_token_layers`), which brings a block's rows x
    the query heads of a KV head as that KV head's group of "query heads":
    the same kernel under the scope `ds.paged_decode_block`. With
    ``first_lengths`` [B] int32 the block pass carries TWO SLOTS, two
    consecutive blocks of a sequence: the first half of each KV head's
    rows (the earlier block's) attends the positions below
    ``first_lengths[b]`` and the second half those below ``lengths[b]``,
    K and V read once for both; a call without it traces what it always
    traced. ``cross``: the call is a cross layer's, over pages another
    layer wrote: the same kernel under the scope `ds.paged_decode_cross`.

    backend: None = auto (Pallas kernel on TPU when
    `paged_decode_supported`, XLA fallback otherwise — CPU test runs
    keep XLA speed unless a test opts into the interpreter); "pallas"
    forces the kernel (interpret-mode off-TPU); "xla" forces the
    fallback.
    """
    B, H, D = q.shape
    if k_pages.shape != v_pages.shape:
        raise ValueError(f"k_pages {k_pages.shape} != v_pages "
                         f"{v_pages.shape}")
    want = ("[P, H, page_size, D] without" if layer is None
            else "[L, P, H, page_size, D] with")
    if k_pages.ndim != want.count(",") + 1:
        raise ValueError(f"k_pages {k_pages.shape} must be {want} a "
                         f"layer index")
    P, Hk, page_size, Dk = k_pages.shape[-4:]
    if Dk != D or H % Hk:
        raise ValueError(f"cache heads/dim {(Hk, Dk)} do not serve query "
                         f"{(H, D)}: the head dim must match and the KV "
                         f"heads divide the query heads")
    if window is not None and (k_scales is not None or int(window) < 1):
        raise ValueError(f"window {window!r}: a positive int, over pools "
                         f"that are not int8")
    if page_table.ndim != 2 or page_table.shape[0] != B:
        raise ValueError(f"page_table shape {page_table.shape} must be "
                         f"[{B}, n_pages]")
    if lengths.shape != (B,):
        raise ValueError(f"lengths shape {lengths.shape} != ({B},)")
    quant = k_scales is not None
    if first_lengths is not None and (
            not block_pass or quant or window is not None or
            H // Hk % 2 or first_lengths.shape != (B,)):
        raise ValueError(
            f"first_lengths {getattr(first_lengths, 'shape', None)}: a "
            f"block pass's [{B}] over plain pools and no window, its two "
            f"slots the halves of an even group of rows a KV head "
            f"(got {H // Hk})")
    scale_shape = k_pages.shape[:-1]
    if quant and (k_scales.shape != scale_shape or v_scales is None or
                  v_scales.shape != scale_shape):
        raise ValueError(
            f"int8 pool scales must both be {list(scale_shape)}; "
            f"got {getattr(k_scales, 'shape', None)} / "
            f"{getattr(v_scales, 'shape', None)}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)

    if backend is None:
        backend = _auto_backend("paged_decode_attention", D, page_size,
                                quant)
    _LAST_BACKEND["decode"] = backend
    _LAST_BACKEND["decode_kv"] = "int8" if quant else str(k_pages.dtype)
    _log_first_dispatch()
    if backend == "xla":
        for key in _STEP_KEYS:
            _LAST_BACKEND.pop(key, None)
        return paged_decode_attention_xla(q, k_pages, v_pages, page_table,
                                          lengths, sm_scale,
                                          k_scales=k_scales,
                                          v_scales=v_scales, layer=layer,
                                          window=window,
                                          first_lengths=first_lengths)
    if backend != "pallas":
        raise ValueError(f"unknown paged decode backend {backend!r}")
    if block_pass:
        return paged_decode_attention_pallas(
            q, k_pages, v_pages, page_table, lengths, sm_scale,
            k_scales=k_scales, v_scales=v_scales, layer=layer,
            window=window, name="ds.paged_decode_block",
            first_lengths=first_lengths)
    return paged_decode_attention_pallas(
        q, k_pages, v_pages, page_table, lengths, sm_scale,
        k_scales=k_scales, v_scales=v_scales, layer=layer, window=window,
        name="ds.paged_decode_cross" if cross else
        "ds.paged_decode" if window is None else "ds.paged_decode_window")


# ---------------------------------------------------------------------------
# the decoded token's K/V row, written in place
# ---------------------------------------------------------------------------

def _write_group(page_size, dtype):
    """Slots of a page one row's write reads, selects into and writes
    back: the row's packed sublane group. A pool's HBM tile holds 8
    sublanes of 32 bits, so a row of `dtype` shares its sublanes with
    the 8 * (4 / itemsize) slots of its group (float32 8, bf16 16,
    int8 32) and with no other; that is the least a read-modify-write
    can move. A page the group does not divide (8 or 24 slots of bf16)
    is moved whole."""
    group = 32 // jnp.dtype(dtype).itemsize
    return group if page_size % group == 0 else page_size


def _run_of(pools, rows):
    """Rows a sequence `rows` bring: 1 ([B, H, ...]), or the length of a
    run ([B, H, run, D], one dim more than a pool's row)."""
    return rows[0].shape[2] if rows[0].ndim == pools[0].ndim - 1 else 1


def _kv_write_kernel(lyr_ref, page_ref, slot_ref, *refs, run=1):
    """One batch row: each pool's tile (the [H, g, D] group of the row's
    page, or the scale pool's whole [H, ps] plane) comes in, gets the row
    at its slot, and goes back out to where it came from (the output
    aliases the pool). `run` > 1: a run of that many rows a batch row, at
    the slots from `slot` on (a block pass's; they lie in one group and
    ride tiled over the group's slots, so slot s holds row s % run)."""
    n = len(refs) // 3
    slot = slot_ref[pl.program_id(0)]
    for row, pool, out in zip(refs[:n], refs[n:2 * n], refs[2 * n:]):
        tile = pool[...]
        slots = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
        # a select, not a dynamic one-row store: a row of a packed
        # (bf16 / int8) tile shares its sublane with its neighbours
        if run == 1:
            out[...] = jnp.where(slots == slot % tile.shape[1], row[...],
                                 tile)
        else:
            first = slot % tile.shape[1]
            out[...] = jnp.where((slots >= first) & (slots < first + run),
                                 row[...], tile)


def paged_kv_write_pallas(pools, rows, layer, page_idx, slot):
    """`paged_kv_write` as a kernel over grid (B,). A data pool is
    blocked by the row's sublane group: the [H, g, D] slots
    ``slot // g * g ...`` of one page (`_write_group`; 64 KB at
    16 x 16 x 128 bf16, a quarter of the page), read, selected into at
    ``slot % g`` and written back. An int8 page's scale pool has the
    slots on its LANES: its [H, ps] plane (2 KB) rides whole. The
    read-modify-write is safe because no two live rows write into one
    page in one step, so none write into one group: a sequence's last
    page is its own (shared prefix pages are full pages and never
    written). Only the trash page 0 sees colliding writes (inactive
    rows), and nothing reads its content."""
    B = page_idx.shape[0]
    n = len(pools)
    _LAST_BACKEND["kv_write_slots"] = _write_group(pools[0].shape[3],
                                                   pools[0].dtype)
    run = _run_of(pools, rows)

    def pool_spec(pool):
        H, page_size, *D = pool.shape[2:]           # no D: a scale pool
        g = _write_group(page_size, pool.dtype) if D else page_size
        return pl.BlockSpec(
            (None, None, H, g, *D),
            lambda b, lyr, pg, sl: (lyr[0], pg[b], 0, sl[b] // g,
                                    *(0,) * len(D)))

    def row_spec(pool):
        # rows ride as [B, H, 1(, D)]: the block's last two dims are the
        # array's own, and the row broadcasts over the tile's slots (a
        # run of rows rides as [B, H, g, D], tiled over the group)
        g = _write_group(pool.shape[3], pool.dtype) if run > 1 else 1
        tile = (pool.shape[2], g, *pool.shape[4:])
        return pl.BlockSpec(
            (None, *tile), lambda b, lyr, pg, sl: (b, *(0,) * len(tile)))

    call = pl.pallas_call(
        _kv_write_kernel if run == 1 else functools.partial(
            _kv_write_kernel, run=run),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[row_spec(p) for p in pools] +
                     [pool_spec(p) for p in pools],
            out_specs=[pool_spec(p) for p in pools],
        ),
        # the alias index counts the three scalar-prefetch operands
        input_output_aliases={3 + n + i: i for i in range(n)},
        compiler_params=CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=_interpret(), name="ds.kv_write",
    )
    if run == 1:
        rows = [jnp.expand_dims(r.astype(p.dtype), 2)
                for r, p in zip(rows, pools)]
    else:
        rows = [jnp.tile(r.astype(p.dtype), (1, 1, _write_group(
            p.shape[3], p.dtype) // run, 1)) for r, p in zip(rows, pools)]
    return tuple(call(_layer_operand(layer), page_idx.astype(jnp.int32),
                      slot.astype(jnp.int32), *rows, *pools))


def paged_kv_write_xla(pools, rows, layer, page_idx, slot):
    """`paged_kv_write` as XLA scatters (on a TPU each would re-lay-out
    the whole stacked pool around itself: the kernel exists for that)."""
    run = _run_of(pools, rows)
    if run > 1:
        slots = slot[:, None] + jnp.arange(run)
        # advanced indices apart: their dims lead, [B, run, H, ...]
        return tuple(p.at[layer, page_idx[:, None], :, slots].set(
            jnp.moveaxis(r, 2, 1).astype(p.dtype))
            for p, r in zip(pools, rows))
    return tuple(p.at[layer, page_idx, :, slot].set(r.astype(p.dtype))
                 for p, r in zip(pools, rows))


@scopes.scoped("ds.kv_write")
def paged_kv_write(pools, rows, layer, page_idx, slot, backend=None):
    """One decoded token's rows into layer ``layer`` of stacked pools:
    ``pool[layer, page_idx[b], :, slot[b]] = row[b]`` for every pool.

    ``pools`` are ``[L, P, H, page_size, ...]`` arrays (K and V data
    pools ``[..., D]``, a data pool first, and for int8 pages their
    ``[L, P, H, page_size]`` scale pools) and ``rows`` the matching
    ``[B, H, ...]`` rows, cast to the pool's dtype here; or a RUN of rows
    a sequence, ``[B, H, run, D]`` into the slots ``slot[b] ..
    slot[b] + run - 1`` (a block pass's: `run` a power of two that
    divides the pool's `_write_group`, `slot` a multiple of it, so a run
    lies in one group and is one read-modify-write; plain pools only).
    Inactive batch rows name the trash page 0. Returns the pools in order. Under jit
    with the pools donated the kernel rewrites B sublane groups a data
    pool (`_write_group` slots of a page each: `dispatch_report()`'s
    ``kv_write_slots``, the useful share of the write's traffic being one
    over it) and nothing else moves.

    backend: as `paged_decode_attention` (None = the kernel on a TPU
    when `paged_decode_supported`, XLA otherwise).
    """
    pools, rows = tuple(pools), tuple(rows)
    if len(pools) != len(rows):
        raise ValueError(f"{len(pools)} pools for {len(rows)} rows")
    B = page_idx.shape[0]
    a_run = rows[0].ndim == pools[0].ndim - 1
    run = _run_of(pools, rows)
    for p, r in zip(pools, rows):
        if a_run:
            group = _write_group(p.shape[3], p.dtype)
            if p.ndim != 5 or r.shape != (B, p.shape[2], run, p.shape[4]) \
                    or run < 2 or group % run:
                raise ValueError(
                    f"rows {r.shape} are no run of rows for pool {p.shape}:"
                    f" expected [{B}, H, run, D] for a [L, P, H, page_size,"
                    f" D] pool, run >= 2 dividing its write group {group}")
        elif p.ndim < 4 or r.shape != (B, p.shape[2], *p.shape[4:]):
            raise ValueError(
                f"rows {r.shape} do not match pool {p.shape}: expected "
                f"[{B}, H, ...] for a [L, P, H, page_size, ...] pool")
    if backend is None:
        data = pools[0]
        backend = _auto_backend("paged_kv_write", data.shape[-1],
                                data.shape[3], data.dtype == jnp.int8)
    _LAST_BACKEND["kv_write"] = backend
    if backend == "xla":
        _LAST_BACKEND.pop("kv_write_slots", None)
        return paged_kv_write_xla(pools, rows, layer, page_idx, slot)
    if backend != "pallas":
        raise ValueError(f"unknown paged kv write backend {backend!r}")
    return paged_kv_write_pallas(pools, rows, layer, page_idx, slot)


# ---------------------------------------------------------------------------
# latent pages (MLA): the absorbed decode, and the token's latent row
# ---------------------------------------------------------------------------

# Pages of one row a grid step of the latent kernel moves. A latent page
# has no head axis ([page_size, row]: 80 KB at 64 x 640 bf16, a sixth of a
# 16-head K and V page), so one page a step would leave the step's fixed
# cost, not its bytes, to set the time. Measured on a v5e at 32 rows over
# 315k attended rows of 20 heads (PERF.md section 6, PR 35): 4 pages a
# step 1.07 ms a call, 8 0.80, 16 0.70 (63% of the roofline's 0.44).
LATENT_PAGES_PER_STEP = 16


def latent_row_width(width):
    """The pool's row for a latent row of `width` features: whole lane
    tiles (576 -> 640)."""
    return -(-width // LANES) * LANES


def _latent_kernel(row_ref, start_ref, pt_ref, len_ref, lyr_ref, q_ref,
                   *refs, sm_scale, page_size, v_width):
    """Grid step `t`: `LATENT_PAGES_PER_STEP` consecutive pages of one
    batch row, each a [ps, width] tile every head reads, meet the row's
    [H, width] queries as ONE [pages * ps, width] operand. The value is
    the tile's first `v_width` columns (c_kv). A page past the row's
    length is the trash page, and masked."""
    *page_refs, o_ref, m_scr, l_scr, acc_scr = refs
    t = pl.program_id(0)
    b = row_ref[t]
    length = len_ref[b]
    span = len(page_refs) * page_size
    first_pos = (t - start_ref[b]) * span

    @pl.when(t == start_ref[b])
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(first_pos < length)         # not an inactive row's one step
    def _compute():
        rows = jnp.concatenate([r[...] for r in page_refs], axis=0)
        s = jax.lax.dot_general(
            q_ref[...], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale      # [H, span]
        live = first_pos + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1) < length
        s = jnp.where(live, s, NEG_INF)
        m = m_scr[:, :1]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        prob = jnp.where(live, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_scr[:, :1] + jnp.sum(prob, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            prob.astype(rows.dtype), rows[:, :v_width],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[:] = acc_scr[:] * alpha + pv

    @pl.when(first_pos + span >= length)             # the row's last step
    def _finalize():
        l = l_scr[:, :1]
        o_ref[...] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)
                      ).astype(o_ref.dtype)


def paged_latent_decode_pallas(q, pool, page_table, lengths, sm_scale,
                               v_width, layer):
    """The latent kernel over grid (steps,), `decode_steps` at a span of
    `LATENT_PAGES_PER_STEP` pages: the pool rides once a page of the
    step, each operand's index map resolving its own page through the
    prefetched page table. (The grid's extent is traced, so the
    `pallas_call` is built where it is called; its body is a dozen
    lines, not flash attention's unrolled strips.)"""
    B, H, W = q.shape
    page_size, table_width = pool.shape[2], page_table.shape[1]
    pages = LATENT_PAGES_PER_STEP
    # the heads are the matmuls' M: padded to whole sublane tiles of the
    # operand's dtype (20 heads of bf16 ride as 32)
    tile = 32 // jnp.dtype(q.dtype).itemsize
    Hp = -(-H // tile) * tile
    if Hp != H:
        q = jnp.pad(q, ((0, 0), (0, Hp - H), (0, 0)))

    def row_block(t, row, start, pt, ln, lyr):
        return row[t], 0, 0

    def page_block(j):
        def block(t, row, start, pt, ln, lyr):
            b = row[t]
            page = (t - start[b]) * pages + j
            # past the row's length (an inactive row's every page): the
            # trash page, which a run of them fetches once
            return (lyr[0], jnp.where(
                page * page_size < ln[b],
                pt[b, jnp.minimum(page, table_width - 1)], 0), 0, 0)
        return block

    with scopes.scope("ds.paged_decode_latent"):
        lengths = lengths.astype(jnp.int32)
        n_steps, row, start = decode_steps(lengths, pages * page_size,
                                           -(-table_width // pages))
        out = pl.pallas_call(
            functools.partial(_latent_kernel, sm_scale=sm_scale,
                              page_size=page_size, v_width=v_width),
            out_shape=jax.ShapeDtypeStruct((B, Hp, v_width), q.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(n_steps,),
                in_specs=[pl.BlockSpec((None, Hp, W), row_block)] +
                [pl.BlockSpec((None, None, page_size, W), page_block(j))
                 for j in range(pages)],
                out_specs=pl.BlockSpec((None, Hp, v_width), row_block),
                scratch_shapes=[
                    pltpu.VMEM((Hp, LANES), jnp.float32),
                    pltpu.VMEM((Hp, LANES), jnp.float32),
                    pltpu.VMEM((Hp, v_width), jnp.float32),
                ],
            ),
            compiler_params=CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=_interpret(), name="ds.paged_decode_latent",
        )(row, start, page_table.astype(jnp.int32), lengths,
          _layer_operand(layer), q, *(pool,) * pages)
    return out[:, :H]


@scopes.scoped("ds.paged_decode_xla")
def paged_latent_decode_xla(q, pool, page_table, lengths, sm_scale, v_width,
                            layer):
    """The kernel's XLA twin: gather the row's pages into [B, S_max,
    width] and run a masked softmax; exact zeros for a length of 0."""
    B = q.shape[0]
    rows = pool[layer, page_table].reshape(B, -1, pool.shape[-1])
    s = jnp.einsum("bhw,bsw->bhs", q, rows,
                   preferred_element_type=jnp.float32) * sm_scale
    live = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, :] < \
        lengths[:, None]
    s = jnp.where(live[:, None, :], s, NEG_INF)
    prob = jnp.where(live[:, None, :],
                     jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    l = jnp.sum(prob, axis=-1, keepdims=True)
    prob = prob / jnp.where(l == 0.0, 1.0, l)
    return jnp.einsum("bhs,bsv->bhv", prob.astype(rows.dtype),
                      rows[..., :v_width],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def paged_latent_supported(width, v_width, page_size):
    """Mosaic constraints of the latent kernel: a page's rows on whole
    sublane tiles, and the value a lane-aligned prefix of the row."""
    if _interpret():
        return True
    return page_size % 16 == 0 and v_width % LANES == 0 and width > v_width


def paged_latent_decode(q, pool, page_table, lengths, sm_scale, v_width,
                        layer, backend=None):
    """One decode step of ABSORBED latent attention (MLA): ``u[b, h] =
    softmax(q[b, h] . rows[b]) . rows[b][:, :v_width]`` over the token
    rows of sequence b, read through ``page_table[b]`` and masked at
    ``lengths[b]`` (which includes the token being decoded; 0 marks an
    inactive row, whose output is exact zero).

    ``q`` [B, H, width]: a head's absorbed query [q_nope W_uk^T |
    rot(q_rope)]; ``pool`` [L, P, page_size, row]: the engine's stacked
    latent pages, read at layer ``layer`` where they lie, a row being
    [c_kv | rot(k_r) | zeros up to a whole lane tile] with NO head axis:
    all H heads read the one tile. (``row`` is `latent_row_width(width)`:
    a TPU lays an array whose rows are not whole lane tiles out with
    another dim innermost, and the kernel would be handed a copy of the
    pool, not the pool.)
    ``sm_scale`` is the model's 1 / sqrt(nope + rope), which the row's
    width does not tell. Returns u [B, H, v_width]: the caller's W_uv
    makes each head's output of it.

    backend: as `paged_decode_attention` (None = the kernel on a TPU
    where `paged_latent_supported`, its XLA twin otherwise)."""
    B, H, W = q.shape
    if pool.ndim != 4 or pool.shape[-1] < W or not 0 < v_width <= W:
        raise ValueError(f"latent pool {pool.shape} must be [L, P, "
                         f"page_size, >= {W}] for queries {q.shape}, with "
                         f"a value width in (0, {W}], got {v_width}")
    # a pool row is the token's row and the padding that fills its last
    # lane tile: the query's zeros meet it
    q = jnp.pad(q, ((0, 0), (0, 0), (0, pool.shape[-1] - W)))
    W = pool.shape[-1]
    if page_table.ndim != 2 or page_table.shape[0] != B or \
            lengths.shape != (B,):
        raise ValueError(f"page_table {page_table.shape} / lengths "
                         f"{lengths.shape} do not serve {B} rows")
    if backend is None:
        if not _interpret() and paged_latent_supported(W, v_width,
                                                       pool.shape[2]):
            backend = "pallas"
        else:
            note_xla_on_tpu(
                "paged_latent_decode",
                f"row width {W}, value width {v_width}, page size "
                f"{pool.shape[2]}: the kernel needs a page size that is a "
                f"multiple of 16 and a value width that is a multiple of "
                f"{LANES}")
            backend = "xla"
    _LAST_BACKEND["decode_latent"] = backend
    if backend == "xla":
        return paged_latent_decode_xla(q, pool, page_table, lengths,
                                       sm_scale, v_width, layer)
    if backend != "pallas":
        raise ValueError(f"unknown paged decode backend {backend!r}")
    return paged_latent_decode_pallas(q, pool, page_table, lengths,
                                      sm_scale, v_width, layer)


def _latent_write_kernel(lyr_ref, page_ref, slot_ref, row_ref, pool_ref,
                         out_ref):
    """One batch row: the [g, width] sublane group of its page comes in,
    gets the row at its slot, and goes back to where it came from (as
    `_kv_write_kernel`)."""
    tile = pool_ref[...]
    slots = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    out_ref[...] = jnp.where(
        slots == slot_ref[pl.program_id(0)] % tile.shape[0], row_ref[...],
        tile)


@scopes.scoped("ds.kv_write")
def paged_latent_write(pool, rows, layer, page_idx, slot, backend=None):
    """One decoded token's latent row into layer ``layer`` of the stacked
    latent pool: ``pool[layer, page_idx[b], slot[b]] = rows[b]``
    (``pool`` [L, P, page_size, row], ``rows`` [B, width <= row], padded
    with zeros to the pool's row). On a TPU a kernel that aliases the
    pool and rewrites the row's sublane group of its page ([g, row],
    `_write_group`; ``kv_write_latent_slots`` in `dispatch_report()`), as
    `paged_kv_write`; XLA's scatter off it. Returns the pool."""
    B = rows.shape[0]
    if pool.ndim != 4 or rows.ndim != 2 or pool.shape[-1] < rows.shape[1]:
        raise ValueError(f"rows {rows.shape} do not match the latent pool "
                         f"{pool.shape}: expected [{B}, width] for "
                         f"[L, P, page_size, >= width]")
    if backend is None:
        backend = "xla" if _interpret() else "pallas"
    _LAST_BACKEND["kv_write_latent"] = backend
    W = pool.shape[-1]
    rows = jnp.pad(rows.astype(pool.dtype),
                   ((0, 0), (0, W - rows.shape[1])))
    if backend == "xla":
        _LAST_BACKEND.pop("kv_write_latent_slots", None)
        return pool.at[layer, page_idx, slot].set(rows)
    if backend != "pallas":
        raise ValueError(f"unknown paged kv write backend {backend!r}")
    g = _write_group(pool.shape[2], pool.dtype)
    _LAST_BACKEND["kv_write_latent_slots"] = g
    pool_spec = pl.BlockSpec(
        (None, None, g, W),
        lambda b, lyr, pg, sl: (lyr[0], pg[b], sl[b] // g, 0))
    return pl.pallas_call(
        _latent_write_kernel,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            # the row rides as [B, 1, width] and broadcasts over the slots
            in_specs=[pl.BlockSpec((None, 1, W),
                                   lambda b, lyr, pg, sl: (b, 0, 0)),
                      pool_spec],
            out_specs=pool_spec,
        ),
        input_output_aliases={4: 0},
        compiler_params=CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=_interpret(), name="ds.kv_write",
    )(_layer_operand(layer), page_idx.astype(jnp.int32),
      slot.astype(jnp.int32), rows[:, None, :], pool)
