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
  layer, head-major so a model-parallel mesh shards dim 1 (heads) and
  each shard runs this kernel on its local heads unchanged (attention is
  head-independent).
- ``page_table`` [B, NP] int32: page ids of sequence b's pages in
  position order. Entries past the sequence's live pages are don't-care
  (the scheduler pads with page 0 — the pool's reserved trash page);
  their loads are masked and contribute nothing.
- ``lengths`` [B] int32: tokens valid for attention — INCLUDING the one
  being decoded (its K/V must already be written to its page). A length
  of 0 marks an inactive (padding) batch row; its output is exact zero.

Mechanics: grid (B, H, NP) with the page dimension innermost and
``arbitrary`` (it carries the online-softmax accumulation); the page
table and lengths ride as scalar prefetch
(`pltpu.PrefetchScalarGridSpec`), so the K/V BlockSpec index maps
resolve page-table indirection at DMA-issue time — the same LUT
mechanism as the compacted causal grids in `flash_attention.py`. Pages
at or past a sequence's length skip all compute (`pl.when`); the last
grid step writes ``acc / l``. No backward exists: decode is inference.

Off a TPU the kernel runs in interpreter mode (slow, test-only) and
`paged_decode_attention` defaults to the XLA form there, a gather +
masked softmax with identical semantics. Which one ran is recorded in
`_LAST_BACKEND`; XLA chosen on a TPU is logged by name.
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

_DIMSEM = CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))

# Test/bench observability: backend ("pallas"/"xla") of the most recent
# paged_decode_attention call — the serving tests pin which path ran.
_LAST_BACKEND = {}
_DISPATCH_LOGGED = False


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


def _decode_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, sm_scale, page_size,
                   ks_ref=None, vs_ref=None):
    """One (batch row, head, page) step of paged flash decode. With
    int8 pools (`ks_ref`/`vs_ref` scale blocks, resolved through the
    SAME page-table LUT as the data blocks), the per-slot scales fold
    into the [1, ps] score / probability rows — ``q·(k·s) == (q·k)·s``
    — so the wire moved 1 byte/element and no [ps, 1] scale column (a
    lane→sublane relayout) is ever built; the math runs fp32."""
    b = pl.program_id(0)
    h = pl.program_id(1)
    p = pl.program_id(2)
    n_pages = pl.num_programs(2)
    length = len_ref[b]

    def scale_row(ref):
        # the block is the page's whole [Hk, ps] scale tile (Mosaic only
        # takes a block whose last two dims are the array's own or
        # (8, 128)-multiples); pick this head's row by mask-and-reduce,
        # which needs no dynamic sublane slice of a packed bf16 tile
        tile = ref[...].astype(jnp.float32)                    # [Hk, ps]
        rows = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
        return jnp.sum(jnp.where(rows == h, tile, 0.0), axis=0,
                       keepdims=True)                          # [1, ps]

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(p * page_size < length)
    def _compute():
        q = q_ref[...]                                         # [1, D]
        k = k_ref[...]                                         # [ps, D]
        if ks_ref is not None:
            q = q.astype(jnp.float32)
            k = k.astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # [1, ps]
        if ks_ref is not None:
            s = s * scale_row(ks_ref)
        s = s * sm_scale
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + \
            p * page_size
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_scr[:, :1]                                  # [1, 1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        prob = jnp.exp(s - m_new)
        # masked slots would see exp(NEG_INF - m) == 0 already, except
        # when the whole page is masked and m_new == NEG_INF; zero them
        # so l stays an exact count of live probability mass
        prob = jnp.where(s <= NEG_INF * 0.5, 0.0, prob)
        l_new = alpha * l_prev + jnp.sum(prob, axis=1, keepdims=True)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        if vs_ref is not None:
            pv = jax.lax.dot_general(
                prob * scale_row(vs_ref), v_ref[...].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)            # [1, D]
        else:
            pv = jax.lax.dot_general(
                prob.astype(v_ref.dtype), v_ref[...],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)            # [1, D]
        acc_scr[:] = acc_scr[:] * alpha + pv

    @pl.when(p == n_pages - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        # inactive rows (length 0) never accumulated: acc == 0 → out 0
        o_ref[...] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def _decode_kernel_quant(pt_ref, len_ref, q_ref, k_ref, v_ref, ks_ref,
                         vs_ref, o_ref, m_scr, l_scr, acc_scr, *,
                         sm_scale, page_size):
    """Positional-arg adapter for the int8 variant (pallas passes refs
    in in_specs order: data pools then scale pools)."""
    _decode_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                   l_scr, acc_scr, sm_scale=sm_scale,
                   page_size=page_size, ks_ref=ks_ref, vs_ref=vs_ref)


def paged_decode_attention_pallas(q, k_pages, v_pages, page_table, lengths,
                                  sm_scale, k_scales=None, v_scales=None):
    B, H, D = q.shape
    Hk, page_size = k_pages.shape[1:3]
    NP = page_table.shape[1]
    quant = k_scales is not None
    # Mosaic takes a block only if its last two dims are (8, 128)-
    # multiples or the array's own. The query/out rows therefore ride as
    # [B, H, 1, D] (a [1, D] block over a [1, D] minor plane) and the
    # scale block is the page's whole [Hk, ps] plane; `None` dims are
    # squeezed out of the kernel's refs.
    row_spec = pl.BlockSpec((None, None, 1, D),
                            lambda b, h, p, pt, ln: (b, h, 0, 0))
    pool_spec = pl.BlockSpec((None, None, page_size, D),
                             lambda b, h, p, pt, ln: (pt[b, p], h, 0, 0))
    # the scale pool rides the SAME scalar-prefetch LUT that resolves
    # the data pool's page indirection — one page id, two DMAs
    scale_spec = pl.BlockSpec((None, Hk, page_size),
                              lambda b, h, p, pt, ln: (pt[b, p], 0, 0))
    in_specs = [row_spec, pool_spec, pool_spec]
    args = [q[:, :, None, :], k_pages, v_pages]
    kernel_fn = _decode_kernel
    if quant:
        in_specs += [scale_spec, scale_spec]
        # scale pools stay at their storage dtype (bf16) on the wire;
        # the kernel widens each tile in VMEM — a whole-pool fp32
        # cast here would materialize a pool-sized copy every step
        args += [k_scales, v_scales]
        kernel_fn = _decode_kernel_quant
    kernel = functools.partial(kernel_fn, sm_scale=sm_scale,
                               page_size=page_size)
    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, NP),
            in_specs=in_specs,
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((1, LANES), jnp.float32),
                pltpu.VMEM((1, LANES), jnp.float32),
                pltpu.VMEM((1, D), jnp.float32),
            ],
        ),
        compiler_params=_DIMSEM,
        interpret=_interpret(), name="ds.paged_decode",
    )
    operands = (page_table.astype(jnp.int32), lengths.astype(jnp.int32),
                *args)
    with scopes.scope("ds.paged_decode"):
        out = call(*operands)
    return out[:, :, 0, :]


@scopes.scoped("ds.paged_decode_xla")
def paged_decode_attention_xla(q, k_pages, v_pages, page_table, lengths,
                               sm_scale, k_scales=None, v_scales=None):
    """Pure-XLA reference/fallback: gather the sequence's pages back
    into a contiguous [B, H, S_max, D] view and run a masked softmax.
    Identical semantics to the kernel, including exact-zero outputs for
    inactive (length 0) rows and the int8 dequant at the gather."""
    B, H, D = q.shape
    out_dtype = q.dtype
    page_size = k_pages.shape[2]
    NP = page_table.shape[1]
    k = jnp.moveaxis(k_pages[page_table], 2, 1).reshape(B, H, NP * page_size,
                                                        D)
    v = jnp.moveaxis(v_pages[page_table], 2, 1).reshape(B, H, NP * page_size,
                                                        D)
    if k_scales is not None:
        ks = jnp.moveaxis(k_scales[page_table], 2, 1).reshape(
            B, H, NP * page_size)
        vs = jnp.moveaxis(v_scales[page_table], 2, 1).reshape(
            B, H, NP * page_size)
        k = k.astype(jnp.float32) * ks[..., None]
        v = v.astype(jnp.float32) * vs[..., None]
        q = q.astype(jnp.float32)
    s = jnp.einsum("bhd,bhsd->bhs", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    pos = jnp.arange(NP * page_size, dtype=jnp.int32)
    s = jnp.where(pos[None, None, :] < lengths[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    prob = jnp.exp(s - m)
    prob = jnp.where(s <= NEG_INF * 0.5, 0.0, prob)
    l = jnp.sum(prob, axis=-1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bhs,bhsd->bhd", (prob / l_safe).astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(out_dtype)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths,
                           sm_scale=None, backend=None, k_scales=None,
                           v_scales=None):
    """One decode step of paged attention: ``out[b, h] = softmax(q[b, h]
    · K[b]) · V[b]`` with K/V read through ``page_table[b]`` and masked
    at ``lengths[b]``.

    ``k_scales``/``v_scales`` [P, page_size... = [P, H, page_size]]
    mark int8 pools (`inference.kv_cache.QuantizedPages`): the kernel
    dequantizes each page tile at the DMA boundary through the same
    page-table LUT; the fallback dequantizes at the gather. Kernel and
    fallback agree to float tolerance either way.

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
    P, Hk, page_size, Dk = k_pages.shape
    if (Hk, Dk) != (H, D):
        raise ValueError(f"cache heads/dim {(Hk, Dk)} != query {(H, D)}")
    if page_table.ndim != 2 or page_table.shape[0] != B:
        raise ValueError(f"page_table shape {page_table.shape} must be "
                         f"[{B}, n_pages]")
    if lengths.shape != (B,):
        raise ValueError(f"lengths shape {lengths.shape} != ({B},)")
    quant = k_scales is not None
    if quant and (k_scales.shape != (P, Hk, page_size) or
                  v_scales is None or
                  v_scales.shape != (P, Hk, page_size)):
        raise ValueError(
            f"int8 pool scales must both be [{P}, {Hk}, {page_size}]; "
            f"got {getattr(k_scales, 'shape', None)} / "
            f"{getattr(v_scales, 'shape', None)}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)

    if backend is None:
        on_tpu = not _interpret()
        backend = ("pallas" if on_tpu and
                   paged_decode_supported(D, page_size, quantized=quant)
                   else "xla")
        if backend == "xla":
            note_xla_on_tpu(
                "paged_decode_attention",
                f"head dim {D}, page size {page_size}, int8 pools "
                f"{quant}: the kernel needs a head dim of 64/128/256 and "
                f"a page size that is a multiple of "
                f"{32 if quant else 8}")
    _LAST_BACKEND["decode"] = backend
    _LAST_BACKEND["decode_kv"] = "int8" if quant else str(k_pages.dtype)
    _log_first_dispatch()
    if backend == "xla":
        return paged_decode_attention_xla(q, k_pages, v_pages, page_table,
                                          lengths, sm_scale,
                                          k_scales=k_scales,
                                          v_scales=v_scales)
    if backend != "pallas":
        raise ValueError(f"unknown paged decode backend {backend!r}")
    return paged_decode_attention_pallas(q, k_pages, v_pages, page_table,
                                         lengths, sm_scale,
                                         k_scales=k_scales,
                                         v_scales=v_scales)
