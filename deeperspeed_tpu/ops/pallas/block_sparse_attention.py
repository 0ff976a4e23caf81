"""Block-sparse flash attention (splash-attention-style) Pallas kernels.

TPU-native replacement for the reference's Triton block-sparse stack
(`deepspeed/ops/sparse_attention/trsrc/{matmul.tr,softmax_*.tr}` driven by
`matmul.py`/`softmax.py`): instead of materializing block-sparse score
matrices through separate SDD-matmul → sparse-softmax → DSD-matmul passes,
one fused kernel visits ONLY the active column blocks of each query-row
block, carried by a scalar-prefetched LUT, with online softmax — compute
and HBM traffic both scale with the number of active blocks.

Layout comes from `SparsityConfig.make_layout(seq)` →
[num_heads, nQ, nK] 0/1 (see `..sparse_attention.sparsity_config`).
`causal=True` applies an element-level triangular mask inside diagonal
blocks (unidirectional patterns).

**Rectangular grouping + K-fanout** (round-4 redesign; the previous
square GROUP×GROUP coarse tiling computed every 128×128 sub-block of a
coarse tile — random patterns share almost no coarse columns, so MXU
work barely dropped with density and per-instance fixed cost dominated):

- the Q side groups `group_q` adjacent 128-row blocks into one tile
  (adjacent rows of windowed/global patterns share most columns, so the
  row-union LUT stays tight);
- the K side stays FINE: the LUT lists individual active 128-column
  blocks, each fetched through its own input ref — `fanout` refs per
  instance, so one grid step processes `fanout` scattered K/V blocks
  back-to-back (fat [group_q·128, fanout·128] score matmuls, no dead
  coarse sub-blocks on the K axis);
- per-entry activity bits (bit r = fine row r of the group attends this
  column block) mask rows dragged in by the union.

Instance count drops ~group_q·fanout× vs one-block-per-instance and MXU
work tracks the ACTIVE block count — the speedup scales with density.
"""

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import scopes
from ...compat import CompilerParams

from .flash_attention import LANES, NEG_INF, _interpret

DEFAULT_BLOCK = 128
DEFAULT_GROUP = 4
DEFAULT_FANOUT = 4


def build_lut(layout):
    """[H, nQ, nK] 0/1 layout → (lut [H, nQ, maxA] int32, sentinel).

    lut[h, qi, :] lists the active column blocks for query-row block qi
    (padded with `sentinel` = nK). For the backward dk/dv kernel call with
    layout.transpose(0, 2, 1)."""
    layout = np.asarray(layout)
    h, n_q, n_k = layout.shape
    counts = layout.sum(axis=2)
    max_active = max(1, int(counts.max()))
    lut = np.full((h, n_q, max_active), n_k, np.int32)
    for hi in range(h):
        for qi in range(n_q):
            cols = np.nonzero(layout[hi, qi])[0]
            lut[hi, qi, :len(cols)] = cols
    return lut, n_k


def build_row_union_lut(layout, group_q, fanout):
    """Row-union fine-column LUT: group `group_q` adjacent 128-row
    blocks; list each group's UNION of active fine column blocks, padded
    to a multiple of `fanout` with sentinel (= nK).

    Returns (lut [H, nGq, maxU] int32, bits [H, nGq, maxU] int32,
    sentinel): bit r of bits[h, g, a] says fine row g*group_q + r is
    active for fine column lut[h, g, a]."""
    layout = np.asarray(layout)
    h, n_q, n_k = layout.shape
    if n_q % group_q:
        raise ValueError(f"{n_q} row blocks not divisible by {group_q}")
    n_gq = n_q // group_q
    grouped = layout.reshape(h, n_gq, group_q, n_k)
    union = grouped.any(axis=2)               # [H, nGq, nK]
    max_u = max(1, int(union.sum(axis=2).max()))
    max_u = -(-max_u // fanout) * fanout      # pad to fanout multiple
    lut = np.full((h, n_gq, max_u), n_k, np.int32)
    bits = np.zeros((h, n_gq, max_u), np.int32)
    rowshift = np.arange(group_q)
    for hi in range(h):
        for g in range(n_gq):
            cols = np.nonzero(union[hi, g])[0]
            lut[hi, g, :len(cols)] = cols
            for a, col in enumerate(cols):
                rows = grouped[hi, g, :, col]           # [group_q]
                bits[hi, g, a] = int((rows.astype(np.int64)
                                      << rowshift).sum())
    return lut, bits, n_k


def _row_bits_mask(s, bits, base_block):
    """Mask score ROWS whose fine row-block is inactive for this fine
    column block: bit r of `bits` covers rows [r·128, (r+1)·128)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // base_block
    return jnp.where(((bits >> rows) & 1) == 1, s, NEG_INF)


def _col_bits_mask(s, bits, base_block):
    """Transposed variant (dk/dv): bit c covers score COLUMNS
    [c·128, (c+1)·128)."""
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) // base_block
    return jnp.where(((bits >> cols) & 1) == 1, s, NEG_INF)


def _fine_causal(s, q_fine0, k_fine, block):
    """Causal mask for a [R·128, 128] strip: rows are fine blocks
    starting at q_fine0, columns the single fine block k_fine."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + \
        q_fine0 * block
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + \
        k_fine * block
    return jnp.where(rows >= cols, s, NEG_INF)


def _lut_at(lut_ref, h, gi, ai, *, n_g, max_u):
    return lut_ref[h * n_g * max_u + gi * max_u + ai]


def _entry_map(lut_ref, bh, gi, ai, j, *, num_heads, max_u, n_g, fanout,
               sentinel):
    """Block index for LUT entry ai*fanout + j; padded slots fetch 0."""
    ki = _lut_at(lut_ref, bh % num_heads, gi, ai * fanout + j,
                 n_g=n_g, max_u=max_u)
    return jax.lax.select(ki < sentinel, ki, 0)


def _sparse_fwd_kernel(lut_ref, bits_ref, q_ref, *rest, sm_scale, causal,
                       block, group_q, fanout, num_heads, max_u,
                       sentinel):
    kv_refs = rest[:2 * fanout]
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest[2 * fanout:]
    bh = pl.program_id(0)
    gi = pl.program_id(1)
    ai = pl.program_id(2)
    h = bh % num_heads
    n_g = pl.num_programs(1)

    @pl.when(ai == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                        # [Gq·128, D]
    strips = []
    any_active = False
    for j in range(fanout):
        ki = _lut_at(lut_ref, h, gi, ai * fanout + j, n_g=n_g,
                     max_u=max_u)
        active = ki < sentinel
        any_active = jnp.logical_or(any_active, active) \
            if j else active
        k = kv_refs[2 * j][0]                           # [128, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * \
            sm_scale                                    # [Gq·128, 128]
        bits = bits_ref[h * n_g * max_u + gi * max_u + ai * fanout + j]
        s = _row_bits_mask(s, bits, block)
        if causal:
            s = _fine_causal(s, gi * group_q, ki, block)
        # padded entries (ki == sentinel → block 0 fetched) are dead
        s = jnp.where(active, s, NEG_INF)
        strips.append(s)

    @pl.when(any_active)
    def _compute():
        s = jnp.concatenate(strips, axis=1)             # [Gq·128, F·128]
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # rows with every entry masked: exp(NEG_INF - NEG_INF) = 1 —
        # zero them so l==0 flags the dead row
        p = jnp.where(s <= NEG_INF * 0.5, 0.0, p)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        v = jnp.concatenate([kv_refs[2 * j + 1][0]
                             for j in range(fanout)], axis=0)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv

    @pl.when(ai == pl.num_programs(2) - 1)
    def _finalize():
        # Rows with NO active blocks (dragged in by the row union) have
        # l == 0: emit 0 and poison their lse to +|NEG_INF| so backward
        # p = exp(s - lse) is exactly 0.
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = jnp.where(l == 0.0, 0.0,
                             acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, -NEG_INF,
                        m_scr[:, :1] + jnp.log(l_safe))
        lse_ref[0] = lse.reshape(1, -1)


def sparse_attention_fwd(q, k, v, lut, bits, sentinel, causal, sm_scale,
                         block, group_q, fanout):
    b, s, h, d = q.shape

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, x.shape[-1])

    qb, kb, vb = to_bh(q), to_bh(k), to_bh(v)
    n_gq = s // (block * group_q)
    max_u = lut.shape[-1]
    lut_flat = jnp.asarray(lut.reshape(-1), jnp.int32)
    bits_flat = jnp.asarray(bits.reshape(-1), jnp.int32)

    kernel = functools.partial(
        _sparse_fwd_kernel, sm_scale=sm_scale, causal=causal,
        block=block, group_q=group_q, fanout=fanout, num_heads=h,
        max_u=max_u, sentinel=sentinel)

    emap = functools.partial(_entry_map, num_heads=h, max_u=max_u,
                             n_g=n_gq, fanout=fanout, sentinel=sentinel)

    in_specs = [pl.BlockSpec((1, block * group_q, d),
                             lambda bh, gi, ai, lref, bref: (bh, gi, 0))]
    inputs = [qb]
    for j in range(fanout):
        in_specs.append(pl.BlockSpec(
            (1, block, d),
            lambda bh, gi, ai, lref, bref, j=j:
            (bh, emap(lref, bh, gi, ai, j), 0)))
        inputs.append(kb)
        in_specs.append(pl.BlockSpec(
            (1, block, d),
            lambda bh, gi, ai, lref, bref, j=j:
            (bh, emap(lref, bh, gi, ai, j), 0)))
        inputs.append(vb)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * h, n_gq, max_u // fanout),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block * group_q, d),
                         lambda bh, gi, ai, lref, bref: (bh, gi, 0)),
            pl.BlockSpec((1, 1, block * group_q),
                         lambda bh, gi, ai, lref, bref: (bh, 0, gi)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block * group_q, LANES), jnp.float32),
            pltpu.VMEM((block * group_q, LANES), jnp.float32),
            pltpu.VMEM((block * group_q, d), jnp.float32),
        ],
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, s), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(), name="ds.sparse_attn_fwd",
    )
    with scopes.scope("ds.sparse_attn_fwd"):
        out, lse = call(lut_flat, bits_flat, *inputs)

    out4 = out.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    return out4, (qb, kb, vb, out, lse.reshape(b * h, s))


def _sparse_dkv_kernel(lut_ref, bits_ref, k_ref, v_ref, *rest, sm_scale,
                       causal, block, group_k, fanout, num_heads, max_u,
                       sentinel):
    """Grid over GROUPED column blocks (k/v/dk/dv tiles [Gk·128, D]);
    each instance processes `fanout` active fine ROW blocks from the
    transposed-layout LUT, fetching q/do/lse/delta per entry."""
    per = rest[:4 * fanout]
    dk_ref, dv_ref, dk_scr, dv_scr = rest[4 * fanout:]
    bh = pl.program_id(0)
    gi = pl.program_id(1)
    ai = pl.program_id(2)
    h = bh % num_heads
    n_g = pl.num_programs(1)

    @pl.when(ai == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    k = k_ref[0]                                        # [Gk·128, D]
    v = v_ref[0]
    for j in range(fanout):
        qi = _lut_at(lut_ref, h, gi, ai * fanout + j, n_g=n_g,
                     max_u=max_u)
        active = qi < sentinel
        q = per[4 * j][0]                               # [128, D]
        do = per[4 * j + 1][0]
        lse = per[4 * j + 2][0].reshape(-1, 1)          # [128, 1]
        delta = per[4 * j + 3][0].reshape(-1, 1)

        @pl.when(active)
        def _one(q=q, do=do, lse=lse, delta=delta, qi=qi, j=j):
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) \
                * sm_scale                              # [128, Gk·128]
            bits = bits_ref[h * n_g * max_u + gi * max_u
                            + ai * fanout + j]
            s = _col_bits_mask(s, bits, block)
            if causal:
                # rows: fine block qi; cols: fine blocks gi·Gk ...
                rows = jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 0) + qi * block
                cols = jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1) + gi * (s.shape[1])
                s = jnp.where(rows >= cols, s, NEG_INF)
            p = jnp.exp(s - lse)                        # [128, Gk·128]
            dv_scr[:] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * sm_scale
            dk_scr[:] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(ai == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _sparse_dq_kernel(lut_ref, bits_ref, q_ref, do_ref, lse_ref,
                      delta_ref, *rest, sm_scale, causal, block, group_q,
                      fanout, num_heads, max_u, sentinel):
    """Row-grouped like the forward kernel; k/v fetched per entry."""
    kv_refs = rest[:2 * fanout]
    dq_ref, dq_scr = rest[2 * fanout:]
    bh = pl.program_id(0)
    gi = pl.program_id(1)
    ai = pl.program_id(2)
    h = bh % num_heads
    n_g = pl.num_programs(1)

    @pl.when(ai == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q = q_ref[0]                                        # [Gq·128, D]
    do = do_ref[0]
    lse = lse_ref[0].reshape(-1, 1)
    delta = delta_ref[0].reshape(-1, 1)
    for j in range(fanout):
        ki = _lut_at(lut_ref, h, gi, ai * fanout + j, n_g=n_g,
                     max_u=max_u)
        active = ki < sentinel
        k = kv_refs[2 * j][0]                           # [128, D]
        v = kv_refs[2 * j + 1][0]

        @pl.when(active)
        def _one(k=k, v=v, ki=ki, j=j):
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) \
                * sm_scale                              # [Gq·128, 128]
            bits = bits_ref[h * n_g * max_u + gi * max_u
                            + ai * fanout + j]
            s = _row_bits_mask(s, bits, block)
            if causal:
                s = _fine_causal(s, gi * group_q, ki, block)
            p = jnp.exp(s - lse)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * sm_scale
            dq_scr[:] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(ai == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def sparse_attention_bwd(res, g, lut, bits, lut_t, bits_t, sentinel,
                         causal, sm_scale, block, group_q, fanout):
    qb, kb, vb, out, lse = res
    bh, s, d = qb.shape
    bdim = g.shape[0]
    h = bh // bdim
    do = g.transpose(0, 2, 1, 3).reshape(bh, s, d)
    lse = lse.reshape(bh, 1, s)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, s)

    n_g = s // (block * group_q)
    max_u, max_ut = lut.shape[-1], lut_t.shape[-1]
    lut_flat = jnp.asarray(lut.reshape(-1), jnp.int32)
    bits_flat = jnp.asarray(bits.reshape(-1), jnp.int32)
    lut_t_flat = jnp.asarray(lut_t.reshape(-1), jnp.int32)
    bits_t_flat = jnp.asarray(bits_t.reshape(-1), jnp.int32)

    # dk/dv: grid over grouped COLUMN blocks; transposed-layout LUT
    # lists active fine row blocks.
    remap = functools.partial(_entry_map, num_heads=h, max_u=max_ut,
                              n_g=n_g, fanout=fanout, sentinel=sentinel)
    dkv_kernel = functools.partial(
        _sparse_dkv_kernel, sm_scale=sm_scale, causal=causal,
        block=block, group_k=group_q, fanout=fanout, num_heads=h,
        max_u=max_ut, sentinel=sentinel)
    dkv_specs = [
        pl.BlockSpec((1, block * group_q, d),
                     lambda b_, gi, ai, lref, bref: (b_, gi, 0)),
        pl.BlockSpec((1, block * group_q, d),
                     lambda b_, gi, ai, lref, bref: (b_, gi, 0)),
    ]
    dkv_inputs = [kb, vb]
    for j in range(fanout):
        for arr, width in ((qb, block), (do, block)):
            dkv_specs.append(pl.BlockSpec(
                (1, width, d),
                lambda b_, gi, ai, lref, bref, j=j:
                (b_, remap(lref, b_, gi, ai, j), 0)))
            dkv_inputs.append(arr)
        for arr in (lse, delta):
            dkv_specs.append(pl.BlockSpec(
                (1, 1, block),
                lambda b_, gi, ai, lref, bref, j=j:
                (b_, 0, remap(lref, b_, gi, ai, j))))
            dkv_inputs.append(arr)
    dkv_grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, n_g, max_ut // fanout),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block * group_q, d),
                         lambda b_, gi, ai, lref, bref: (b_, gi, 0)),
            pl.BlockSpec((1, block * group_q, d),
                         lambda b_, gi, ai, lref, bref: (b_, gi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block * group_q, d), jnp.float32),
            pltpu.VMEM((block * group_q, d), jnp.float32),
        ],
    )
    dkv_call = pl.pallas_call(
        dkv_kernel, grid_spec=dkv_grid,
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), kb.dtype),
            jax.ShapeDtypeStruct((bh, s, d), vb.dtype),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(), name="ds.sparse_attn_bwd_dkv",
    )
    with scopes.scope("ds.sparse_attn_bwd_dkv"):
        dk, dv = dkv_call(lut_t_flat, bits_t_flat, *dkv_inputs)

    # dq: row-grouped; k/v per entry.
    emap = functools.partial(_entry_map, num_heads=h, max_u=max_u,
                             n_g=n_g, fanout=fanout, sentinel=sentinel)
    dq_kernel = functools.partial(
        _sparse_dq_kernel, sm_scale=sm_scale, causal=causal, block=block,
        group_q=group_q, fanout=fanout, num_heads=h, max_u=max_u,
        sentinel=sentinel)
    dq_specs = [
        pl.BlockSpec((1, block * group_q, d),
                     lambda b_, gi, ai, lref, bref: (b_, gi, 0)),
        pl.BlockSpec((1, block * group_q, d),
                     lambda b_, gi, ai, lref, bref: (b_, gi, 0)),
        pl.BlockSpec((1, 1, block * group_q),
                     lambda b_, gi, ai, lref, bref: (b_, 0, gi)),
        pl.BlockSpec((1, 1, block * group_q),
                     lambda b_, gi, ai, lref, bref: (b_, 0, gi)),
    ]
    dq_inputs = [qb, do, lse, delta]
    for j in range(fanout):
        for arr in (kb, vb):
            dq_specs.append(pl.BlockSpec(
                (1, block, d),
                lambda b_, gi, ai, lref, bref, j=j:
                (b_, emap(lref, b_, gi, ai, j), 0)))
            dq_inputs.append(arr)
    dq_grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, n_g, max_u // fanout),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec(
            (1, block * group_q, d),
            lambda b_, gi, ai, lref, bref: (b_, gi, 0)),
        scratch_shapes=[pltpu.VMEM((block * group_q, d), jnp.float32)],
    )
    dq_call = pl.pallas_call(
        dq_kernel, grid_spec=dq_grid,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), qb.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(), name="ds.sparse_attn_bwd_dq",
    )
    with scopes.scope("ds.sparse_attn_bwd_dq"):
        dq = dq_call(lut_flat, bits_flat, *dq_inputs)

    def from_bh(x):
        return x.reshape(bdim, h, s, d).transpose(0, 2, 1, 3)

    return from_bh(dq), from_bh(dk), from_bh(dv)


class BlockSparseAttention:
    """Callable bound to one (layout, block, causal) configuration.

    Precomputes forward/backward (row-union) LUTs host-side once; the
    kernels are then pure functions of (q, k, v) with a custom VJP.
    `group` adjacent layout rows share one grid instance (the coarse Q
    tile); `fanout` active fine K blocks are processed per grid step.
    Pass group=1, fanout=1 for one-block-at-a-time execution."""

    def __init__(self, layout, block=DEFAULT_BLOCK, causal=False,
                 sm_scale=None, group=DEFAULT_GROUP,
                 fanout=DEFAULT_FANOUT):
        layout = np.asarray(layout)
        self.layout = layout
        self.block = block
        self.causal = causal
        self.sm_scale = sm_scale
        n_q, n_k = layout.shape[1], layout.shape[2]
        while group > 1 and (n_q % group or n_k % group or group > 32):
            group //= 2
        self.group = max(1, group)
        self.fanout = max(1, fanout)
        self.lut, self.bits, self.sentinel = build_row_union_lut(
            layout, self.group, self.fanout)
        self.lut_t, self.bits_t, _ = build_row_union_lut(
            layout.transpose(0, 2, 1), self.group, self.fanout)

        @jax.custom_vjp
        def attend(q, k, v):
            scale = self.sm_scale or 1.0 / math.sqrt(q.shape[-1])
            out, _ = sparse_attention_fwd(
                q, k, v, self.lut, self.bits, self.sentinel, self.causal,
                scale, self.block, self.group, self.fanout)
            return out

        def fwd(q, k, v):
            scale = self.sm_scale or 1.0 / math.sqrt(q.shape[-1])
            return sparse_attention_fwd(
                q, k, v, self.lut, self.bits, self.sentinel, self.causal,
                scale, self.block, self.group, self.fanout)

        def bwd(res, g):
            scale = self.sm_scale or 1.0 / math.sqrt(res[0].shape[-1])
            return sparse_attention_bwd(
                res, g, self.lut, self.bits, self.lut_t, self.bits_t,
                self.sentinel, self.causal, scale, self.block, self.group,
                self.fanout)

        attend.defvjp(fwd, bwd)
        self._attend = attend

    def __call__(self, q, k, v):
        """q/k/v: [B, S, H, D] with H == layout heads, S == layout
        seq (= nQ * block)."""
        b, s, h, d = q.shape
        if h != self.layout.shape[0]:
            raise ValueError(
                f"got {h} heads, layout has {self.layout.shape[0]}")
        if s != self.layout.shape[1] * self.block:
            raise ValueError(
                f"seq {s} != layout blocks {self.layout.shape[1]} × block "
                f"{self.block}")
        return self._attend(q, k, v)
