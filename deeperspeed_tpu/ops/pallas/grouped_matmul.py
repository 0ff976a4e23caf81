"""Grouped (ragged) expert matmul as a Pallas TPU kernel.

The sort-based MoE dispatch engine (`moe/layer.py`, `dispatch="sort"`)
permutes routed tokens into per-expert contiguous spans and needs
``y[r] = x[r] @ w[expert_of(r)]`` over that buffer. The GShard einsum
formulation spends MXU flops multiplying the [T, E, C] one-hot dispatch
tensor — at top-2/cf=1.25 most of them against zeros; this kernel runs
ONLY the real expert matmuls, one `pallas_call` for all experts.

Contract (shared by kernel and XLA fallback):

- ``x`` [R, K]: rows grouped into G contiguous spans of ``span`` rows
  each (R = G·span). Spans are the caller's capacity bound rounded up to
  the row-block size.
- ``w`` [W, K, N]: stacked weights. Span s multiplies ``w[lut[s]]`` —
  ``lut`` is a STATIC non-decreasing map (spans of one weight must be
  contiguous; identity when G == W). Expert parallelism uses it to point
  the ep·g spans received from every source rank at this rank's local
  expert weights.
- ``group_sizes`` [G] int32 (traced): valid rows per span — the RAGGED
  part (actual routed counts, including empty experts). Rows at or past
  the size produce exact-zero output (masked tail tiles), contribute
  nothing to ``dw``, and receive zero ``dx``.

Mechanics: the grid is (N/bn, R/bm) with the row dimension innermost, so
consecutive instances stream one weight's row tiles while its [K, bn]
tile stays VMEM-resident. A scalar-prefetched LUT
(`pltpu.PrefetchScalarGridSpec`) resolves row tile → weight row in the
BlockSpec index map; prefetched group sizes drive the in-kernel tail
masks, and tiles entirely past their span's size skip the MXU work
(`pl.when`). Backward is a `custom_vjp`: dx reuses the forward kernel
against w^T; dw accumulates x^T·dy tiles into a revisited fp32 output
block (zeroed at each weight's first visit — the flash dkv pattern).

On non-TPU backends the kernel runs in interpreter mode (slow,
test-only); `grouped_matmul` defaults to the XLA fallback there, a
batched segment einsum with the same masking semantics.
"""

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import scopes
from ...compat import CompilerParams
from .flash_attention import _interpret, note_xla_on_tpu

DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 256

_DIMSEM = CompilerParams(dimension_semantics=("parallel", "arbitrary"))


# Backend ("pallas"/"xla") of the most recent grouped_matmul dispatch —
# the same record `decode_attention._LAST_BACKEND` keeps.
_LAST_BACKEND = {}


class GmmSpec(NamedTuple):
    """Static launch geometry (hashable — rides custom_vjp
    nondiff_argnums)."""
    span: int       # rows per group span (multiple of block_m)
    lut: tuple      # span index -> weight row (non-decreasing)
    block_m: int
    block_n: int
    interpret: bool


def _fit_rows(block, span):
    """Largest row-block ≤ `block` dividing `span` (8-aligned when
    possible — the fp32 sublane tile)."""
    if span <= block:
        return span
    for cand in range(block - block % 8, 7, -8):
        if span % cand == 0:
            return cand
    return span if span <= 2 * block else 8


def _fit_cols(block, n):
    """Largest 128-multiple ≤ `block` dividing n; n itself when no
    128-aligned divisor exists (interpret-mode shapes)."""
    for cand in range(min(block, n), 127, -128):
        if cand % 128 == 0 and n % cand == 0:
            return cand
    return n


def pick_span(capacity, block_m=None):
    """(span, block_m) for a grouped-matmul buffer: span = capacity
    rounded up to the row-block, preferring fat blocks but never padding
    a span by more than ~12.5% (padding is wasted HBM in the dense MoE
    path and wasted ICI in the expert-parallel exchange). Small
    capacities degrade to a single 8-aligned tile per span. Shared by
    the MoE layer and the autotuner so the measured geometry is exactly
    the deployed one."""
    cap = max(1, int(capacity))
    target = int(block_m) if block_m else DEFAULT_BLOCK_M
    for cand in (target, target // 2, target // 4):
        if cand >= 8:
            span = -(-cap // cand) * cand
            if span - cap <= max(cap // 8, 7):
                return span, cand
    span = -(-cap // 8) * 8
    return span, span


def grouped_matmul_supported(k, n, span):
    """Mosaic constraints for the real-TPU kernel: 128-aligned
    contraction/output minor dims, 8-aligned spans. Interpret mode
    (CPU tests) has no tiling rules."""
    if _interpret():
        return True
    return k % 128 == 0 and n % 128 == 0 and span % 8 == 0


# ---------------------------------------------------------------------------
# forward kernel (also computes dx against w^T in backward)
# ---------------------------------------------------------------------------

def _fwd_kernel(lut_ref, sizes_ref, x_ref, w_ref, o_ref, *, tpg, block_m,
                block_n):
    i = pl.program_id(1)
    g = i // tpg
    row0 = (i % tpg) * block_m
    size = sizes_ref[g]

    @pl.when(row0 < size)
    def _run():
        acc = jax.lax.dot_general(
            x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        rows = jax.lax.broadcasted_iota(
            jnp.int32, (block_m, block_n), 0) + row0
        o_ref[...] = jnp.where(rows < size, acc, 0.0).astype(o_ref.dtype)

    @pl.when(row0 >= size)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)


def _gmm_pallas(x, w, sizes, spec):
    R, K = x.shape
    _, _, N = w.shape
    tpg = spec.span // spec.block_m
    grid = (N // spec.block_n, R // spec.block_m)
    kernel = functools.partial(_fwd_kernel, tpg=tpg,
                               block_m=spec.block_m, block_n=spec.block_n)
    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((R, N), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((spec.block_m, K),
                             lambda j, i, lut, sz: (i, 0)),
                pl.BlockSpec((1, K, spec.block_n),
                             lambda j, i, lut, sz: (lut[i // tpg], 0, j)),
            ],
            out_specs=pl.BlockSpec((spec.block_m, spec.block_n),
                                   lambda j, i, lut, sz: (i, j)),
        ),
        compiler_params=_DIMSEM,
        interpret=spec.interpret, name="ds.grouped_matmul",
    )
    with scopes.scope("ds.grouped_matmul"):
        return call(jnp.asarray(spec.lut, jnp.int32), sizes, x, w)


# ---------------------------------------------------------------------------
# dw kernel: accumulate x^T @ dy per weight over its spans' row tiles
# ---------------------------------------------------------------------------

def _dw_kernel(lut_ref, sizes_ref, x_ref, dy_ref, dw_ref, *, tpg, block_m,
               block_n):
    i = pl.program_id(1)
    g = i // tpg
    row0 = (i % tpg) * block_m
    size = sizes_ref[g]
    wsel = lut_ref[g]
    prev = lut_ref[jnp.maximum(g - 1, 0)]
    # first row tile of this weight in the current j sweep: row tiles run
    # innermost, so the output block is revisited for every tile of the
    # weight and must be zeroed exactly once per sweep
    first = jnp.logical_or(i == 0,
                           jnp.logical_and(jnp.logical_and(row0 == 0,
                                                           i % tpg == 0),
                                           wsel != prev))

    @pl.when(first)
    def _zero():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(row0 < size)
    def _acc():
        rows = jax.lax.broadcasted_iota(
            jnp.int32, (block_m, block_n), 0) + row0
        dyb = jnp.where(rows < size, dy_ref[...], 0).astype(dy_ref.dtype)
        dw_ref[...] += jax.lax.dot_general(
            x_ref[...], dyb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[None]


def _dw_pallas(x, dy, sizes, spec, n_weights):
    R, K = x.shape
    _, N = dy.shape
    tpg = spec.span // spec.block_m
    grid = (N // spec.block_n, R // spec.block_m)
    kernel = functools.partial(_dw_kernel, tpg=tpg,
                               block_m=spec.block_m, block_n=spec.block_n)
    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_weights, K, N), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((spec.block_m, K),
                             lambda j, i, lut, sz: (i, 0)),
                pl.BlockSpec((spec.block_m, spec.block_n),
                             lambda j, i, lut, sz: (i, j)),
            ],
            out_specs=pl.BlockSpec((1, K, spec.block_n),
                                   lambda j, i, lut, sz:
                                   (lut[i // tpg], 0, j)),
        ),
        compiler_params=_DIMSEM,
        interpret=spec.interpret, name="ds.grouped_matmul_dw",
    )
    with scopes.scope("ds.grouped_matmul_dw"):
        return call(jnp.asarray(spec.lut, jnp.int32), sizes, x, dy)


# ---------------------------------------------------------------------------
# custom_vjp assembly
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(x, w, sizes, spec):
    return _gmm_pallas(x, w, sizes, spec)


def _gmm_vjp_fwd(x, w, sizes, spec):
    return _gmm_pallas(x, w, sizes, spec), (x, w, sizes)


def _gmm_vjp_bwd(spec, res, dy):
    x, w, sizes = res
    # dx = dy @ w^T: the forward kernel against transposed weights; its
    # row mask also zeroes dx for tail rows
    dx_spec = spec._replace(block_n=_fit_cols(spec.block_n, w.shape[1]))
    dx = _gmm_pallas(dy, jnp.swapaxes(w, 1, 2), sizes, dx_spec)
    dw = _dw_pallas(x, dy, sizes, spec, w.shape[0]).astype(w.dtype)
    return dx, dw, np.zeros(sizes.shape, jax.dtypes.float0)


_gmm.defvjp(_gmm_vjp_fwd, _gmm_vjp_bwd)


# ---------------------------------------------------------------------------
# XLA fallback: batched segment einsum with identical masking semantics
# ---------------------------------------------------------------------------

def grouped_matmul_xla(x, w, group_sizes, span, lut=None):
    """Pure-XLA reference/fallback. Spans sharing a weight (a uniform
    repeat LUT — the expert-parallel layout) collapse into one batched
    einsum over the weight dim; arbitrary LUTs gather per-span weights.
    Differentiable natively (the segment masks make dw/dx match the
    kernel's tail-row semantics)."""
    R, K = x.shape
    n_w, _, N = w.shape
    G = R // span
    lut_arr = (np.arange(n_w, dtype=np.int32) if lut is None
               else np.asarray(lut, np.int32))
    valid = (jnp.arange(span)[None, :]
             < group_sizes[:, None])[..., None]          # [G, span, 1]
    reps = G // n_w
    if n_w * reps == G and np.array_equal(
            lut_arr, np.repeat(np.arange(n_w), reps)):
        x4 = x.reshape(n_w, reps * span, K)
        y = jnp.einsum("gsk,gkn->gsn", x4, w,
                       preferred_element_type=jnp.float32)
        y = y.reshape(G, span, N)
    else:
        x3 = x.reshape(G, span, K)
        y = jnp.einsum("gsk,gkn->gsn", x3, w[lut_arr],
                       preferred_element_type=jnp.float32)
    return jnp.where(valid, y, 0.0).astype(x.dtype).reshape(R, N)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def grouped_matmul(x, w, group_sizes, span, lut=None, block_m=None,
                   block_n=None, backend=None):
    """y[r] = x[r] @ w[lut[r // span]] with ragged tail masking.

    backend: None = auto (Pallas kernel on TPU when the shape satisfies
    `grouped_matmul_supported`, XLA fallback otherwise — CPU test runs
    keep XLA speed unless a test opts into the interpreter);
    "pallas" forces the kernel (interpret-mode off-TPU); "xla" forces
    the fallback.
    """
    R, K = x.shape
    n_w, kw, N = w.shape
    if kw != K:
        raise ValueError(f"w contraction dim {kw} != x feature dim {K}")
    if span < 1 or R % span:
        raise ValueError(f"span={span} must divide the {R} buffer rows")
    G = R // span
    lut_t = tuple(range(n_w)) if lut is None else tuple(int(v) for v in lut)
    if len(lut_t) != G:
        raise ValueError(f"lut has {len(lut_t)} entries for {G} spans")
    if any(b > a for a, b in zip(lut_t[1:], lut_t)) or \
            set(lut_t) != set(range(n_w)):
        # every weight must be covered: the dw kernel only writes the
        # output blocks of visited weights — a gap LUT would return
        # uninitialized memory as the skipped weight's gradient
        raise ValueError("lut must be non-decreasing and cover every "
                         "weight row 0..n_w-1 (spans of one weight "
                         "contiguous, no gaps)")
    if group_sizes.shape != (G,):
        raise ValueError(f"group_sizes shape {group_sizes.shape} != ({G},)")

    if backend is None:
        from ...parallel.mesh import ambient_auto_mesh
        on_tpu = not _interpret()
        # GSPMD cannot partition a Mosaic kernel, and the sorted-row
        # buffer has no dim to split per shard: under a multi-device
        # mesh the kernel is for `shard_map` callers (moe.MoELayer)
        partitioned = ambient_auto_mesh() is not None
        backend = ("pallas" if on_tpu and not partitioned and
                   grouped_matmul_supported(K, N, span) else "xla")
        if backend == "xla":
            note_xla_on_tpu(
                "grouped_matmul",
                f"K={K}, N={N}, span={span}, under a GSPMD-partitioned "
                f"mesh {partitioned}: the kernel needs 128-aligned K and "
                f"N, an 8-aligned span, and one device or a shard_map")
    _LAST_BACKEND["grouped_matmul"] = backend
    if backend == "xla":
        return grouped_matmul_xla(x, w, group_sizes, span, lut_t)
    if backend != "pallas":
        raise ValueError(f"unknown grouped_matmul backend {backend!r}")

    spec = GmmSpec(
        span=span, lut=lut_t,
        block_m=_fit_rows(block_m or DEFAULT_BLOCK_M, span),
        block_n=_fit_cols(block_n or DEFAULT_BLOCK_N, N),
        interpret=_interpret())
    return _gmm(x, w, group_sizes.astype(jnp.int32), spec)
