"""Grouped (ragged) expert matmul as a Pallas TPU kernel.

The sort-based MoE dispatch (`moe/layer.py`) permutes routed tokens into
per-expert contiguous groups of one buffer and needs
``y[r] = x[r] @ w[expert_of(r)]`` over it: ONE `pallas_call` for all
experts, running only the real expert matmuls.

One kernel, two layouts of the buffer, both said by the same two traced
maps over the buffer's row tiles (``block_m`` rows each), which ride the
`pltpu.PrefetchScalarGridSpec` slots:

    tile_expert [M]   the weight row tile m multiplies
    tile_rows   [M]   the tile's valid rows, 0 for a tile nobody owns

- **ragged** (`ragged_matmul`, the dropless router): groups of any
  length, each starting at a tile boundary, in a buffer of about
  ``rows + E * (block_m - 1)`` rows. `ragged_tile_maps` builds the maps
  from the per-expert counts. An expert with no row owns no tile.
- **fixed span** (`grouped_matmul`, the GShard capacity router and the
  expert-parallel exchange): G spans of ``span`` rows, span s
  multiplying ``w[lut[s]]`` (a static non-decreasing map: the spans of
  one weight are contiguous) with ``group_sizes[s]`` valid rows.
  `span_tile_maps` says it as a case of the ragged layout.

Rows at or past a tile's valid rows produce exact-zero output,
contribute nothing to ``dw`` and receive zero ``dx``.

Mechanics: the grid is (N/bn, M) with the row tiles innermost, so
consecutive instances stream one weight's row tiles while its [K, bn]
slab stays VMEM-resident, and each non-empty expert's weights are read
once a call. Tiles without rows skip the MXU work (`pl.when`) and point
at the last live tile's weight, so they fetch nothing. Backward is a
`custom_vjp`: dx is the forward kernel contracting against w's other
dimension (no transposed copy of the weights); dw accumulates x^T.dy
tiles into a revisited fp32 output block, zeroed at each weight's first
tile. A weight no tile visits is never written: its gradient is
selected to zero afterwards, from the maps.

On non-TPU backends the kernel runs in interpreter mode (slow,
test-only); both entries default to an XLA fallback there with the same
masking semantics.
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
# the ragged layout's row tile: at least bf16's (16, 128) native tile,
# at most one pass of the MXU's 128 rows
RAGGED_MIN_BLOCK_M = 16
RAGGED_MAX_BLOCK_M = 128
RAGGED_BLOCK_N = 512

_DIMSEM = CompilerParams(dimension_semantics=("parallel", "arbitrary"))


# Backend ("pallas"/"xla") of the most recent grouped_matmul dispatch —
# the same record `decode_attention._LAST_BACKEND` keeps.
_LAST_BACKEND = {}

# `ops.dispatch_report()["moe"]["plan"]`: the traces of the dropless MoE
# layer in this process by the form of the ragged layout's plan
# (`moe.layer.dropless_plan` bumps it)
_PLANS_TRACED = {}


class LayerOf(NamedTuple):
    """Layer `layer` (a traced int32 scalar) of expert weights stacked
    [L, E, K, N], for a layer loop: the kernel's index maps take the
    layer, so the loop never slices 0.8 GB of experts out of the stack
    (a copy a layer a step, 34% of the OLMoE serve cell's device time
    when the scan did it). Forward only: a serving program's operand."""
    stacked: jax.Array
    layer: jax.Array

    @property
    def shape(self):
        return self.stacked.shape[1:]

    def astype(self, dtype):
        return LayerOf(self.stacked.astype(dtype), self.layer)


class GmmSpec(NamedTuple):
    """Static launch geometry (hashable — rides custom_vjp
    nondiff_argnums)."""
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
    """(span, block_m) for a fixed-span buffer: span = capacity
    rounded up to the row-block, preferring fat blocks but never padding
    a span by more than ~12.5% (padding is wasted HBM in the dense MoE
    path and wasted ICI in the expert-parallel exchange). Small
    capacities degrade to a single 8-aligned tile per span."""
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
    contraction/output minor dims, 8-aligned row tiles (`span`: the
    fixed layout's span, or the ragged layout's `block_m`). Interpret
    mode (CPU tests) has no tiling rules."""
    if _interpret():
        return True
    return k % 128 == 0 and n % 128 == 0 and span % 8 == 0


# ---------------------------------------------------------------------------
# the two layouts, as tile maps
# ---------------------------------------------------------------------------

def span_tile_maps(group_sizes, span, lut, block_m):
    """The fixed-span layout's (tile_expert, tile_rows): tile m lies in
    span m // (span / block_m)."""
    tpg = span // block_m
    n_tiles = len(lut) * tpg
    tile = np.arange(n_tiles)
    tile_expert = jnp.asarray(np.asarray(lut, np.int32)[tile // tpg])
    row0 = jnp.asarray((tile % tpg) * block_m, jnp.int32)
    tile_rows = jnp.clip(jnp.repeat(group_sizes.astype(jnp.int32), tpg)
                         - row0, 0, block_m)
    return tile_expert, tile_rows


def ragged_block_m(rows, n_experts):
    """Row tile of the ragged layout for `rows` rows over `n_experts`
    groups: the power of two at or above the mean group, within
    [RAGGED_MIN_BLOCK_M, RAGGED_MAX_BLOCK_M]. Padding is at most one
    tile a group, so the tile follows the group's size."""
    mean = max(1, -(-int(rows) // max(int(n_experts), 1)))
    bm = 1 << (mean - 1).bit_length()
    return int(min(RAGGED_MAX_BLOCK_M, max(RAGGED_MIN_BLOCK_M, bm)))


def ragged_buffer_rows(rows, n_experts, block_m):
    """Rows of the ragged buffer that holds `rows` rows in `n_experts`
    groups whatever their lengths: each group is padded to a whole tile,
    at most `block_m - 1` rows each."""
    need = int(rows) + int(n_experts) * (block_m - 1)
    return -(-need // block_m) * block_m


def ragged_tile_maps(counts, block_m, n_tiles):
    """The ragged layout from the per-expert row `counts` [E]: group e
    starts at row `starts[e]` (a tile boundary) and owns
    ceil(counts[e] / block_m) tiles. Returns (tile_expert, tile_rows,
    starts). Tiles past the last group have no rows and point at the
    last live tile's expert, so they move no weight.

    Every table lookup here is a compare-and-sum over [n_tiles, E] or
    [E, E]: on a TPU an operation that takes a dynamic index an element
    (a search's steps, a gather of `n_tiles` ids) costs more than the
    whole table compared at once (PERF.md section 6, PR 45)."""
    counts = counts.astype(jnp.int32)
    e = jnp.arange(counts.shape[0], dtype=jnp.int32)
    tiles = (counts + block_m - 1) // block_m             # [E]
    ends = jnp.sum(jnp.where(e[None, :] <= e[:, None], tiles[None, :], 0),
                   axis=1)                                # running sum
    first = ends - tiles                                  # first tile of e
    m = jnp.arange(n_tiles, dtype=jnp.int32)
    live = m < ends[-1]
    last_live = jnp.maximum(ends[-1] - 1, 0)
    # the group whose tiles hold m: the groups that end at or before it
    owner = jnp.sum(jnp.where(live, m, last_live)[:, None] >= ends[None, :],
                    axis=1, dtype=jnp.int32)
    owner = jnp.minimum(owner, counts.shape[0] - 1)
    rows = jnp.sum(jnp.where(owner[:, None] == e[None, :],
                             counts[None, :] -
                             (m[:, None] - first[None, :]) * block_m, 0),
                   axis=1)
    return owner, jnp.where(live, jnp.clip(rows, 0, block_m), 0), \
        first * block_m


# ---------------------------------------------------------------------------
# forward kernel (also computes dx, contracting w's other dimension)
# ---------------------------------------------------------------------------

def _fwd_kernel(te_ref, tr_ref, layer_ref, x_ref, w_ref, o_ref, *, block_m,
                block_n, trans_w):
    del te_ref, layer_ref                       # read by the index maps
    rows_valid = tr_ref[pl.program_id(1)]

    @pl.when(rows_valid > 0)
    def _run():
        # trans_w: w_ref is a [bn, K] slab of w [E, N, K]; contract K
        acc = jax.lax.dot_general(
            x_ref[...], w_ref[0, 0],
            (((1,), (1 if trans_w else 0,)), ((), ())),
            preferred_element_type=jnp.float32)
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_m, block_n), 0)
        o_ref[...] = jnp.where(rows < rows_valid, acc,
                               0.0).astype(o_ref.dtype)

    @pl.when(rows_valid <= 0)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)


def _gmm_pallas(x, w, tile_expert, tile_rows, spec, trans_w=False):
    """x [R, K] times w [E, K, N] (or, `trans_w`, w [E, N, K]); `w` may
    be a `LayerOf` stacked weights, indexed by the kernel."""
    if isinstance(w, LayerOf):
        stacked, layer = w.stacked, w.layer.astype(jnp.int32).reshape(1)
    else:
        stacked, layer = w[None], jnp.zeros((1,), jnp.int32)
    R, K = x.shape
    N = stacked.shape[2] if trans_w else stacked.shape[3]
    bm, bn = spec.block_m, spec.block_n
    kernel = functools.partial(_fwd_kernel, block_m=bm, block_n=bn,
                               trans_w=trans_w)
    if trans_w:
        w_spec = pl.BlockSpec((1, 1, bn, K),
                              lambda j, i, te, tr, ly: (ly[0], te[i], j, 0))
    else:
        w_spec = pl.BlockSpec((1, 1, K, bn),
                              lambda j, i, te, tr, ly: (ly[0], te[i], 0, j))
    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((R, N), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N // bn, R // bm),
            in_specs=[
                pl.BlockSpec((bm, K), lambda j, i, te, tr, ly: (i, 0)),
                w_spec,
            ],
            out_specs=pl.BlockSpec((bm, bn),
                                   lambda j, i, te, tr, ly: (i, j)),
        ),
        compiler_params=_DIMSEM,
        interpret=spec.interpret, name="ds.grouped_matmul",
    )
    with scopes.scope("ds.grouped_matmul"):
        return call(tile_expert, tile_rows, layer, x, stacked)


# ---------------------------------------------------------------------------
# dw kernel: accumulate x^T @ dy per weight over its row tiles
# ---------------------------------------------------------------------------

def _dw_kernel(te_ref, tr_ref, x_ref, dy_ref, dw_ref, *, block_m, block_n):
    i = pl.program_id(1)
    rows_valid = tr_ref[i]
    # first row tile of this weight in the current j sweep: row tiles run
    # innermost, so the output block is revisited for every tile of the
    # weight and must be zeroed exactly once per sweep
    first = jnp.logical_or(
        i == 0, te_ref[i] != te_ref[jnp.maximum(i - 1, 0)])

    @pl.when(first)
    def _zero():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(rows_valid > 0)
    def _acc():
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_m, block_n), 0)
        dyb = jnp.where(rows < rows_valid, dy_ref[...],
                        0).astype(dy_ref.dtype)
        dw_ref[...] += jax.lax.dot_general(
            x_ref[...], dyb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[None]


def _dw_pallas(x, dy, tile_expert, tile_rows, spec, n_weights):
    R, K = x.shape
    _, N = dy.shape
    bm, bn = spec.block_m, spec.block_n
    kernel = functools.partial(_dw_kernel, block_m=bm, block_n=bn)
    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_weights, K, N), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N // bn, R // bm),
            in_specs=[
                pl.BlockSpec((bm, K), lambda j, i, te, tr: (i, 0)),
                pl.BlockSpec((bm, bn), lambda j, i, te, tr: (i, j)),
            ],
            out_specs=pl.BlockSpec((1, K, bn),
                                   lambda j, i, te, tr: (te[i], 0, j)),
        ),
        compiler_params=_DIMSEM,
        interpret=spec.interpret, name="ds.grouped_matmul_dw",
    )
    with scopes.scope("ds.grouped_matmul_dw"):
        dw = call(tile_expert, tile_rows, x, dy)
    # a weight no tile visited was never written (an expert without a
    # token): select it to zero
    visited = jnp.zeros((n_weights,), jnp.bool_).at[tile_expert].max(
        tile_rows >= 0)
    return jnp.where(visited[:, None, None], dw, 0.0)


# ---------------------------------------------------------------------------
# custom_vjp assembly
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gmm(x, w, tile_expert, tile_rows, spec):
    return _gmm_pallas(x, w, tile_expert, tile_rows, spec)


def _gmm_vjp_fwd(x, w, tile_expert, tile_rows, spec):
    return (_gmm_pallas(x, w, tile_expert, tile_rows, spec),
            (x, w, tile_expert, tile_rows))


def _gmm_vjp_bwd(spec, res, dy):
    x, w, tile_expert, tile_rows = res
    # dx = dy @ w^T: the forward kernel over w's [N, K] slabs; its row
    # mask also zeroes dx for tail rows
    dx_spec = spec._replace(block_n=_fit_cols(spec.block_n, w.shape[1]))
    dx = _gmm_pallas(dy, w, tile_expert, tile_rows, dx_spec, trans_w=True)
    dw = _dw_pallas(x, dy, tile_expert, tile_rows, spec,
                    w.shape[0]).astype(w.dtype)
    no_grad = np.zeros(tile_expert.shape, jax.dtypes.float0)
    return dx, dw, no_grad, no_grad


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


def ragged_matmul_xla(x, w, tile_expert, tile_rows, block_m):
    """Pure-XLA fallback of the ragged layout: the row tiles one after
    another, each against its expert's weights (a dynamic index, so
    nothing of size [tiles, K, N] is gathered) and no more flops than the
    kernel's. Sequential: for CPU tests and for a program GSPMD
    partitions, not for speed. Differentiable natively."""
    if isinstance(w, LayerOf):
        w = w.stacked[w.layer]
    R, K = x.shape
    rows = jnp.arange(block_m, dtype=jnp.int32)[:, None]

    def one(tile):
        xt, e, valid = tile
        y = jnp.dot(xt, w[e].astype(x.dtype),
                    preferred_element_type=jnp.float32)
        return jnp.where(rows < valid, y, 0.0).astype(x.dtype)

    y = jax.lax.map(one, (x.reshape(R // block_m, block_m, K),
                          tile_expert, tile_rows))
    return y.reshape(R, w.shape[2])


# ---------------------------------------------------------------------------
# public entries
# ---------------------------------------------------------------------------

def _pick_backend(backend, K, N, block_m, what):
    """None = auto: the Pallas kernel on a TPU when the shape satisfies
    `grouped_matmul_supported`, the XLA fallback otherwise (CPU test runs
    keep XLA speed unless a test opts into the interpreter). "pallas"
    forces the kernel (interpret mode off a TPU), "xla" the fallback."""
    if backend is None:
        from ...parallel.mesh import ambient_auto_mesh
        on_tpu = not _interpret()
        # GSPMD cannot partition a Mosaic kernel, and the sorted-row
        # buffer has no dim to split per shard: under a multi-device
        # mesh the kernel is for `shard_map` callers (moe.MoELayer)
        partitioned = ambient_auto_mesh() is not None
        backend = ("pallas" if on_tpu and not partitioned and
                   grouped_matmul_supported(K, N, block_m) else "xla")
        if backend == "xla":
            note_xla_on_tpu(
                "grouped_matmul",
                f"{what}: K={K}, N={N}, row tile {block_m}, under a "
                f"GSPMD-partitioned mesh {partitioned}: the kernel needs "
                f"128-aligned K and N, an 8-aligned row tile, and one "
                f"device or a shard_map")
    if backend not in ("pallas", "xla"):
        raise ValueError(f"unknown grouped_matmul backend {backend!r}")
    _LAST_BACKEND["grouped_matmul"] = backend
    return backend


def _check_operands(x, w):
    if w.shape[1] != x.shape[1]:
        raise ValueError(f"w contraction dim {w.shape[1]} != x feature "
                         f"dim {x.shape[1]}")


def grouped_matmul(x, w, group_sizes, span, lut=None, block_m=None,
                   block_n=None, backend=None):
    """Fixed-span layout: y[r] = x[r] @ w[lut[r // span]], rows at or
    past their span's `group_sizes` entry zero. `backend`: see
    `_pick_backend`."""
    _check_operands(x, w)
    R, K = x.shape
    n_w, _, N = w.shape
    if span < 1 or R % span:
        raise ValueError(f"span={span} must divide the {R} buffer rows")
    G = R // span
    lut_t = tuple(range(n_w)) if lut is None else tuple(int(v) for v in lut)
    if len(lut_t) != G:
        raise ValueError(f"lut has {len(lut_t)} entries for {G} spans")
    if any(b > a for a, b in zip(lut_t[1:], lut_t)) or \
            not set(lut_t) <= set(range(n_w)):
        # the dw kernel zeroes a weight's block at the first tile of a
        # run of that weight: a weight that comes back after another
        # would lose what it had accumulated. A weight the lut leaves
        # out is fine: its gradient is zero.
        raise ValueError("lut must be non-decreasing over weight rows "
                         "0..n_w-1 (the spans of one weight contiguous)")
    if group_sizes.shape != (G,):
        raise ValueError(f"group_sizes shape {group_sizes.shape} != ({G},)")

    backend = _pick_backend(backend, K, N, span, "fixed-span layout")
    if backend == "xla":
        return grouped_matmul_xla(x, w, group_sizes, span, lut_t)
    spec = GmmSpec(block_m=_fit_rows(block_m or DEFAULT_BLOCK_M, span),
                   block_n=_fit_cols(block_n or DEFAULT_BLOCK_N, N),
                   interpret=_interpret())
    tile_expert, tile_rows = span_tile_maps(group_sizes, span, lut_t,
                                            spec.block_m)
    return _gmm(x, w, tile_expert, tile_rows, spec)


def ragged_matmul(x, w, tile_expert, tile_rows, block_m, block_n=None,
                  backend=None):
    """Ragged layout: y[r] = x[r] @ w[tile_expert[r // block_m]] for the
    first `tile_rows[r // block_m]` rows of each tile, zero for the
    rest; the maps as `ragged_tile_maps` builds them (the tiles of one
    expert contiguous). `w` [E, K, N], or a `LayerOf` weights stacked
    [L, E, K, N] (forward only). `backend`: see `_pick_backend`."""
    _check_operands(x, w)
    R, K = x.shape
    N = w.shape[2]
    if block_m < 1 or R % block_m:
        raise ValueError(f"block_m={block_m} must divide the {R} buffer "
                         f"rows")
    n_tiles = R // block_m
    if tile_expert.shape != (n_tiles,) or tile_rows.shape != (n_tiles,):
        raise ValueError(
            f"tile_expert {tile_expert.shape} and tile_rows "
            f"{tile_rows.shape} must both be ({n_tiles},): one entry a "
            f"row tile")
    tile_expert = tile_expert.astype(jnp.int32)
    tile_rows = tile_rows.astype(jnp.int32)
    backend = _pick_backend(backend, K, N, block_m, "ragged layout")
    if backend == "xla":
        return ragged_matmul_xla(x, w, tile_expert, tile_rows, block_m)
    spec = GmmSpec(block_m=block_m,
                   block_n=_fit_cols(block_n or RAGGED_BLOCK_N, N),
                   interpret=_interpret())
    if isinstance(w, LayerOf):
        return _gmm_pallas(x, w, tile_expert, tile_rows, spec)
    return _gmm(x, w, tile_expert, tile_rows, spec)
