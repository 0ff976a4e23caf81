"""Flash attention as Pallas TPU kernels.

TPU-native replacement for the reference's fused softmax/attention CUDA
kernels (`csrc/transformer/softmax_kernels.cu`,
`ds_transformer_cuda.cpp` attention path): online-softmax tiling keeps the
[S, S] score matrix out of HBM entirely — O(S) memory instead of O(S²) —
which is both the perf win (HBM bandwidth is the bottleneck) and the
long-sequence enabler.

Layout: [B, S, H, D] in, [B, S, H, D] out. Mosaic's last-two-dims tiling
rule rules out indexing that 4-D layout with per-head singleton blocks,
so a kernel sees the heads one of two ways. The training call
(`flash_attention`, forward and fused backward, where `heads_in_place`
admits the shape) takes each tensor TRANSPOSED, [B, H*D, S], a head its
block of D rows: that is where XLA keeps a `[B, S, H, D]` tensor of a
train step on a TPU (sequence minor), so the transpose in front of the
kernel is a bitcast and nothing is copied. Every other call (segments, a
window, grouped KV heads, a layout, a key bias, dropout; a single block)
runs on [B*H, S, D], a COPY of each operand and result. Forward saves the
per-row logsumexp as a compact [BH, S] row-vector (not a lane-broadcast
[.., 128] tile — 128x less residual HBM traffic); backward recomputes
probabilities blockwise (no SxS residual). The training call can take the
rotate-half rotary of q and k into its kernels (`rotates_in_kernel`): it
is then handed the projections as projected, and no pass over q, k, dq or
dk stands between the projections' matmuls and the kernels.

Block sizes default to 1024x1024, auto-fitted down to the largest
128-multiple dividing the sequence length (`ops/autotune.py`): a fat
block pushes each weight tile through the MXU for more rows, and the
tile body walks it in strips (below), so a grid step's cost is its
matmuls' and not its size's. Matmuls run at the input dtype (bf16 → full
MXU rate) with fp32 accumulation; softmax math is fp32.

Causal grids are COMPACTED (splash-attention style): instead of an
n_q x n_k grid whose upper-triangle instances are gated off in-kernel
(each still launched, still prefetching its K/V or Q/dO tiles over HBM,
still paying the ~6us fixed cost), the (qi, ki) schedule is flattened
host-side into one `arbitrary` grid dimension that enumerates ONLY the
causally-alive tiles — ~n(n+1)/2 instances for n = n_q = n_k instead of
n². Scalar-prefetch index maps (`pltpu.PrefetchScalarGridSpec` LUTs,
the splash-attention mechanism) route each flat instance to its (qi, ki)
blocks, so dead tiles generate no HBM traffic at all. See
`causal_grid_maps` for the schedule and `docs/long-context.md` for the
design.

Off a TPU the kernels run in interpreter mode (slow, test-only).
"""

import functools
import math
import time

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import scopes
from ...compat import CompilerParams
from ..autotune import FLASH_BLOCK_K as BLOCK_K, FLASH_BLOCK_Q as BLOCK_Q, \
    fit_block as _fit_block, flash_blocks, flash_bwd_vmem_limit, \
    flash_dq_slab_admitted, flash_k_slab_admitted

LANES = 128  # TPU minor-dim tile
NEG_INF = -1e30

_DIMSEM = ("parallel", "parallel", "arbitrary")
# compacted causal grids: (batch·head, flat trapezoid) — the flat dim
# carries the per-row/-column sequential accumulation, so `arbitrary`
_DIMSEM_FLAT = ("parallel", "arbitrary")


def _interpret():
    """True off a TPU: the kernels then run in the Pallas interpreter,
    which exists for the CPU tests and for nothing else."""
    return jax.default_backend() != "tpu"


# Kernel-or-XLA choice of the most recent attention dispatch
# ({"attention": "pallas" | "xla"}); `decode_attention`, `grouped_matmul`
# and `quant_matmul` keep the same record for their own dispatchers and
# `ops.dispatch_report()` reads them all.
_LAST_BACKEND = {}
_XLA_NOTED = set()
# Attention projections traced in this process by the form their reshape
# to heads took (`models/gpt_neox.py::_heads_dot`: "plain" | "folded"), and
# the fused QKV projections that ran as three dots against the weight's
# q, k and v columns (`_block_qkv`: "split").
_HEAD_PROJECTIONS = {"plain": 0, "folded": 0, "split": 0}
# Tiled flash calls traced in this process by where they found the heads:
# "in_place" read q, k, v (and dO) and wrote out (dq, dk, dv) where the
# program holds them (`heads_in_place`); "moved" went through a
# [B, S, H, D] -> [B*H, S, D] copy of each. `ops.dispatch_report()
# ["flash"]["heads"]` reads it.
_HEADS = {"fwd": {"in_place": 0, "moved": 0},
          "bwd": {"in_place": 0, "moved": 0}}
# Tiled forwards on heads in place traced in this process by how often
# they turn a k^T block into the k their score matmuls read: "once_a_head"
# keeps the head's turned k in VMEM (`autotune.flash_k_slab_admitted`),
# "every_step" turns the step's block again. `ops.dispatch_report()
# ["flash"]["k_turns"]` reads it.
_K_TURNS = {"once_a_head": 0, "every_step": 0}
# Rotate-half rotaries of a q, k pair traced in this process by where they
# run: "in_kernel" the tiled training forwards handed the UN-rotated
# projections and the tables (`rotates_in_kernel`: the forward and the
# fused backward rotate the blocks they load and un-rotate dq and dk where
# they store them), "xla" the passes over [B, S, H, D] in front of an
# attention (`models/gpt_neox.py::apply_rotary`: a serving prefill, a
# decode step, every call the rule does not admit).
# `ops.dispatch_report()["flash"]["rotary"]` reads it.
_ROTARY = {"in_kernel": 0, "xla": 0}


def heads_in_place(h, g, d):
    """Can the tiled training kernels take the heads where the model's
    program holds them, with no copy of a tensor? As many KV heads as
    query heads, and a head dim of whole packed sublane tiles (16 rows of
    bfloat16). A fact of the shape and of nothing else.

    WHERE that is, is XLA's choice, not the model's source: a
    `[B, S, H, D]` tensor of head dim 64 is laid out with the SEQUENCE
    minor (`{1,3,2,0}`: physically [B, H, D, S]; D minor would leave
    half of every lane tile empty), the projections' fusions write it so
    and the weight-gradient dots read its gradient so. The kernels
    therefore take q^T, k^T, v^T (and dO^T) and give out^T (dq^T, dk^T,
    dv^T) as [B, H*D, S]: the
    `transpose(0, 2, 3, 1)` in front of them is a bitcast of that
    layout, a head is the block of D ROWS at row block `head`, and the
    tile bodies, which hold their tiles transposed already, lose their
    own transposes (`_fwd_kernel`, `_bwd_dkv_kernel`: `by_rows`). What
    is left is k, which the forward's score matmuls want turned: once a
    k block and head where the head's k fits VMEM
    (`autotune.flash_k_slab_admitted`), as the fused backward turns k and
    v once a column."""
    return g == h and d % 16 == 0


def tiled_in_place(shape, g, causal=True):
    """Will `flash_attention` on q `shape` [B, S, H, D] and `g` KV heads,
    at the blocks `ops.autotune.flash_blocks` gives it, run the TILED
    kernels on the heads in place (`heads_in_place`)? Not a shape the
    kernels do not take, not a call of one block (`_fwd`'s single-block
    kernel moves its heads)."""
    _, s, h, d = shape
    if not flash_attention_supported(shape):
        return False
    (block_q, block_k), _ = _resolve_blocks(shape, causal, None, None, None)
    return not _one_block(s, block_q, block_k) and heads_in_place(h, g, d)


def _one_block(s, block_q, block_k):
    """Is a sequence of `s` one block of the forward, at fitted blocks?"""
    return s // block_q == 1 and s // block_k == 1


def _rotates(s, h, g, d, itemsize, causal, rot_dim, fwd_blocks, bwd_blocks):
    """Can a `flash_attention` call at these (fitted) blocks rotate q and
    k inside its kernels? Both kernels on the heads in place: a tiled
    forward, the ONE tiled backward (its dq slab admitted, not a call of
    one block); `rot_dim` features that are whole packed sublane tiles of
    the transposed blocks (16 rows of bfloat16; the halves are then whole
    float32 tiles, and swapping them moves no data); and a forward that
    turns a k block once, in the query row that covers the block's own
    positions (causal, the head's k kept, `block_q` a multiple of
    `block_k`), as the backward's column walk starts a k column at the
    query row that covers it: that row's columns of the table are then
    the ONE more operand a kernel takes, for its q block and for the k
    blocks alike (an operand costs a grid step some 45 cycles of the
    pipeline's bookkeeping whether or not its block changes: PERF.md
    section 6, PR 63)."""
    return heads_in_place(h, g, d) and flash_dq_slab_admitted(s, d) and \
        flash_k_slab_admitted(s, d, itemsize, causal) and \
        not _one_block(s, *fwd_blocks) and not _one_block(s, *bwd_blocks) \
        and fwd_blocks[0] % fwd_blocks[1] == 0 \
        and bwd_blocks[0] % bwd_blocks[1] == 0 \
        and 0 < rot_dim <= d and rot_dim % 16 == 0


def rotates_in_kernel(shape, g, rot_dim, dtype=jnp.bfloat16, causal=True):
    """Will `flash_attention(..., rotary=(cos, sin, rot_dim))` on q
    `shape` [B, S, H, D] of `dtype` and `g` KV heads, at the blocks
    `ops.autotune.flash_blocks` gives it, take the rotate-half rotary of
    q and k INTO its kernels (`_rotates`)? The caller then hands the
    projections un-rotated and runs no rotary of its own; where this says
    no it keeps its XLA passes and calls without `rotary`. A fact of the
    shape and of nothing else; what the caller alone knows (per-row
    positions, a caller that needs the rotated k) is the caller's to add
    (`models/gpt_neox.py::_rotary_in_kernel`)."""
    _, s, h, d = shape
    if not flash_attention_supported(shape):
        return False
    fwd, bwd = _resolve_blocks(shape, causal, None, None, None)
    return _rotates(s, h, g, d, jnp.dtype(dtype).itemsize, causal, rot_dim,
                    fwd, bwd)


def note_xla_on_tpu(op, why):
    """A dispatcher on a TPU took XLA where a Pallas kernel exists: say
    so once per op, by name. Off a TPU XLA is the expected stand-in and
    nothing is logged."""
    if _interpret() or op in _XLA_NOTED:
        return
    _XLA_NOTED.add(op)
    from ...utils.logging import logger
    logger.warning(f"ops.dispatch {op}: running on XLA, not the Pallas "
                   f"kernel, on a TPU ({why})")


# ---------------------------------------------------------------------------
# compacted causal grid (trapezoidal schedule)
# ---------------------------------------------------------------------------

def causal_grid_maps(n_q, n_k, block_q, block_k, order="row", window=None):
    """The compacted causal (qi, ki) schedule: every tile with
    ki*block_k <= qi*block_q + block_q - 1, i.e. exactly the causally
    alive blocks. Returns (qmap, kmap) int32 numpy arrays consumed as
    scalar-prefetch LUTs by the kernels' BlockSpec index maps.

    order="row" (fwd / dq): qi-major, ki ascending — each q row's
    running softmax/accumulator scratch spans one contiguous run whose
    output block stays VMEM-resident until the row finishes.
    order="col" (dkv): ki-major, qi ascending — ditto for each k
    column's dk/dv accumulators.

    For n = n_q = n_k (equal blocks) the schedule has n(n+1)/2 entries
    instead of the dense grid's n² — the compile-time-verifiable
    invariant (`_LAST_GRIDS` records what each call launched). With a
    `window` (row order only) a row starts at the first tile that holds a
    key within `window` positions of the row's first query: the band."""
    import numpy as np
    qs, ks = [], []
    if order == "row":
        for qi in range(n_q):
            kmax = min(n_k - 1, (qi * block_q + block_q - 1) // block_k)
            for ki in range(_first_k(qi, block_q, block_k, window),
                            kmax + 1):
                qs.append(qi)
                ks.append(ki)
    elif order == "col":
        for ki in range(n_k):
            for qi in range((ki * block_k) // block_q, n_q):
                qs.append(qi)
                ks.append(ki)
    else:
        raise ValueError(f"unknown order {order!r}")
    return np.asarray(qs, np.int32), np.asarray(ks, np.int32)


def _first_k(qi, block_q, block_k, window):
    """The first key tile a query tile `qi` sees: tile 0, or under a
    `window` the tile of the key `window - 1` positions before the
    tile's first query (`qi` a python int or a traced scalar)."""
    if window is None:
        return 0
    first = qi * block_q - (window - 1)
    if isinstance(first, int):
        return max(first, 0) // block_k
    return jnp.maximum(first, 0) // block_k


def causal_grid_size(s, block_q=BLOCK_Q, block_k=BLOCK_K):
    """Instances a causal flash call launches per (batch·head) at seq s
    (after block auto-fitting) — the trapezoid, not the square."""
    bq, bk = _fit_block(block_q, s), _fit_block(block_k, s)
    if not bq or not bk:
        raise ValueError(f"no block fits seq {s}")
    if s // bq == 1 and s // bk == 1:
        return 1                       # single-block specialization
    return len(causal_grid_maps(s // bq, s // bk, bq, bk)[0])


# Test/debug observability: grid of the most recent tiled pallas_call per
# kernel family ("fwd"; of the backward "bwd", the one fused kernel, or
# "dkv" and "dq", the two a sequence over the slab's budget takes). The
# compaction invariant tests assert on this instead of re-deriving
# lowering internals.
_LAST_GRIDS = {}

# Ditto, (masked, launched) tiles per (batch x head) of the most recent
# tiled call per kernel family: how many take the masked body
# (`masked_tile_count`).
_LAST_MASKED = {}

# The set-up account: [times built, host seconds] of each tiled kernel's
# BODY in this process. A body is python that unrolls pairs x strips x
# diagonal bodies into one jaxpr, which Pallas then lowers to Mosaic MLIR:
# host time on every run, compile cache hit or not (the cache's key is
# computed from the lowered module). `_fwd_call` / `_bwd_calls` keep one
# `pallas_call` a call signature for the life of the process, so a model
# of 24 unrolled layers builds each body once and not 24 times (PERF.md,
# PR 34). `ops.dispatch_report()["flash"]["bodies_built"]` reads it.
_BODY_BUILDS = {"fwd": [0, 0.0], "bwd": [0, 0.0], "dkv": [0, 0.0],
                "dq": [0, 0.0]}


def _accounted(kind, kernel):
    """`kernel`, every trace of it entered in `_BODY_BUILDS[kind]`."""
    def body(*refs):
        t0 = time.perf_counter()
        try:
            kernel(*refs)
        finally:
            built = _BODY_BUILDS[kind]
            built[0] += 1
            built[1] += time.perf_counter() - t0
    return body

# Ditto for dispatched block geometry: {"fwd"/"dkv"/"dq": (bq, bk)} plus
# {"fwd_variant"/"bwd_variant": "single"/"trapezoid"/"dense"} of the most
# recent call, the backward's "fused-trapezoid"/"fused-dense" where it ran
# as one tiled kernel (`ops.dispatch_report()`).
_LAST_BLOCKS = {}
_DISPATCH_LOGGED = False


def _log_first_dispatch():
    """One structured log line at the FIRST flash dispatch of the
    process: which block geometry / grid variant is live. Later
    dispatches update `_LAST_BLOCKS` silently — `ops.dispatch_report()`
    is the query interface; this line exists so every training log
    records the kernel configuration without anyone asking."""
    global _DISPATCH_LOGGED
    if _DISPATCH_LOGGED:
        return
    _DISPATCH_LOGGED = True
    import json

    from ...utils.logging import logger
    logger.info("ops.dispatch flash_attention first dispatch: "
                + json.dumps(_LAST_BLOCKS, default=str))


def _index_adapter(compact, kv_major=False):
    """BlockSpec index maps are written once, as functions of
    (bh, qi, ki); this returns the wrapper that adapts them to the grid
    in use. The flat index t of a compacted grid resolves through the
    prefetched LUTs, whichever order the schedule has; a dense grid
    hands (bh, qi, ki) itself, or (bh, ki, qi) where it is ``kv_major``
    (the dkv kernel's)."""
    if compact:
        return lambda f: lambda bh, t, qm, km: f(bh, qm[t], km[t])
    if kv_major:
        return lambda f: lambda bh, ki, qi: f(bh, qi, ki)
    return lambda f: f


def _tiled_call(kind, name, kernel, compact, grid, in_specs, out_specs,
                scratch, out_shape, maps, interpret, vmem_limit=None):
    """One pallas_call for both grid flavors: compacted trapezoid
    (scalar-prefetch LUT grid spec) or dense, named `name` (a kernel
    scope of `scopes.SCOPES`) and run under that scope; its body's traces
    are entered under `kind` in the set-up account. `vmem_limit`: bytes,
    where the compiler's default does not hold the kernel. Returns the
    function of the kernel's inputs: kept by the caller's cache, every
    call of it at one signature and in one trace context binds the one
    traced body (`pallas_call` is a `jit(inline=True)` of its own)."""
    if compact:
        call_kw = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch))
    else:
        call_kw = dict(grid=grid, in_specs=in_specs, out_specs=out_specs,
                       scratch_shapes=scratch)
    call = pl.pallas_call(
        _accounted(kind, kernel), out_shape=out_shape, name=name,
        compiler_params=CompilerParams(
            dimension_semantics=_DIMSEM_FLAT if compact else _DIMSEM,
            vmem_limit_bytes=vmem_limit),
        interpret=interpret, **call_kw)

    def run(*inputs):
        with scopes.scope(name):
            # the LUTs as the numpy arrays they are: constants of
            # whichever trace calls, never an array kept from another
            return call(*maps, *inputs)
    return run


def flash_attention_supported(shape, block_q=BLOCK_Q, block_k=BLOCK_K):
    """Kernel constraints: seq divisible by some 128-multiple block ≤ the
    requested size, MXU-friendly head dim. Callers take the XLA path
    otherwise and record it (`_LAST_BACKEND`, `note_xla_on_tpu`)."""
    b, s, h, d = shape
    return _fit_block(block_q, s) > 0 and _fit_block(block_k, s) > 0 and \
        d in (64, 128, 256)


def _causal_mask(s):
    """Key j of a whole-sequence tile is visible to query i iff j <= i."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(rows >= cols, s, NEG_INF)


MASK_GRAIN = 128  # layout-mask granularity (one sparsity block)


def _dropout_keep(seed, pid, row0, col0, shape, rate, q_axis=0):
    """Deterministic keep-mask for in-kernel attention-probability
    dropout: a 2-round avalanche hash of (seed, batch*head, absolute
    row, absolute col). The same call sites in the backward kernels
    regenerate the exact forward mask — the Pallas analogue of the
    reference's curand Philox-offset scheme
    (`csrc/transformer/dropout_kernels.cu`). Pure int32 jnp ops
    (wrapping mul/xor/shift): lowers on Mosaic AND in interpret mode
    (pltpu.prng_* has no CPU lowering). Comparison uses the low 31 bits
    so int32 arithmetic stays sign-safe."""
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis) + row0
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis) + col0
    x = rows * (-1640531527) ^ cols * (-2048144789)   # 0x9E3779B9/0x85EBCA6B
    x = x ^ (seed + pid * (-1028477387))              # 0xC2B2AE35
    x = (x ^ ((x >> 16) & 0xFFFF)) * 0x7FEB352D
    x = (x ^ ((x >> 15) & 0x1FFFF)) * (-2073452917)   # 0x846CA68B
    x = x ^ ((x >> 16) & 0xFFFF)
    thresh = jnp.int32(int(min(max(rate, 0.0), 1.0) * 2147483647))
    return (x & 0x7FFFFFFF) >= thresh


def _apply_dropout(p, seed, pid, row0, col0, rate):
    """Scale-at-train dropout on (unnormalized) probabilities: the
    softmax denominator is computed from the UNdropped p, so this equals
    torch's dropout(softmax(s)) — dropped entries are zeroed, survivors
    scaled by 1/keep, no renormalization."""
    keep = _dropout_keep(seed, pid, row0, col0, p.shape, rate)
    return jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)


# ---------------------------------------------------------------------------
# forward — single-block specialization
# ---------------------------------------------------------------------------

CAUSAL_STRIPS = 8  # column strips for dead-sub-block exp skipping


def _head_fwd(q, k, v, bias_row, seed, pid, *, sm_scale, causal,
              use_bias, dropout_rate):
    """One head's whole-sequence attention: straight (non-online)
    softmax — no running max/denominator scratch, no alpha rescale, no
    accumulator round-trips. For causal tiles the columns are processed
    in strips so exp/sum only touch rows at or below each strip (the
    upper ~(1 - (n+1)/2n) of the triangle never reaches the VPU —
    37.5% of the softmax work at 4 strips).

    With ``use_bias`` an additive per-key row [1, S] is fused into the
    scores pre-max — the TPU equivalent of the reference's mask-taking
    fused softmax (`csrc/transformer/softmax_kernels.cu` attn_softmax
    taking attn_mask): key-padding masks never materialize [S, S].

    q/k/v [S, D]; bias_row [1, S] or None; pid keys the dropout hash
    (must match the backward's regeneration). Returns
    (o/l [S, D] fp32, lse [S, 1] fp32)."""
    s_q, s_k = q.shape[0], k.shape[0]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale        # [Sq, Sk]
    if use_bias:
        s = s + bias_row                                      # [1, Sk] bcast
    # NOTE: per-strip matmuls (skipping dead sub-blocks' MXU work) were
    # measured SLOWER than one dense matmul — ragged [S-lo, w] shapes
    # cost the MXU more than the skipped flops save. Strips only gate
    # the VPU softmax work.

    if causal and s_q == s_k and s_k % CAUSAL_STRIPS == 0:
        w = s_k // CAUSAL_STRIPS
        # per-strip masked scores + [S, 1] row maxima over ALIVE rows
        # only (1-D vectors don't lower on Mosaic; keep stats 2-D)
        strips, m_parts = [], []
        for c in range(CAUSAL_STRIPS):
            lo = c * w
            sc = s[lo:, c * w:(c + 1) * w]                   # alive rows
            rows = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0) + lo
            cols = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1) + lo
            sc = jnp.where(rows >= cols, sc, NEG_INF)
            strips.append(sc)
            mc = jnp.max(sc, axis=1, keepdims=True)           # [Sq-lo, 1]
            if lo:
                mc = jnp.concatenate(
                    [jnp.full((lo, 1), NEG_INF, jnp.float32), mc], axis=0)
            m_parts.append(mc)
        m = m_parts[0]
        for mc in m_parts[1:]:
            m = jnp.maximum(m, mc)                            # [Sq, 1]

        l = jnp.zeros((s_q, 1), jnp.float32)
        p_strips = []
        for c in range(CAUSAL_STRIPS):
            lo = c * w
            pc = jnp.exp(strips[c] - m[lo:])
            if use_bias:
                # a fully-masked row has m == NEG_INF and exp(s - m) == 1
                # uniformly; zero masked entries so l == 0 flags the dead
                # row (poisoned-lse convention)
                pc = jnp.where(strips[c] <= NEG_INF * 0.5, 0.0, pc)
            lc = jnp.sum(pc, axis=1, keepdims=True)
            if lo:
                lc = jnp.concatenate(
                    [jnp.zeros((lo, 1), jnp.float32), lc], axis=0)
                pc = jnp.concatenate(
                    [jnp.zeros((lo, w), jnp.float32), pc], axis=0)
            l = l + lc
            p_strips.append(pc)
        p = jnp.concatenate(p_strips, axis=1)                 # [Sq, Sk]
    else:
        if causal:
            s = _causal_mask(s)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        if use_bias:
            p = jnp.where(s <= NEG_INF * 0.5, 0.0, p)
        l = jnp.sum(p, axis=1, keepdims=True)
    if dropout_rate > 0.0:
        # post-l: the denominator sums the undropped probabilities
        # (torch dropout(softmax(s)) semantics). Coordinates are the
        # full-tile globals — the strips branch concatenates back to
        # full [Sq, Sk] layout first, so fwd/bwd coords agree.
        p = _apply_dropout(p, seed, pid, 0, 0, dropout_rate)
    o = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    l_safe = jnp.where(l == 0.0, 1.0, l)
    lse = jnp.where(l == 0.0, -NEG_INF, m + jnp.log(l_safe))
    return o / l_safe, lse


def _fwd_single_kernel(*refs, sm_scale, causal, use_bias=False,
                       dropout_rate=0.0):
    """Grid (B·H,): one head per instance (see `_head_fwd`)."""
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    b_ref = next(it) if use_bias else None
    seed_ref = next(it) if dropout_rate > 0.0 else None
    o_ref, lse_ref = next(it), next(it)
    o, lse = _head_fwd(
        q_ref[0], k_ref[0], v_ref[0],
        b_ref[0] if use_bias else None,
        seed_ref[0] if dropout_rate > 0.0 else None,
        pl.program_id(0), sm_scale=sm_scale, causal=causal,
        use_bias=use_bias, dropout_rate=dropout_rate)
    o_ref[0] = o.astype(o_ref.dtype)
    lse_ref[0] = lse.reshape(1, -1)


def _fwd_single_mh_kernel(*refs, sm_scale, causal, use_bias, dropout_rate,
                          hb, h_total):
    """Grid (B, H/hb): a BLOCK of hb heads per instance. At short
    sequences the per-head tiles are tiny and the per-instance fixed
    cost dominates a (B·H,) launch; batching heads amortizes it while
    every tile stays VMEM-resident (the reference's fused short-seq
    kernel — its flagship seq-128 BERT benchmark — has the same
    batching, `csrc/transformer/softmax_kernels.cu` launches over
    batch×heads in one kernel). Dropout hash pid = global b·H + head —
    identical formula in `_bwd_single_mh_kernel`."""
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    b_ref = next(it) if use_bias else None
    seed_ref = next(it) if dropout_rate > 0.0 else None
    o_ref, lse_ref = next(it), next(it)
    for j in range(hb):
        pid = pl.program_id(0) * h_total + pl.program_id(1) * hb + j
        o, lse = _head_fwd(
            q_ref[0, j], k_ref[0, j], v_ref[0, j],
            b_ref[0] if use_bias else None,
            seed_ref[0] if dropout_rate > 0.0 else None,
            pid, sm_scale=sm_scale, causal=causal,
            use_bias=use_bias, dropout_rate=dropout_rate)
        o_ref[0, j] = o.astype(o_ref.dtype)
        lse_ref[0, j] = lse.reshape(1, -1)


MH_MAX_SEQ = 256           # above this, per-head tiles amortize launches
                           # (S=512 hb=2 measured SLOWER than hb=1)
_MH_VMEM_BUDGET = 6 << 20  # conservative per-instance VMEM bound


def _mh_heads(s, d, h):
    """Heads per grid instance for the heads-batched single-block
    kernels: the largest divisor of `h` whose fwd+bwd working set
    (q/k/v/do tiles + two [S, S] fp32 score tensors + grad tiles) fits
    the VMEM budget. 1 = use the plain per-(b·h) kernels."""
    if s > MH_MAX_SEQ or h <= 1:
        return 1
    per_head = 4 * s * d * 2 + 3 * s * s * 4 + 3 * s * d * 4
    hb = max(1, min(h, _MH_VMEM_BUDGET // per_head))
    while h % hb:
        hb -= 1
    return hb


def _fwd_single(qb, kb, vb, causal, sm_scale, s, d, interpret, kbias=None,
                h=None, dropout_rate=0.0, seed=None):
    bh = qb.shape[0]
    use_bias = kbias is not None
    hb = _mh_heads(s, d, h or 1)
    if hb > 1:
        b = bh // h
        kernel = functools.partial(
            _fwd_single_mh_kernel, sm_scale=sm_scale, causal=causal,
            use_bias=use_bias, dropout_rate=dropout_rate, hb=hb,
            h_total=h)
        in_specs = [pl.BlockSpec((1, hb, s, d),
                                 lambda b, hg: (b, hg, 0, 0))] * 3
        inputs = [t.reshape(b, h, s, d) for t in (qb, kb, vb)]
        if use_bias:
            in_specs.append(pl.BlockSpec((1, 1, s),
                                         lambda b, hg: (b, 0, 0)))
            inputs.append(kbias)
        if dropout_rate > 0.0:
            in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
            inputs.append(seed)
        out, lse = pl.pallas_call(
            kernel,
            grid=(b, h // hb),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, hb, s, d), lambda b, hg: (b, hg, 0, 0)),
                pl.BlockSpec((1, hb, 1, s), lambda b, hg: (b, hg, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, h, s, d), qb.dtype),
                jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
            ],
            compiler_params=CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret, name="ds.flash_fwd",
        )(*inputs)
        return out.reshape(bh, s, d), lse.reshape(bh, 1, s)
    kernel = functools.partial(_fwd_single_kernel, sm_scale=sm_scale,
                               causal=causal, use_bias=use_bias,
                               dropout_rate=dropout_rate)
    in_specs = [pl.BlockSpec((1, s, d), lambda bh: (bh, 0, 0))] * 3
    inputs = [qb, kb, vb]
    if kbias is not None:
        # kbias is [B, 1, S]; the grid runs over B*H — index by batch
        in_specs.append(pl.BlockSpec((1, 1, s),
                                     lambda i, h=h: (i // h, 0, 0)))
        inputs.append(kbias)
    if dropout_rate > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        inputs.append(seed)
    return pl.pallas_call(
        kernel,
        grid=(bh,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, s, d), lambda bh: (bh, 0, 0)),
            pl.BlockSpec((1, 1, s), lambda bh: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), qb.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret, name="ds.flash_fwd",
    )(*inputs)


# ---------------------------------------------------------------------------
# the tile body of the tiled kernels
# ---------------------------------------------------------------------------
#
# The forward and the dkv kernel hold a grid step's [block_k, block_q]
# tile of scores TRANSPOSED: keys on sublanes, queries on lanes (s^T =
# k q^T). A query's running maximum, denominator, lse and delta are then
# lane-dense rows ([1, block_q]: the layout lse and delta already have in
# HBM), a reduction over keys is elementwise across vregs with one 8-to-1
# sublane step at its end, and the forward's output accumulates as
# [D, block_q]. The dq kernel keeps queries on sublanes: its dS is the
# left operand of dS K.
#
# The forward never holds its tile whole. It walks it in PAIRS of CHUNK
# key rows x GROUP query columns, whose scores are one matmul (every
# MXU gets a 128-column weight tile of q and streams the chunk's keys),
# each pair in STRIPS of 128 query columns, and each strip in BLOCKS of
# 128 keys: a block's chain (max, exp2, sum, cast, second matmul, the
# rescale of the running state) works on [BLOCK, STRIP] fp32, 16 of the
# 64 vector registers, and the strip's state ([1, 128] max and sum,
# [D, 128] output) stays in registers from the strip's first block to
# its last. A block of 512 keys was the WHOLE register file, walked
# twice (the max, then exp2 against it), under four strips' worth of the
# next pair's scores: every score went MXU -> register -> VMEM ->
# register and a tile's body ran 2,384 cycles where its matmuls hold an
# MXU 1,600 (PERF.md, PR 51). The scores of the next AHEAD pairs are
# issued before a pair's strips, so the MXU and the VPU overlap: the way
# from a pair's last pop to its first weight push (pop, max, exp2, cast,
# push: some 350 cycles) is longer than one pair's matmuls. Pairs run
# keys outermost, so the pairs in flight share no query column and
# neither's softmax waits for the other's running max.
#
# The walk is one basic block the scheduler may reorder as it likes: the
# pairs and strips no mask touches are `lax.fori_loop`s with
# `unroll=True`, which the Mosaic lowering unrolls with the index a
# constant, so the body is TRACED once a loop (set-up: the jaxpr holds a
# pair's strip once, not thirty-two) and COMPILED straight. What picks a
# value by the index (`switch`) is folded there too. In interpret mode
# the loops stay rolled (the same operations in the same order; XLA's
# CPU compiler would otherwise compile every block of every test).
#
# A tile an edge crosses (the causal diagonal, a window's far edge, a
# document boundary; a layout mask, a key bias or dropout always) takes
# the MASKED body; every other tile takes the same body with no iota,
# compare or select in it. A tile on the causal diagonal takes a masked
# body of its own, compiled for the one offset its first query has from
# its first key (square blocks have one such offset, 2:1 blocks two):
# which of its blocks lie wholly beyond the diagonal is then a trace-time
# fact, and they are not computed; nor is the causal compare made in the
# blocks wholly before it.
#
# `sm_scale` never touches a tile: scores stay raw, the exponent is
# exp2((s - m) * sm_scale * log2(e)) (the multiply `exp` would spend on
# log2(e) anyway), the bias row is divided by the scale once a tile, and
# dS's scale waits for the accumulators' last step.

STRIP = 128   # query columns a strip: one lane tile
BLOCK = STRIP  # key rows a block: [BLOCK, STRIP] fp32 is 16 vector registers
CHUNK = 256   # key rows a pair: what one push of q's weight tiles streams
GROUP = 512   # query columns a pair: one weight tile of q an MXU
AHEAD = 2     # pairs whose scores are on the MXU before a pair's softmax
# Columns a step of the backward kernels' diagonal bodies takes: narrower
# skips more of the dead triangle, wider pushes each weight tile for more
# rows. dkv + dq at [16, 2048, 16, 64] on a v5e: 8.00 ms at 512, 7.35 at
# 256, 8.44 at 128 (PERF.md, PR 33).
DIAGONAL_GROUP = 256
LOG2E = 1.4426950408889634

_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_TN = (((0,), (0,)), ((), ()))   # a^T @ b
_NN = (((1,), (0,)), ((), ()))   # a @ b


def _part(block, most):
    """The largest 128-multiple under `most` that divides `block`."""
    return next(n for n in range(most, 0, -128) if block % n == 0)


def _rectangles(pairs):
    """`pairs` ((key row, query column), keys outermost) as lists that
    are each a rectangle in the same order: all of them where they are
    one, else a list a query column (the whole pairs of a tile on the
    causal diagonal are a staircase)."""
    rows, cols = ({p[i] for p in pairs} for i in (0, 1))
    if len(pairs) == len(rows) * len(cols):
        return [pairs] if pairs else []
    return [[p for p in pairs if p[1] == g0] for g0 in sorted(cols)]


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _rotated(x, table, inverse=False):
    """The rotate-half rotary of a head's TRANSPOSED block: rows
    0 .. rot of x [D, n] (a feature a row) against `table` [2 x rot, n]
    float32 (cos over sin, the columns of the block's positions), in
    float32, the other rows as they are; rounded once, to x's dtype.
    y = x cos + R(x) sin with R [x1, x2] = [-x2, x1] over the halves of
    the rot rows; `inverse` its transpose, dx = dy cos + R^T(dy sin) with
    R^T [z1, z2] = [z2, -z1]: what takes the gradient of a rotated block
    back to the block's. A half is rot / 2 rows, whole float32 sublane
    tiles (`_rotates`), so the halves' swap is a choice of registers."""
    rot = table.shape[0] // 2
    half = rot // 2
    cos, sin = table[:rot], table[rot:]
    x1 = x[:half].astype(jnp.float32)
    x2 = x[half:rot].astype(jnp.float32)
    c1, c2, s1, s2 = cos[:half], cos[half:], sin[:half], sin[half:]
    if inverse:
        y1, y2 = x1 * c1 + x2 * s2, x2 * c2 - x1 * s1
    else:
        y1, y2 = x1 * c1 - x2 * s1, x2 * c2 + x1 * s2
    y = jnp.concatenate([y1, y2], axis=0).astype(x.dtype)
    return y if rot == x.shape[0] else \
        jnp.concatenate([y, x[rot:]], axis=0)


def _when(cond, fn):
    """`pl.when` that also takes a python bool (a trace-time fact)."""
    if cond is True:
        fn()
    elif cond is not False:
        pl.when(cond)(fn)


# and / or / not of facts that are python bools (known at trace time) or
# traced scalars (known a grid step)

def _and(a, b):
    if isinstance(a, bool):
        return b if a else False
    if isinstance(b, bool):
        return a if b else False
    return jnp.logical_and(a, b)


def _not(a):
    return (not a) if isinstance(a, bool) else jnp.logical_not(a)


def _or(a, b):
    return _not(_and(_not(a), _not(b)))


def _segment_facts(sq_ref, sk_ref):
    """(run, uniform) of a tile's query and key id slices: can the two
    share an id (their ranges overlap; exact wherever a document's ids
    are contiguous, as `runtime.packing` and a prefill's pad rows make
    them, and a superset otherwise: a tile that shares none adds zeros),
    and are all of them one id (no boundary inside the tile)."""
    sq, sk = sq_ref[0], sk_ref[0]
    q_lo, q_hi, k_lo, k_hi = jnp.min(sq), jnp.max(sq), jnp.min(sk), \
        jnp.max(sk)
    run = jnp.logical_and(q_lo <= k_hi, k_lo <= q_hi)
    uniform = jnp.logical_and(jnp.logical_and(q_lo == q_hi, k_lo == k_hi),
                              q_lo == k_lo)
    return run, uniform


def _edge_crosses(qi, ki, block_q, block_k, causal, window):
    """Does the causal diagonal, or a window's far edge, pass through
    tile (qi, ki)? True where some key of the tile lies after some query
    of it, or `window` or more positions before one. `qi` / `ki` python
    ints (the trace-time count) or traced scalars (the kernel)."""
    if not causal:
        return False
    crossed = ki * block_k + block_k - 1 > qi * block_q
    if window is not None:
        crossed = _or(crossed,
                      qi * block_q + block_q - 1 - ki * block_k >= window)
    return crossed


class _Tile:
    """What the tiled kernels share of one grid step: which body the
    tile takes, and a block's masks in the transposed orientation (the
    segmented forward takes the first alone)."""

    def __init__(self, qi, ki, block_q, block_k, *, sm_scale, causal,
                 window=None, sq_ref=None, sk_ref=None, m_ref=None,
                 b_ref=None, seed_ref=None, dropout_rate=0.0,
                 kseg_scr=None, kbias_scr=None):
        self.qi, self.ki = qi, ki
        self.block_q, self.block_k = block_q, block_k
        self.sm_scale, self.causal, self.window = sm_scale, causal, window
        self.sq_ref, self.sk_ref, self.m_ref, self.b_ref = \
            sq_ref, sk_ref, m_ref, b_ref
        self.seed_ref, self.dropout_rate = seed_ref, dropout_rate
        # read here, at the kernel's top level: the interpreter has no
        # program_id inside a loop body
        self.pid = pl.program_id(0) if dropout_rate > 0.0 else None
        self.kseg_scr, self.kbias_scr = kseg_scr, kbias_scr
        # the tile's first query and key, and how far the key lies past
        # the query: once a tile, not once a block
        self.q_base, self.k_base = qi * block_q, ki * block_k
        self.lead = self.k_base - self.q_base
        # rows whose every key is masked must end with l == 0 (the
        # poisoned lse): where that can happen, masked entries' exp is
        # forced to 0 (exp2((NEG_INF - NEG_INF) * c) is 1, not 0)
        self.zero_masked = sq_ref is not None or m_ref is not None or \
            b_ref is not None or window is not None
        self.run = True
        # an edge of the geometry crosses the tile / a mask the data or
        # the call brings applies to it
        self.crossed = _edge_crosses(qi, ki, block_q, block_k, causal,
                                     window)
        self.other = False
        if sq_ref is not None:
            self.run, uniform = _segment_facts(sq_ref, sk_ref)
            self.other = _not(uniform)
        if m_ref is not None or b_ref is not None or dropout_rate > 0.0:
            self.other = True
        # a block of a diagonal tile that the diagonal misses is masked
        # only where the call brings such a mask at all
        self.any_other = self.other is not False

    def bodies(self, body, diagonal=True):
        """Run `body(masked, offset, crossed)` as the tile takes it (not
        at all where no id is shared). A tile the causal diagonal crosses
        runs `body(True, offset, True)` under the offset of its first
        query from its first key: a trace-time int, one body for each
        offset the geometry has (`diagonal_offsets`), from which the body
        knows which of its blocks lie beyond the diagonal. Every other
        tile, and every tile of a windowed call, of a geometry with too
        many offsets or of a kernel that takes no `diagonal` bodies, runs
        `body(masked, None, crossed)`: `crossed` False where the diagonal
        is known to miss it."""
        def masked(offset, crossed):
            def masked_body():
                self.stage_key_columns()
                body(True, offset, crossed)
            return masked_body

        offsets = diagonal_offsets(self.block_q, self.block_k) \
            if diagonal and self.causal and self.window is None else ()
        for d in offsets:
            _when(_and(self.run, self.lead == -d), masked(d, True))
        # what is left: the tiles the diagonal misses, or every tile
        rest = _and(self.run, _not(self.crossed)) if offsets else self.run
        any_mask = self.other if offsets else _or(self.crossed, self.other)
        _when(_and(rest, any_mask), masked(None, not offsets))
        _when(_and(rest, _not(any_mask)),
              lambda: body(False, None, not offsets))

    def stage_key_columns(self):
        """The key side's per-key rows ([1, block_k] segment ids, bias)
        as the [block_k, 1] columns a transposed block compares and adds:
        one relayout a masked tile, not one a block."""
        if self.kseg_scr is not None:
            self.kseg_scr[...] = self.sk_ref[0].reshape(self.block_k, 1)
        if self.kbias_scr is not None:
            self.kbias_scr[...] = (self.b_ref[0] * (1.0 / self.sm_scale)
                                   ).reshape(self.block_k, 1)

    def mask(self, s, c0, r0, causal=True, q_axis=1):
        """Raw scores of the tile's keys r0.. x queries c0.. (python
        ints; transposed, or with `q_axis=0` queries on sublanes), masked
        entries at NEG_INF and the bias (over the scale) added.
        `causal=False`: the caller knows every key of the block is at or
        before every query of it."""
        nq, nk = s.shape[q_axis], s.shape[1 - q_axis]
        qs, ks = pl.ds(c0, nq), pl.ds(r0, nk)
        masked_out = lax.full_like(s, NEG_INF)
        if self.causal and causal:
            rel = lax.sub(lax.broadcasted_iota(jnp.int32, s.shape, q_axis),
                          lax.broadcasted_iota(jnp.int32, s.shape,
                                               1 - q_axis))
            lead = self.lead + (r0 - c0)             # key - query, block's
            seen = lax.ge(rel, lax.broadcast(lead, s.shape))  # key <= query
            if self.window is not None:
                seen = seen & (rel < self.window + lead)
            s = lax.select(seen, s, masked_out)
        if self.sq_ref is not None:
            if q_axis:
                kseg, qseg = self.kseg_scr[ks, :], self.sq_ref[0, :, qs]
                same = lax.eq(lax.broadcast_in_dim(kseg, s.shape, (0, 1)),
                              lax.broadcast_in_dim(qseg, s.shape, (0, 1)))
            else:
                same = self.sq_ref[0, :, qs].reshape(-1, 1) == \
                    self.sk_ref[0, :, ks]
            s = lax.select(same, s, masked_out)
        if self.m_ref is not None:
            q0, k0 = self.q_base + c0, self.k_base + r0
            # the head's [S/128, S/128] block-activity map in SMEM: one
            # scalar a 128 x 128 sub-block, added (NEG_INF + a finite
            # score stays ~NEG_INF)
            g = MASK_GRAIN

            def sub(a, b):       # sub-block a of keys, b of queries
                part = s[a * g:(a + 1) * g, b * g:(b + 1) * g] if q_axis \
                    else s[b * g:(b + 1) * g, a * g:(a + 1) * g]
                return part + jnp.where(
                    self.m_ref[0, q0 // g + b, k0 // g + a] > 0, 0.0,
                    NEG_INF)

            major, minor = (nk, nq) if q_axis else (nq, nk)
            s = jnp.concatenate([jnp.concatenate([
                sub(i, j) if q_axis else sub(j, i)
                for j in range(minor // g)], axis=1)
                for i in range(major // g)], axis=0)
        if self.b_ref is not None:
            s = s + (self.kbias_scr[ks, :] if q_axis else
                     self.b_ref[0, :, ks] * (1.0 / self.sm_scale))
        return s

    def keep(self, c0, r0, shape, q_axis=1):
        """The dropout keep-mask of a block, from the same absolute
        (query, key) coordinates in every kernel."""
        return _dropout_keep(
            self.seed_ref[0], self.pid, self.q_base + c0, self.k_base + r0,
            shape, self.dropout_rate, q_axis=q_axis)


MAX_DIAGONAL_BODIES = 4


def diagonal_offsets(block_q, block_k):
    """The offsets (first query - first key) a tile the causal diagonal
    crosses can have: the multiples of gcd(block_q, block_k) in
    (-block_q, block_k). One for square blocks (0), two at 2:1; empty
    where a geometry would need more than MAX_DIAGONAL_BODIES bodies
    (its crossed tiles then take the one masked body, every block)."""
    g = math.gcd(block_q, block_k)
    offsets = tuple(range(g - block_q, block_k, g))
    return offsets if len(offsets) <= MAX_DIAGONAL_BODIES else ()


def masked_tile_count(n_q, n_k, block_q, block_k, causal, window=None,
                      always=False):
    """(masked, launched): of the tiles a call launches per (batch x
    head), how many an edge of the GEOMETRY crosses and so take the
    masked body (`always`: a layout mask, a key bias or dropout sends
    every tile there). A segmented call adds the tiles a document
    boundary crosses, which only the data knows."""
    if causal:
        qmap, kmap = causal_grid_maps(n_q, n_k, block_q, block_k, "row",
                                      window)
        tiles = list(zip(qmap.tolist(), kmap.tolist()))
    else:
        tiles = [(qi, ki) for qi in range(n_q) for ki in range(n_k)]
    masked = sum(1 for qi, ki in tiles if always or _edge_crosses(
        qi, ki, block_q, block_k, causal, window))
    return masked, len(tiles)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, sm_scale, causal, block_q, block_k, n_k=None,
                use_mask=False, use_bias=False, dropout_rate=0.0,
                compact=False, window=None, unroll=True, by_rows=False,
                k_slab=False, rotary=False):
    """`by_rows`: the blocks are a head's TRANSPOSED tensors
    (`heads_in_place`): q^T [D, block_q], k^T and v^T [D, block_k] in,
    out^T [D, block_q] out. The tile body is the one every call runs; v^T
    and out^T are the operands it wanted (it multiplies v^T by P^T into
    out^T), q^T is the k q^T matmul's right operand as it lies, and k
    alone is transposed, into a scratch the score matmuls read.

    `k_slab` (`autotune.flash_k_slab_admitted`; by rows and causal): the
    scratch is the head's whole k, [S, D], and a step turns its k^T block
    into rows ki * block_k.. of it only on the block's FIRST visit within
    the head, which in the row-ordered causal schedule is the step of row
    (ki * block_k) // block_q. The grid's flat dimension is sequential and
    a head's first visit of a block precedes its later ones, so a step
    reads nothing this head has not written. Without it the scratch is
    one block, turned every grid step.

    `rotary` (with `k_slab`; `_rotates`): q^T and k^T come UN-rotated,
    with the table's columns of the q block's positions ([2 x rot,
    block_q] float32, cos over sin). A query row's q^T block is rotated
    on the row's first tile, into a scratch the score matmuls read in
    `q_ref`'s place; a k^T block where it is turned, which is in the row
    whose q block covers its positions: from the same columns."""
    it = iter(refs)
    if compact:
        qmap_ref, kmap_ref = next(it), next(it)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    m_ref = next(it) if use_mask else None
    b_ref = next(it) if use_bias else None
    seed_ref = next(it) if dropout_rate > 0.0 else None
    rot_ref = next(it) if rotary else None
    o_ref, lse_ref = next(it), next(it)
    m_scr, l_scr, acc_scr = next(it), next(it), next(it)
    kbias_scr = next(it) if use_bias else None
    k_scr = next(it) if by_rows else None
    q_scr = next(it) if rotary else None
    if by_rows and not k_slab:
        k_scr[...] = k_ref[0].T                                # [BK, D]
    if compact:
        # flat trapezoidal schedule: (qi, ki) from the prefetched LUTs;
        # the row ends at its causal k-extent, not at n_k - 1
        t = pl.program_id(1)
        qi, ki = qmap_ref[t], kmap_ref[t]
        last_k = jnp.minimum(n_k - 1,
                             (qi * block_q + block_q - 1) // block_k)
    else:
        qi = pl.program_id(1)
        ki = pl.program_id(2)
        last_k = pl.num_programs(2) - 1

    k_row = 0          # the scratch row that holds the block's first key
    if k_slab:
        k_row = pl.multiple_of(ki * block_k, block_k)

        @pl.when(qi == (ki * block_k) // block_q)
        def _turn():
            kT = k_ref[0]
            if rotary:
                # the block's columns of its row's table
                first = pl.multiple_of(k_row - qi * block_q, block_k)
                kT = _rotated(kT, rot_ref[:, pl.ds(first, block_k)])
            k_scr[pl.ds(k_row, block_k), :] = kT.T

    # a window's rows start at their band's first tile (compact only)
    @pl.when(ki == _first_k(qi, block_q, block_k, window))
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        if rotary:
            q_scr[...] = _rotated(q_ref[0], rot_ref[...])

    tile = _Tile(qi, ki, block_q, block_k, sm_scale=sm_scale,
                 causal=causal, window=window, m_ref=m_ref, b_ref=b_ref,
                 seed_ref=seed_ref, dropout_rate=dropout_rate,
                 kbias_scr=kbias_scr)
    c2 = jnp.float32(sm_scale * LOG2E)
    ck, gw = _part(block_k, CHUNK), _part(block_q, GROUP)
    # keys outermost: two pairs in flight never share a query column, so
    # neither's softmax waits for the other's running max
    pairs = [(r0, g0) for r0 in range(0, block_k, ck)
             for g0 in range(0, block_q, gw)]
    # (first row, rows, masked, diagonal) of a strip no mask touches
    whole_strip = [(b, BLOCK, False, False) for b in range(0, ck, BLOCK)]

    def scores(r0, g0):
        # raw, transposed: keys r0.. x queries g0..  [ck, gw]
        if by_rows:
            cols = pl.ds(g0, gw)
            return _dot(k_scr[pl.ds(k_row + r0, ck), :],
                        q_scr[:, cols] if rotary else q_ref[0, :, cols],
                        _NN)
        return _dot(k_ref[0, pl.ds(r0, ck), :], q_ref[0, pl.ds(g0, gw), :],
                    _NT)

    def pv(r0, pT):
        # [D, w]: v^T of the keys r0.. (as many as pT has rows) times pT
        if by_rows:
            return _dot(v_ref[0, :, pl.ds(r0, pT.shape[0])],
                        pT.astype(v_ref.dtype), _NN)
        v = v_ref[0, pl.ds(r0, pT.shape[0]), :]
        return _dot(v, pT.astype(v.dtype), _TN)

    def block(state, sT, r0, c0, masked, diagonal):
        """One step of a strip's online softmax: `state` (m, l [1, w], acc
        [D, w]) after sT, the raw scores of the keys r0.. (as many as sT
        has rows) x the queries c0.. + STRIP. `diagonal`: the causal
        compare is needed. Written in `lax` primitives with explicit
        broadcasts: a `jnp` operator costs ten times a primitive's bind to
        trace (PERF.md, PR 33: set-up)."""
        m, l, acc = state
        if masked:
            sT = tile.mask(sT, c0, r0, diagonal)
        m_new = lax.max(m, lax.reduce_max(sT, (0,)).reshape(1, STRIP))
        alpha = lax.exp2(lax.mul(lax.sub(m, m_new), c2))
        pT = lax.exp2(lax.mul(
            lax.sub(sT, lax.broadcast_in_dim(m_new, sT.shape, (0, 1))), c2))
        if masked and tile.zero_masked:
            pT = lax.select(lax.le(sT, jnp.float32(NEG_INF * 0.5)),
                            lax.full_like(pT, 0.0), pT)
        l = lax.add(lax.mul(alpha, l),
                    lax.reduce_sum(pT, (0,)).reshape(1, STRIP))
        if dropout_rate > 0.0:
            # post-l: the denominator sums the undropped probabilities
            pT = jnp.where(tile.keep(c0, r0, pT.shape),
                           pT * (1.0 / (1.0 - dropout_rate)), 0.0)
        acc = lax.add(
            lax.mul(acc, lax.broadcast_in_dim(alpha, acc.shape, (0, 1))),
            pv(r0, pT))
        return m_new, l, acc

    def strip(sT, r0, c0, blocks):
        """A strip of a pair: its state read once, walked through
        `blocks` ((first row, rows, masked, diagonal) of sT), written
        once."""
        cols = pl.ds(c0, STRIP)
        state = m_scr[:, cols], l_scr[:, cols], acc_scr[:, cols]
        for b, rows, masked, diagonal in blocks:
            state = block(state, lax.slice(sT, (b, 0), (b + rows, STRIP)),
                          r0 + b, c0, masked, diagonal)
        m_scr[:, cols], l_scr[:, cols], acc_scr[:, cols] = state

    def whole(sT, r0, g0, first=0):
        """The strips of a pair from `first` on, where no mask touches
        them: a BLOCK of keys at a time, 16 registers of scores from the
        max to the cast, a strip's state in registers from its first block
        to its last. The strips are a loop the lowering unrolls, as the
        pairs are (`run_of_whole`): `switch` picks the strip's columns by
        the loop's index, a constant there."""
        def one(i, carry):
            strip(lax.switch(i, [
                functools.partial(lax.slice, sT, (0, c), (ck, c + STRIP))
                for c in range(0, gw, STRIP)]), r0, g0 + i * STRIP,
                whole_strip)
            return carry

        lax.fori_loop(first, gw // STRIP, one, 0, unroll=unroll)

    def plan(r0, c0, masked, offset, crossed):
        """The blocks a strip at query c0 walks of the chunk at key r0.
        `offset` None: every row, and the diagonal may pass where it may
        cross the tile at all. A tile on the causal diagonal that no other
        mask touches: the diagonal crosses ONE block of the strip, the
        blocks before it are whole and those after it are not computed.
        Under any other mask a strip is one masked block."""
        if offset is not None and not tile.any_other:
            # BLOCK == STRIP: the block it crosses
            t = (offset + c0 - r0) // BLOCK
            return [(b, BLOCK, b == t * BLOCK, b == t * BLOCK)
                    for b in range(0, min(ck, (t + 1) * BLOCK), BLOCK)]
        rows, diagonal = ck, crossed
        if offset is not None:
            last = offset + c0 + STRIP - 1 - r0        # the last key it sees
            rows, diagonal = max(0, min(ck, last + 1)), last < ck + STRIP - 1
        if masked and (diagonal or tile.any_other):
            return [(0, rows, True, diagonal)] * bool(rows)
        return whole_strip[:rows // BLOCK]

    def run_of_whole(pairs_, ahead):
        """The pairs of a rectangle of whole pairs (keys outermost), as
        ONE loop the lowering unrolls: its index is a constant in the
        kernel, and its body is traced once and not once a pair. `ahead`:
        the scores of its first pairs, already on the MXU."""
        (r_lo, g_lo), n = pairs_[0], len(pairs_)
        n_g = len({g0 for _, g0 in pairs_})

        def where(i):
            return (r_lo + (i // n_g) * ck, g_lo + (i % n_g) * gw)

        assert [where(i) for i in range(n)] == pairs_, pairs_
        nothing = jnp.zeros((ck, gw), jnp.float32)
        ahead = list(ahead) + [nothing] * (AHEAD - len(ahead))

        def one(j, ahead):
            # j <= 0 while a pair AHEAD of this one exists: `switch`
            # clamps its index to a branch, and the lowering folds a
            # clamp of a constant, so no branch is left in the kernel
            i = j + (n - 1 - AHEAD)
            nxt = lax.switch(j, [lambda: scores(*where(i + AHEAD)),
                                 lambda: nothing])
            whole(ahead[0], *where(i))
            return (*ahead[1:], nxt)

        lax.fori_loop(AHEAD + 1 - n, AHEAD + 1, one, tuple(ahead),
                      unroll=unroll)

    def body(masked, offset, crossed):
        plans = {(r0, g0): [plan(r0, g0 + c, masked, offset, crossed)
                            for c in range(0, gw, STRIP)]
                 for r0, g0 in pairs}
        live = [pair for pair in pairs if any(plans[pair])]
        # the pairs an edge touches first, strip by strip as each needs;
        # then the whole pairs, a loop a rectangle of them
        edge = [pair for pair in live
                if any(blocks != whole_strip for blocks in plans[pair])]
        runs = _rectangles([pair for pair in live if pair not in edge])
        order = edge + (runs[0] if runs else [])
        # AHEAD pairs' scores go to the MXU before a pair's softmax starts
        # on the VPU: a pair's matmuls are shorter than the way from its
        # last pop to its first weight push
        ahead = [scores(*pair) for pair in order[:AHEAD]]
        for i, (r0, g0) in enumerate(edge):
            sT = ahead.pop(0)
            if i + AHEAD < len(order):
                ahead.append(scores(*order[i + AHEAD]))
            strips = plans[r0, g0]
            # a strip sees no fewer keys than the one before it
            first = next((j for j, blocks in enumerate(strips)
                          if blocks == whole_strip), len(strips))
            for j, blocks in enumerate(strips[:first]):
                if blocks:
                    c = j * STRIP
                    strip(lax.slice(sT, (0, c), (ck, c + STRIP)), r0, g0 + c,
                          blocks)
            if first < len(strips):
                whole(sT, r0, g0, first)
        for i, run in enumerate(runs):
            run_of_whole(run, ahead if i == 0 else
                         [scores(*pair) for pair in run[:AHEAD]])

    tile.bodies(body)

    @pl.when(ki == last_k)
    def _finalize():
        l = l_scr[...]                                         # [1, BQ]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        oT = acc_scr[...] / l_safe                             # [D, BQ]
        o_ref[0] = (oT if by_rows else oT.T).astype(o_ref.dtype)
        # Dead rows (no visible key: a layout mask, a pad row's window)
        # get POISONED lse (+1e30) so backward's exp(s - lse) is exactly
        # 0, the block-sparse kernels' invariant.
        lse_ref[0] = jnp.where(l == 0.0, -NEG_INF,
                               m_scr[...] * sm_scale + jnp.log(l_safe))


# A SEGMENTED forward (a serving prefill, a packed batch) keeps the
# WHOLE-TILE form: one max, exp2 and sum over the [block_q, block_k] tile,
# lane-broadcast running stats, no strip walk and no body a diagonal
# offset. A serving engine builds a prefill program a bucket and a kernel
# a layer kind in each (24 kernels in Laguna's cell), and it was the strip
# walk's unrolled bodies that doubled that cell's warm set-up (47.8 ->
# 100.8 s; a loop over pairs with four strips unrolled still read 55 s:
# PERF.md, PR 33), not the choice of a body by tile: the kernel has two
# whole-tile bodies, a few dozen equations each. A tile no edge crosses
# and whose id slices are one document (`_Tile.crossed`, `.other`) takes
# the INTERIOR body: scores, max, exp2, sum, cast, PV, rescale. Any other
# tile that runs takes the EDGE body: the same, with the causal / window /
# block-causal compare, the segment compare and the zeroing of masked
# entries around it. The backward of a segmented call takes the strip
# walk's tile bodies.

def _window_mask(s, qi, ki, block_q, block_k, window=None, mask_block=0):
    """Key j is visible to query i iff j <= i, and under a `window` also
    i - j < window. With `mask_block` (a power of two) the first rule is
    BLOCK-causal: j <= i | (mask_block - 1), every key of the query's own
    block of positions; a block divides every tile edge, so the tiles that
    are launched are the causal rule's."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + \
        qi * block_q
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + \
        ki * block_k
    if mask_block:
        rows = rows | (mask_block - 1)
    seen = rows >= cols
    if window is not None:
        seen = seen & (rows - cols < window)
    return jnp.where(seen, s, NEG_INF)


def _fwd_segmented_kernel(*refs, sm_scale, causal, block_q, block_k,
                          n_k=None, compact=False, window=None,
                          mask_block=0):
    it = iter(refs)
    if compact:
        qmap_ref, kmap_ref = next(it), next(it)
    q_ref, k_ref, v_ref, sq_ref, sk_ref = (next(it) for _ in range(5))
    o_ref, lse_ref = next(it), next(it)
    m_scr, l_scr, acc_scr = next(it), next(it), next(it)
    if compact:
        # flat trapezoidal schedule: (qi, ki) from the prefetched LUTs;
        # the row ends at its causal k-extent, not at n_k - 1
        t = pl.program_id(1)
        qi, ki = qmap_ref[t], kmap_ref[t]
        last_k = jnp.minimum(n_k - 1,
                             (qi * block_q + block_q - 1) // block_k)
    else:
        qi = pl.program_id(1)
        ki = pl.program_id(2)
        last_k = pl.num_programs(2) - 1

    # a window's rows start at their band's first tile (compact only)
    @pl.when(ki == _first_k(qi, block_q, block_k, window))
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    tile = _Tile(qi, ki, block_q, block_k, sm_scale=sm_scale,
                 causal=causal, window=window, sq_ref=sq_ref, sk_ref=sk_ref)
    # scores stay raw and so does the running max: the scale rides the
    # exponent's multiply, as in `_fwd_kernel`
    c2 = jnp.float32(sm_scale * LOG2E)

    def body(masked, *_):
        # Matmuls take the inputs' native dtype (bf16 → MXU-rate) and
        # accumulate fp32; only the softmax math is explicitly fp32.
        s = _dot(q_ref[0], k_ref[0], _NT)                     # [BQ, BK]
        if masked:
            if causal:
                s = _window_mask(s, qi, ki, block_q, block_k, window,
                                 mask_block)
            # [BQ, 1] vs [1, BK] segment ids: a document boundary, pad rows
            s = jnp.where(sq_ref[0].reshape(-1, 1) == sk_ref[0], s, NEG_INF)
        m_prev = m_scr[:, :1]                                 # [BQ, 1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp2((m_prev - m_new) * c2)               # [BQ, 1]
        p = jnp.exp2((s - m_new) * c2)                        # [BQ, BK]
        if masked:
            # rows with EVERY entry masked would otherwise see exp2(0)
            # == 1 uniformly; zero masked entries so l == 0 flags the
            # dead row (poisoned-lse convention)
            p = jnp.where(s <= NEG_INF * 0.5, 0.0, p)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[:] = acc_scr[:] * alpha + _dot(p.astype(v_ref.dtype),
                                               v_ref[0], _NN)  # [BQ, D]

    # the edge body, the interior body, or (a tile whose q and k slices
    # can share no document) NO body
    tile.bodies(body, diagonal=False)

    @pl.when(ki == last_k)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # lse row-vector [1, BQ]: the [BQ]-per-row stats transposed onto
        # the lane dim — 128x less HBM than a lane-broadcast [BQ, LANES].
        # Dead rows (every key another document's, or behind a pad row's
        # window) get POISONED lse (+1e30) so backward's exp(s - lse) is
        # exactly 0, the block-sparse kernels' invariant.
        lse = jnp.where(l == 0.0, -NEG_INF,
                        m_scr[:, :1] * sm_scale + jnp.log(l_safe))
        lse_ref[0] = lse.reshape(1, -1)


def _key_column_scratch(block_k, use_seg, use_bias):
    """The [block_k, 1] columns `_Tile.stage_key_columns` fills."""
    return [pltpu.VMEM((block_k, 1), jnp.int32)] * use_seg + \
        [pltpu.VMEM((block_k, 1), jnp.float32)] * use_bias


def _optional_specs(ix, h, s, block_q, block_k, use_seg, use_mask, use_bias,
                    dropout_rate):
    """BlockSpecs of the inputs a call may bring after its tensors, in
    the order `_optional_inputs` hands them; `ix` adapts the index maps,
    written as (bh, qi, ki), to the grid (`_index_adapter`)."""
    specs = []
    if use_seg:
        # per-token segment ids [B, 1, S]: one q-row slice and one k-row
        # slice per tile (same batch-indexed layout as the kbias row)
        specs.append(pl.BlockSpec(
            (1, 1, block_q), ix(lambda bh, qi, ki: (bh // h, 0, qi))))
        specs.append(pl.BlockSpec(
            (1, 1, block_k), ix(lambda bh, qi, ki: (bh // h, 0, ki))))
    if use_mask:
        specs.append(_mask_spec(h, s // MASK_GRAIN, s // MASK_GRAIN, ix))
    if use_bias:
        specs.append(pl.BlockSpec(
            (1, 1, block_k), ix(lambda bh, qi, ki: (bh // h, 0, ki))))
    if dropout_rate > 0.0:
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    return specs


def _optional_inputs(seg, layout, kbias, seed, dropout_rate):
    """The inputs `_optional_specs` describes: the segment ids twice (the
    q side's and the k side's), the layout, the key bias, the seed."""
    return [x for x in (seg, seg, layout, kbias) if x is not None] + \
        ([seed] if dropout_rate > 0.0 else [])


def _rotary_specs(ix, rot, block_q):
    """BlockSpec of the rotary's table [2 x rot, S] (cos over sin) as a
    rotating kernel takes it after `_optional_inputs`: its columns of the
    q block's positions."""
    return [pl.BlockSpec((2 * rot, block_q),
                         ix(lambda bh, qi, ki: (0, qi)))] * bool(rot)


def _rot_dim(table):
    """The features a rotary's table [2 x rot, S] rotates; 0 of None."""
    return 0 if table is None else table.shape[0] // 2


def _mask_spec(h, n_fine_q, n_fine_k, ix=lambda f: f):
    """BlockSpec for the [H, S/128, S/128] layout mask: the WHOLE
    per-head map as one SMEM block (Mosaic requires trailing block dims
    to be 8/128-multiples or full-size; scalar SMEM reads then take
    dynamic indices). `ix` adapts the index map to the grid in use
    (`_index_adapter`)."""
    return pl.BlockSpec((1, n_fine_q, n_fine_k),
                        ix(lambda bh, i, j: (bh % h, 0, 0)),
                        memory_space=pltpu.SMEM)


def _tag_residuals(out, lse):
    """Name the forward results BEFORE they fan out to the primal output
    and the custom_vjp residuals: under `jax.checkpoint` with the
    `attn_residuals` policy (`save_only_these_names(ds_attn_out,
    ds_attn_lse)`), both survive the remat boundary, so the backward
    kernels consume saved tensors and this forward kernel never re-runs
    during the backward replay."""
    from jax.ad_checkpoint import checkpoint_name
    return (checkpoint_name(out, "ds_attn_out"),
            checkpoint_name(lse, "ds_attn_lse"))


def _head_spec(ix, by_rows, h, d, block, which, row_of=lambda bh: bh):
    """BlockSpec of `block` positions of one head, the `which(qi, ki)`-th
    such block: rows of [B*H, S, D] (of its row `row_of(bh)`), or with
    `by_rows` columns of the head's D rows of [B, H*D, S]. `ix` adapts
    the index map, written as (bh, qi, ki), to the grid."""
    if by_rows:
        return pl.BlockSpec((1, d, block), ix(
            lambda bh, qi, ki: (bh // h, bh % h, which(qi, ki))))
    return pl.BlockSpec((1, block, d), ix(
        lambda bh, qi, ki: (row_of(bh), which(qi, ki), 0)))


@functools.cache
def _fwd_call(b, s, h, g, d, dtype, block_q, block_k, causal, sm_scale,
              use_mask, use_bias, dropout_rate, segmented, window,
              interpret, mask_block=0, by_rows=False, k_slab=False, rot=0):
    """The tiled forward at one call signature: (the function of its
    inputs, its grid, its (masked, launched) tiles), built once a process
    (`_BODY_BUILDS`). Inputs in order: q, k, v as [B*H | B*G, S, D], then
    `_optional_inputs`, then with `rot` the rotary's table.

    `by_rows` (`heads_in_place`): q^T, k^T, v^T in and out^T out, each
    [B, H*D, S]; a BlockSpec picks a head's (1, D, block) at row block
    `head` of row `batch`, on the same grid. `k_slab`: its scratch for
    the turned k is the head's [S, D] and not a block's (`_fwd_kernel`).
    `rot` (with `k_slab`): the kernel rotates the first `rot` features of
    q and k itself, from the table [2 x rot, S] (`_fwd_kernel`)."""
    n_q, n_k = s // block_q, s // block_k
    if by_rows:
        assert g == h and not (use_mask or use_bias or segmented) and \
            dropout_rate == 0.0 and window is None
    assert not k_slab or (by_rows and causal)
    assert not rot or (k_slab and block_q % block_k == 0)

    def kv_of(bh):
        """The [B*G, S, D] row that holds query row `bh`'s KV head."""
        return bh if g == h else (bh // h) * g + (bh % h) // (h // g)

    compact = causal   # causal ⇒ trapezoidal schedule (no dead launches)
    if segmented:
        assert not use_mask and not use_bias and dropout_rate == 0.0
        kernel = functools.partial(_fwd_segmented_kernel, sm_scale=sm_scale,
                                   causal=causal, block_q=block_q,
                                   block_k=block_k, n_k=n_k,
                                   compact=compact, window=window,
                                   mask_block=mask_block)
    else:
        kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale,
                                   causal=causal, block_q=block_q,
                                   block_k=block_k, n_k=n_k,
                                   use_mask=use_mask, use_bias=use_bias,
                                   dropout_rate=dropout_rate,
                                   compact=compact, window=window,
                                   # the interpreter gains nothing from a
                                   # body written out, and compiles it
                                   unroll=not interpret, by_rows=by_rows,
                                   k_slab=k_slab, rotary=bool(rot))
    if compact:
        maps = causal_grid_maps(n_q, n_k, block_q, block_k, "row", window)
        grid = (b * h, len(maps[0]))
    else:
        maps = ()
        grid = (b * h, n_q, n_k)
    ix = _index_adapter(compact)
    q_spec = _head_spec(ix, by_rows, h, d, block_q, lambda qi, ki: qi)
    kv_spec = _head_spec(ix, by_rows, h, d, block_k, lambda qi, ki: ki,
                         kv_of)
    in_specs = [q_spec, kv_spec, kv_spec]
    out_specs = [
        q_spec,
        pl.BlockSpec((1, 1, block_q),
                     ix(lambda bh, qi, ki: (bh, 0, qi))),
    ]
    in_specs += _optional_specs(ix, h, s, block_q, block_k, segmented,
                                use_mask, use_bias, dropout_rate)
    in_specs += _rotary_specs(ix, rot, block_q)
    out_shape = [
        jax.ShapeDtypeStruct((b, h * d, s) if by_rows else (b * h, s, d),
                             dtype),
        jax.ShapeDtypeStruct((b * h, 1, s), jnp.float32),
    ]
    scratch_shapes = [
        pltpu.VMEM((block_q, LANES), jnp.float32),   # running max
        pltpu.VMEM((block_q, LANES), jnp.float32),   # running denom
        pltpu.VMEM((block_q, d), jnp.float32),       # out accumulator
    ] if segmented else [
        pltpu.VMEM((1, block_q), jnp.float32),       # running max (raw)
        pltpu.VMEM((1, block_q), jnp.float32),       # running denom
        pltpu.VMEM((d, block_q), jnp.float32),       # out accumulator^T
    ] + _key_column_scratch(block_k, False, use_bias) \
        + [pltpu.VMEM((s if k_slab else block_k, d), dtype)] * by_rows \
        + [pltpu.VMEM((d, block_q), dtype)] * bool(rot)   # k; rotated q^T
    masked = masked_tile_count(
        n_q, n_k, block_q, block_k, causal, window,
        always=use_mask or use_bias or dropout_rate > 0.0)
    run = _tiled_call(
        "fwd", "ds.flash_fwd" if window is None else "ds.flash_fwd_window",
        kernel, compact, grid, in_specs, out_specs, scratch_shapes,
        out_shape, maps, interpret)
    return run, grid, masked


def _to_bh(x):
    """[B, S, H, D] -> [B*H, S, D]: a head's rows contiguous, by a COPY of
    the tensor (at head dim 64 a transpose of half-filled lane tiles: a
    quarter of the memory's rate on a v5e, PERF.md PR 53)."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bh(x, h):
    """`_to_bh`'s inverse, and as much a copy."""
    bh, s, d = x.shape
    return x.reshape(bh // h, h, s, d).transpose(0, 2, 1, 3)


def _to_rows(x):
    """[B, S, H, D] -> [B, H*D, S], a head's D rows together: the tensor
    where XLA holds it (`heads_in_place`), so no copy on the chip."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 3, 1).reshape(b, h * d, s)


def _from_rows(x, h):
    """`_to_rows`' inverse."""
    b, hd, s = x.shape
    return x.reshape(b, h, hd // h, s).transpose(0, 3, 1, 2)


def _fwd(q, k, v, causal, sm_scale, block_q=BLOCK_Q, block_k=BLOCK_K,
         layout=None, kbias=None, dropout_rate=0.0, seed=None, seg=None,
         window=None, mask_block=0, in_place=False, table=None):
    """`k` / `v` may hold fewer heads than `q` (G under H: query head i
    reads KV head ``i // (H / G)``, through the K and V index maps), and
    a `window` (causal only) keeps keys less than `window` positions
    behind their query: the compacted grid launches the band's tiles
    alone, under the scope `ds.flash_fwd_window`. `mask_block` (segmented
    and causal only) makes the diagonal BLOCK-causal (`_window_mask`).
    All three are the forward's (a serving prefill's); the backward
    kernels take none.

    `in_place` (`flash_attention`'s call alone): a tiled call whose shape
    `heads_in_place` admits hands the kernel q^T, k^T, v^T and takes
    out^T, [B, H*D, S] each (`_to_rows`: no copy where XLA holds the
    tensors sequence-minor), and its residuals are those four as
    [B, H, D, S]. Every other call (a layout, a key bias, dropout,
    segments, a window, grouped KV heads; a single block) MOVES the
    heads: a `[B, S, H, D] -> [B*H, S, D]` copy of each operand and of
    out, residuals [B*H, S, D].

    `table` (an `in_place` call `_rotates` admits): the rotary's,
    [2 x rot, S] float32, cos over sin; q and k are the UN-rotated
    projections, the kernel rotates them, and so are the residuals."""
    b, s, h, d = q.shape
    g = k.shape[2]
    block_q, block_k = _fit_block(block_q, s), _fit_block(block_k, s)
    single = _one_block(s, block_q, block_k) and layout is None \
        and seg is None and window is None and g == h
    in_place = in_place and not single and heads_in_place(h, g, d)
    assert in_place or table is None
    # [B, S, H, D] → [B, H*D, S] where it lies so, else → [B*H, S, D]
    # for contiguous per-head tiles
    heads = tuple((_to_rows if in_place else _to_bh)(x) for x in (q, k, v))

    if single:
        # whole sequence in one block: the online-softmax machinery is
        # pure overhead — run the specialized straight-softmax kernel
        _LAST_BLOCKS["fwd"] = (s, s)
        _LAST_BLOCKS["fwd_variant"] = "single"
        _log_first_dispatch()
        with scopes.scope("ds.flash_fwd"):
            out, lse = _fwd_single(*heads, causal, sm_scale, s, d,
                                   _interpret(), kbias=kbias, h=h,
                                   dropout_rate=dropout_rate, seed=seed)
        out, lse = _tag_residuals(out, lse)
        return _from_bh(out, h), (*heads, out, lse.reshape(b * h, s))

    _HEADS["fwd"]["in_place" if in_place else "moved"] += 1
    k_slab = in_place and flash_k_slab_admitted(s, d, q.dtype.itemsize,
                                                causal)
    if in_place:
        _K_TURNS["once_a_head" if k_slab else "every_step"] += 1
    if table is not None:
        _ROTARY["in_kernel"] += 1
    _LAST_BLOCKS["fwd"] = (block_q, block_k)
    _LAST_BLOCKS["fwd_variant"] = "trapezoid" if causal else "dense"
    _log_first_dispatch()
    run, _LAST_GRIDS["fwd"], _LAST_MASKED["fwd"] = _fwd_call(
        b, s, h, g, d, q.dtype, block_q, block_k, causal, sm_scale,
        layout is not None, kbias is not None, dropout_rate,
        seg is not None, window, _interpret(), mask_block, in_place, k_slab,
        _rot_dim(table))
    out, lse = run(*heads, *_optional_inputs(seg, layout, kbias, seed,
                                             dropout_rate),
                   *[table] * (table is not None))
    out, lse = _tag_residuals(out, lse)
    if in_place:
        return _from_rows(out, h), (
            *(x.reshape(b, h, d, s) for x in (*heads, out)),
            lse.reshape(b * h, s))
    return _from_bh(out, h), (*heads, out, lse.reshape(b * h, s))


# ---------------------------------------------------------------------------
# backward — single-block specialization (fused dq/dk/dv)
# ---------------------------------------------------------------------------

def _bwd_single_kernel(*refs, sm_scale, causal, use_bias=False,
                       dropout_rate=0.0):
    """Whole-sequence tile: ONE pass computes dq, dk AND dv — the split
    dkv/dq kernels each recompute s and p, so fusing saves a full QKᵀ
    matmul, a dO·Vᵀ matmul, and an exp pass per layer. Causal tiles
    process column strips: dead sub-blocks skip exp/multiply AND their
    share of the dv/dk/dq matmul flops. With ``use_bias`` the additive
    per-key row is re-applied pre-exp (p = exp(s + bias - lse) is then
    exactly the forward's probabilities; masked entries exp to 0)."""
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    do_ref, lse_ref, delta_ref = next(it), next(it), next(it)
    b_ref = next(it) if use_bias else None
    seed_ref = next(it) if dropout_rate > 0.0 else None
    dq_ref, dk_ref, dv_ref = next(it), next(it), next(it)
    dq, dk, dv = _head_bwd(
        q_ref[0], k_ref[0], v_ref[0], do_ref[0],
        lse_ref[0].reshape(-1, 1), delta_ref[0].reshape(-1, 1),
        b_ref[0] if use_bias else None,
        seed_ref[0] if dropout_rate > 0.0 else None,
        pl.program_id(0), sm_scale=sm_scale, causal=causal,
        use_bias=use_bias, dropout_rate=dropout_rate)
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_single_mh_kernel(*refs, sm_scale, causal, use_bias, dropout_rate,
                          hb, h_total):
    """Heads-batched counterpart of `_fwd_single_mh_kernel` (same pid
    formula for the dropout hash)."""
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    do_ref, lse_ref, delta_ref = next(it), next(it), next(it)
    b_ref = next(it) if use_bias else None
    seed_ref = next(it) if dropout_rate > 0.0 else None
    dq_ref, dk_ref, dv_ref = next(it), next(it), next(it)
    for j in range(hb):
        pid = pl.program_id(0) * h_total + pl.program_id(1) * hb + j
        dq, dk, dv = _head_bwd(
            q_ref[0, j], k_ref[0, j], v_ref[0, j], do_ref[0, j],
            lse_ref[0, j].reshape(-1, 1),
            delta_ref[0, j].reshape(-1, 1),
            b_ref[0] if use_bias else None,
            seed_ref[0] if dropout_rate > 0.0 else None,
            pid, sm_scale=sm_scale, causal=causal, use_bias=use_bias,
            dropout_rate=dropout_rate)
        dq_ref[0, j] = dq.astype(dq_ref.dtype)
        dk_ref[0, j] = dk.astype(dk_ref.dtype)
        dv_ref[0, j] = dv.astype(dv_ref.dtype)


def _head_bwd(q, k, v, do, lse, delta, bias_row, seed, pid, *, sm_scale,
              causal, use_bias, dropout_rate):
    """One head's whole-sequence backward: recompute scores from the
    saved lse, regenerate the dropout mask at the same (pid, coords),
    and produce (dq, dk, dv) [S, D] fp32."""
    s_q, s_k = q.shape[0], k.shape[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale        # [Sq, Sk]
    if use_bias:
        s = s + bias_row                                      # [1, Sk] bcast
    dp_full = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                   # [Sq, Sk]
    # (dense matmuls; per-strip ragged matmuls measured slower — see fwd)

    if causal and s_q == s_k and s_k % CAUSAL_STRIPS == 0:
        w = s_k // CAUSAL_STRIPS
        dq = jnp.zeros((s_q, q.shape[1]), jnp.float32)
        dk_parts, dv_parts = [], []
        for c in range(CAUSAL_STRIPS):
            lo = c * w
            sc = s[lo:, c * w:(c + 1) * w]                    # alive rows
            rows = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0) + lo
            cols = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1) + lo
            sc = jnp.where(rows >= cols, sc, NEG_INF)
            pc = jnp.exp(sc - lse[lo:])                       # [Sq-lo, w]
            dpc = dp_full[lo:, c * w:(c + 1) * w]
            pc_v = pc
            if dropout_rate > 0.0:
                # regenerate the forward mask at this strip's absolute
                # coordinates (rows lo.., cols c*w..)
                keep_c = _dropout_keep(seed, pid, lo, c * w, pc.shape,
                                       dropout_rate)
                inv = 1.0 / (1.0 - dropout_rate)
                pc_v = jnp.where(keep_c, pc * inv, 0.0)
                dpc = jnp.where(keep_c, dpc * inv, 0.0)
            dsc = pc * (dpc - delta[lo:]) * sm_scale
            do_alive = do[lo:]
            dv_parts.append(jax.lax.dot_general(
                pc_v.astype(do.dtype), do_alive, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))          # [w, D]
            dk_parts.append(jax.lax.dot_general(
                dsc.astype(q.dtype), q[lo:], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))          # [w, D]
            dq_c = jax.lax.dot_general(
                dsc.astype(k.dtype), k[c * w:(c + 1) * w],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [Sq-lo, D]
            if lo:
                dq_c = jnp.concatenate(
                    [jnp.zeros((lo, q.shape[1]), jnp.float32), dq_c],
                    axis=0)
            dq = dq + dq_c
        dk = jnp.concatenate(dk_parts, axis=0)
        dv = jnp.concatenate(dv_parts, axis=0)
    else:
        if causal:
            s = _causal_mask(s)
        p = jnp.exp(s - lse)
        p_v = p
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed, pid, 0, 0, p.shape, dropout_rate)
            inv = 1.0 / (1.0 - dropout_rate)
            p_v = jnp.where(keep, p * inv, 0.0)
            dp_full = jnp.where(keep, dp_full * inv, 0.0)
        ds = p * (dp_full - delta) * sm_scale
        dv = jax.lax.dot_general(
            p_v.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk = jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq = jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    return dq, dk, dv


def _bwd_single(qb, kb, vb, do, lse, delta, causal, sm_scale, s, d,
                interpret, kbias=None, h=None, dropout_rate=0.0,
                seed=None):
    bh = qb.shape[0]
    use_bias = kbias is not None
    hb = _mh_heads(s, d, h or 1)
    if hb > 1:
        b = bh // h
        kernel = functools.partial(
            _bwd_single_mh_kernel, sm_scale=sm_scale, causal=causal,
            use_bias=use_bias, dropout_rate=dropout_rate, hb=hb,
            h_total=h)
        in_specs = [pl.BlockSpec((1, hb, s, d),
                                 lambda b, hg: (b, hg, 0, 0))] * 4 + \
            [pl.BlockSpec((1, hb, 1, s), lambda b, hg: (b, hg, 0, 0))] * 2
        inputs = [t.reshape(b, h, s, d) for t in (qb, kb, vb, do)] + \
            [t.reshape(b, h, 1, s) for t in (lse, delta)]
        if use_bias:
            in_specs.append(pl.BlockSpec((1, 1, s),
                                         lambda b, hg: (b, 0, 0)))
            inputs.append(kbias)
        if dropout_rate > 0.0:
            in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
            inputs.append(seed)
        dq, dk, dv = pl.pallas_call(
            kernel,
            grid=(b, h // hb),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, hb, s, d),
                                    lambda b, hg: (b, hg, 0, 0))] * 3,
            out_shape=[
                jax.ShapeDtypeStruct((b, h, s, d), qb.dtype),
                jax.ShapeDtypeStruct((b, h, s, d), kb.dtype),
                jax.ShapeDtypeStruct((b, h, s, d), vb.dtype),
            ],
            compiler_params=CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret, name="ds.flash_bwd",
        )(*inputs)
        return (dq.reshape(bh, s, d), dk.reshape(bh, s, d),
                dv.reshape(bh, s, d))
    kernel = functools.partial(_bwd_single_kernel, sm_scale=sm_scale,
                               causal=causal, use_bias=kbias is not None,
                               dropout_rate=dropout_rate)
    in_specs = [
        pl.BlockSpec((1, s, d), lambda bh: (bh, 0, 0)),
        pl.BlockSpec((1, s, d), lambda bh: (bh, 0, 0)),
        pl.BlockSpec((1, s, d), lambda bh: (bh, 0, 0)),
        pl.BlockSpec((1, s, d), lambda bh: (bh, 0, 0)),
        pl.BlockSpec((1, 1, s), lambda bh: (bh, 0, 0)),
        pl.BlockSpec((1, 1, s), lambda bh: (bh, 0, 0)),
    ]
    inputs = [qb, kb, vb, do, lse, delta]
    if kbias is not None:
        in_specs.append(pl.BlockSpec((1, 1, s),
                                     lambda i, h=h: (i // h, 0, 0)))
        inputs.append(kbias)
    if dropout_rate > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        inputs.append(seed)
    return pl.pallas_call(
        kernel,
        grid=(bh,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, s, d), lambda bh: (bh, 0, 0))] * 3,
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), qb.dtype),
            jax.ShapeDtypeStruct((bh, s, d), kb.dtype),
            jax.ShapeDtypeStruct((bh, s, d), vb.dtype),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret, name="ds.flash_bwd",
    )(*inputs)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

# The tiled backward is ONE kernel (PR 36): the dk/dv walk, column by
# column over the tiles of a (batch x head), which also adds each tile's
# dS into dq. A tile's scores, dO V^T and exponentials are computed once
# and five matmuls run where two kernels ran seven.
#
# dq's sum crosses the columns, so it lives in a float32 SLAB over the
# whole sequence, [S / block_q, D, block_q], that stays in VMEM for all
# the steps of a (batch x head). A query row's slice is zeroed at its
# first column (column 0) and, at its LAST (the tile on its diagonal; in
# a dense grid the last column), scaled, transposed, rounded once and
# stored to the dq block, which the pipeline writes back while the walk
# goes on (`_dq_block`).
#
# The slab is TRANSPOSED, and so are the fused kernel's dk and dv
# accumulators ([D, block_k]): the tile is held transposed, and
# dq^T += k^T dS^T, dk^T += q^T dS, dv^T += dO^T P take it as the
# matmul's WEIGHTS, as it lies, with the D rows of k^T, q^T or dO^T
# streamed past each 128 x 128 unit of it. At head dim 64 that is 4
# pushes a unit and a dense [64, 128] result, where the tile as the left
# operand is 8 pushes and a result half of whose lanes are padding; the
# weights' loads hide behind the pushes (PERF.md, PR 36: 11.3 ms a layer
# at 16k tokens against 13.9 with dk and dv the other way round, 21.5 as
# two kernels; at head dim 128 the two forms are within 5%). And
# [D, S] float32 has no lane padding where [S, 64] would double.
#
# Which sequences: those whose slab `ops.autotune.flash_dq_slab_admitted`
# admits. A longer one takes this kernel without dq, its dk and dv as
# [block_k, D] with the tile as the left operand, and `_bwd_dq_kernel`
# beside it: the two passes every tiled backward was before, as they
# were (MXU-bound: PERF.md, PR 33).

def _last_k(qi, n_k, block_q, block_k, causal):
    """The last key column query row `qi` meets."""
    if not causal:
        return n_k - 1
    return jnp.minimum(n_k - 1, (qi * block_q + block_q - 1) // block_k)


def _dq_block(qi, ki, n_k, block_q, block_k, causal):
    """The dq block the fused kernel holds at tile (qi, ki) of its column
    walk: the query row that was completed last (row 0 before any is).
    Rows complete in ascending order, each at one tile, so a block is
    held for one run of steps, stored at the first of them, and written
    back when the run ends."""
    if not causal:
        return jnp.where(ki == n_k - 1, qi, 0)
    # rows up to this one whose last column is `ki` or an earlier one
    done = jnp.maximum((ki + 1) * block_k - block_q, 0) // block_q
    return jnp.minimum(qi, done)


def _bwd_dkv_kernel(*refs, sm_scale, causal, block_q, block_k, n_q=None,
                    n_k=None, use_seg=False, use_mask=False,
                    use_bias=False, dropout_rate=0.0, compact=False,
                    fused=False, by_rows=False, rotary=False):
    """dk and dv of a key column, and with `fused` dq as well.

    `by_rows` (fused only): the blocks are a head's TRANSPOSED tensors
    (`heads_in_place`): q^T and dO^T [D, block_q], k^T and v^T
    [D, block_k] in, dk^T, dv^T and dq^T out. q^T and dO^T are the
    operands the three matmuls on the tile's weights wanted, k^T is
    dq^T's, the accumulators are the out blocks' layout, and k and v for
    the two score matmuls are transposed once a key COLUMN: a step
    transposes nothing.

    `rotary` (by rows and causal; `_rotates`): q^T and k^T come
    UN-rotated, with the table's columns of the q block's positions
    ([2 x rot, block_q] float32, cos over sin); a column's first tile is
    the row that covers the k block's positions, so the k block's columns
    are among them and are kept for the column's end. k^T is rotated once
    a column, where k and v are turned, into a scratch dq's matmul reads
    in `k_ref`'s place; q^T once a step, into a scratch the groups read in
    `q_ref`'s place; dk and dq are rotated BACK where they are stored, in
    float32 before the cast, so they are the gradients of the
    projections."""
    it = iter(refs)
    if compact:
        qmap_ref, kmap_ref = next(it), next(it)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    do_ref, lse_ref, delta_ref = next(it), next(it), next(it)
    sq_ref = next(it) if use_seg else None
    sk_ref = next(it) if use_seg else None
    m_ref = next(it) if use_mask else None
    b_ref = next(it) if use_bias else None
    seed_ref = next(it) if dropout_rate > 0.0 else None
    rot_q = next(it) if rotary else None
    dk_ref, dv_ref = next(it), next(it)
    dq_ref = next(it) if fused else None
    dk_scr, dv_scr = next(it), next(it)
    dq_scr = next(it) if fused else None
    kseg_scr = next(it) if use_seg else None
    kbias_scr = next(it) if use_bias else None
    if compact:
        # column-major trapezoid: column ki starts at its first alive
        # row (the diagonal) and always ends at the bottom row
        t = pl.program_id(1)
        qi, ki = qmap_ref[t], kmap_ref[t]
        first_q = (ki * block_k) // block_q
        last_q = n_q - 1
    else:
        ki = pl.program_id(1)
        qi = pl.program_id(2)
        first_q = 0
        last_q = pl.num_programs(2) - 1

    if by_rows:
        k_scr, v_scr = next(it), next(it)
    if rotary:
        kT_scr, rot_k, q_scr = next(it), next(it), next(it)
        q_scr[...] = _rotated(q_ref[0], rot_q[...])

    @pl.when(qi == first_q)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)
        if rotary:
            first = pl.multiple_of(ki * block_k - qi * block_q, block_k)
            rot_k[...] = rot_q[:, pl.ds(first, block_k)]
            kT_scr[...] = _rotated(k_ref[0], rot_k[...])
            k_scr[...], v_scr[...] = kT_scr[...].T, v_ref[0].T
        elif by_rows:
            k_scr[...], v_scr[...] = k_ref[0].T, v_ref[0].T    # [BK, D]

    if fused:
        @pl.when(ki == 0)
        def _init_dq():
            dq_scr[qi] = jnp.zeros(dq_scr.shape[1:], dq_scr.dtype)

    tile = _Tile(qi, ki, block_q, block_k, sm_scale=sm_scale,
                 causal=causal, sq_ref=sq_ref, sk_ref=sk_ref, m_ref=m_ref,
                 b_ref=b_ref, seed_ref=seed_ref, dropout_rate=dropout_rate,
                 kseg_scr=kseg_scr, kbias_scr=kbias_scr)

    def group(g0, gw, rows, masked, diagonal):
        # the tile transposed (k q^T, v dO^T): lse and delta broadcast
        # along lanes as the rows they are, and no matmul transposes a
        # tile-sized operand. Queries g0.. + gw against the keys before
        # `rows`
        cols = slice(g0, g0 + gw)
        if by_rows:
            q, do = q_ref[0, :, cols], do_ref[0, :, cols]      # [D, gw]
            if rotary:
                q = q_scr[:, cols]
            sT = _dot(k_scr[:rows, :], q, _NN)                 # [rows, gw]
        else:
            q, do = q_ref[0, cols, :], do_ref[0, cols, :]      # [gw, D]
            sT = _dot(k_ref[0, :rows, :], q, _NT)              # [rows, gw]
        if masked:
            sT = tile.mask(sT, g0, 0, diagonal)
        pT = jnp.exp2(sT * (sm_scale * LOG2E)
                      - lse_ref[0, :, cols] * LOG2E)
        dpT = _dot(v_scr[:rows, :], do, _NN) if by_rows else \
            _dot(v_ref[0, :rows, :], do, _NT)
        pT_v = pT
        if dropout_rate > 0.0:
            keep = tile.keep(g0, 0, pT.shape)
            inv = 1.0 / (1.0 - dropout_rate)
            pT_v = jnp.where(keep, pT * inv, 0.0)
            dpT = jnp.where(keep, dpT * inv, 0.0)
        # dS = P o (M o dO V^T / keep - delta), unscaled. P and dS are
        # quantized to the wire dtype for MXU rate, matching the
        # reference's fp16 kernel precision
        pT_v = pT_v.astype(do.dtype)
        dsT = (pT * (dpT - delta_ref[0, :, cols])).astype(q.dtype)
        if by_rows:
            dv_scr[:, :rows] += _dot(do, pT_v, _NT)
            dk_scr[:, :rows] += _dot(q, dsT, _NT)
            dq_scr[qi, :, cols] += _dot(
                kT_scr[:, :rows] if rotary else k_ref[0, :, :rows],
                dsT.astype(k_ref.dtype), _NN)
        elif fused:
            # the tile as the weights of all three: [D, rows] += dO^T P,
            # [D, rows] += q^T dS, [D, gw] += k^T dS^T
            dv_scr[:, :rows] += _dot(do.T, pT_v, _NT)
            dk_scr[:, :rows] += _dot(q.T, dsT, _NT)
            dq_scr[qi, :, cols] += _dot(
                k_ref[0, :rows, :], dsT.astype(k_ref.dtype), _TN)
        else:
            dv_scr[:rows, :] += _dot(pT_v, do, _NN)
            dk_scr[:rows, :] += _dot(dsT, q, _NN)

    def body(masked, offset, crossed):
        if offset is None:
            return group(0, block_q, block_k, masked, crossed)
        # a tile on the diagonal, a group of query columns at a time: each
        # against the keys up to its last query alone
        gw = _part(block_q, DIAGONAL_GROUP)
        for g0 in range(0, block_q, gw):
            rows = max(0, min(block_k, offset + g0 + gw))
            if rows:
                diagonal = rows - 1 > offset + g0
                group(g0, gw, rows,
                      diagonal or tile.any_other, diagonal)

    tile.bodies(body)

    @pl.when(qi == last_q)
    def _finalize():
        dk, dv = dk_scr[...] * sm_scale, dv_scr[...]
        if fused and not by_rows:
            dk, dv = dk.T, dv.T
        if rotary:
            dk = _rotated(dk, rot_k[...], inverse=True)
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)

    if fused:
        @pl.when(ki == _last_k(qi, n_k, block_q, block_k, causal))
        def _finalize_dq():
            dq = dq_scr[qi] * sm_scale
            if rotary:
                dq = _rotated(dq, rot_q[...], inverse=True)
            dq_ref[0] = (dq if by_rows else dq.T).astype(dq_ref.dtype)


# The dq pass of a sequence too long for the fused kernel's slab: the
# same tiles in row order, their scores and dO V^T computed a second time.

def _bwd_dq_kernel(*refs, sm_scale, causal, block_q, block_k, n_k=None,
                   use_seg=False, use_mask=False, use_bias=False,
                   dropout_rate=0.0, compact=False):
    it = iter(refs)
    if compact:
        qmap_ref, kmap_ref = next(it), next(it)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    do_ref, lse_ref, delta_ref = next(it), next(it), next(it)
    sq_ref = next(it) if use_seg else None
    sk_ref = next(it) if use_seg else None
    m_ref = next(it) if use_mask else None
    b_ref = next(it) if use_bias else None
    seed_ref = next(it) if dropout_rate > 0.0 else None
    dq_ref, dq_scr = next(it), next(it)
    if compact:
        t = pl.program_id(1)
        qi, ki = qmap_ref[t], kmap_ref[t]
    else:
        qi = pl.program_id(1)
        ki = pl.program_id(2)
    last_k = _last_k(qi, n_k, block_q, block_k, causal)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    tile = _Tile(qi, ki, block_q, block_k, sm_scale=sm_scale,
                 causal=causal, sq_ref=sq_ref, sk_ref=sk_ref, m_ref=m_ref,
                 b_ref=b_ref, seed_ref=seed_ref, dropout_rate=dropout_rate)

    def group(r0, kw, q0, masked, diagonal):
        # queries on sublanes here: dS is the left operand of dS K. Keys
        # r0.. + kw against the queries from q0 on
        rows, keys = slice(q0, block_q), slice(r0, r0 + kw)
        k = k_ref[0, keys, :]
        s = _dot(q_ref[0, rows, :], k, _NT)                    # [nq, kw]
        if masked:
            s = tile.mask(s, q0, r0, diagonal, q_axis=0)
        p = jnp.exp2(s * (sm_scale * LOG2E)
                     - (lse_ref[0, :, rows] * LOG2E).reshape(-1, 1))
        dp = _dot(do_ref[0, rows, :], v_ref[0, keys, :], _NT)
        if dropout_rate > 0.0:
            dp = jnp.where(tile.keep(q0, r0, p.shape, q_axis=0),
                           dp * (1.0 / (1.0 - dropout_rate)), 0.0)
        ds = p * (dp - delta_ref[0, :, rows].reshape(-1, 1))
        dq_scr[rows, :] += _dot(ds.astype(k.dtype), k, _NN)

    def body(masked, offset, crossed):
        if offset is None:
            return group(0, block_k, 0, masked, crossed)
        # a tile on the diagonal, a group of keys at a time: each against
        # the queries from its first key on alone
        kw = _part(block_k, DIAGONAL_GROUP)
        for r0 in range(0, block_k, kw):
            q0 = max(0, min(block_q, r0 - offset))
            if q0 < block_q:
                diagonal = r0 + kw - 1 > offset + q0
                group(r0, kw, q0, diagonal or tile.any_other, diagonal)

    tile.bodies(body)

    @pl.when(ki == last_k)
    def _finalize():
        dq_ref[0] = (dq_scr[...] * sm_scale).astype(dq_ref.dtype)


@functools.cache
def _bwd_calls(bh, s, h, d, dtypes, block_q, block_k, causal, sm_scale,
               use_seg, use_mask, use_bias, dropout_rate, fused, interpret,
               by_rows=False, rot=0):
    """The tiled backward at one call signature: (the function of its
    inputs that returns (dq, dk, dv), the grid of each kernel it runs by
    kind, their (masked, launched) tiles), built once a process as
    `_fwd_call` is. `fused`: one kernel ("bwd", under the scope
    `ds.flash_bwd`); else the dk/dv walk and the dq pass ("dkv", "dq").
    `dtypes` are q's, k's and v's; every kernel takes q, k, v, dO as
    [B*H, S, D], lse and delta as [B*H, 1, S], then `_optional_inputs`.

    `by_rows` (`heads_in_place`; the fused kernel's): q^T, k^T, v^T and dO^T
    in, dq^T, dk^T and dv^T out, each [B, H*D, S], a head's (1, D, block)
    at row block `head` of row `batch`; lse and delta as ever. `rot` (by
    rows): q^T and k^T are un-rotated, the rotary's table follows, and
    dq^T and dk^T are the un-rotated tensors' gradients
    (`_bwd_dkv_kernel`)."""
    n_q, n_k = s // block_q, s // block_k
    compact = causal   # mirror the forward's trapezoidal schedule
    flags = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                 block_k=block_k, n_k=n_k, use_seg=use_seg,
                 use_mask=use_mask, use_bias=use_bias,
                 dropout_rate=dropout_rate, compact=compact)

    assert fused or not by_rows
    assert not rot or (by_rows and causal and block_q % block_k == 0)

    def spec(ix, block, which):
        return _head_spec(ix, by_rows, h, d, block, which)

    def specs(ix):
        """The inputs' BlockSpecs, index maps written as (bh, qi, ki)."""
        row = pl.BlockSpec((1, 1, block_q),
                           ix(lambda bh, qi, ki: (bh, 0, qi)))
        q_spec = spec(ix, block_q, lambda qi, ki: qi)
        kv_spec = spec(ix, block_k, lambda qi, ki: ki)
        return [q_spec, kv_spec, kv_spec, q_spec, row, row] + \
            _optional_specs(ix, h, s, block_q, block_k, use_seg, use_mask,
                            use_bias, dropout_rate) + \
            _rotary_specs(ix, rot, block_q)

    # the two schedules launch the same tiles in another order
    masked = masked_tile_count(
        n_q, n_k, block_q, block_k, causal,
        always=use_mask or use_bias or dropout_rate > 0.0)

    # dk and dv accumulate per k column → column-major trapezoid; the
    # dense grid's order is (bh, ki, qi)
    if compact:
        dkv_maps = causal_grid_maps(n_q, n_k, block_q, block_k, "col")
        dkv_grid = (bh, len(dkv_maps[0]))
    else:
        dkv_maps = ()
        dkv_grid = (bh, n_k, n_q)
    ixc = _index_adapter(compact, kv_major=True)
    kv_spec = spec(ixc, block_k, lambda qi, ki: ki)
    shape = (bh // h, h * d, s) if by_rows else (bh, s, d)
    dq_shape = jax.ShapeDtypeStruct(shape, dtypes[0])
    dkv_shapes = [jax.ShapeDtypeStruct(shape, dtypes[1]),
                  jax.ShapeDtypeStruct(shape, dtypes[2])]
    if fused:
        acc = pltpu.VMEM((d, block_k), jnp.float32)   # dk^T, dv^T
        run = _tiled_call(
            "bwd", "ds.flash_bwd",
            functools.partial(_bwd_dkv_kernel, n_q=n_q, fused=True,
                              by_rows=by_rows, rotary=bool(rot), **flags),
            compact, dkv_grid, specs(ixc),
            [kv_spec, kv_spec, spec(ixc, block_q, lambda qi, ki: _dq_block(
                qi, ki, n_k, block_q, block_k, causal))],
            [acc, acc, pltpu.VMEM((n_q, d, block_q), jnp.float32)]
            + _key_column_scratch(block_k, use_seg, use_bias)
            # k and v of a column's k^T and v^T blocks; its rotated k^T,
            # its columns of the rotary's table, the step's rotated q^T
            + [pltpu.VMEM((block_k, d), dtypes[i]) for i in (1, 2)] * by_rows
            + [pltpu.VMEM((d, block_k), dtypes[1]),
               pltpu.VMEM((2 * rot, block_k), jnp.float32),
               pltpu.VMEM((d, block_q), dtypes[0])] * bool(rot),
            dkv_shapes + [dq_shape], dkv_maps, interpret,
            vmem_limit=flash_bwd_vmem_limit(s, d))

        def fused_run(*inputs):
            dk, dv, dq = run(*inputs)
            return dq, dk, dv
        return fused_run, {"bwd": dkv_grid}, masked

    dkv_run = _tiled_call(
        "dkv", "ds.flash_bwd_dkv",
        functools.partial(_bwd_dkv_kernel, n_q=n_q, **flags), compact,
        dkv_grid, specs(ixc), [kv_spec, kv_spec],
        [pltpu.VMEM((block_k, d), jnp.float32)] * 2
        + _key_column_scratch(block_k, use_seg, use_bias),
        dkv_shapes, dkv_maps, interpret)

    # dq accumulates per q row → row-major trapezoid (same as fwd)
    if compact:
        dq_maps = causal_grid_maps(n_q, n_k, block_q, block_k, "row")
        dq_grid = (bh, len(dq_maps[0]))
    else:
        dq_maps = ()
        dq_grid = (bh, n_q, n_k)
    ix = _index_adapter(compact)
    dq_run = _tiled_call(
        "dq", "ds.flash_bwd_dq",
        functools.partial(_bwd_dq_kernel, **flags), compact,
        dq_grid, specs(ix),
        spec(ix, block_q, lambda qi, ki: qi),
        [pltpu.VMEM((block_q, d), jnp.float32)], dq_shape, dq_maps,
        interpret)

    def two_runs(*inputs):
        dk, dv = dkv_run(*inputs)
        return dq_run(*inputs), dk, dv
    return two_runs, {"dkv": dkv_grid, "dq": dq_grid}, masked


def _bwd(causal, sm_scale_arg, block_q, block_k, res, g, layout=None,
         kbias=None, dropout_rate=0.0, seed=None, seg=None, table=None):
    """(dq, dk, dv) [B, S, H, D] from a forward's residuals and the
    cotangent `g` of its out. The residuals of a forward that took the
    heads in place ([B, H, D, S]: `_fwd`) go to the fused kernel as they
    are, with dO^T, and dq^T, dk^T and dv^T come back ([B, H*D, S]:
    `_to_rows`). A backward of one block, or of the two kernels of a
    sequence over the slab's budget, moves them to [B*H, S, D] first, as
    every other forward's residuals are, with dO, and moves dq, dk and dv
    back. `table`: as `_fwd` took it; the residuals' q and k are
    un-rotated and so are what dq and dk are the gradients of."""
    qb, kb, vb, out, lse = res
    bdim, s, h, d = g.shape
    bh = bdim * h
    block_q, block_k = _fit_block(block_q, s), _fit_block(block_k, s)
    lse = lse.reshape(bh, 1, s)     # row-vector layout, lanes = seq
    sm_scale = sm_scale_arg if sm_scale_arg is not None else \
        1.0 / math.sqrt(d)
    single = s // block_q == 1 and s // block_k == 1 and layout is None \
        and seg is None
    fused = flash_dq_slab_admitted(s, d)
    in_place = qb.ndim == 4 and fused and not single
    assert in_place or table is None
    if in_place:
        do = g.transpose(0, 2, 3, 1)                           # [B, H, D, S]
        # summed where dO and out lie: over D, the rows of a head's block
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=2).reshape(bh, 1, s)
        qb, kb, vb, do = (x.reshape(bdim, h * d, s)
                          for x in (qb, kb, vb, do))
        back = functools.partial(_from_rows, h=h)
    else:
        if qb.ndim == 4:
            qb, kb, vb, out = (x.transpose(0, 1, 3, 2).reshape(bh, s, d)
                               for x in (qb, kb, vb, out))
        # g arrives as [B, S, H, D]; reshape like the saved qb.
        do = _to_bh(g)
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1).reshape(bh, 1, s)             # [BH, 1, S]

        back = functools.partial(_from_bh, h=h)

    if single:
        _LAST_BLOCKS["dkv"] = _LAST_BLOCKS["dq"] = (s, s)
        _LAST_BLOCKS["bwd_variant"] = "single"
        with scopes.scope("ds.flash_bwd"):
            dq, dk, dv = _bwd_single(qb, kb, vb, do, lse, delta, causal,
                                     sm_scale, s, d, _interpret(),
                                     kbias=kbias, h=h,
                                     dropout_rate=dropout_rate, seed=seed)
        return back(dq), back(dk), back(dv)

    _HEADS["bwd"]["in_place" if in_place else "moved"] += 1
    _LAST_BLOCKS["dkv"] = _LAST_BLOCKS["dq"] = (block_q, block_k)
    _LAST_BLOCKS["bwd_variant"] = "fused-" * fused + \
        ("trapezoid" if causal else "dense")
    run, grids, masked = _bwd_calls(
        bh, s, h, d, (qb.dtype, kb.dtype, vb.dtype), block_q, block_k,
        causal, sm_scale, seg is not None, layout is not None,
        kbias is not None, dropout_rate, fused, _interpret(), in_place,
        _rot_dim(table))
    # what the most recent backward launched, and nothing an earlier one did
    for kind in ("bwd", "dkv", "dq"):
        _LAST_GRIDS.pop(kind, None)
        _LAST_MASKED.pop(kind, None)
    _LAST_GRIDS.update(grids)
    _LAST_MASKED.update(dict.fromkeys(grids, masked))
    dq, dk, dv = run(qb, kb, vb, do, lse, delta, *_optional_inputs(
        seg, layout, kbias, seed, dropout_rate),
        *[table] * (table is not None))
    return back(dq), back(dk), back(dv)


def _resolve_blocks(shape, causal, block_q, block_k, bwd_blocks):
    """((fwd block_q, block_k), (bwd block_q, block_k)) of a public
    call: `ops.autotune.flash_blocks` decides unless the caller pins. A
    caller that pins the forward pair alone pins the backward to it."""
    if block_q is None and block_k is None:
        fwd, bwd = flash_blocks(shape, causal)
    else:
        fwd = bwd = (block_q or BLOCK_Q, block_k or BLOCK_K)
    return fwd, tuple(bwd_blocks) if bwd_blocks is not None else bwd


def flash_attention(q, k, v, causal=True, sm_scale=None, block_q=None,
                    block_k=None, bwd_blocks=None, rotary=None):
    """Tiled online-softmax attention on [B, S, H, D].

    Block geometry comes from `ops.autotune.flash_blocks` at the call's
    own shape; pass `block_q` / `block_k` and / or `bwd_blocks` (a
    `(bwd_block_q, bwd_block_k)` tuple for the dkv/dq kernels, whose
    working set is larger than the forward's) only to pin it. The saved
    residuals (out, lse) are block-independent, so forward and backward
    geometry can differ freely.

    Where the heads are read: a tiled call with as many KV heads as query
    heads and a head dim of 16 rows or a multiple (64, 128, 256) takes
    them IN PLACE (`heads_in_place`): the forward and the fused backward
    read q^T, k^T, v^T, dO^T and write out^T, dq^T, dk^T, dv^T as
    [B, H*D, S], which on a TPU is the layout a train step's
    `[B, S, H, D]` tensors have, so no tensor is copied on either side of
    either kernel, and the residuals are the operands themselves. MOVED
    to [B*H, S, D] and back, by a copy of each: a call of one block (the
    single-block kernels), fewer KV heads than query heads, and a
    backward whose dq slab is over the budget (the two kernels; it moves
    the residuals it was left). No option chooses: the shape does.
    `ops.dispatch_report()["flash"]["heads"]` counts both.

    `rotary` = (cos, sin, rot_dim), cos and sin [S, rot_dim] of the one
    position stream every row shares: q and k are the projections as they
    are, and the KERNELS apply the rotate-half rotary to their first
    `rot_dim` features (`models/gpt_neox.py::apply_rotary`'s arithmetic, in
    float32, rounded once) where they load a block, and take it back out
    of dq and dk where they store them: no pass over q, k, dq or dk in
    front of or behind the call, and the residuals are the un-rotated
    projections. Only where `rotates_in_kernel` admits the call (ask it
    first and keep the XLA rotary where it says no: a call it does not
    admit raises). `ops.dispatch_report()["flash"]["rotary"]` counts."""
    (bq, bk), bwd = _resolve_blocks(q.shape, causal, block_q, block_k,
                                    bwd_blocks)
    if rotary is None:
        return _flash_attention(q, k, v, causal, sm_scale, bq, bk, bwd)
    cos, sin, rot_dim = rotary
    _, s, h, d = q.shape
    if not (flash_attention_supported(q.shape) and cos.ndim == 2 and
            _rotates(s, h, k.shape[2], d, q.dtype.itemsize, causal, rot_dim,
                     (_fit_block(bq, s), _fit_block(bk, s)),
                     tuple(_fit_block(x, s) for x in bwd))):
        raise ValueError(
            f"flash_attention cannot rotate q {tuple(q.shape)} / k "
            f"{tuple(k.shape)} by {rot_dim} features of tables "
            f"{tuple(cos.shape)} in its kernels (`rotates_in_kernel`): "
            f"rotate them first and call without `rotary`")
    # a feature a row, a position a column (the blocks' own orientation),
    # cos over sin
    table = jnp.concatenate([t[:, :rot_dim].astype(jnp.float32).T
                             for t in (cos, sin)])
    return _flash_rotating(q, k, v, table, causal, sm_scale, bq, bk, bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, sm_scale, block_q, block_k,
                     bwd_blocks):
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    out, _ = _fwd(q, k, v, causal, scale, block_q, block_k, in_place=True)
    return out


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, bwd_blocks):
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    out, res = _fwd(q, k, v, causal, scale, block_q, block_k, in_place=True)
    return out, res


def _flash_bwd(causal, sm_scale, block_q, block_k, bwd_blocks, res, g):
    return _bwd(causal, sm_scale, *bwd_blocks, res, g)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_rotating(q, k, v, table, causal, sm_scale, block_q, block_k,
                    bwd_blocks):
    """`_flash_attention` of the UN-rotated q and k and the rotary's
    table [2 x rot, S]: the kernels rotate."""
    return _flash_rotating_fwd(q, k, v, table, causal, sm_scale, block_q,
                               block_k, bwd_blocks)[0]


def _flash_rotating_fwd(q, k, v, table, causal, sm_scale, block_q, block_k,
                        bwd_blocks):
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    out, res = _fwd(q, k, v, causal, scale, block_q, block_k, in_place=True,
                    table=table)
    return out, (res, table)


def _flash_rotating_bwd(causal, sm_scale, block_q, block_k, bwd_blocks,
                        res_table, g):
    res, table = res_table
    dq, dk, dv = _bwd(causal, sm_scale, *bwd_blocks, res, g, table=table)
    # the table is the step's constant: positions are not learned
    return dq, dk, dv, jnp.zeros_like(table)


_flash_rotating.defvjp(_flash_rotating_fwd, _flash_rotating_bwd)


def flash_attention_segmented(q, k, v, segment_ids, causal=True,
                              sm_scale=None, block_q=None, block_k=None,
                              bwd_blocks=None, window=None, mask_block=0):
    """Flash attention over PACKED ragged batches: tokens attend only
    within their own document (`segment_ids` [B, S] int32, 0 = pad —
    see `runtime.packing`), composed with the causal mask.

    Masking is block-granular first, element-granular second: each tile
    compares its q-block and k-block segment-id slices and SKIPS the
    whole tile (no matmul, no softmax — the same `pl.when` gate as the
    dense grid's causal gating) when no id is shared; surviving tiles
    mask the stray cross-document elements to -inf. The fwd, dkv and dq
    kernels all carry the gate, so packed batches spend MXU time only on
    intra-document attention. Fully-masked rows follow the layout-mask
    kernels' poisoned-lse convention (zero output, zero grads).

    segment_ids is data, not a parameter: its cotangent is float0
    (int inputs cannot carry gradients). Block geometry as in
    `flash_attention`.

    Forward only (a serving prefill's): `k` / `v` [B, S, G, D] with G
    KV heads under q's H (query head h reads KV head ``h // (H / G)``),
    and / or a `window` (a static int; causal): key j is visible to
    query i iff ``j <= i and i - j < window``, the tiles wholly behind
    the window are never launched, and the call runs under the scope
    `ds.flash_fwd_window`; and / or `mask_block` (a static power of two
    up to 128; causal): the BLOCK-causal mask of a model that generates a
    block of positions at a time, key j visible to query i iff
    ``j // mask_block <= i // mask_block``, on the tiles the causal rule
    launches. The backward kernels compute none of the three, so a
    gradient through such a call raises.
    """
    (bq, bk), bwd = _resolve_blocks(q.shape, causal, block_q, block_k,
                                    bwd_blocks)
    if mask_block and (not causal or mask_block & (mask_block - 1) or
                       not 2 <= mask_block <= 128):
        raise ValueError(f"mask_block {mask_block!r} needs causal "
                         f"attention and a power of two from 2 to 128")
    if window is not None or k.shape[2] != q.shape[2] or mask_block:
        if window is not None and (not causal or int(window) < 1):
            raise ValueError(f"window {window!r} needs causal attention "
                             f"and a positive int")
        if q.shape[2] % k.shape[2] or k.shape != v.shape:
            raise ValueError(f"KV heads {k.shape} / {v.shape} do not "
                             f"divide the query heads {q.shape}")
        if window is not None and block_q is None and block_k is None:
            # a tile wider than the window computes mostly masked scores
            cap = max(int(window), 128)
            bq = _fit_block(min(bq, cap), q.shape[1]) or bq
            bk = _fit_block(min(bk, cap), q.shape[1]) or bk
        return _flash_serving(q, k, v, segment_ids, causal, sm_scale, bq,
                              bk, window, mask_block)
    return _flash_segmented(q, k, v, segment_ids, causal, sm_scale, bq,
                            bk, bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_serving(q, k, v, segment_ids, causal, sm_scale, block_q,
                   block_k, window, mask_block=0):
    """The segmented forward with grouped KV heads, a window and / or a
    block-causal mask."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    seg3 = segment_ids.astype(jnp.int32).reshape(
        segment_ids.shape[0], 1, -1)
    return _fwd(q, k, v, causal, scale, block_q, block_k, seg=seg3,
                window=window, mask_block=mask_block)[0]


def _flash_serving_fwd(*args):
    raise NotImplementedError(
        "flash attention with grouped KV heads, a window or a block-causal "
        "mask has no backward: the dq / dkv kernels compute full causal "
        "attention with one KV head a query head (training of a planned "
        "block is not built)")


_flash_serving.defvjp(_flash_serving_fwd, lambda *a: None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_segmented(q, k, v, segment_ids, causal, sm_scale, block_q,
                     block_k, bwd_blocks):
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    seg3 = segment_ids.astype(jnp.int32).reshape(
        segment_ids.shape[0], 1, -1)
    out, _ = _fwd(q, k, v, causal, scale, block_q, block_k, seg=seg3)
    return out


def _flash_seg_fwd(q, k, v, segment_ids, causal, sm_scale, block_q,
                   block_k, bwd_blocks):
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    seg3 = segment_ids.astype(jnp.int32).reshape(
        segment_ids.shape[0], 1, -1)
    out, res = _fwd(q, k, v, causal, scale, block_q, block_k, seg=seg3)
    return out, (res, segment_ids)


def _flash_seg_bwd(causal, sm_scale, block_q, block_k, bwd_blocks,
                   res_seg, g):
    import numpy as np
    res, segment_ids = res_seg
    seg3 = segment_ids.astype(jnp.int32).reshape(
        segment_ids.shape[0], 1, -1)
    dq, dk, dv = _bwd(causal, sm_scale, *bwd_blocks, res, g, seg=seg3)
    return dq, dk, dv, np.zeros(segment_ids.shape, jax.dtypes.float0)


_flash_segmented.defvjp(_flash_seg_fwd, _flash_seg_bwd)


def flash_attention_kbias(q, k, v, kbias, causal=False, sm_scale=None,
                          block_q=None, block_k=None):
    """Flash attention with an additive PER-KEY bias fused into the
    softmax — the TPU-native form of the reference's mask-taking fused
    softmax kernel (`csrc/transformer/softmax_kernels.cu:18-140`,
    ``attn_softmax(vals, attn_mask, ...)``): key-padding / attention
    masks ride the tiled online softmax instead of materializing a
    [B, H, S, S] score tensor.

    kbias: [B, S] float32, added to every query row's scores for that
    batch (0 = keep; ~-1e30 = masked; finite values act as biases).
    Rows whose keys are ALL masked produce zero output and zero grads
    (poisoned-lse convention shared with the layout-mask kernels).

    NOT differentiable w.r.t. kbias: its cotangent is hardwired to zero
    (it is an input mask/bias, not a parameter — the reference's
    attn_mask operand has the same contract). Do NOT route a TRAINABLE
    bias (ALiBi/relative-position tables) through kbias: jax.grad would
    silently return zeros for it. Wrap such biases into the scores
    outside the kernel, or extend the bwd kernels with the
    d(kbias) = Σ_h,q p·(dp − δ) reduction first.
    """
    (bq, bk), bwd = _resolve_blocks(q.shape, causal, block_q, block_k,
                                    None)
    return _flash_kbias(q, k, v, kbias, causal, sm_scale, bq, bk, bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_kbias(q, k, v, kbias, causal, sm_scale, block_q, block_k,
                 bwd_blocks):
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    kb3 = kbias.astype(jnp.float32).reshape(kbias.shape[0], 1, -1)
    out, _ = _fwd(q, k, v, causal, scale, block_q, block_k, kbias=kb3)
    return out


def _flash_kbias_fwd(q, k, v, kbias, causal, sm_scale, block_q, block_k,
                     bwd_blocks):
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    kb3 = kbias.astype(jnp.float32).reshape(kbias.shape[0], 1, -1)
    out, res = _fwd(q, k, v, causal, scale, block_q, block_k, kbias=kb3)
    return out, (res, kbias)


def _flash_kbias_bwd(causal, sm_scale, block_q, block_k, bwd_blocks,
                     res_kb, g):
    res, kbias = res_kb
    kb3 = kbias.astype(jnp.float32).reshape(kbias.shape[0], 1, -1)
    dq, dk, dv = _bwd(causal, sm_scale, *bwd_blocks, res, g, kbias=kb3)
    return dq, dk, dv, jnp.zeros_like(kbias)


_flash_kbias.defvjp(_flash_kbias_fwd, _flash_kbias_bwd)


def flash_attention_train(q, k, v, kbias, seed, causal=False,
                          sm_scale=None, block_q=None, block_k=None,
                          dropout_rate=0.0):
    """Training-mode flash attention: fused additive per-key mask AND
    in-kernel attention-probability dropout — the full fused stack of
    the reference's training transformer kernel (attn_softmax +
    attn_prob_dropout, `csrc/transformer/softmax_kernels.cu` /
    `dropout_kernels.cu`) with O(S) memory.

    kbias: [B, S] f32 additive mask/bias (see flash_attention_kbias —
    same non-differentiable contract) or None to skip the bias refs
    entirely (unmasked training pays no bias overhead).
    seed: int32 [1] array; the dropout mask is a deterministic hash of
    (seed, batch*head, row, col), so the backward pass regenerates the
    forward's mask exactly. Derive a fresh seed per step from the step
    rng. Dropout semantics are torch's dropout(softmax(s)): the
    denominator sums the undropped probabilities and survivors scale by
    1/keep. kbias and seed receive zero cotangents.
    """
    _check_dropout_rate(dropout_rate)
    (bq, bk), bwd = _resolve_blocks(q.shape, causal, block_q, block_k,
                                    None)
    return _flash_train(q, k, v, kbias, seed, causal, sm_scale, bq, bk,
                        bwd, dropout_rate)


def _check_dropout_rate(rate):
    """The survivor scale 1/(1-rate) is meaningless at rate >= 1 (inf/
    NaN outputs rather than an error) and negative rates silently keep
    everything — reject both at the entry point."""
    if not 0.0 <= rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got {rate}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_train(q, k, v, kbias, seed, causal, sm_scale, block_q, block_k,
                 bwd_blocks, dropout_rate):
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    kb3 = None if kbias is None else \
        kbias.astype(jnp.float32).reshape(kbias.shape[0], 1, -1)
    out, _ = _fwd(q, k, v, causal, scale, block_q, block_k, kbias=kb3,
                  dropout_rate=dropout_rate, seed=seed)
    return out


def _flash_train_fwd(q, k, v, kbias, seed, causal, sm_scale, block_q,
                     block_k, bwd_blocks, dropout_rate):
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    kb3 = None if kbias is None else \
        kbias.astype(jnp.float32).reshape(kbias.shape[0], 1, -1)
    out, res = _fwd(q, k, v, causal, scale, block_q, block_k, kbias=kb3,
                    dropout_rate=dropout_rate, seed=seed)
    return out, (res, kbias, seed)


def _flash_train_bwd(causal, sm_scale, block_q, block_k, bwd_blocks,
                     dropout_rate, res_kb, g):
    res, kbias, seed = res_kb
    kb3 = None if kbias is None else \
        kbias.astype(jnp.float32).reshape(kbias.shape[0], 1, -1)
    dq, dk, dv = _bwd(causal, sm_scale, *bwd_blocks, res, g,
                      kbias=kb3, dropout_rate=dropout_rate, seed=seed)
    dkb = None if kbias is None else jnp.zeros_like(kbias)
    return dq, dk, dv, dkb, jnp.zeros_like(seed)


_flash_train.defvjp(_flash_train_fwd, _flash_train_bwd)


def make_masked_flash_attention(layout128, causal=False, sm_scale=None,
                                block_q=None, block_k=None):
    """Dense-iteration flash attention honoring a STATIC 128-granular
    block layout: every tile is computed (dense-flash cost, independent
    of density) and inactive 128x128 blocks are masked to -inf — the
    exact block-sparse pattern semantics at dense-kernel throughput.

    This is the high-density arm of `SparseSelfAttention`'s auto
    dispatch: above the measured sparse-vs-dense crossover (~30% active
    blocks, docs/sparse-attention.md) iterating everything beats the
    sparse kernels' LUT/two-pass overheads.

    layout128: [H, S/128, S/128] numpy bool/int block-activity mask
    (static — baked into the compiled kernel's mask operand).
    Returns fn(q, k, v) on [B, S, H, D] with a custom VJP.
    """
    import numpy as np
    layout = jnp.asarray(np.asarray(layout128) != 0, jnp.int32)

    def check(q):
        # the SMEM mask index map clamps out-of-range blocks — mismatched
        # shapes would silently reuse wrong masks, so validate here (the
        # sparse arm raises the same way, block_sparse_attention.py:504)
        h, s = q.shape[2], q.shape[1]
        if h != layout.shape[0]:
            raise ValueError(
                f"got {h} heads, layout has {layout.shape[0]}")
        if s != layout.shape[1] * MASK_GRAIN:
            raise ValueError(
                f"got seq {s}, layout covers "
                f"{layout.shape[1] * MASK_GRAIN}")

    def fwd(q, k, v):
        check(q)
        scale = sm_scale if sm_scale is not None else \
            1.0 / math.sqrt(q.shape[-1])
        blocks, _ = _resolve_blocks(q.shape, causal, block_q, block_k,
                                    None)
        return _fwd(q, k, v, causal, scale, *blocks, layout=layout)

    @functools.partial(jax.custom_vjp, nondiff_argnums=())
    def fn(q, k, v):
        return fwd(q, k, v)[0]

    def bwd(res, g):
        # the cotangent has q's [B, S, H, D] shape
        _, blocks = _resolve_blocks(g.shape, causal, block_q, block_k,
                                    None)
        return _bwd(causal, sm_scale, *blocks, res, g, layout=layout)

    fn.defvjp(fwd, bwd)
    return fn
