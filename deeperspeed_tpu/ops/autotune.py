"""Kernel launch geometry, decided in one place: for each Pallas kernel a
pure function from the call's shape (and, for flash attention, the
device kind) to concrete blocks. Nothing here runs a kernel or reads a
clock: a candidate ladder, a VMEM screen that drops what Mosaic would
refuse, `fit_block` fitting to the call's dims, and for long flash
sequences a small checked-in table of blocks measured through the
benchmark. Two processes, or two hosts of one job, always get the same
program. (XLA tiles its own GEMMs; launch geometry is the knob that
remains ours.)

A kernel's public wrapper calls its function here when the caller passes
no blocks; a caller passes blocks only to pin them (a test, a plan).

The module also keeps the compile-time memory screen
(`compiled_memory_stats`, `memory_feasible`, `hbm_bytes_limit`) and the
two shape rules of a dot that is XLA's (`head_projection_plain`,
`head_projection_split`).
"""

import os

import jax
import jax.numpy as jnp


def _device_kind():
    try:
        return getattr(jax.devices()[0], "device_kind", "unknown")
    except Exception:
        return "unknown"


def fit_block(block, s):
    """Largest 128-multiple ≤ `block` that divides s (0 if none)."""
    for cand in range(min(block, s), 127, -128):
        if cand % 128 == 0 and s % cand == 0:
            return cand
    return 0


def whole_blocks(s):
    """`s` rounded up to the least length `fit_block` fits: whole 128-row
    blocks."""
    return -(-s // 128) * 128


# ---------------------------------------------------------------------------
# attention's projection to heads (models/gpt_neox.py::_heads_dot)
# ---------------------------------------------------------------------------

def head_projection_plain(rows, k):
    """Does `x[rows, k] @ w[k, out]`, whose result is reshaped to heads,
    keep that reshape out of the dot? XLA folds it in (a convolution
    over the heads) and pays with a re-layout of the WEIGHT, `k x out`;
    kept out, it is a re-layout of the RESULT, `rows x out`: the plain
    form wins where the result is the smaller of the two. A fact of the
    two shapes: every decode step is plain (16-256 rows under a hidden
    size of 1,024 and up) and so is a prefill bucket under the hidden
    size; a train step's 16 x 2048 rows are XLA's to fold. Measured on a
    v5e at k = 2048, out = 6144, 24 layers (PERF.md section 6, PR 40):
    a prefill program is 0.80 ms shorter plain at 128 rows, 0.14 ms at
    1,536, 0.16 ms LONGER at 2,048."""
    return rows < k


def head_projection_split(head_dim, tiled_in_place):
    """Does the fused QKV projection run as THREE dots against the q, k
    and v columns of its one weight (`gpt_neox._block_qkv`)? Where the
    tiled flash kernels are about to read the heads in place
    (`tiled_in_place`: `flash_attention.tiled_in_place` of the call
    `causal_attention` hands to `flash_attention`, a train step's) and
    the head dim is under one lane tile, the layout XLA holds such heads
    in with the SEQUENCE minor.

    The evidence is compiled text (one layer's loss and gradients for a
    described v5e, `tests/test_tpu_compile.py`; PERF.md section 6, PR 59).
    At 16 heads of 64 the one fused dot is a convolution that writes
    `[B, S, H, 3, D]` feature-minor, and the per-head `[H, 3, D]`
    interleave between it and the kernels costs a
    `copy bf16[B,S,3*hidden]` a pass (16 x 2,048 tokens; an async copy
    of it at 1 x 16,384) and the backward a pass that assembles dq, dk,
    dv into one `dqkv`. Three dots have nothing between them and the
    kernels: XLA writes each result sequence-minor, `[B, H*D, S]`, the
    kernels' operand, and the program holds no copy of `B*S*hidden` elements
    or of three times that. At 16 heads of 128 XLA lays a head dim of a
    whole lane tile the other way, and the split gains nothing (4 x 2,048
    tokens: six copies of `B*S*hidden` where the fused form has one of
    `3*B*S*hidden`; forced there, the four-chip cell read 15,802 tok/s/chip
    against 15,812 and 15,816): the fused form stays. A serving forward, a
    decode step, a call of one block and the XLA fallback do not read the
    heads in place and keep it too."""
    return bool(tiled_in_place) and head_dim < 128


# ---------------------------------------------------------------------------
# flash attention (ops/pallas/flash_attention.py)
# ---------------------------------------------------------------------------

# Candidate (block_q, block_k) geometries, the default first. Non-square
# entries exist for the compacted causal grid: its trapezoid rows grow
# with qi, so a fat block_q with a narrower block_k keeps per-instance
# VMEM bounded while the schedule already skips the dead tiles.
FLASH_BLOCK_CANDIDATES = ((1024, 1024), (2048, 1024), (1024, 512),
                          (2048, 512), (512, 512), (512, 1024),
                          (1024, 256), (512, 256), (256, 512),
                          (256, 256), (256, 128), (128, 128))

# The geometry of every call under FLASH_LONG_SEQ tokens, forward and
# backward alike.
FLASH_BLOCK_Q, FLASH_BLOCK_K = FLASH_BLOCK_CANDIDATES[0]

# The fp32 [block_q, block_k] score tile is the VMEM limiter of the
# forward and of every backward kernel: Mosaic holds a few copies of it (the
# scores, their exponentials, the cast operand of the second matmul).
# 4 MiB admits 1024 x 1024 and 2048 x 512 and drops 2048 x 1024, which a
# described v5e refuses at head dim 64, 128 and 256, forward and backward
# (tests/test_tpu_compile.py compiles what this admits).
_FLASH_SCORE_TILE_BUDGET = 4 << 20

# Sequences at or over this read FLASH_LONG_SEQ_BLOCKS. Under it every
# call runs the default: what the two 2k train cells and the serve cells'
# prefill trace.
FLASH_LONG_SEQ = 8192

# (device_kind, head_dim, causal) -> ((fwd block_q, block_k),
#                                     (bwd block_q, block_k)),
# each row a pair of blocks a benchmark cell measured on that chip. A
# shape class without a row takes `_flash_fallback`; rows are not
# invented for shapes no cell runs (head dim 128 at 8k and over,
# non-causal long sequences: ROADMAP.md, D10). Head dim 256, causal,
# forward (the latent cell's expanded prefill, 8k-16k) was measured and
# has no row because the fallback won: (1024, 1024) 6.98 / 25.3 ms at
# 8k / 16k against (1024, 512) 7.59 / 27.7, (512, 1024) 7.43 / 27.0,
# (512, 512) 8.44 / 31.6; (2048, 512) ran out of VMEM (PERF.md, PR 35).
FLASH_LONG_SEQ_BLOCKS = {
    # pythia-410m.train_16k: of the geometries the old in-trace timer
    # picked in three runs (PR 23), the faster of the two that compiled
    ("TPU v5 lite", 64, True): ((1024, 512), (1024, 1024)),
}

def flash_blocks_admitted(block_q, block_k):
    """The VMEM screen: does the fp32 score tile fit its budget?"""
    return block_q * block_k * 4 <= _FLASH_SCORE_TILE_BUDGET


# The tiled backward is ONE kernel where dq of a (batch x head) can stay
# in VMEM while the dk/dv column walk crosses it: a float32 [D, S] slab,
# `S * D * 4` bytes, beside the score tile's copies. 8 MiB admits 16k
# tokens at head dim 128 and 32k at 64, which a described v5e compiles at
# (1024, 1024) blocks with the limit `flash_bwd_vmem_limit` asks for, and
# leaves 32k at 128 / 64k at 64 to the two kernels
# (tests/test_tpu_compile.py compiles both sides of the line).
_FLASH_DQ_SLAB_BUDGET = 8 << 20


def flash_dq_slab_admitted(s, d):
    """Does a sequence's dq slab fit VMEM: does the tiled backward run
    as one kernel? A fact of the input's shape and of nothing else."""
    return s * d * 4 <= _FLASH_DQ_SLAB_BUDGET


def flash_bwd_vmem_limit(s, d):
    """`vmem_limit_bytes` of the fused backward: the 16 MiB the dk/dv
    walk has by the compiler's default (the blocks' buffers, the score
    tile's copies) and the slab. The default alone holds the kernel up
    to 32k tokens at head dim 64 and not 16k at 128."""
    return (16 << 20) + s * d * 4


# The tiled forward on heads in place (`flash_attention.heads_in_place`)
# reads k^T blocks and its score matmuls want k: it turns a block on the
# block's first visit within the head and keeps the head's whole k, [S, D]
# in the input's dtype, in VMEM while the row walk crosses it. VMEM lays a
# minor dim under 128 out as a whole lane tile, so head dim 64 costs what
# 128 does: 4 MiB at 16k tokens of bfloat16, 8 MiB at 32k, which a
# described v5e compiles at the compiler's default limit at head dim 64
# and 128 (tests/test_tpu_compile.py compiles both sides of the line);
# 64k tokens turn each k block once a grid step, as every call did before.
_FLASH_K_SLAB_BUDGET = 8 << 20


def flash_k_slab_admitted(s, d, itemsize, causal):
    """Does the by-rows forward keep a head's turned k in VMEM (one
    transpose a k block and head) or turn its k block every grid step? A
    fact of the call's shape and of nothing else. Causal calls alone: the
    causal grid's second dimension is sequential, so a head's row walk
    meets a block first where it turns it; the dense grid declares its q
    rows `parallel`, and a core of a two-core chip could meet a block its
    own rows never turned."""
    return causal and s * max(d, 128) * itemsize <= _FLASH_K_SLAB_BUDGET


def _flash_fit(blocks, s):
    """`blocks` fitted to sequence `s`, or None where no 128-multiple
    under a requested size divides it."""
    fit = (fit_block(blocks[0], s), fit_block(blocks[1], s))
    return None if 0 in fit else fit


def _flash_fallback(s):
    """First candidate the screen admits and `s` fits, fitted: the
    default wherever some 128-multiple divides `s`."""
    for cand in FLASH_BLOCK_CANDIDATES:
        fit = _flash_fit(cand, s)
        if fit is not None and flash_blocks_admitted(*fit):
            return fit
    raise ValueError(f"no flash block candidate fits sequence {s}")


def _flash_env_blocks(env_name, s):
    """'bq,bk' from the environment, validated against the sequence and
    fitted, or None when unset."""
    raw = os.environ.get(env_name)
    if not raw:
        return None
    try:
        bq, bk = (int(x) for x in raw.split(","))
    except ValueError as e:
        raise ValueError(
            f"{env_name} must be 'bq,bk' ints, got {raw!r}") from e
    fit = _flash_fit((bq, bk), s)
    if fit is None:
        raise ValueError(
            f"{env_name}={raw} does not fit seq {s} "
            f"(needs a 128-multiple block dividing the sequence)")
    return fit


def flash_blocks(shape, causal, device_kind=None):
    """((fwd block_q, block_k), (bwd block_q, block_k)) for a flash call
    on [B, S, H, D] — under a mesh, the shard the kernel really sees.
    Always concrete, fitted pairs.

    At FLASH_LONG_SEQ and over: the row of FLASH_LONG_SEQ_BLOCKS for
    (device kind, head dim, causal). Under it, and without a row: the
    first candidate the VMEM screen admits (the default), for both
    passes.

    `DS_FLASH_BLOCKS` / `DS_FLASH_BWD_BLOCKS` ('bq,bk') replace the
    forward / backward pair. This is the only place that reads them, and
    they exist for one reader: `pythia-410m.train_16k` sets them to what
    the table's first row now holds (ROADMAP.md, D10)."""
    _, s, _, d = shape
    row = ()
    if s >= FLASH_LONG_SEQ:
        row = FLASH_LONG_SEQ_BLOCKS.get(
            (device_kind or _device_kind(), d, bool(causal)), ())
    fitted = [_flash_fit(blocks, s) for blocks in row]
    if fitted and None not in fitted:
        fwd, bwd = fitted
    else:
        fwd = bwd = _flash_fallback(s)
    return (_flash_env_blocks("DS_FLASH_BLOCKS", s) or fwd,
            _flash_env_blocks("DS_FLASH_BWD_BLOCKS", s) or bwd)


# ---------------------------------------------------------------------------
# Compile-time memory screening: an AOT lower+compile over abstract shapes
# costs seconds and zero HBM, where a program that does not fit costs a
# whole run (the planner's `aot_screen`).
# ---------------------------------------------------------------------------

# Per-generation HBM capacities (spec sheet), used when the runtime does
# not report `bytes_limit`.
_HBM_BYTES_BY_KIND = {
    "v5 lite": 16 << 30, "v5e": 16 << 30,
    "v5p": 95 << 30,
    "v4": 32 << 30,
    "v6": 32 << 30, "v6e": 32 << 30,
}


def hbm_bytes_limit(device=None):
    """Usable device-memory budget in bytes, or None when unknown (CPU
    backends report no limit — screening is then skipped)."""
    try:
        device = device or jax.devices()[0]
    except Exception:
        return None
    try:
        stats = device.memory_stats()
        if stats and stats.get("bytes_limit"):
            return int(stats["bytes_limit"])
    except Exception:
        pass
    kind = (getattr(device, "device_kind", "") or str(device)).lower()
    if getattr(device, "platform", "") != "tpu":
        return None
    for key, val in _HBM_BYTES_BY_KIND.items():
        if key in kind:
            return val
    # unknown TPU kind: no budget rather than a guess — screening must
    # never block a rung it cannot reason about (memory_feasible treats
    # None as "skip the screen")
    return None


def compiled_memory_stats(fn, abstract_args):
    """AOT-compile `fn` over `jax.ShapeDtypeStruct` args (nothing is
    materialized or executed) and return its `memory_analysis()` as a
    dict: argument/output/temp/alias bytes plus a `peak` estimate
    (args + outputs + temps − donated aliases). Returns None when the
    backend provides no analysis."""
    compiled = jax.jit(fn).lower(*abstract_args).compile()
    ma = compiled.memory_analysis()
    if ma is None:
        return None

    def field(name):
        v = getattr(ma, name, 0) or 0
        return int(v)

    stats = {
        "argument_bytes": field("argument_size_in_bytes"),
        "output_bytes": field("output_size_in_bytes"),
        "temp_bytes": field("temp_size_in_bytes"),
        "alias_bytes": field("alias_size_in_bytes"),
        "generated_code_bytes": field("generated_code_size_in_bytes"),
    }
    stats["peak"] = max(
        stats["argument_bytes"] + stats["output_bytes"]
        + stats["temp_bytes"] - stats["alias_bytes"], 0)
    return stats


def memory_feasible(fn, abstract_args, budget_bytes=None, safety=0.92,
                    extra_bytes=0):
    """Pre-screen a candidate program: does its compiled peak (plus
    `extra_bytes` of resident state the program does not see, e.g.
    optimizer moments) fit the device budget?

    Returns (fits, stats). Unknown budgets or backends without
    `memory_analysis` return (True, stats_or_None) — screening never
    blocks a rung it cannot reason about. `safety` holds back headroom
    for fragmentation and the runtime's own buffers."""
    if budget_bytes is None:
        budget_bytes = hbm_bytes_limit()
    try:
        stats = compiled_memory_stats(fn, abstract_args)
    except Exception as e:  # noqa: BLE001 - screening must not kill rungs
        from ..utils.logging import logger
        logger.info(f"memory screen: AOT compile failed "
                    f"({type(e).__name__}: {e}); skipping screen")
        return True, None
    if stats is None or budget_bytes is None:
        return True, stats
    need = stats["peak"] + int(extra_bytes)
    return need <= budget_bytes * safety, stats


# ---------------------------------------------------------------------------
# grouped expert matmul (ops/pallas/grouped_matmul.py — the sort-based
# MoE dispatch engine's FFN kernel)
# ---------------------------------------------------------------------------

# (block_m, block_n) targets, fattest first. The kernel fits each to the
# actual span/output dims.
GMM_BLOCK_CANDIDATES = ((512, 512), (512, 256), (256, 512), (256, 256),
                        (128, 256), (256, 128), (128, 128))

# Conservative per-instance VMEM bound for the static screen: the fwd
# working set is double-buffered x [bm, K] and w [K, bn] tiles plus the
# output tile; the bwd dw kernel's is the same order with a [K, bn] fp32
# accumulator block in place of the output tile.
_GMM_VMEM_BUDGET = 10 << 20


def gmm_vmem_bytes(block_m, block_n, k_dim, itemsize):
    """Estimated VMEM working set of one grouped-matmul instance
    (fwd/bwd superset): double-buffered input tiles + the fp32
    accumulator/output block (max of the fwd [bm, bn] and dw [K, bn])."""
    return (2 * (block_m * k_dim + k_dim * block_n) * itemsize
            + max(block_m * block_n, k_dim * block_n) * 4)


def grouped_matmul_blocks(k_dim, n_dim, dtype):
    """(block_m, block_n) for `grouped_matmul` at the given expert-FFN
    geometry: the fattest candidate inside the VMEM model. The SAME block
    pair serves both FFN matmuls — (k_dim → n_dim) and back (n_dim →
    k_dim) — so candidates are screened at BOTH contraction dims (an
    over-budget geometry is a Mosaic allocation failure, not a slow
    rung). Where nothing fits the model, the narrowest candidate."""
    itemsize = jnp.dtype(dtype).itemsize
    for c in GMM_BLOCK_CANDIDATES:
        if max(gmm_vmem_bytes(c[0], c[1], k_dim, itemsize),
               gmm_vmem_bytes(c[0], c[1], n_dim, itemsize)) \
                <= _GMM_VMEM_BUDGET:
            return c
    return GMM_BLOCK_CANDIDATES[-1]


# ---------------------------------------------------------------------------
# quantized weight-only matmul (ops/pallas/quant_matmul.py — the serving
# int8 decode/prefill weight path)
# ---------------------------------------------------------------------------

# (block_m, block_k, block_n) targets, the default first. The kernel fits
# each to the operands. The weight tile is int8 (1 byte/element), so fat
# k-blocks are cheap on the wire; the fp32 accumulator block is the VMEM
# limiter.
QMM_BLOCK_CANDIDATES = ((256, 512, 256), (512, 512, 256), (256, 512, 512),
                        (256, 256, 256), (128, 512, 256), (128, 256, 256),
                        (128, 256, 128))

_QMM_VMEM_BUDGET = 10 << 20


def qmm_vmem_bytes(block_m, block_k, block_n, itemsize):
    """Estimated VMEM working set of one quant-matmul instance:
    double-buffered x (compute dtype) and weight (int8) tiles, the fp32
    accumulator block, the scale row and the output tile."""
    return (2 * block_m * block_k * itemsize        # x tiles
            + 2 * block_k * block_n * 1             # int8 weight tiles
            + block_m * block_n * 4                 # fp32 accumulator
            + block_n * 4                           # scale row
            + block_m * block_n * itemsize)         # output tile


def quant_matmul_blocks(dtype):
    """(block_m, block_k, block_n) for `quant_matmul` with activations
    of `dtype`: the first candidate inside the VMEM model, else the
    narrowest."""
    itemsize = jnp.dtype(dtype).itemsize
    for c in QMM_BLOCK_CANDIDATES:
        if qmm_vmem_bytes(*c, itemsize=itemsize) <= _QMM_VMEM_BUDGET:
            return c
    return QMM_BLOCK_CANDIDATES[-1]
