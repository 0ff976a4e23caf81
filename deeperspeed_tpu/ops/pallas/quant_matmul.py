"""Quantized matmuls: int8 weight-only Pallas kernel + delayed-scaling
fp8/int8 fake-quant path.

Two distinct consumers share this module (docs/quantization.md):

1. **Weight-only int8 (serving).** Decode is weight-bandwidth bound (PR 8
   measured pre-stacking the block weights as a win before any flop
   change), so storing matmul weights as int8 with per-output-channel
   fp32 scales halves the bytes every decode step streams from HBM.
   `QuantizedWeight` is a registered pytree holding ``(qval int8 [K, N],
   scale fp32 [N])``; `quant_matmul` runs ``y = (x @ qval) * scale`` with
   the dequant INSIDE the kernel (the weight tile crosses the HBM→VMEM
   boundary at 1 byte/element, widens in VMEM, accumulates fp32). The
   XLA fallback computes the identical expression — per-channel scaling
   commutes with the contraction, so kernel and fallback agree to float
   tolerance and CPU tests run at XLA speed. Inference-only: there is no
   backward (weights at rest in int8 have no master to update).

2. **Delayed-scaling fp8/int8 (training).** The dense-FFN / grouped
   expert matmuls quantize BOTH operands per step using scales derived
   from an **amax history** (TransformerEngine-style delayed scaling:
   the scale applied at step t comes from the running max of |x| over
   the previous ``history_len`` steps, so the quantize step needs no
   fresh reduction of the current tensor before the matmul). The
   history rides `EngineState.quant` as a trailing-default field (the
   sentinel `HealthState` pattern) and is checkpointed for bit-exact
   resume. The quantize is a fake-quant (quantize→dequantize with a
   straight-through estimator), so the backward pass is the ordinary
   full-precision matmul transpose — exactly the reference recipe,
   where only the forward GEMM runs low-precision.

Bootstrap: a zero amax history (step 0, or a resumed-then-extended
history) falls back to the CURRENT tensor's amax for that step, so the
first quantized step never collapses to a degenerate scale.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import scopes
from ...compat import CompilerParams
from ..autotune import quant_matmul_blocks
from .flash_attention import _interpret, note_xla_on_tpu

_DIMSEM = CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))

# Test/bench observability: backend ("pallas"/"xla") of the most recent
# quant_matmul dispatch — `ops.dispatch_report()` surfaces it next to
# the flash/decode records.
_LAST_BACKEND = {}
_DISPATCH_LOGGED = False

# quantization targets per recipe: (qmax, cast dtype or None for round)
INT8_QMAX = 127.0
FP8_QMAX = 448.0      # float8_e4m3fn finite max
QUANT_RECIPES = ("int8", "fp8")


def _log_first_dispatch():
    """One structured log line at the first quant-matmul dispatch (the
    flash/decode kernels' discipline; `ops.dispatch_report()` queries)."""
    global _DISPATCH_LOGGED
    if _DISPATCH_LOGGED:
        return
    _DISPATCH_LOGGED = True
    from ...utils.logging import logger
    logger.info("ops.dispatch quant_matmul first dispatch: "
                f"backend={_LAST_BACKEND.get('quant_matmul')}")


# ---------------------------------------------------------------------------
# weight-only int8 (serving): QuantizedWeight + quant_matmul
# ---------------------------------------------------------------------------

class QuantizedWeight:
    """Int8 weight at rest + per-output-channel fp32 scales, as a pytree
    node: ``dequant = qval.astype(f32) * scale[None, :]``. Flows through
    jit/scan/stacking like any parameter leaf (its children stack/slice
    independently); the model block body dispatches matmuls on it via
    `models.gpt_neox._wmat`."""

    __slots__ = ("qval", "scale")

    def __init__(self, qval, scale):
        self.qval = qval
        self.scale = scale

    @property
    def shape(self):
        return self.qval.shape

    @property
    def ndim(self):
        return self.qval.ndim

    @property
    def dtype(self):
        return self.qval.dtype

    def dequant(self, dtype=jnp.float32):
        return (self.qval.astype(jnp.float32) *
                self.scale[..., None, :]).astype(dtype)

    def __repr__(self):
        return (f"QuantizedWeight(shape={tuple(self.qval.shape)}, "
                f"scale={tuple(self.scale.shape)})")


jax.tree_util.register_pytree_node(
    QuantizedWeight,
    lambda qw: ((qw.qval, qw.scale), None),
    lambda _, children: QuantizedWeight(*children))


def quantize_weight(w, qmax=INT8_QMAX):
    """[K, N] (or [..., K, N]) float weight → `QuantizedWeight` with
    per-output-channel symmetric scales over the contraction dim:
    ``scale[n] = max_k |w[k, n]| / 127``. Zero columns keep scale 1 (the
    quantized column is exactly zero either way)."""
    w = jnp.asarray(w)
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale[..., None, :]),
                 -qmax, qmax).astype(jnp.int8)
    return QuantizedWeight(q, scale)


def quant_matmul_supported(m, k, n, block_m, block_k, block_n):
    """Mosaic constraints for the real-TPU kernel: fitted blocks must
    tile the operands exactly (int8 min tile is (32, 128), fp32/bf16
    (8, 128)). Interpret mode (CPU tests) has no tiling rules."""
    if _interpret():
        return True
    return (m % block_m == 0 and k % block_k == 0 and n % block_n == 0
            and block_k % 32 == 0 and block_n % 128 == 0
            and block_m % 8 == 0)


def _fit(block, dim, align):
    """Largest `align`-multiple ≤ block dividing dim (dim itself when no
    aligned divisor exists — interpret-mode shapes)."""
    for cand in range(min(block, dim) - min(block, dim) % align, align - 1,
                      -align):
        if dim % cand == 0:
            return cand
    return dim


def _wq_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_k):
    """One (bm, bn) output tile: accumulate x[bm, bk] · dequant(q[bk, bn])
    over the k grid dim in fp32 scratch, scale once at the end.

    The weight tile is read as int8 (1 byte/element over the HBM→VMEM
    wire — the whole point) and widened in VMEM; per-channel scaling
    commutes with the k-contraction so one multiply at k == n_k-1
    replaces a dequant of every tile."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[:]
    w = q_ref[:].astype(x.dtype)
    acc_ref[:] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _finalize():
        o_ref[:] = (acc_ref[:] * s_ref[0, :][None, :]).astype(o_ref.dtype)


def quant_matmul_pallas(x, qw, block_m=256, block_k=512, block_n=256):
    M, K = x.shape
    N = qw.qval.shape[1]
    bm, bk, bn = (_fit(block_m, M, 8), _fit(block_k, K, 32),
                  _fit(block_n, N, 128))
    grid = (M // bm, N // bn, K // bk)
    kernel = functools.partial(_wq_kernel, n_k=grid[2])
    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_DIMSEM,
        interpret=_interpret(), name="ds.quant_matmul",
    )
    scale = qw.scale.reshape(1, N).astype(jnp.float32)
    with scopes.scope("ds.quant_matmul"):
        return call(x, qw.qval, scale)


def quant_matmul_xla(x, qw):
    """Fallback with identical semantics: widen the int8 weight, contract
    with fp32 accumulation, apply the per-channel scale to the output
    (scaling commutes with the contraction)."""
    y = jax.lax.dot_general(
        x, qw.qval.astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (y * qw.scale[None, :]).astype(x.dtype)


def quant_matmul(x, qw, backend=None, blocks=None):
    """``y[m, n] = sum_k x[m, k] · qval[k, n] · scale[n]`` — weight-only
    int8 matmul, fp32 accumulate, output in x.dtype.

    backend: None = auto (Pallas kernel on TPU when the fitted blocks
    tile the shape, XLA fallback otherwise — CPU tests keep XLA speed
    unless a test opts into the interpreter); "pallas"/"xla" force.
    blocks: (bm, bk, bn) to pin; None takes
    `ops.autotune.quant_matmul_blocks`.
    """
    if x.ndim != 2:
        lead = x.shape[:-1]
        y = quant_matmul(x.reshape(-1, x.shape[-1]), qw, backend=backend,
                         blocks=blocks)
        return y.reshape(lead + (y.shape[-1],))
    M, K = x.shape
    Kw, N = qw.qval.shape
    if K != Kw:
        raise ValueError(f"x contraction dim {K} != weight rows {Kw}")
    if qw.scale.shape != (N,):
        raise ValueError(f"scale shape {qw.scale.shape} != ({N},)")
    bm, bk, bn = blocks if blocks is not None else \
        quant_matmul_blocks(x.dtype)
    if backend is None:
        on_tpu = not _interpret()
        fits = quant_matmul_supported(M, K, N, _fit(bm, M, 8),
                                      _fit(bk, K, 32), _fit(bn, N, 128))
        backend = "pallas" if on_tpu and fits else "xla"
        if backend == "xla":
            note_xla_on_tpu(
                "quant_matmul",
                f"[M, K, N] = {(M, K, N)} with blocks {(bm, bk, bn)}: "
                f"the fitted blocks must tile the operands at the int8 "
                f"(32, 128) tile")
    _LAST_BACKEND["quant_matmul"] = backend
    _log_first_dispatch()
    if backend == "xla":
        return quant_matmul_xla(x, qw)
    if backend != "pallas":
        raise ValueError(f"unknown quant_matmul backend {backend!r}")
    return quant_matmul_pallas(x, qw, bm, bk, bn)


# ---------------------------------------------------------------------------
# delayed scaling (training): amax history + fake-quant matmul
# ---------------------------------------------------------------------------

def recipe_qmax(recipe):
    if recipe == "int8":
        return INT8_QMAX
    if recipe == "fp8":
        return FP8_QMAX
    raise ValueError(
        f"unknown quantization recipe {recipe!r}; expected one of "
        f"{list(QUANT_RECIPES)}")


def scale_from_history(history, current_amax, qmax, margin=1.0):
    """Delayed-scaling scale: ``margin · max(history) / qmax``, falling
    back to the current step's amax while the history is still all-zero
    (step 0 / freshly-extended state) so the bootstrap step never
    quantizes against a degenerate scale."""
    hist_amax = jnp.max(history)
    amax = jnp.where(hist_amax > 0.0, hist_amax, current_amax)
    amax = jnp.maximum(amax, 1e-12)
    return amax * jnp.asarray(margin, jnp.float32) / qmax


def amax_history_update(history, current_amax):
    """Roll the window one step and record the current amax at slot 0."""
    return jnp.roll(history, 1).at[0].set(current_amax)


def _fake_quant(v, scale, recipe):
    """Quantize→dequantize at `scale` with a straight-through estimator:
    the forward value is the exact representable low-precision value,
    the backward is identity (the reference delayed-scaling recipe runs
    only the forward GEMM low-precision)."""
    f = v.astype(jnp.float32) / scale
    if recipe == "int8":
        dq = jnp.clip(jnp.round(f), -INT8_QMAX, INT8_QMAX) * scale
    else:
        # SATURATING cast: float8_e4m3fn has no inf, so an out-of-range
        # conversion lands NaN — and a delayed scale is stale by
        # construction (this step's amax can exceed the history's), so
        # overflow WILL happen on amax-growth steps; clamp to the
        # representable range first (the TE saturation discipline)
        f = jnp.clip(f, -FP8_QMAX, FP8_QMAX)
        dq = (f.astype(jnp.float8_e4m3fn).astype(jnp.float32)) * scale
    dq = dq.astype(v.dtype)
    return v + jax.lax.stop_gradient(dq - v)


def scaled_matmul(x, w, hist_x, hist_w, recipe, margin=1.0,
                  dim_numbers=None):
    """One delayed-scaled matmul: quantize both operands with scales from
    their amax HISTORIES, contract with fp32 accumulation, and return
    ``(y, new_hist_x, new_hist_w)`` — the histories advanced with this
    step's amaxes (amax observation is stop-gradiented; it never enters
    the differentiated graph).

    ``dim_numbers`` defaults to a plain last-dim × first-dim contraction.
    """
    qmax = recipe_qmax(recipe)
    amax_x = jax.lax.stop_gradient(
        jnp.max(jnp.abs(x.astype(jnp.float32))))
    amax_w = jax.lax.stop_gradient(
        jnp.max(jnp.abs(w.astype(jnp.float32))))
    sx = scale_from_history(hist_x, amax_x, qmax, margin)
    sw = scale_from_history(hist_w, amax_w, qmax, margin)
    xq = _fake_quant(x, sx, recipe)
    wq = _fake_quant(w, sw, recipe)
    if dim_numbers is None:
        dim_numbers = (((x.ndim - 1,), (0,)), ((), ()))
    y = jax.lax.dot_general(xq, wq, dim_numbers,
                            preferred_element_type=jnp.float32)
    return (y.astype(x.dtype),
            amax_history_update(hist_x, amax_x),
            amax_history_update(hist_w, amax_w))


def grouped_scaled_operands(x, w, hist_x, hist_w, recipe, margin=1.0):
    """Delayed-scaling fake-quant of a grouped-expert-matmul operand
    pair: `x` [R, K] (the span-packed token buffer) and `w` [E, K, N]
    (stacked expert weights) are quantized against their amax histories
    and fed UNCHANGED into `grouped_matmul` — the kernel's masking/LUT
    machinery is orthogonal to operand precision, so the sort-dispatch
    MoE engine gains the quantized forward without a second kernel.
    Returns (xq, wq, new_hist_x, new_hist_w)."""
    qmax = recipe_qmax(recipe)
    amax_x = jax.lax.stop_gradient(
        jnp.max(jnp.abs(x.astype(jnp.float32))))
    amax_w = jax.lax.stop_gradient(
        jnp.max(jnp.abs(w.astype(jnp.float32))))
    sx = scale_from_history(hist_x, amax_x, qmax, margin)
    sw = scale_from_history(hist_w, amax_w, qmax, margin)
    return (_fake_quant(x, sx, recipe), _fake_quant(w, sw, recipe),
            amax_history_update(hist_x, amax_x),
            amax_history_update(hist_w, amax_w))


# per-block dense-FFN amax state layout: 4 tensors (ffn-in x/w,
# ffn-out x/w), each with its own history row — models.gpt_neox
# threads one [4, history_len] row per layer through the block scan
# (the MoE sort-dispatch grouped path reuses the same 4-row layout:
# in-buf/in-w, out-buf/out-w)
FFN_AMAX_TENSORS = 4


def init_amax_history(num_layers, history_len,
                      n_tensors=FFN_AMAX_TENSORS):
    """Zero-initialized per-layer amax history: [L, n_tensors, H]."""
    return jnp.zeros((int(num_layers), int(n_tensors), int(history_len)),
                     jnp.float32)


def ffn_scaled_matmuls(x2d, w_in, b_in, w_out, amax_row, recipe,
                       margin=1.0, activation=jax.nn.gelu):
    """The dense-FFN pair under delayed scaling: in-proj → gelu →
    out-proj, both matmuls quantized against `amax_row` [4, H] (rows:
    in-x, in-w, out-x, out-w). Returns (y2d, new_amax_row); the output
    bias is NOT added (callers fold it after their reduce, mirroring
    the row-parallel bias discipline of the plain FFN)."""
    h, hx_in, hw_in = scaled_matmul(x2d, w_in.astype(x2d.dtype),
                                    amax_row[0], amax_row[1], recipe,
                                    margin)
    h = activation(h + b_in.astype(h.dtype))
    y, hx_out, hw_out = scaled_matmul(h, w_out.astype(h.dtype),
                                      amax_row[2], amax_row[3], recipe,
                                      margin)
    new_row = jnp.stack([hx_in, hw_in, hx_out, hw_out])
    return y, new_row
