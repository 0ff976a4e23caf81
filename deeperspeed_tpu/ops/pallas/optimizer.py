"""Fused flat-shard optimizer kernels (reference:
`csrc/adam/multi_tensor_adam.cu` + `multi_tensor_apply.cuh` — one CUDA
kernel applying Adam across chunked tensor lists).

TPU-native shape of the same idea: ZeRO keeps each rank's optimizer
partition as ONE flat fp32 shard, so "multi-tensor apply" degenerates to a
single elementwise kernel over that shard. The Pallas kernel below reads
param/grad/m/v tiles from HBM through VMEM once and writes the three
updated arrays — one fused pass, no per-leaf kernel launches and no
intermediate HBM round-trips. Hyperparameters arrive as scalar-prefetch
operands so LR/beta changes never recompile.

The engine's default on-device path keeps the per-leaf XLA-fused update
(XLA emits the same fused elementwise kernel per parameter); this flat
variant serves the flat-partition paths (ZeRO stage-1/2 standalone
optimizers, host-offload staging buffers) where the state already lives
as one contiguous shard.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import scopes
from .flash_attention import _interpret

LANES = 128
SUBLANES = 8
_TILE = 8 * 1024  # elements per grid step (fp32: 4 arrays * 32 KiB in VMEM)


def _adam_kernel(scalars, p_ref, g_ref, m_ref, v_ref,
                 p_out, m_out, v_out, *, adam_w):
    """One VMEM tile of the flat shard: standard Adam(W) update.

    scalars: [lr, beta1, beta2, eps, weight_decay, bias_c1, bias_c2]
    (bias_c* = 1 - beta^t precomputed; 1.0 when bias correction is off).
    """
    lr = scalars[0]
    beta1, beta2 = scalars[1], scalars[2]
    eps, wd = scalars[3], scalars[4]
    bias_c1, bias_c2 = scalars[5], scalars[6]

    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    if not adam_w:
        # classic Adam applies decay through the gradient/moments
        g = g + wd * p
    m = beta1 * m_ref[...] + (1.0 - beta1) * g
    v = beta2 * v_ref[...] + (1.0 - beta2) * g * g
    update = (m / bias_c1) / (jnp.sqrt(v / bias_c2) + eps)
    if adam_w:
        update = update + wd * p
    p_out[...] = (p - lr * update).astype(p_out.dtype)
    m_out[...] = m
    v_out[...] = v


@functools.partial(jax.jit, static_argnames=("adam_w", "bias_correction"))
def fused_adam_flat(p, g, m, v, lr, step, *, beta1=0.9, beta2=0.999,
                    eps=1e-8, weight_decay=0.0, adam_w=True,
                    bias_correction=True):
    """Adam(W) over a flat 1-D shard → (new_p, new_m, new_v).

    `p` may be fp32 or bf16 (updated in its own dtype from the fp32 moment
    math); `m`/`v` must be fp32; `g` any float dtype. `lr`/`step` are
    traced scalars — schedules don't recompile.
    """
    n = p.shape[0]
    pad = (-n) % _TILE
    padded = n + pad

    def flat2d(x, dtype=None):
        x = x.astype(dtype) if dtype is not None else x
        if pad:
            # a full-shard copy — keep shards _TILE-aligned to avoid it
            x = jnp.pad(x, (0, pad))
        return x.reshape(padded // LANES, LANES)

    step_f = jnp.asarray(step, jnp.float32)
    if bias_correction:
        bias_c1 = 1.0 - jnp.asarray(beta1, jnp.float32) ** step_f
        bias_c2 = 1.0 - jnp.asarray(beta2, jnp.float32) ** step_f
    else:
        bias_c1 = bias_c2 = jnp.asarray(1.0, jnp.float32)
    scalars = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        jnp.asarray(beta1, jnp.float32),
        jnp.asarray(beta2, jnp.float32),
        jnp.asarray(eps, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32),
        bias_c1, bias_c2])

    rows_per_tile = _TILE // LANES
    grid = (padded // _TILE,)
    # index_map takes (grid_idx, scalar_ref) under scalar prefetch
    spec = pl.BlockSpec((rows_per_tile, LANES), lambda i, s: (i, 0))
    out_shapes = [
        jax.ShapeDtypeStruct((padded // LANES, LANES), p.dtype),
        jax.ShapeDtypeStruct((padded // LANES, LANES), jnp.float32),
        jax.ShapeDtypeStruct((padded // LANES, LANES), jnp.float32),
    ]
    kernel = functools.partial(_adam_kernel, adam_w=adam_w)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[spec] * 4, out_specs=[spec] * 3),
        out_shape=out_shapes,
        interpret=_interpret(), name="ds.adam",
    )
    operands = (scalars, flat2d(p), flat2d(g), flat2d(m), flat2d(v))
    with scopes.scope("ds.adam"):
        new_p, new_m, new_v = call(*operands)
    return (new_p.reshape(-1)[:n], new_m.reshape(-1)[:n],
            new_v.reshape(-1)[:n])


def adam_flat_reference(p, g, m, v, lr, step, *, beta1=0.9, beta2=0.999,
                        eps=1e-8, weight_decay=0.0, adam_w=True,
                        bias_correction=True):
    """Plain-jnp Adam(W) for kernel parity tests."""
    g = g.astype(jnp.float32)
    p32 = p.astype(jnp.float32)
    if not adam_w and weight_decay != 0:
        g = g + weight_decay * p32
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * g * g
    step_f = jnp.asarray(step, jnp.float32)
    c1 = 1 - beta1 ** step_f if bias_correction else 1.0
    c2 = 1 - beta2 ** step_f if bias_correction else 1.0
    update = (m / c1) / (jnp.sqrt(v / c2) + eps)
    if adam_w and weight_decay != 0:
        update = update + weight_decay * p32
    return (p32 - lr * update).astype(p.dtype), m, v
