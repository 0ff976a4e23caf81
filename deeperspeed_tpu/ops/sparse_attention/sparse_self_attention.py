"""Sparse self-attention module (reference:
`deepspeed/ops/sparse_attention/sparse_self_attention.py:174`).

Applies a `SparsityConfig`-driven block-sparse attention to q/k/v. The
reference composes three Triton ops (SDD matmul → block softmax → DSD
matmul); here one fused Pallas kernel does all three
(`..pallas.block_sparse_attention`), falling back to a dense masked XLA
path for shapes the kernel doesn't cover.
"""

import math

import numpy as np

import jax
import jax.numpy as jnp

from ..pallas.block_sparse_attention import BlockSparseAttention
from .matmul import MatMul
from .softmax import Softmax
from .sparsity_config import FixedSparsityConfig, SparsityConfig


def layout_to_token_mask(layout, block):
    """[H, nQ, nK] block layout → [H, S, S] boolean token mask."""
    layout = np.asarray(layout, bool)
    return np.repeat(np.repeat(layout, block, axis=1), block, axis=2)


def dense_masked_attention(q, k, v, token_mask, causal, sm_scale=None):
    """Reference/fallback path: dense attention with the block mask
    applied elementwise. [B, S, H, D] layout; token_mask is [H, S, S]
    (shared across batch) or [B, H, S, S] (e.g. with key padding)."""
    b, s, h, d = q.shape
    scale = sm_scale or 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = jnp.asarray(token_mask)
    if mask.ndim == 3:
        mask = mask[None]  # [1, H, S, S]
    if causal:
        mask = jnp.logical_and(mask, jnp.tril(jnp.ones((s, s), bool)))
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    # Fully-masked rows produce uniform probs over -1e30 → NaN-free zeros.
    probs = jnp.where(mask.any(axis=-1, keepdims=True), probs, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


class SparseSelfAttention:
    """Layout-cached sparse attention, one instance per layer.

    `forward(q, k, v)` takes [B, S, H, D] (the reference takes
    [B, H, S, D]; use `transpose_inputs=True` for that layout).
    """

    # Measured sparse-vs-dense crossover on v5e (docs/sparse-attention.md):
    # BigBird at 18% active wins on the sparse kernels, Fixed at 30%
    # loses — above this active-block fraction a dense-iteration masked
    # flash kernel (cost independent of density) is faster.
    DENSE_DISPATCH_DENSITY = 0.25

    def __init__(self, sparsity_config=None, key_padding_mask_mode="add",
                 attn_mask_mode="mul", max_seq_length=2048,
                 transpose_inputs=False, dense_dispatch_density=None):
        self.sparsity_config = sparsity_config or FixedSparsityConfig(
            num_heads=4)
        if not isinstance(self.sparsity_config, SparsityConfig):
            raise TypeError("sparsity_config must be a SparsityConfig")
        self.key_padding_mask_mode = key_padding_mask_mode
        self.attn_mask_mode = attn_mask_mode
        self.max_seq_length = max_seq_length
        self.transpose_inputs = transpose_inputs
        # auto kernel dispatch threshold; 1.0 forces the sparse kernels,
        # 0.0 forces the masked dense-flash path
        self.dense_dispatch_density = (
            self.DENSE_DISPATCH_DENSITY if dense_dispatch_density is None
            else dense_dispatch_density)
        self._cache = {}

    @property
    def block(self):
        return self.sparsity_config.block

    def get_layout(self, seq_len):
        if seq_len not in self._cache:
            layout = self.sparsity_config.make_layout(seq_len)
            causal = getattr(self.sparsity_config, "attention",
                             "bidirectional") == "unidirectional"
            kernel = None
            block = self.block
            if seq_len % 128 == 0 and block % 128 == 0:
                # Kernel path uses 128-sized blocks; coarser layouts are
                # refined to 128 granularity.
                refine = block // 128
                fine = np.repeat(np.repeat(layout, refine, axis=1),
                                 refine, axis=2)
                density = float(np.asarray(fine, bool).mean())
                # masked flash keeps the whole per-head block map in
                # SMEM; cap it (64x64 int32 = 16KB fits, 16k-seq maps
                # don't — those are low-density anyway)
                mask_fits_smem = fine.shape[1] * fine.shape[2] * 4 <= 32768
                if density >= self.dense_dispatch_density and \
                        mask_fits_smem:
                    # auto dispatch: dense-ish layouts run the masked
                    # dense-flash kernel (same pattern semantics, cost
                    # independent of density — never slower than dense)
                    from ..pallas.flash_attention import \
                        make_masked_flash_attention
                    kernel = make_masked_flash_attention(fine,
                                                         causal=causal)
                else:
                    kernel = BlockSparseAttention(fine, block=128,
                                                  causal=causal)
            # Mid-tier for masked/rpe calls: the reference's own
            # three-op pipeline (sdd → block softmax → dsd) — compute
            # still scales with active blocks, unlike the dense fallback.
            # fp32 scores into the softmax: parity with the fused-kernel
            # path's fp32 accumulation (don't round logits to bf16)
            ops = (MatMul(layout, block, "sdd", trans_b=True,
                          out_dtype=jnp.float32),
                   Softmax(layout, block),
                   MatMul(layout, block, "dsd"))
            self._cache[seq_len] = (layout, kernel, causal, ops)
        return self._cache[seq_len]

    def forward(self, query, key, value, rpe=None, key_padding_mask=None,
                attn_mask=None):
        if self.transpose_inputs:
            query, key, value = (x.transpose(0, 2, 1, 3)
                                 for x in (query, key, value))
        b, s, h, d = query.shape
        if s % self.block != 0:
            raise ValueError(
                f"sequence length {s} must be divisible by block "
                f"{self.block}")
        layout, kernel, causal, (sdd, softmax, dsd) = self.get_layout(s)

        use_kernel = (kernel is not None and d in (64, 128, 256)
                      and rpe is None and key_padding_mask is None
                      and attn_mask is None)
        from ...parallel.mesh import DATA_AXIS, per_shard
        from ..pallas.flash_attention import _LAST_BACKEND, note_xla_on_tpu
        _LAST_BACKEND["sparse_attention"] = "pallas" if use_kernel else "xla"
        if use_kernel:
            # the layout is per head, so only the batch splits per shard
            out = per_shard(kernel, (query, key, value), {0: DATA_AXIS})
        else:
            note_xla_on_tpu(
                "sparse_self_attention",
                f"seq {s}, block {self.block}, head dim {d}, rpe/masks "
                f"{rpe is not None or key_padding_mask is not None or attn_mask is not None}"
                f": the block-sparse kernels need 128-multiples, a head "
                f"dim of 64/128/256 and no rpe or mask operand")
            # The reference's own three-op pipeline (sdd → block softmax
            # → dsd, `sparse_self_attention.py:150-170`): compute scales
            # with active blocks and every mask/rpe option applies.
            qh, kh, vh = (x.transpose(0, 2, 1, 3)
                          for x in (query, key, value))     # [B, H, S, D]
            scores = sdd(qh, kh)
            am, am_mode = attn_mask, self.attn_mask_mode
            if causal:
                # unidirectional patterns leave intra-block causality to
                # the attention mask (block layouts are block-granular);
                # fold the triangular mask into any user mask additively
                from .softmax import _NEG, _mask_term
                tril = jnp.where(
                    jnp.tril(jnp.ones((s, s), jnp.bool_)), 0.0, _NEG)
                if am is not None:
                    am = _mask_term(jnp.asarray(am), am_mode) + tril
                else:
                    am = tril
                am_mode = "add"
            if (key_padding_mask is not None
                    and self.key_padding_mask_mode == "add"
                    and not jnp.issubdtype(
                        jnp.asarray(key_padding_mask).dtype,
                        jnp.floating)):
                raise ValueError(
                    "bool/int key_padding_mask with mode 'add' looks like "
                    "a 0/1 keep-mask: pass an additive float mask (e.g. "
                    "-1e4 on padded keys), or use "
                    "key_padding_mask_mode='mul' for keep-masks")
            probs = softmax(
                scores, scale=1.0 / math.sqrt(d), rpe=rpe,
                key_padding_mask=key_padding_mask, attn_mask=am,
                key_padding_mask_mode=self.key_padding_mask_mode,
                attn_mask_mode=am_mode)
            out = dsd(probs, vh).transpose(0, 2, 1, 3).astype(query.dtype)
        if self.transpose_inputs:
            out = out.transpose(0, 2, 1, 3)
        return out

    __call__ = forward
