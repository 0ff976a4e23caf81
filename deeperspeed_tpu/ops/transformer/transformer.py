"""Fused BERT-style transformer layer (reference:
`deepspeed/ops/transformer/transformer.py:39,470` over ~7k LoC of CUDA in
`csrc/transformer/`).

The reference hand-fuses QKV strided-batch GEMMs, masked softmax,
bias+gelu, bias+dropout+residual and layernorm into CUDA kernels. On TPU
the same fusion set is achieved with (a) XLA fusing elementwise chains into
the surrounding matmuls automatically and (b) the Pallas flash-attention
kernel for the softmax·V core. The memory-saving config knobs map to remat:

- ``normalize_invertible``  → remat the whole block (drops inputs).
- ``gelu_checkpoint``       → remat the FFN span.
- ``attn_dropout_checkpoint`` → remat the attention span.
- ``stochastic_mode``       → accepted (bf16 on TPU already gives the
  throughput the reference's stochastic rounding chased).

`DeepSpeedTransformerLayer` follows the framework layer protocol
(init/apply) so it can be listed in a `PipelineModule` or injected by
`module_inject.replace_transformer_layer`.
"""

import math

import jax
import jax.numpy as jnp

from ...parallel.mesh import (DATA_AXIS, MODEL_AXIS, ambient_auto_mesh,
                              per_shard)
from ..pallas.flash_attention import (_LAST_BACKEND, flash_attention,
                                      flash_attention_kbias,
                                      flash_attention_supported,
                                      flash_attention_train,
                                      note_xla_on_tpu)


# [B, S, H, D] attention is independent over batch and heads
_ATTN_SHARD_DIMS = {0: DATA_AXIS, 2: MODEL_AXIS}


class TransformerConfig:
    def __init__(self, batch_size=-1, hidden_size=-1, intermediate_size=-1,
                 heads=-1, attn_dropout_ratio=-1, hidden_dropout_ratio=-1,
                 num_hidden_layers=-1, initializer_range=-1):
        self.layer_id = -1
        self.batch_size = batch_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.heads = heads
        self.attn_dropout_ratio = attn_dropout_ratio
        self.hidden_dropout_ratio = hidden_dropout_ratio
        self.num_hidden_layers = num_hidden_layers
        self.initializer_range = initializer_range


class DeepSpeedTransformerConfig(TransformerConfig):
    """Config-compatible with the reference (same fields/defaults)."""

    def __init__(self, batch_size=-1, hidden_size=-1, intermediate_size=-1,
                 heads=-1, attn_dropout_ratio=-1, hidden_dropout_ratio=-1,
                 num_hidden_layers=-1, initializer_range=-1,
                 layer_norm_eps=1e-12, local_rank=-1, seed=-1, fp16=False,
                 pre_layer_norm=True, normalize_invertible=False,
                 gelu_checkpoint=False, adjust_init_range=True,
                 attn_dropout_checkpoint=False, stochastic_mode=False,
                 huggingface=False, training=True):
        super().__init__(
            batch_size, hidden_size,
            intermediate_size if intermediate_size > 0 else 4 * hidden_size,
            heads, attn_dropout_ratio, hidden_dropout_ratio,
            num_hidden_layers, initializer_range)
        self.fp16 = fp16
        self.pre_layer_norm = pre_layer_norm
        self.local_rank = local_rank
        self.seed = seed
        self.normalize_invertible = normalize_invertible
        self.gelu_checkpoint = gelu_checkpoint
        self.adjust_init_range = adjust_init_range
        self.test_gemm = False
        self.layer_norm_eps = layer_norm_eps
        self.training = training
        self.is_grad_enabled = True
        self.attn_dropout_checkpoint = attn_dropout_checkpoint
        self.stochastic_mode = stochastic_mode
        self.huggingface = huggingface

    @classmethod
    def from_dict(cls, json_object):
        config = cls()
        for key, value in json_object.items():
            setattr(config, key, value)
        return config

    @classmethod
    def from_json_file(cls, json_file):
        import json
        with open(json_file, "r", encoding="utf-8") as reader:
            return cls.from_dict(json.loads(reader.read()))


def _layer_norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps) *
            scale.astype(jnp.float32) +
            bias.astype(jnp.float32)).astype(x.dtype)


def _flash_min_seq():
    """Shortest sequence the fused flash kernels take over the
    materialized-[B,H,S,S] XLA path. At short S the score tensor is
    small and XLA's fused einsum+softmax beats the kernel's per-instance
    fixed costs (measured on v5e BERT-Large seq128 train: 45.9% vs
    39.1% MFU); at long S flash's O(S) memory wins. Tunable like the
    reference's gemm algo selection (`csrc/includes/gemm_test.h`)."""
    import os
    return int(os.environ.get("DS_FLASH_MIN_SEQ", "256"))


def _dropout(x, rate, rng, deterministic):
    """Hash-mask dropout: one scalar threefry draw seeds an int32
    avalanche hash over element indices (the reference generates masks
    with curand Philox inside its kernels, `dropout_kernels.cu`, for
    the same reason) — per-element threefry costs ~18% of a BERT-Large
    step on TPU (measured); the hash is a handful of fused VPU ops."""
    if deterministic or rate <= 0.0 or rng is None:
        return x
    import numpy as np
    seed = jax.random.randint(rng, (), 0, 2**31 - 1, dtype=jnp.int32)
    n = int(np.prod(x.shape))
    idx = jax.lax.iota(jnp.int32, n)
    h = idx * (-1640531527) ^ seed          # 0x9E3779B9
    h = (h ^ ((h >> 16) & 0xFFFF)) * 0x7FEB352D
    h = (h ^ ((h >> 15) & 0x1FFFF)) * (-2073452917)   # 0x846CA68B
    h = h ^ ((h >> 16) & 0xFFFF)
    thresh = int(min(max(rate, 0.0), 1.0) * 2147483647)
    keep = ((h & 0x7FFFFFFF) >= thresh).reshape(x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


class DeepSpeedTransformerLayer:
    """BERT-style encoder layer with the reference's option surface.

    apply(params, x, attention_mask=None, rng=None, deterministic=None)
    with x [B, S, H]; attention_mask [B, S] (1 = attend) or additive
    [B, 1, 1, S].
    """

    layer_id = 0

    def __init__(self, config, initial_weights=None, initial_biases=None):
        for name in ("attn_dropout_ratio", "hidden_dropout_ratio"):
            rate = getattr(config, name, -1)
            # -1/negative = unset (reference default); >= 1 would make
            # the survivor scale 1/(1-rate) inf/NaN instead of erroring
            if rate >= 1.0:
                raise ValueError(f"{name} must be < 1.0, got {rate}")
        self.config = config
        self.config.layer_id = DeepSpeedTransformerLayer.layer_id
        DeepSpeedTransformerLayer.layer_id += 1
        self.initial_weights = initial_weights
        self.initial_biases = initial_biases

    # -- params ------------------------------------------------------------

    def init(self, rng, x=None):
        cfg = self.config
        h = cfg.hidden_size
        inter = cfg.intermediate_size
        std = cfg.initializer_range if cfg.initializer_range > 0 else 0.02
        out_std = std
        if cfg.adjust_init_range and cfg.num_hidden_layers > 0:
            out_std = std / math.sqrt(2.0 * cfg.num_hidden_layers)
        keys = jax.random.split(rng, 4)
        dtype = jnp.float32

        def dense(key, shape, s):
            return (jax.random.normal(key, shape) * s).astype(dtype)

        params = {
            "attn_qkvw": dense(keys[0], (h, 3 * h), std),
            "attn_qkvb": jnp.zeros((3 * h,), dtype),
            "attn_ow": dense(keys[1], (h, h), out_std),
            "attn_ob": jnp.zeros((h,), dtype),
            "attn_nw": jnp.ones((h,), dtype),
            "attn_nb": jnp.zeros((h,), dtype),
            "inter_w": dense(keys[2], (h, inter), std),
            "inter_b": jnp.zeros((inter,), dtype),
            "output_w": dense(keys[3], (inter, h), out_std),
            "output_b": jnp.zeros((h,), dtype),
            "norm_w": jnp.ones((h,), dtype),
            "norm_b": jnp.zeros((h,), dtype),
        }
        if self.initial_weights is not None:
            qkv = jnp.concatenate(
                [jnp.asarray(w).T for w in self.initial_weights[:3]], axis=1)
            params["attn_qkvw"] = qkv.astype(dtype)
            params["attn_ow"] = jnp.asarray(self.initial_weights[3]).T
            params["attn_nw"] = jnp.asarray(self.initial_weights[4])
            params["inter_w"] = jnp.asarray(self.initial_weights[5]).T
            params["output_w"] = jnp.asarray(self.initial_weights[6]).T
            params["norm_w"] = jnp.asarray(self.initial_weights[7])
        if self.initial_biases is not None:
            qkvb = jnp.concatenate(
                [jnp.asarray(b) for b in self.initial_biases[:3]])
            params["attn_qkvb"] = qkvb.astype(dtype)
            params["attn_ob"] = jnp.asarray(self.initial_biases[3])
            params["attn_nb"] = jnp.asarray(self.initial_biases[4])
            params["inter_b"] = jnp.asarray(self.initial_biases[5])
            params["output_b"] = jnp.asarray(self.initial_biases[6])
            params["norm_b"] = jnp.asarray(self.initial_biases[7])
        return params

    # -- forward -----------------------------------------------------------

    def _attention(self, params, x, attention_mask, rng, deterministic,
                   segment_ids=None):
        cfg = self.config
        b, s, h = x.shape
        heads = cfg.heads
        hd = h // heads
        qkv = x @ params["attn_qkvw"].astype(x.dtype) + \
            params["attn_qkvb"].astype(x.dtype)
        # qkv columns are [Q | K | V] blocks (BERT convention; GPT-NeoX uses
        # per-head interleave instead — see models/gpt_neox.py).
        q, k, v = (t.reshape(b, s, heads, hd)
                   for t in jnp.split(qkv, 3, axis=-1))

        # Per-key masks ([B, S] keep-masks and [B, 1, 1, S] additive — every
        # BERT/SQuAD batch) reduce to a [B, S] additive row that the flash
        # kernel fuses pre-max (reference: attn_softmax taking attn_mask,
        # csrc/transformer/softmax_kernels.cu:18-140). Only full [B, H, S, S]
        # biases fall back to the materialized path.
        additive_mask = None
        kbias = None
        if attention_mask is not None:
            am = jnp.asarray(attention_mask)
            if am.ndim == 2:  # [B or 1, S] keep-mask
                kb = jnp.where(am > 0, 0.0, -1e30).astype(jnp.float32)
                kbias = jnp.broadcast_to(kb, (b, s))
                additive_mask = kbias[:, None, None, :]
            elif am.ndim == 4 and am.shape[1] == 1 and am.shape[2] == 1:
                # [B or 1, 1, 1, S] additive (HF convention); batch-
                # shared masks broadcast up to the kernel's [B, S] form
                kbias = jnp.broadcast_to(
                    am.reshape(am.shape[0], s).astype(jnp.float32),
                    (b, s))
                additive_mask = kbias[:, None, None, :]
            else:
                additive_mask = am.astype(jnp.float32)

        # The fused path covers per-key masks AND in-kernel attention
        # dropout (flash_attention_train mirrors the reference's fused
        # attn_softmax + attn_prob_dropout); only full-rank [B, H, S, S]
        # biases fall back to the materialized path.
        attn_drop_active = (not deterministic and
                            cfg.attn_dropout_ratio > 0 and rng is not None)
        if segment_ids is not None:
            # packed ragged batches (bidirectional): intra-document
            # attention via the segmented flash kernel when the shape
            # and option set allow (no per-key bias, no in-kernel
            # dropout — those kernels carry no segment gate), else the
            # materialized pairwise-mask path below
            if (additive_mask is None and not attn_drop_active and
                    s >= _flash_min_seq() and
                    flash_attention_supported((b, s, heads, hd))):
                from ..pallas.flash_attention import \
                    flash_attention_segmented
                _LAST_BACKEND["attention"] = "pallas"

                def seg_kernel(q, k, v, seg):
                    return flash_attention_segmented(q, k, v, seg, False)

                ctx = per_shard(seg_kernel, (q, k, v, segment_ids),
                                _ATTN_SHARD_DIMS)
                ctx = ctx.reshape(b, s, h)
                return ctx @ params["attn_ow"].astype(x.dtype) + \
                    params["attn_ob"].astype(x.dtype)
            seg_pen = jnp.where(
                segment_ids[:, None, :, None] ==
                segment_ids[:, None, None, :], 0.0, -1e30)  # [B,1,S,S]
            additive_mask = seg_pen if additive_mask is None else \
                additive_mask + seg_pen
        # the in-kernel dropout keys its mask on the kernel-local
        # batch·head index, so a call split per shard would repeat one
        # mask on every shard: under a multi-device mesh it takes XLA
        drop_unsharded = attn_drop_active and \
            ambient_auto_mesh() is not None
        if segment_ids is None and \
                (additive_mask is None or kbias is not None) and \
                s >= _flash_min_seq() and not drop_unsharded and \
                flash_attention_supported((b, s, heads, hd)):
            _LAST_BACKEND["attention"] = "pallas"

            def kernel(q, k, v, *kb):
                if not kb:
                    return flash_attention(q, k, v, False)
                return flash_attention_kbias(q, k, v, kb[0], False)

            if attn_drop_active:    # one device: see drop_unsharded
                seed = jax.random.randint(rng, (1,), 0, 2**31 - 1,
                                          dtype=jnp.int32)
                ctx = flash_attention_train(
                    q, k, v, kbias, seed,
                    dropout_rate=float(cfg.attn_dropout_ratio))
            else:
                kb = () if kbias is None else (kbias,)
                ctx = per_shard(kernel, (q, k, v) + kb, _ATTN_SHARD_DIMS)
        else:
            _LAST_BACKEND["attention"] = "xla"
            note_xla_on_tpu(
                "transformer_attention",
                f"[B, S, H, D] = {(b, s, heads, hd)}, min flash seq "
                f"{_flash_min_seq()}, full-rank mask "
                f"{additive_mask is not None and kbias is None}, dropout "
                f"under a multi-device mesh {drop_unsharded}")
            scale = 1.0 / math.sqrt(hd)
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                                preferred_element_type=jnp.float32) * scale
            if additive_mask is not None:
                logits = logits + additive_mask
            probs = jax.nn.softmax(logits, axis=-1)
            probs = _dropout(probs.astype(x.dtype), cfg.attn_dropout_ratio,
                             rng, deterministic)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        ctx = ctx.reshape(b, s, h)
        return ctx @ params["attn_ow"].astype(x.dtype) + \
            params["attn_ob"].astype(x.dtype)

    def _ffn(self, params, x, rng, deterministic):
        inter = x @ params["inter_w"].astype(x.dtype) + \
            params["inter_b"].astype(x.dtype)
        inter = jax.nn.gelu(inter, approximate=False)
        return inter @ params["output_w"].astype(x.dtype) + \
            params["output_b"].astype(x.dtype)

    def apply(self, params, x, attention_mask=None, rng=None,
              deterministic=None, segment_ids=None):
        cfg = self.config
        if deterministic is None:
            deterministic = not cfg.training
        eps = cfg.layer_norm_eps
        rngs = (jax.random.split(rng, 3) if rng is not None
                else (None, None, None))

        def attn_span(x):
            if cfg.pre_layer_norm:
                normed = _layer_norm(x, params["attn_nw"],
                                     params["attn_nb"], eps)
                attn = self._attention(params, normed, attention_mask,
                                       rngs[0], deterministic,
                                       segment_ids=segment_ids)
                return x + _dropout(attn, cfg.hidden_dropout_ratio, rngs[1],
                                    deterministic)
            attn = self._attention(params, x, attention_mask, rngs[0],
                                   deterministic,
                                   segment_ids=segment_ids)
            attn = _dropout(attn, cfg.hidden_dropout_ratio, rngs[1],
                            deterministic)
            return _layer_norm(x + attn, params["attn_nw"],
                               params["attn_nb"], eps)

        def ffn_span(y):
            if cfg.pre_layer_norm:
                normed = _layer_norm(y, params["norm_w"], params["norm_b"],
                                     eps)
                out = self._ffn(params, normed, rngs[2], deterministic)
                return y + _dropout(out, cfg.hidden_dropout_ratio, rngs[2],
                                    deterministic)
            out = self._ffn(params, y, rngs[2], deterministic)
            out = _dropout(out, cfg.hidden_dropout_ratio, rngs[2],
                           deterministic)
            return _layer_norm(y + out, params["norm_w"], params["norm_b"],
                               eps)

        if cfg.attn_dropout_checkpoint or cfg.normalize_invertible:
            attn_span = jax.checkpoint(attn_span)
        if cfg.gelu_checkpoint or cfg.normalize_invertible:
            ffn_span = jax.checkpoint(ffn_span)

        return ffn_span(attn_span(x))

    def forward(self, params, hidden_states, attention_mask=None, **kw):
        return self.apply(params, hidden_states,
                          attention_mask=attention_mask, **kw)

    __call__ = apply
