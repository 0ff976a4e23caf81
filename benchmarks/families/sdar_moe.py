"""The SDAR-MoE family (`model_type: sdar_moe`; JetLM's SDAR-30B-A3B-Chat,
a block-diffusion language model over a mixture of experts): a published
`config.json` -> the program's model.

The only file that knows how this architecture is spelled inside
`deeperspeed_tpu`: a PLANNED `GPTNeoXConfig` of `num_hidden_layers` x one
kind (`full32.experts`: full attention, 32 query heads over 4 KV heads of
128, an RMS norm on every head of q and k, rotary over the whole head, 128
SiLU-gated experts of width 768 behind a softmax router, 8 a token,
renormalised, no shared expert, RMSNorm, no biases, an untied head), so
the serving engine holds the weights once, and a model that GENERATES A
BLOCK of tokens at a time under the block-causal mask. Its reference is
`reference/sdar_moe.py`.

Each fact the public file has no key for (the configuration file's
`assumed`) is set in ONE place, so that a correction is one line:

- `QK_NORM` (here): the RMS norm over each head's features of q and of k,
  one scale vector for all heads (`GPTNeoXConfig.qk_norm = "head"`; the
  block: `models.gpt_neox._block_qkv`);
- `GENERATION["block"]` (here): the block length, 4
  (`GPTNeoXConfig.generation_block`);
- `GENERATION["denoising_steps"]`, `["confidence_threshold"]` (here): the
  generation defaults, 4 steps a block and a threshold of 0.9
  (`GPTNeoXConfig.generation_steps`, `generation_threshold`) under the
  rule the published code calls `low_confidence_dynamic` remasking (the
  one rule `InferenceEngine.planned_block_decode` computes: no key names
  it);
- `GENERATION["mask_token_id"]` (here): the mask token's id, 151669,
  inside the vocabulary (`GPTNeoXConfig.mask_token_id`);
- that the PROMPT too is read under the block-causal mask is what
  `generation_block > 0` computes and has no switch: the one line is
  `InferenceEngine._prefill_fn`'s `attention`, its twin
  `models.gpt_neox._block_core` (the reference: `reference/sdar_moe.py::
  block_causal`).
"""

import jax.numpy as jnp

from benchmarks.families.gpt_neox import init_params  # noqa: F401
from deeperspeed_tpu.models.gpt_neox import (GPTNeoX, GPTNeoXConfig,
                                             LayerSpec)

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
QK_NORM = "head"
GENERATION = {"block": 4, "mask_token_id": 151669, "denoising_steps": 4,
              "confidence_threshold": 0.9}
# what this family's block computes: a key of the public file that says
# otherwise is refused, not ignored
_FIXED = {"hidden_act": "silu", "attention_bias": False,
          "rope_scaling": None, "use_sliding_window": False,
          "tie_word_embeddings": False, "norm_topk_prob": True,
          "decoder_sparse_step": 1, "mlp_only_layers": []}


def generation(conf):
    """`GENERATION` for this configuration: a rehearsal at a tiny
    vocabulary brings a mask token of its own, and a test a threshold
    low enough to fire (`mask_token_id`, `confidence_threshold`: keys no
    published file has)."""
    return dict(GENERATION, **{
        k: conf[k] for k in ("mask_token_id", "confidence_threshold")
        if k in conf})


def model_config(conf, param_dtype, max_seq_len=None):
    for key, value in _FIXED.items():
        if conf[key] != value:
            raise ValueError(f"the SDAR-MoE block here has {key}={value!r};"
                             f" the configuration says {conf[key]!r}")
    L, heads = conf["num_hidden_layers"], conf["num_attention_heads"]
    gen = generation(conf)
    plan = (LayerSpec(attn="full", heads=heads, rotary_pct=1.0,
                      rotary_base=float(conf["rope_theta"]),
                      ffn="experts"),) * L
    return GPTNeoXConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        num_layers=L, num_heads=heads,
        num_kv_heads=conf["num_key_value_heads"],
        # the serving window decides how long the rotary table is
        max_seq_len=max_seq_len or conf["max_position_embeddings"],
        layernorm_eps=conf["rms_norm_eps"], use_parallel_residual=False,
        tie_word_embeddings=False, param_dtype=_DTYPES[param_dtype],
        norm="rmsnorm", use_bias=False, qk_norm=QK_NORM, hidden_act="silu",
        ffn_gated=True,
        # `intermediate_size` (6144) is a dense layer's and no layer is
        # dense (`mlp_only_layers` []): an expert's width is the one used
        ffn_width=conf["moe_intermediate_size"],
        layer_plan=plan, attn_head_dim=conf["head_dim"],
        moe_num_experts=conf["num_experts"],
        moe_top_k=conf["num_experts_per_tok"], moe_dropless=True,
        moe_norm_topk_prob=conf["norm_topk_prob"],
        moe_router_score="softmax",
        moe_expert_width=conf["moe_intermediate_size"],
        generation_block=gen["block"], mask_token_id=gen["mask_token_id"],
        generation_steps=gen["denoising_steps"],
        generation_threshold=gen["confidence_threshold"])


def build_model(conf, param_dtype, options):
    """`options` are `GPTNeoX`'s own keywords, from the cell's file, and
    `max_seq_len`: the positions the rotary table covers (the cell's
    serving window; the published 32,768 otherwise)."""
    options = dict(options)
    return GPTNeoX(model_config(conf, param_dtype,
                                options.pop("max_seq_len", None)),
                   **options)
