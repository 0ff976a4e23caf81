"""The OLMoE family: a published `config.json` -> the program's model.

The only file that knows how this architecture is spelled inside
`deeperspeed_tpu`: the GPT-NeoX block code with RMSNorm, no biases, an RMS
norm on q and k, full rotary, a sequential residual, and SiLU-gated
experts behind a router that drops nothing. Its reference is
`reference/olmoe.py`.
"""

import jax.numpy as jnp

from benchmarks.families.gpt_neox import init_params  # noqa: F401
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# what this family's block computes: a key of the public file that says
# otherwise is refused, not ignored
_FIXED = {"hidden_act": "silu", "attention_bias": False, "clip_qkv": None,
          "rope_scaling": None, "tie_word_embeddings": False}
# the published file: one KV head a query head (checked against this, not
# against `num_attention_heads`, which a rehearsal shrinks)
_PUBLISHED_KV_HEADS = 16


def model_config(conf, param_dtype):
    for key, value in _FIXED.items():
        if conf[key] != value:
            raise ValueError(f"the OLMoE block here has {key}={value!r}; "
                             f"the configuration says {conf[key]!r}")
    if conf["num_key_value_heads"] != _PUBLISHED_KV_HEADS:
        raise ValueError(
            f"num_key_value_heads {conf['num_key_value_heads']}: OLMoE "
            f"as published has {_PUBLISHED_KV_HEADS}, one a query head "
            f"(no grouped-query attention here)")
    return GPTNeoXConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        num_layers=conf["num_hidden_layers"],
        num_heads=conf["num_attention_heads"],
        max_seq_len=conf["max_position_embeddings"],
        rotary_pct=1.0, rotary_emb_base=conf["rope_theta"],
        layernorm_eps=conf["rms_norm_eps"], use_parallel_residual=False,
        tie_word_embeddings=False, param_dtype=_DTYPES[param_dtype],
        norm="rmsnorm", use_bias=False, qk_norm=True, hidden_act="silu",
        ffn_gated=True, ffn_width=conf["intermediate_size"],
        moe_num_experts=conf["num_experts"],
        moe_top_k=conf["num_experts_per_tok"], moe_dropless=True,
        moe_norm_topk_prob=conf["norm_topk_prob"],
        moe_aux_loss_coef=conf["router_aux_loss_coef"])


def build_model(conf, param_dtype, options):
    """`options` are `GPTNeoX`'s own keywords, from the cell's file."""
    return GPTNeoX(model_config(conf, param_dtype), **options)
