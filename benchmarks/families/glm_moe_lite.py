"""The GLM-MoE-lite family (`model_type: glm4_moe_lite`; GLM-4.7-Flash):
a published `config.json` -> the program's model.

The only file that knows how this architecture is spelled inside
`deeperspeed_tpu`: a PLANNED `GPTNeoXConfig` whose every layer's
attention is `latent` (DeepSeek-V2/V3-style MLA: a low-rank query, one
compressed row a token for keys and values, a rotary key row shared by
all heads), the first `first_k_dense_replace` layers a dense gated MLP
and the rest routed experts with `n_shared_experts` shared ones, a
router that scores by sigmoid and chooses with a correction bias
(`topk_method: noaux_tc`), RMSNorm, no biases, and `num_nextn_predict_
layers` next-token-prediction blocks. Its reference is
`reference/glm_moe_lite.py`.

Each choice the public file leaves open (the configuration file's
`assumed`) is ONE fact here or in the model's latent block, so that a
correction is one line:

- the rotary pairing of the `qk_rope_head_dim` features: rotate-half,
  feature i with i + rope / 2 (`models.gpt_neox._rotary_rows`; the
  interleaved pairing is a permutation of `q_b`'s and `kv_a`'s columns,
  which random weights do not tell apart);
- where the two low-rank norms sit: on c_q after `q_a`, and on c_kv alone
  (never on k_r) after `kv_a` (`models.gpt_neox._latent_rows`);
- the softmax scale: 1 / sqrt(qk_nope_head_dim + qk_rope_head_dim), with
  `rope_scaling: null` no further factor;
- the router: sigmoid scores in float32, the bias added for the choice
  only, the kept scores renormalised (`norm_topk_prob`) and scaled by
  `routed_scaling_factor`; `n_group` 1 / `topk_group` 1 (no group
  limit: any other value is refused by name);
- the shared experts: ONE gated MLP of width `n_shared_experts *
  moe_intermediate_size`, added ungated by the router;
- the next-token-prediction block: [rms(hidden) | rms(embedding)] in
  that order under the [2h, h] projection, reading the last layer's
  hidden state BEFORE the final norm, one layer of the last layer's kind,
  its own final norm, the model's head; its loss weight `MTP_LOSS_WEIGHT`
  (the file has no key for it).
"""

import jax.numpy as jnp

from benchmarks.families.gpt_neox import init_params  # noqa: F401
from deeperspeed_tpu.models.gpt_neox import (GPTNeoX, GPTNeoXConfig,
                                             LayerSpec)

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# what this family's block computes: a key of the public file that says
# otherwise is refused, not ignored
_FIXED = {"attention_bias": False, "tie_word_embeddings": False,
          "hidden_act": "silu", "topk_method": "noaux_tc",
          "rope_scaling": None, "partial_rotary_factor": 1}
# the weight of the next-token-prediction block's loss term (DeepSeek-V3
# trained with 0.3, later 0.1; the public file does not say)
MTP_LOSS_WEIGHT = 0.3


def model_config(conf, param_dtype, max_seq_len=None):
    for key, value in _FIXED.items():
        if conf[key] != value:
            raise ValueError(f"the GLM-MoE-lite block here has {key}="
                             f"{value!r}; the configuration says "
                             f"{conf[key]!r}")
    heads = conf["num_attention_heads"]
    if conf["num_key_value_heads"] != heads:
        raise ValueError("latent attention has one key and value head a "
                         "query head: num_key_value_heads "
                         f"{conf['num_key_value_heads']} != {heads}")
    L, dense = conf["num_hidden_layers"], conf["first_k_dense_replace"]
    plan = tuple(LayerSpec(attn="latent", heads=heads, rotary_pct=1.0,
                           rotary_base=float(conf["rope_theta"]),
                           ffn="dense" if i < dense else "experts")
                 for i in range(L))
    return GPTNeoXConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        num_layers=L, num_heads=heads, num_kv_heads=heads,
        # the serving window decides how long the rotary table is
        max_seq_len=max_seq_len or conf["max_position_embeddings"],
        layernorm_eps=conf["rms_norm_eps"], use_parallel_residual=False,
        tie_word_embeddings=False, param_dtype=_DTYPES[param_dtype],
        norm="rmsnorm", use_bias=False, qk_norm=False, hidden_act="silu",
        ffn_gated=True, ffn_width=conf["intermediate_size"],
        layer_plan=plan,
        attn_head_dim=conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"],
        mla_q_rank=conf["q_lora_rank"], mla_kv_rank=conf["kv_lora_rank"],
        mla_nope_dim=conf["qk_nope_head_dim"],
        mla_rope_dim=conf["qk_rope_head_dim"],
        mla_v_dim=conf["v_head_dim"],
        moe_num_experts=conf["n_routed_experts"],
        moe_top_k=conf["num_experts_per_tok"], moe_dropless=True,
        moe_norm_topk_prob=conf["norm_topk_prob"],
        moe_router_score="sigmoid", moe_n_group=conf["n_group"],
        moe_topk_group=conf["topk_group"],
        moe_expert_width=conf["moe_intermediate_size"],
        moe_shared_width=conf["n_shared_experts"] *
        conf["moe_intermediate_size"],
        moe_routing_scale=conf["routed_scaling_factor"],
        mtp_layers=conf["num_nextn_predict_layers"],
        mtp_loss_weight=MTP_LOSS_WEIGHT
        if conf["num_nextn_predict_layers"] else 0.0)


def build_model(conf, param_dtype, options):
    """`options` are `GPTNeoX`'s own keywords, from the cell's file, and
    `max_seq_len`: the positions the rotary table covers (the cell's
    serving window; the published 202,752 otherwise)."""
    options = dict(options)
    return GPTNeoX(model_config(conf, param_dtype,
                                options.pop("max_seq_len", None)),
                   **options)
