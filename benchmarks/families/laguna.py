"""The Laguna family: a published `config.json` -> the program's model.

The only file that knows how this architecture is spelled inside
`deeperspeed_tpu`: a PLANNED `GPTNeoXConfig` (a `LayerSpec` a layer:
full or window attention with the layer's own query heads over the
model's KV heads, its rotary facts, a dense gated MLP or routed experts
with a shared one), RMSNorm, no biases, a per-head attention gate, and a
router that drops nothing and is told which experts are held here. Its
reference is `reference/laguna.py`.

Each choice the public file leaves open (the configuration file's
`assumed`) is ONE fact set here, so that a correction is one line:
router scores `moe_router_score="softmax"`; the gate `attn_gate=
"per-head"` (sigmoid of a projection of the normed input, on the
attention output before Wo); `qk_norm=False`; `hidden_act="silu"`; the
shared expert ungated (the block has no other); pre-norm with two norms a
layer (`norm="rmsnorm"`, `use_parallel_residual=False`); YaRN as
`rope_type: yarn` (`LayerSpec.rope`).

The head dim is the file's `head_dim` (128), not `hidden_size /
num_attention_heads` (64), and parameters are counted by layer kind:
`GPTNeoXConfig.num_params()` of what is held here, `num_params(held=
False)` of the published model at this depth.
"""

import jax.numpy as jnp

from benchmarks.families.gpt_neox import init_params  # noqa: F401
from deeperspeed_tpu.models.gpt_neox import (GPTNeoX, GPTNeoXConfig,
                                             LayerSpec)

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# what this family's block computes: a key of the public file that says
# otherwise is refused, not ignored
_FIXED = {"attention_bias": False, "tie_word_embeddings": False,
          "gating": "per-head", "decoder_sparse_step": 1,
          "moe_apply_router_weight_on_input": False,
          "moe_router_logit_softcapping": 0}
_ATTN = {"full_attention": "full", "sliding_attention": "window"}
_FFN = {"dense": "dense", "sparse": "experts"}


def _layer_spec(conf, attn, heads, ffn):
    rope = conf["rope_parameters"][attn]
    yarn = ()
    if rope["rope_type"] == "yarn":
        yarn = ("yarn", rope["factor"],
                rope["original_max_position_embeddings"],
                rope["beta_fast"], rope["beta_slow"],
                rope["attention_factor"])
    elif rope["rope_type"] != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r}: the Laguna "
                         f"block here computes 'default' and 'yarn'")
    return LayerSpec(attn=_ATTN[attn], heads=heads,
                     rotary_pct=float(rope["partial_rotary_factor"]),
                     rotary_base=float(rope["rope_theta"]), rope=yarn,
                     ffn=_FFN[ffn])


def model_config(conf, param_dtype, max_seq_len=None):
    for key, value in _FIXED.items():
        if conf[key] != value:
            raise ValueError(f"the Laguna block here has {key}={value!r}; "
                             f"the configuration says {conf[key]!r}")
    L = conf["num_hidden_layers"]
    lists = [conf[k] for k in ("layer_types",
                               "num_attention_heads_per_layer",
                               "mlp_layer_types", "gating_types")]
    if any(len(x) != L for x in lists) or \
            set(conf["gating_types"]) != {"per_head"}:
        raise ValueError("the per-layer lists must name num_hidden_layers "
                         "layers, every one gated per head")
    dense = [i for i, t in enumerate(conf["mlp_layer_types"])
             if t == "dense"]
    if dense != [i for i in conf["mlp_only_layers"] if i < L]:
        raise ValueError("mlp_layer_types and mlp_only_layers disagree")
    lo, hi = (int(t) for t in conf["held_experts"].split("-"))
    if hi + 1 - lo != conf["num_experts"]:
        raise ValueError(f"held_experts {conf['held_experts']!r} does not "
                         f"name num_experts = {conf['num_experts']}")
    plan = tuple(_layer_spec(conf, *facts) for facts in zip(*lists[:3]))
    return GPTNeoXConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        num_layers=L, num_heads=conf["num_attention_heads"],
        # the serving window decides how long the rotary tables are; the
        # published 1M positions would be 0.5 GB of float32 cos and sin
        max_seq_len=max_seq_len or conf["max_position_embeddings"],
        layernorm_eps=conf["rms_norm_eps"], use_parallel_residual=False,
        tie_word_embeddings=False, param_dtype=_DTYPES[param_dtype],
        norm="rmsnorm", use_bias=False, qk_norm=False, hidden_act="silu",
        ffn_gated=True, ffn_width=conf["intermediate_size"],
        layer_plan=plan, attn_head_dim=conf["head_dim"],
        num_kv_heads=conf["num_key_value_heads"],
        attn_window=conf["sliding_window"], attn_gate="per-head",
        moe_num_experts=conf["num_experts_published"],
        moe_top_k=conf["num_experts_per_tok"], moe_dropless=True,
        moe_norm_topk_prob=conf["norm_topk_prob"],
        moe_router_score="softmax",
        moe_expert_width=conf["moe_intermediate_size"],
        moe_shared_width=conf["shared_expert_intermediate_size"],
        moe_routing_scale=conf["moe_routed_scaling_factor"],
        moe_held=() if hi + 1 - lo == conf["num_experts_published"]
        else (lo, hi + 1))


def build_model(conf, param_dtype, options):
    """`options` are `GPTNeoX`'s own keywords, from the cell's file, and
    `max_seq_len`: the positions the rotary tables cover (the cell's
    serving window; the published 1,048,576 otherwise)."""
    options = dict(options)
    return GPTNeoX(model_config(conf, param_dtype,
                                options.pop("max_seq_len", None)),
                   **options)
