"""The qwen3_next family (`model_type: qwen3_next`; Qwen3-Next-80B-A3B): a
published `config.json` -> the program's model.

The only file that knows how this architecture is spelled inside
`deeperspeed_tpu`: a PLANNED `GPTNeoXConfig` of two mixers. Layer l is
`full` where (l + 1) % `full_attention_interval` == 0 (softmax attention
over grouped KV heads, an RMS norm on every head of q and k, rotary on
`partial_rotary_factor` of the head, an ELEMENTWISE sigmoid gate on the
attention output) and `gdn` elsewhere (Gated DeltaNet: the gated delta
rule behind a causal convolution of `linear_conv_kernel_dim` taps, a gated
RMS norm on its output); every layer's FFN is the routed experts with ONE
shared expert behind its own sigmoid gate; RMS norms scale by 1 + w; no
bias; an untied head. Its reference is `reference/qwen3_next.py`.

Each fact the public file has no key for (the configuration file's
`assumed`) is set in ONE place, so that a correction is one edit:

- `_FIXED` (here): every layer sparse, no dense-only layer, no sliding
  window, no rope scaling, an untied head, silu;
- `ATTN_GATE` (here): the gate is `heads x head_dim` wide and is published
  as the second half of each head's query projection; the program holds
  it as a leaf of its own (`GPTNeoXConfig.attn_gate = "elementwise"`,
  `gate_w`);
- `QK_NORM`, `NORM_UNIT_OFFSET` (here): the norm on each head of q and k,
  and the scale 1 + w of every RMS norm but the delta rule's output norm
  (`models.gpt_neox._rms_scale`, `_gdn_out`);
- the shared expert's gate (`moe_shared_gate`), the softmax router over
  all experts with the kept weights renormalised (`norm_topk_prob`);
- the delta rule's equations (`models.gpt_neox.gdn_mixer` / `gdn_token`,
  `ops.pallas.gdn`), its state in float32 and its convolution rows in
  bfloat16 (`inference.kv_cache.StateCache`).

A share of the experts is held here where the file says so
(`held_experts`, `num_experts_published`): the router scores all the
published experts and the layer computes the held ones' part.
"""

import jax.numpy as jnp

from benchmarks.families.gpt_neox import init_params  # noqa: F401
from deeperspeed_tpu.models.gpt_neox import (GPTNeoX, GPTNeoXConfig,
                                             LayerSpec)

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
ATTN_GATE = "elementwise"
QK_NORM = "head"
NORM_UNIT_OFFSET = True
# what this family's block computes: a key of the public file that says
# otherwise is refused, not ignored
_FIXED = {"decoder_sparse_step": 1, "mlp_only_layers": [],
          "hidden_act": "silu", "tie_word_embeddings": False,
          "use_sliding_window": False, "rope_scaling": None}


def layer_plan(conf):
    every = conf["full_attention_interval"]
    full = LayerSpec(attn="full", heads=conf["num_attention_heads"],
                     rotary_pct=float(conf["partial_rotary_factor"]),
                     rotary_base=float(conf["rope_theta"]), ffn="experts")
    gdn = LayerSpec(attn="gdn", heads=0, ffn="experts")
    return tuple(full if (i + 1) % every == 0 else gdn
                 for i in range(conf["num_hidden_layers"]))


def model_config(conf, param_dtype, max_seq_len=None):
    for key, value in _FIXED.items():
        if conf[key] != value:
            raise ValueError(f"the qwen3_next block here has {key}="
                             f"{value!r}; the configuration says "
                             f"{conf[key]!r}")
    published = conf.get("num_experts_published", conf["num_experts"])
    lo, hi = (int(t) for t in conf.get(
        "held_experts", f"0-{published - 1}").split("-"))
    if hi + 1 - lo != conf["num_experts"]:
        raise ValueError(f"held_experts {lo}-{hi} does not name "
                         f"num_experts = {conf['num_experts']}")
    return GPTNeoXConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        num_layers=conf["num_hidden_layers"],
        num_heads=conf["num_attention_heads"],
        # the serving window decides how long the rotary tables are
        max_seq_len=max_seq_len or conf["max_position_embeddings"],
        layernorm_eps=conf["rms_norm_eps"], use_parallel_residual=False,
        tie_word_embeddings=False, param_dtype=_DTYPES[param_dtype],
        norm="rmsnorm", norm_unit_offset=NORM_UNIT_OFFSET, use_bias=False,
        qk_norm=QK_NORM, hidden_act="silu", ffn_gated=True,
        ffn_width=conf["intermediate_size"], layer_plan=layer_plan(conf),
        attn_head_dim=conf["head_dim"],
        num_kv_heads=conf["num_key_value_heads"], attn_gate=ATTN_GATE,
        gdn_key_heads=conf["linear_num_key_heads"],
        gdn_value_heads=conf["linear_num_value_heads"],
        gdn_key_dim=conf["linear_key_head_dim"],
        gdn_value_dim=conf["linear_value_head_dim"],
        gdn_conv=conf["linear_conv_kernel_dim"],
        moe_num_experts=published, moe_top_k=conf["num_experts_per_tok"],
        moe_dropless=True, moe_norm_topk_prob=conf["norm_topk_prob"],
        moe_router_score="softmax",
        moe_expert_width=conf["moe_intermediate_size"],
        moe_shared_width=conf["shared_expert_intermediate_size"],
        moe_shared_gate=True,
        moe_held=() if hi + 1 - lo == published else (lo, hi + 1))


def build_model(conf, param_dtype, options):
    """`options` are `GPTNeoX`'s own keywords, from the cell's file, and
    `max_seq_len`: the positions the rotary tables cover (the cell's
    serving window; the published 262,144 otherwise)."""
    options = dict(options)
    return GPTNeoX(model_config(conf, param_dtype,
                                options.pop("max_seq_len", None)),
                   **options)
