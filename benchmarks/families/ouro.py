"""The Ouro family (`model_type: ouro`; ByteDance's looped language
models, Ouro-1.4B / Ouro-2.6B): a published `config.json` -> the
program's model.

The only file that knows how this architecture is spelled inside
`deeperspeed_tpu`: a PLANNED `GPTNeoXConfig` of `num_hidden_layers` x one
kind (full attention, one KV head a query head, rotary over the whole
head, a dense SiLU-gated MLP, RMSNorm, no biases), a norm on each
sublayer's OUTPUT as well as its input, the whole stack applied
`total_ut_steps` times over the same weights with a KV cache a pass, and
the exit gate held to `early_exit_threshold`. Its reference is
`reference/ouro.py`.

Each fact the public file has no key for (the configuration file's
`assumed`) is set in ONE place, so that a correction is one edit:

- `SUBLAYER_OUT_NORM` (here): an RMS norm on the attention's and on the
  MLP's output before the residual add
  (`GPTNeoXConfig.sublayer_out_norm`; the block:
  `models.gpt_neox._block_post_attn`);
- `_FIXED["attention_bias"]` / `["mlp_bias"]` (here): no projection
  carries a bias (a configuration that names either key otherwise is
  refused);
- the final norm after EVERY pass, its output the next pass's input, is
  what `loop_steps > 1` computes and has no switch: the one line is in
  `models.gpt_neox._forward_hidden_planned` and its twin in
  `InferenceEngine._loop` (the reference: `reference/ouro.py::passes`);
- the exit gate is one output a token WITH a bias:
  `models.gpt_neox.loop_exit` and the `loop_exit` leaves `w` and `b`
  (`init_params`), nowhere else.
"""

import jax.numpy as jnp

from benchmarks.families.gpt_neox import init_params  # noqa: F401
from deeperspeed_tpu.models.gpt_neox import (GPTNeoX, GPTNeoXConfig,
                                             LayerSpec)

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
SUBLAYER_OUT_NORM = True
# what this family's block computes: a key of the public file that says
# otherwise is refused, not ignored (the last two are absent from the
# published file: absent reads as the value here)
_FIXED = {"hidden_act": "silu", "tie_word_embeddings": False,
          "rope_scaling": None, "use_sliding_window": False,
          "attention_bias": False, "mlp_bias": False}


def model_config(conf, param_dtype, max_seq_len=None):
    for key, value in _FIXED.items():
        if conf.get(key, value) != value:
            raise ValueError(f"the Ouro block here has {key}={value!r}; "
                             f"the configuration says {conf[key]!r}")
    L, heads = conf["num_hidden_layers"], conf["num_attention_heads"]
    if conf["num_key_value_heads"] != heads:
        raise ValueError(f"num_key_value_heads {conf['num_key_value_heads']}"
                         f" != num_attention_heads {heads}: the published "
                         f"Ouro models keep one KV head a query head")
    if set(conf["layer_types"]) != {"full_attention"} or \
            len(conf["layer_types"]) != L:
        raise ValueError("layer_types must name num_hidden_layers layers, "
                         "every one full_attention")
    plan = (LayerSpec(attn="full", heads=heads, rotary_pct=1.0,
                      rotary_base=float(conf["rope_theta"]), ffn="dense"),
            ) * L
    return GPTNeoXConfig(
        vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
        num_layers=L, num_heads=heads, num_kv_heads=heads,
        # the serving window decides how long the rotary table is
        max_seq_len=max_seq_len or conf["max_position_embeddings"],
        layernorm_eps=conf["rms_norm_eps"], use_parallel_residual=False,
        tie_word_embeddings=False, param_dtype=_DTYPES[param_dtype],
        norm="rmsnorm", use_bias=False, qk_norm=False, hidden_act="silu",
        ffn_gated=True, ffn_width=conf["intermediate_size"],
        layer_plan=plan, attn_head_dim=conf["head_dim"],
        sublayer_out_norm=SUBLAYER_OUT_NORM,
        loop_steps=conf["total_ut_steps"],
        loop_exit_threshold=float(conf["early_exit_threshold"]))


def build_model(conf, param_dtype, options):
    """`options` are `GPTNeoX`'s own keywords, from the cell's file, and
    `max_seq_len`: the positions the rotary table covers (the cell's
    serving window; the published 65,536 otherwise)."""
    options = dict(options)
    return GPTNeoX(model_config(conf, param_dtype,
                                options.pop("max_seq_len", None)),
                   **options)
