"""The phi4flash family (`model_type: phi4flash`; Microsoft's
Phi-4-mini-flash-reasoning, the SambaY decoder-hybrid-decoder of
arXiv:2507.06607): a published `config.json` -> the program's model.

The only file that knows how this architecture is spelled inside
`deeperspeed_tpu`: a PLANNED `GPTNeoXConfig` whose `LayerSpec`s name five
mixers. With L = `num_hidden_layers`: Mamba-1 (`ssm`) on the even layers
up to and including L/2, window attention on the odd ones below it, ONE
full attention layer at L/2 + 1, and behind it Gated Memory Units (`gmu`,
even) on layer L/2's scan output and cross attention (`cross`, odd) over
the full layer's K and V; LayerNorm with bias, biases on the attention
projections, a tied head, no position term. Its reference is
`reference/phi4flash.py`.

Each fact the public file has no key for (the configuration file's
`assumed`) is set in ONE place, so that a correction is one edit:

- `ATTN_DIFF` (here): every attention is DIFFERENTIAL over pairs of
  ADJACENT heads (`GPTNeoXConfig.attn_diff`): the program's head is a
  pair, `num_attention_heads / 2` of them of width `2 * hidden /
  num_attention_heads` over `num_key_value_heads / 2` KV heads; lam0 by
  0-based layer index (`models.gpt_neox.diff_lambda_init`), one norm scale
  a layer (`init_stack_params`: `subln`);
- `ROTARY_PCT` (here): no positional encoding;
- `_FIXED` (here): no bias on the MLP or the head, dropout 0, a tied
  head, two mixers a period; biases on q, k, v and o (`use_bias`; the
  planned block puts none on the MLP or on Mamba's in / x / out
  projections and one each on its convolution and its step);
- `SSM_STATE`, `SSM_CONV`, `SSM_EXPAND`, `ssm_dt_rank` (here): Mamba's
  d_state 16, d_conv 4, expand 2, dt_rank ceil(hidden / 16);
- the memory is the scan's output after the skip and before the gate
  (`models.gpt_neox.ssm_mixer`), the packed MLP's first half is the gate
  (`_gated_mlp`), the scan state rests in float32
  (`inference.kv_cache.StateCache`).
"""

import jax.numpy as jnp

from benchmarks.families.gpt_neox import init_params  # noqa: F401
from deeperspeed_tpu.models.gpt_neox import (GPTNeoX, GPTNeoXConfig,
                                             LayerSpec)

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
ATTN_DIFF = True
ROTARY_PCT = 0.0
SSM_STATE, SSM_CONV, SSM_EXPAND = 16, 4, 2
# what this family's block computes: a key of the public file that says
# otherwise is refused, not ignored
_FIXED = {"hidden_act": "silu", "tie_word_embeddings": True,
          "mlp_bias": False, "lm_head_bias": False, "mb_per_layer": 2,
          "embd_pdrop": 0, "resid_pdrop": 0}


def layer_plan(conf):
    L = conf["num_hidden_layers"]
    heads, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    if L % 4 or heads % 2 or kv % 2 or conf["hidden_size"] % heads:
        raise ValueError(
            f"num_hidden_layers {L} must be a multiple of 4 (the memory "
            f"layer L/2 is a Mamba layer, the full layer L/2 + 1 an "
            f"attention layer) and the {heads} heads and {kv} KV heads "
            f"pair up")

    def spec(attn):
        pairs = 0 if attn in ("ssm", "gmu") else heads // 2
        return LayerSpec(attn=attn, heads=pairs, rotary_pct=ROTARY_PCT,
                         ffn="dense")

    half = L // 2
    return tuple(
        spec(("window" if i % 2 else "ssm") if i <= half else
             "full" if i == half + 1 else
             ("cross" if i % 2 else "gmu")) for i in range(L))


def model_config(conf, param_dtype, max_seq_len=None):
    for key, value in _FIXED.items():
        if conf[key] != value:
            raise ValueError(f"the phi4flash block here has {key}={value!r};"
                             f" the configuration says {conf[key]!r}")
    h, heads = conf["hidden_size"], conf["num_attention_heads"]
    return GPTNeoXConfig(
        vocab_size=conf["vocab_size"], hidden_size=h,
        num_layers=conf["num_hidden_layers"], num_heads=heads // 2,
        num_kv_heads=conf["num_key_value_heads"] // 2,
        attn_head_dim=2 * (h // heads),
        max_seq_len=max_seq_len or conf["max_position_embeddings"],
        layernorm_eps=conf["layer_norm_eps"], use_parallel_residual=False,
        tie_word_embeddings=True, param_dtype=_DTYPES[param_dtype],
        norm="layernorm", use_bias=True, qk_norm=False, hidden_act="silu",
        ffn_gated=True, ffn_width=conf["intermediate_size"],
        layer_plan=layer_plan(conf), attn_window=conf["sliding_window"],
        attn_diff=ATTN_DIFF, ssm_inner=SSM_EXPAND * h, ssm_state=SSM_STATE,
        ssm_conv=SSM_CONV, ssm_dt_rank=-(-h // 16))


def build_model(conf, param_dtype, options):
    """`options` are `GPTNeoX`'s own keywords, from the cell's file, and
    `max_seq_len`: the positions the cell serves (the published 262,144
    otherwise; nothing is sized by it, the model has no position table)."""
    options = dict(options)
    return GPTNeoX(model_config(conf, param_dtype,
                                options.pop("max_seq_len", None)),
                   **options)
