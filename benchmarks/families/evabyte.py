"""The evabyte family (`model_type: evabyte`; EvaByte 6.5B, a byte-level
decoder whose attention is EVA, arXiv:2302.04542): a published
`config.json` -> the program's model.

The only file that knows how this architecture is spelled inside
`deeperspeed_tpu`: a PLANNED `GPTNeoXConfig` whose every `LayerSpec` is the
mixer `eva` (exact causal attention inside the query's window of
`window_size` positions and, in the same softmax, one pooled key and value
for each chunk of `chunk_size` positions of every earlier window), one
query head a KV head, an RMS norm whose scale is 1 + w
(`norm_add_unit_offset`), a SiLU-gated MLP, no bias anywhere, an untied
head of `num_pred_heads` x `vocab_size` logits. Its reference is
`reference/evabyte.py`.

Each fact the public file has no key for (the configuration file's
`assumed`) is set in ONE place, so that a correction is one edit:

- the pooling logits carry the attention's scale and read the keys AFTER
  the rotary: `deeperspeed_tpu.ops.pallas.eva.eva_pool` (and the kernel
  beside it, which a test holds to it), called with
  `models.gpt_neox.eva_attention`'s / `InferenceEngine`'s rotated keys;
- a chunk's pooled row becomes visible at the END of its window, not of
  its chunk: `models.gpt_neox.eva_attention`'s `visible` (a prefill) and
  `inference.scheduler`'s roll (decode: the pending pages join the table
  when the window ends);
- `ROTARY_PCT` (here): rotate-half rotary (a feature with the one 64
  behind it, `models.gpt_neox._rotate_half`) over the whole head.
"""

import jax.numpy as jnp

from benchmarks.families.gpt_neox import init_params  # noqa: F401
from deeperspeed_tpu.models.gpt_neox import (GPTNeoX, GPTNeoXConfig,
                                             LayerSpec)

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
ROTARY_PCT = 1.0
# what this family's block computes: a key of the public file that says
# otherwise is refused, not ignored
_FIXED = {"attention_class": "eva", "attention_bias": False,
          "hidden_act": "silu", "norm_add_unit_offset": True,
          "tie_word_embeddings": False, "rope_scaling": None,
          "num_chunks": None}


def model_config(conf, param_dtype, max_seq_len=None):
    if "eva_window" not in GPTNeoXConfig.__dataclass_fields__:
        from benchmarks.harness import BenchmarkError
        raise BenchmarkError(
            "this checkout's GPTNeoXConfig has no chunk-pooled (eva) mixer, "
            "unit-offset norm or prediction heads: the evabyte "
            "configuration cannot be built")
    for key, value in _FIXED.items():
        if conf[key] != value:
            raise ValueError(f"the evabyte block here has {key}={value!r}; "
                             f"the configuration says {conf[key]!r}")
    h, heads = conf["hidden_size"], conf["num_attention_heads"]
    if conf["num_key_value_heads"] != heads or h % heads:
        raise ValueError(
            f"{heads} heads over {conf['num_key_value_heads']} KV heads of "
            f"a hidden size {h}: a chunk is pooled by its own head's phi "
            f"and mu, one query head a KV head")
    spec = LayerSpec(attn="eva", heads=heads, rotary_pct=ROTARY_PCT,
                     rotary_base=float(conf["rope_theta"]), ffn="dense")
    return GPTNeoXConfig(
        vocab_size=conf["vocab_size"], hidden_size=h,
        num_layers=conf["num_hidden_layers"], num_heads=heads,
        max_seq_len=max_seq_len or conf["max_position_embeddings"],
        layernorm_eps=conf["rms_norm_eps"], use_parallel_residual=False,
        tie_word_embeddings=False, param_dtype=_DTYPES[param_dtype],
        norm="rmsnorm", use_bias=False, qk_norm=False, hidden_act="silu",
        ffn_gated=True, ffn_width=conf["intermediate_size"],
        layer_plan=(spec,) * conf["num_hidden_layers"],
        eva_window=conf["window_size"], eva_chunk=conf["chunk_size"],
        norm_unit_offset=True, num_pred_heads=conf["num_pred_heads"])


def build_model(conf, param_dtype, options):
    """`options` are `GPTNeoX`'s own keywords, from the cell's file, and
    `max_seq_len`: the positions the cell serves (the rotary table is
    built for those; the published 32,768 otherwise)."""
    options = dict(options)
    return GPTNeoX(model_config(conf, param_dtype,
                                options.pop("max_seq_len", None)),
                   **options)
