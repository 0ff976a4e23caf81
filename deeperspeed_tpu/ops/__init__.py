from . import adam, lamb, op_builder, pallas, sparse_attention, transformer
from .transformer import (DeepSpeedTransformerConfig,
                          DeepSpeedTransformerLayer)
from .sparse_attention import SparseSelfAttention


def dispatch_report():
    """Last-dispatched kernel configuration, as one dict — the PUBLIC
    accessor over the kernels' internal dispatch records
    (`flash_attention._LAST_BLOCKS`, `decode_attention._LAST_BACKEND`).
    The benchmark's fallback counters, the telemetry capture exports and
    the fleet trace metadata all consume this; WHICH block geometry / grid
    variant / decode backend produced a number is as load-bearing as
    the number itself.

    Keys (present once the corresponding kernel has dispatched):
    ``flash``: {"fwd": (bq, bk), "fwd_variant", "dkv", "dq" (the
    backward's blocks), "bwd_variant" ("fused-trapezoid" / "fused-dense":
    the tiled backward as one kernel; "trapezoid" / "dense": the two
    kernels of a sequence whose dq slab is over the budget; "single"),
    "masked_tiles": {"fwd" / "bwd" / "dkv" / "dq": (masked, launched)
    tiles a head of the last tiled call of that kind of kernel; of the
    backward's kinds, those the last backward ran}, "bodies_built":
    {"fwd" / "bwd" / "dkv" / "dq": (times the kernel's body was built in
    this process, host seconds that took): set-up every run pays, compile
    cache or not}, "heads": {"fwd" / "bwd": {"in_place": n, "moved": n}},
    the tiled calls traced in this process by where they found the heads:
    in the [B, S, H*D] the model holds, or through a [B, S, H, D] ->
    [B*H, S, D] copy of every operand and result
    (`flash_attention.heads_in_place`: a training call at a head dim of
    whole lane tiles, or of 64 with an even number of heads, is in place;
    a serving prefill moves them), "k_turns": {"once_a_head": n,
    "every_step": n}, the tiled forwards on heads in place traced in this
    process by how often they turn a k^T block into the k the score
    matmuls read: once a block and head, the head's turned k kept in VMEM
    (`autotune.flash_k_slab_admitted`: a causal call whose [S, D] fits,
    every train cell's), or every grid step (a dense grid, a sequence
    over the budget), "rotary": {"in_kernel": n, "xla": n}, the
    rotate-half rotaries of a q, k pair traced in this process by where
    they run: in_kernel, the tiled training forwards that took the
    UN-rotated projections and the tables, whose kernels rotate each q^T
    and k^T block they load and rotate dq and dk back where they store
    them (`flash_attention.rotates_in_kernel` and
    `gpt_neox._rotary_in_kernel`: the training call on heads in place
    with the fused backward, one position stream, `rot_dim` a multiple of
    16, nobody needing the rotated k; every train cell's); xla, the
    `gpt_neox.apply_rotary` calls, passes over [B, S, H, D] in front of
    the attention (a serving prefill, which writes the rotated k to its
    pages, a decode step, a packed or windowed batch, grouped KV heads,
    an `attn_fn`, a call of one block)}; ``attention``: {"attention" / "sparse_attention":
    backend} of the model-side dispatchers, and "head_projection":
    {"plain": n, "folded": n, "split": n}, the attention projections
    traced in this process by the form their reshape to heads took
    (plain: kept out of the dot, the weight read where it lies, every
    decode step's; folded: XLA's to place, a train step's at a head dim
    of 128, a prefill's; `gpt_neox._heads_dot`) and, "split", the fused
    QKV projections that ran as three dots against the q, k and v
    columns of the one weight, whose results XLA writes where the tiled
    flash kernels read them: a train step's at a head dim under 128
    (`autotune.head_projection_split`);
    ``decode_attention``:
    {"decode": backend, "decode_kv": pool dtype — "int8" when the paged
    pools are quantized, "decode_heads_per_step" / "decode_pages_per_step":
    the KV heads and the pages of a row one grid step of the paged kernel
    moved at the last call traced (`decode_attention.step_geometry`: 16 and
    2 at 16 heads of 128, 4 and 8 at SDAR's 4), "decode_scores": how a
    step scored them, "per_head" (a KV head's group of query rows against
    its own slots) or "collapsed" (all rows against all slots: one row a
    head, int8 pages); the three are absent under XLA; "kv_write":
    backend of the one-token row
    write, "kv_write_slots": the slots of a page that kernel read and
    wrote back for one row (the row's packed sublane group: 16 for bf16,
    32 for int8, 8 for float32, or the page; absent under XLA),
    "kv_write_latent" / "kv_write_latent_slots": the same of a latent
    layer's row write}; ``quant_matmul`` / ``grouped_matmul``:
    {name: backend}. A backend is "pallas" (the kernel, interpreted off
    a TPU) or "xla". ``moe``: {"plan": {"counted": n, "choice_major":
    n}}, the traces of the dropless MoE layer in this process by the
    form the ragged layout's plan took (counted: each pair's row from
    its rank among its expert's pairs, never a sort by expert; the
    buffer row -> pair map by one sort of the rows:
    `moe.layer.dropless_plan`) and by the pairs' numbering
    (choice_major: pair p = j * T + t, the fill one gather and the
    combine summed over the gathered rows' major axis:
    `moe.layer.moe_ffn_dropless`); empty until that layer is traced.
    ``ssm``: {"scan" / "step": backend} of a state-space layer's
    selective scan and its one-token step (`ops.pallas.ssm`).
    ``ce_head``: {"loss_and_grads": n, "loss_only": n}, the calls of `models.gpt_neox.fused_lm_head_loss` traced in this
    process by the rule that ran: its `custom_vjp`'s forward rule (the
    loss and both gradients from one logits tile a chunk: a train step's)
    or its primal (the loss alone: evaluation; also traced, and thrown
    away, where `jax.checkpoint`, `scan` or `shard_map` stages the call
    before it is differentiated). ``xla_on_tpu`` names every dispatcher
    that, on a TPU, took XLA where it has a kernel (`note_xla_on_tpu`).
    """
    from ..models.gpt_neox import _CE_HEAD_TRACED
    from .pallas.decode_attention import _LAST_BACKEND
    from .pallas.flash_attention import _LAST_BACKEND as _ATTN_BACKEND
    from .pallas.flash_attention import (_BODY_BUILDS, _HEAD_PROJECTIONS,
                                         _HEADS, _K_TURNS, _LAST_BLOCKS,
                                         _LAST_MASKED, _ROTARY, _XLA_NOTED)
    from .pallas.grouped_matmul import _LAST_BACKEND as _GMM_BACKEND
    from .pallas.grouped_matmul import _PLANS_TRACED
    from .pallas.quant_matmul import _LAST_BACKEND as _QMM_BACKEND
    from .pallas.ssm import _LAST_BACKEND as _SSM_BACKEND
    return {"flash": dict(_LAST_BLOCKS, masked_tiles=dict(_LAST_MASKED),
                          bodies_built={k: (n, round(t, 3)) for k, (n, t)
                                        in _BODY_BUILDS.items()},
                          heads={k: dict(v) for k, v in _HEADS.items()},
                          k_turns=dict(_K_TURNS), rotary=dict(_ROTARY)),
            "attention": dict(_ATTN_BACKEND,
                              head_projection=dict(_HEAD_PROJECTIONS)),
            "decode_attention": dict(_LAST_BACKEND),
            "quant_matmul": dict(_QMM_BACKEND),
            "grouped_matmul": dict(_GMM_BACKEND),
            "moe": {"plan": dict(_PLANS_TRACED)},
            "ssm": dict(_SSM_BACKEND),
            "ce_head": dict(_CE_HEAD_TRACED),
            "xla_on_tpu": sorted(_XLA_NOTED)}


__all__ = ["adam", "lamb", "op_builder", "pallas", "sparse_attention",
           "transformer", "DeepSpeedTransformerConfig",
           "DeepSpeedTransformerLayer", "SparseSelfAttention",
           "dispatch_report"]
