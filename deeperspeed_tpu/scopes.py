"""The names the program gives its own work on the device.

`jax.named_scope` puts a name on the jax name stack; every operation
traced under it carries the name in its HLO `op_name`, and the profiler
copies that into the device trace (XProf's `tf_op`). The name survives
`grad`, `custom_vjp`, `jax.checkpoint`, `lax.scan` and `shard_map` as a
plain path component, so a kernel keeps its name whatever transformation
wraps it:

    jit(step)/transpose(jvp())/checkpoint/ds.block/ds.attn/ds.flash_bwd/...
    jit(step)/.../checkpoint/rematted_computation/ds.block/ds.mlp/dot_general

Scopes change metadata only: the compiled program is the same with and
without them (`tests/test_scopes.py`). One table, so that the trace
readers (`benchmarks/scope_reduce.py`), the tests and the documents agree
on the names: `scope` refuses a name that is not here.

Kinds: a `kernel` scope holds one `pallas_call`, which is given the same
name as its `name=`, and nothing else; a `region` is a stretch of model
or engine code (`ds.kv_write` alone also names a kernel: the one-token
row write is a `pallas_call` on a TPU and a scatter off it, and both are
the same work under the same name); a `container` holds other scopes, and what lies in it but
in no inner scope is its own overhead (a scan's slicing, stacking and
carried-state copies). A reader gives each operation to the innermost
scope of its `op_name`. docs/observability.md, "Device scopes".
"""

import functools

import jax

# name -> (kind, what it covers)
SCOPES = {
    "ds.flash_fwd": ("kernel", "flash attention forward, tiled or "
                               "single-block, segmented or not"),
    "ds.flash_bwd_dq": ("kernel", "flash attention backward, dq pass of a "
                                  "sequence over the fused kernel's budget"),
    "ds.flash_bwd_dkv": ("kernel", "flash attention backward, dk/dv pass of "
                                   "such a sequence"),
    "ds.flash_bwd": ("kernel", "flash attention backward as one kernel: the "
                               "tiled dq/dk/dv walk, or a single block"),
    "ds.paged_decode": ("kernel", "paged decode attention"),
    "ds.flash_fwd_window": ("kernel", "the flash forward of a window "
                                      "layer: the same kernel, tiles "
                                      "wholly behind the window skipped"),
    "ds.paged_decode_window": ("kernel", "the paged decode of a window "
                                         "layer: the same kernel over the "
                                         "pages inside the window"),
    "ds.paged_decode_block": ("kernel", "the paged decode of a block "
                                        "pass: the same kernel, a block's "
                                        "rows x a KV head's query heads "
                                        "as that head's one group"),
    "ds.paged_decode_latent": ("kernel", "the absorbed paged decode of a "
                                         "latent layer: every head over "
                                         "ONE [page, row] tile a page"),
    "ds.paged_decode_cross": ("kernel", "the paged decode of a cross "
                                        "layer: the same kernel over "
                                        "ANOTHER layer's pages (the one "
                                        "full layer's, which alone "
                                        "writes them)"),
    "ds.ssm_scan": ("kernel", "a state-space layer's selective scan over "
                              "a prompt: a lane tile's state resident "
                              "over the time walk"),
    "ds.ssm_step": ("kernel", "the scan's one-token step of a decode "
                              "batch: each row's recurrent state read "
                              "from its slot, updated and written back "
                              "in place"),
    "ds.gdn_chunk": ("kernel", "a Gated DeltaNet layer's delta rule over "
                               "a prompt, 64 rows at a time as matmuls: a "
                               "group of heads' matrix states resident "
                               "over the chunk walk, float32"),
    "ds.gdn_step": ("kernel", "the delta rule's one-token step of a "
                              "decode batch: each row's matrix states "
                              "read from its slot, decayed, corrected "
                              "and written back in place"),
    "ds.eva_summarize": ("kernel", "an eva layer's pooling: a chunk's rows "
                                   "to ONE key and ONE value under a "
                                   "softmax over the chunk, in float32. A "
                                   "decode step's closing rows: the gather "
                                   "of their chunks from the window's "
                                   "pages and the kernel of this name, "
                                   "which pools and writes the row into a "
                                   "pending page in place; a prefill's: "
                                   "every chunk of the prompt at once, a "
                                   "fusion"),
    "ds.adam": ("kernel", "fused Adam over a flat shard"),
    "ds.sparse_attn_fwd": ("kernel", "block-sparse attention forward"),
    "ds.sparse_attn_bwd_dkv": ("kernel", "block-sparse backward, dk/dv"),
    "ds.sparse_attn_bwd_dq": ("kernel", "block-sparse backward, dq"),
    "ds.grouped_matmul": ("kernel", "grouped (per-expert) matmul"),
    "ds.grouped_matmul_dw": ("kernel", "grouped matmul, weight gradient"),
    "ds.quant_matmul": ("kernel", "int8-weight matmul"),
    "ds.moe_route": ("region", "a dropless MoE's router: the float32 "
                               "router matmul, softmax, top-k"),
    "ds.moe_dispatch": ("region", "sort by expert, the groups' offsets "
                                  "and tile maps, the gather of token "
                                  "rows into the experts' buffer"),
    "ds.moe_combine": ("region", "the experts' rows gathered back, "
                                 "weighted and summed over a token's "
                                 "experts"),
    "ds.attn_gate": ("region", "the sigmoid gate on the attention "
                               "output (a scalar a head, or elementwise): "
                               "its projection from the normed input, "
                               "and the product"),
    "ds.mla_q": ("region", "a latent layer's query: the low-rank "
                           "projection, its norm, the projection to the "
                           "heads, the rotary of their rope parts"),
    "ds.mla_kv": ("region", "a latent layer's cache row: the low-rank "
                            "projection, the norm of c_kv, the rotary of "
                            "the one key row k_r"),
    "ds.mla_expand": ("region", "the expanded form (training, prefill): "
                                "every head's keys and values from the "
                                "latent rows"),
    "ds.mla_absorb": ("region", "the absorbed form (decode): q' = q_nope "
                                "W_uk^T before the kernel, o = u W_uv "
                                "after it"),
    "ds.ssm_in": ("region", "a state-space layer before its scan: the "
                            "in-projection to [u | z], the causal "
                            "depthwise convolution of u with its "
                            "state's rows, the projections to the step, "
                            "B and C, the step's softplus"),
    "ds.ssm_out": ("region", "a state-space layer after its scan: the "
                             "gate s * silu(z) and the out-projection"),
    "ds.gdn_in": ("region", "a Gated DeltaNet layer before its delta "
                            "rule: the projections to [q | k | v | z] and "
                            "[b | a], the causal depthwise convolution of "
                            "q | k | v with its slot's rows, the l2 norms "
                            "of q and k, the step beta and the decay g"),
    "ds.gdn_out": ("region", "a Gated DeltaNet layer after its delta "
                             "rule: the RMS norm over each head's output "
                             "gated by silu(z), and the out-projection"),
    "ds.gmu": ("region", "a gated memory unit: the memory state-space "
                         "layer's scan output of the same token, gated "
                         "by silu of a projection of the normed input, "
                         "and the out-projection"),
    "ds.attn_diff": ("region", "differential attention around its two "
                               "softmaxes: the pairs' queries laid out "
                               "for the kernel, lambda, the subtraction, "
                               "the norm over a pair's features and its "
                               "scale"),
    "ds.eva_prefill": ("region", "an eva layer's attention over a prompt: "
                                 "each window's rows laid behind the "
                                 "pooled rows of the windows before it as "
                                 "one sequence, the flash forward over "
                                 "them (`ds.flash_fwd`, inside), the "
                                 "window's rows taken back out"),
    "ds.moe_shared": ("region", "the shared expert every token passes "
                                "through, beside the routed ones, and "
                                "its own sigmoid gate where it has one"),
    "ds.attn_xla": ("region", "the XLA fallback of attention"),
    "ds.paged_decode_xla": ("region", "the XLA fallback of paged decode"),
    "ds.embed": ("region", "token (and position) embedding gather"),
    "ds.block": ("region", "one transformer block: what is in no inner "
                           "scope is the residual adds"),
    "ds.attn": ("region", "ln1, QKV projection, rotary, the attention "
                          "core, the output projection"),
    "ds.mlp": ("region", "ln2 and the MLP (dense or MoE)"),
    "ds.ce_head": ("region", "output head fused with cross entropy"),
    "ds.lm_head": ("region", "output head of a serving program"),
    "ds.optimizer": ("region", "the engine's update: unscale, norm and "
                               "clip, Adam, weight cast, loss scale"),
    "ds.kv_write": ("region", "new K/V into the paged pools: prefill's "
                              "whole-page scatter, and the one-token "
                              "step's row, written in place by the "
                              "kernel of this name"),
    "ds.sample": ("region", "sampling the next token from the logits"),
    "ds.unmask": ("region", "a block pass after the head: each row's "
                            "confidence (the softmax probability of its "
                            "argmax), the choice among the masked rows, "
                            "the block state's update, the commit flag"),
    "ds.layers": ("container", "the loop or scan over the blocks"),
    "ds.loop": ("container", "one pass of a looped model's stack: what is "
                             "in no inner scope is the loop's own "
                             "overhead (slicing, carried-state copies)"),
    "ds.loop_exit": ("region", "a looped model between passes and after "
                               "them: the final norm of a pass, the exit "
                               "gate, the choice of the pass the head "
                               "reads"),
}


def scope(name):
    """`jax.named_scope(name)` for a name of the table."""
    if name not in SCOPES:
        raise KeyError(f"{name!r} is not a device scope; "
                       f"deeperspeed_tpu/scopes.py has {sorted(SCOPES)}")
    return jax.named_scope(name)


def scoped(name):
    """Decorator: the whole function runs under `scope(name)`."""
    scope(name)                 # refuse an unknown name where it is used

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
