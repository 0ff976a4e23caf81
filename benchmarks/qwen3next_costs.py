"""What the qwen3_next cell's new kernels need (Qwen3-Next-80B-A3B: Gated
DeltaNet layers beside ONE output-gated full layer, a held share of the
experts): the bytes and operations of `ds.gdn_step` and `ds.gdn_chunk`,
the numerators of `serve_gdn_step_roofline` and
`serve_gdn_chunk_roofline`, and the reader of the share of the held
experts a decode step touches. Beside `kernel_costs.py`, which is left as
it is.

    least_s = max(flops / peak_flops, bytes / peak_bytes_per_s)
    roofline share = least_s of the traced stretch's calls / their time

The work is counted whatever implements it, from the rows and true tokens
the program counted over the traced stretch (`traced_stats`:
`gdn_state_updates`, rows x gdn layers of the decode steps;
`gdn_prefill_tokens`, true prompt tokens x gdn layers, padding not
counted) and the model's facts, never from a kernel's grid or block
sizes:

- a STEP (a row of one layer): the float32 state [n_v, d_k, d_v] read and
  written once, q and k [n_k, d_k], v and o [n_v, d_v], g and beta [n_v];
  7 operations a state element (the decay, S^T k, the update, S^T q);
- the CHUNKED rule at the chunk of `CHUNK` = 64 rows this file states (a
  token of one layer, a value head): K K^T and Q K^T (4 C d_k a token),
  the unit-triangular solve for [W | U] (C (d_k + d_v)), W S0, Q S0 and
  K^T D (6 d_k d_v), the inner product with D (2 C d_v); q, k, v, g, beta
  in and o out once a token; the state in and out once a sequence (a
  call's rows).

`peaks.json` has a peak for the matrix unit in bfloat16 and for memory.
The step is elementwise work on the VECTOR unit, so its share is of the
memory floor; the chunk walk's matmuls run in float32 at the matrix
unit's highest precision (the state is carried over thousands of rows),
several passes each, so its share of the bfloat16 peak reads low however
well it is written. Neither can pass 100%.

Every reader returns None where the program has no such scope or counter
(a commit from before this configuration), and raises nothing.
"""

from benchmarks import scope_reduce

STEP, CHUNK_SCOPE = "ds.gdn_step", "ds.gdn_chunk"
CHUNK = 64


def dims(conf):
    """(n_k, n_v, d_k, d_v) of a gdn layer."""
    return (conf["linear_num_key_heads"], conf["linear_num_value_heads"],
            conf["linear_key_head_dim"], conf["linear_value_head_dim"])


def gdn_layers(conf):
    every = conf["full_attention_interval"]
    return sum(1 for i in range(conf["num_hidden_layers"])
               if (i + 1) % every)


def gdn_step(rows, conf):
    """(flops, bytes) of `rows` one-token steps (rows x layers)."""
    nk, nv, dk, dv = dims(conf)
    state = nv * dk * dv
    return rows * 7 * state, \
        rows * 4 * (2 * state + 2 * nk * dk + 2 * nv * dv + 2 * nv)


def gdn_chunk(tokens, conf, calls):
    """(flops, bytes) of `calls` chunk walks (sequences x layers) over
    `tokens` true tokens (tokens x layers) in all."""
    nk, nv, dk, dv = dims(conf)
    flops = tokens * nv * (4 * CHUNK * dk + CHUNK * (dk + dv) +
                           6 * dk * dv + 2 * CHUNK * dv)
    bytes_ = tokens * 4 * (2 * nk * dk + 2 * nv * dv + 2 * nv) + \
        calls * 2 * nv * dk * dv * 4
    return flops, bytes_


def _traced(rec, name, *keys):
    """(traced counters, (calls, seconds) of kernel `name`) or (None,
    None) where the run has no such counter or call."""
    stats = rec.get("traced_stats") or {}
    reduced = scope_reduce.of_run(rec)
    if reduced is None or any(k not in stats for k in keys):
        return None, None
    calls = reduced.get("calls", {}).get(name, (0, 0.0))
    return (stats, calls) if calls[0] and calls[1] else (None, None)


def gdn_step_roofline(rec):
    stats, calls = _traced(rec, STEP, "gdn_state_updates")
    if stats is None or not stats["gdn_state_updates"]:
        return None
    return scope_reduce.roofline(
        rec, *gdn_step(stats["gdn_state_updates"], rec["spec"]["config"]),
        calls[1])


def gdn_chunk_roofline(rec):
    stats, calls = _traced(rec, CHUNK_SCOPE, "gdn_prefill_tokens")
    if stats is None or not stats["gdn_prefill_tokens"]:
        return None
    return scope_reduce.roofline(
        rec, *gdn_chunk(stats["gdn_prefill_tokens"], rec["spec"]["config"],
                        calls[0]), calls[1])


def moe_experts_touched_share(rec):
    """Held experts that got at least one row, over the held experts, in
    the window's decode steps' routing layers: the share of the expert
    weights a decode step streams."""
    stats = rec.get("stats") or {}
    conf = rec["spec"]["config"]
    steps = stats.get("decode_steps", 0) * conf["num_hidden_layers"]
    if "moe_experts_touched" not in stats or not steps:
        return None
    return 100.0 * stats["moe_experts_touched"] / \
        (steps * conf["num_experts"])
