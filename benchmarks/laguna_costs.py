"""What a planned model's kernels need, for the cells that serve one
(Laguna: window and full layers with their own query heads over grouped
KV heads, a held share of the experts): the numerators of
`serve_attn_kinds_roofline` and `serve_expert_share_roofline`, and the
bytes of `serve_kv_bytes_per_token`. Beside `kernel_costs.py` and
`moe_costs.py`, which are left as they are (their cost code takes
`hidden / heads` as the head dim, query heads as KV heads and
`tokens x experts a token` as useful rows, and would read over 100% here).

    least_s = max(flops / peak_flops, bytes / peak_bytes_per_s)
    roofline share = least_s / measured kernel time

Every reader returns None where the program has no such scope or counter
(a commit from before this configuration), and raises nothing.
"""

from benchmarks import harness, kernel_costs, moe_costs, scope_reduce

FULL, WINDOW = "ds.paged_decode", "ds.paged_decode_window"
_KINDS = {"full_attention": FULL, "sliding_attention": WINDOW}


def layers_by_kind(conf):
    """{kernel scope: [query heads of each layer of that cache kind]}."""
    out = {FULL: [], WINDOW: []}
    for kind, heads in zip(conf["layer_types"],
                           conf["num_attention_heads_per_layer"]):
        out[_KINDS[kind]].append(heads)
    return out


def paged_decode(rows, kv_tokens, heads, kv_heads, head_dim, itemsize=2):
    """(flops, bytes) of one decode step of one layer: `kv_tokens` rows
    of K and of V of `kv_heads` heads read once, `4 * kv_tokens * heads *
    head_dim` flops (every query head meets every attended row), and the
    `rows` queries read and their outputs written."""
    flops = 4 * kv_tokens * heads * head_dim
    bytes_ = 2 * kv_tokens * kv_heads * head_dim * itemsize + \
        2 * rows * heads * head_dim * itemsize
    return flops, bytes_


def attn_kinds_roofline(rec):
    """100 * the least time a decode step's paged attention could take,
    both cache kinds' layers together, over the time the two kernels
    took a step. The rows each kind attended are the program's own count
    (`stats["decode_kv_tokens"]`, and `["decode_kv_tokens_window"]`: a
    window layer's row attends over at most the window), as a mean per
    decode step of the window (the closed loop's population is fixed, so
    the window's mean stands for the traced stretch's)."""
    stats, steps = rec.get("stats") or {}, rec.get("decode_steps")
    reduced = scope_reduce.of_run(rec)
    if not steps or reduced is None or \
            "decode_kv_tokens_window" not in stats:
        return None
    conf = rec["spec"]["config"]
    layers = layers_by_kind(conf)
    calls = {k: reduced["calls"].get(k, [0, 0.0]) for k in (FULL, WINDOW)}
    # a traced decode step ran each layer's kernel once
    traced_steps = sum(calls[k][0] for k in calls) / \
        max(sum(len(v) for v in layers.values()), 1)
    if not traced_steps:
        return None
    peaks = harness.peaks_for(rec["spec"], rec["device_kind"])
    rows = max(1, round(stats["decode_tokens"] / steps))
    attended = {FULL: stats["decode_kv_tokens"] / steps,
                WINDOW: stats["decode_kv_tokens_window"] / steps}
    least = sum(
        kernel_costs.least_seconds(*paged_decode(
            rows, attended[kind], heads, conf["num_key_value_heads"],
            conf["head_dim"]), peaks)[0]
        for kind, per_layer in layers.items() for heads in per_layer)
    took = sum(calls[k][1] for k in calls) / traced_steps
    return 100.0 * least / took if took else None


def touched_experts(rows, experts):
    """The experts `rows` rows reach when each row falls on one of
    `experts` alike: experts * (1 - (1 - 1 / experts) ** rows). Uniform
    routing reaches the most, so this is the most weights a call's useful
    rows can make it read."""
    return experts * (1.0 - (1.0 - 1.0 / experts) ** rows)


def expert_share(rows, k, n, experts, itemsize=2):
    """(flops, bytes) of one grouped matmul of the held share: `rows`
    useful rows (pairs that fell on a held expert) through [K, N] weights
    of `experts` held experts, each touched expert's weights read once."""
    flops = 2 * rows * k * n
    bytes_ = touched_experts(rows, experts) * k * n * itemsize + \
        rows * (k + n) * itemsize
    return flops, bytes_


def expert_share_roofline(rec):
    """100 * the least time the traced stretch's grouped matmuls could
    take / the time they took. A call's buffer rows (from its HLO text,
    `moe_costs.calls`) tell a decode step from each prefill bucket; its
    useful rows are that program's tokens times the experts a token times
    the share of routed pairs that fell on a held expert, the program's
    own count over the window (`stats["moe_rows_held"]` /
    `["moe_rows_routed"]`)."""
    stats = rec.get("stats") or {}
    if not stats.get("moe_rows_routed") or "moe_rows_held" not in stats:
        return None
    traced = moe_costs.calls(rec)
    if not traced:
        return None
    spec = rec["spec"]
    peaks = harness.peaks_for(spec, rec["device_kind"])
    held = stats["moe_rows_held"] / stats["moe_rows_routed"]
    # the cell's programs, keyed by their buffer rows: the configuration's
    # `num_experts` is the held count, which is what the buffer is cut for
    useful = moe_costs.useful_rows_by_buffer(spec)
    least = took = 0.0
    for rows, k, n, experts, seconds in traced:
        if rows not in useful:
            continue
        flops, bytes_ = expert_share(useful[rows] * held, k, n, experts)
        least += kernel_costs.least_seconds(flops, bytes_, peaks)[0]
        took += seconds
    return 100.0 * least / took if took else None


def kv_bytes_per_token(rec):
    """Bytes of the page pools in use a context token attended, over the
    window's decode steps: the pages that held a step's context, by cache
    kind (`stats["kv_page_steps_full"]`, `["kv_page_steps_window"]`),
    times a page's bytes in that kind's layers, over
    `stats["decode_kv_tokens"]`. Were every layer to keep everything it
    would be 2 * layers * kv_heads * head_dim * itemsize (20,480 here),
    plus the last page's unused slots."""
    stats = rec.get("stats") or {}
    if not stats.get("decode_kv_tokens") or \
            "kv_page_steps_window" not in stats:
        return None
    conf = rec["spec"]["config"]
    page = rec["spec"]["cell"]["engine"]["inference"]["page_size"]
    layers = layers_by_kind(conf)
    page_bytes = 2 * conf["num_key_value_heads"] * page * \
        conf["head_dim"] * 2                      # K and V, bf16, a layer
    held = stats["kv_page_steps_full"] * len(layers[FULL]) + \
        stats["kv_page_steps_window"] * len(layers[WINDOW])
    return held * page_bytes / stats["decode_kv_tokens"]
