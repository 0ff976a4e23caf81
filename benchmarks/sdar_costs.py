"""What a BLOCK-GENERATING model's decode pass needs, for the cell that
serves one (SDAR-30B-A3B-Chat: a block of 4 positions a sequence and pass
under the block-causal mask, 128 experts of width 768 a layer, 8 query
heads a KV head): the paged kernel at a block's rows
(`serve_block_decode_roofline`), the experts' grouped matmuls at a pass's
rows (`serve_block_grouped_matmul_roofline`), the whole block-pass program
(`serve_block_step_roofline`), the two invariants of the pass counters
(`serve_block_tokens_per_pass`, `serve_block_commit_share`), the rows of
the pass program that hold a sequence (`serve_block_pass_occupancy`) and
the time to a request's first unmasked row
(`serve_block_first_unmask_ms`). Beside
`kernel_costs.py`, which is left as it is. No new kernel: the paged decode
runs at a group of 32 rows a KV head, the row write moves 4 rows of one
packed group, the grouped matmul runs 128 groups of width 768.

    least_s = max(flops / peak_flops, bytes / peak_bytes_per_s)
    roofline share = least_s / the time the SAME calls took

A row-pass is one sequence's block through one program. The rows and the
attended positions are the program's own counts BETWEEN THE TRACED
STRETCH'S EDGES (`rec["traced_stats"]`, which `closed_loop_probed` notes
when the profiler starts and stops). Every reader returns None where the
program has no such scope or counter (a commit from before this
configuration), and raises nothing.
"""

import functools

from benchmarks import (harness, kernel_costs, moe_costs, scope_reduce,
                        trace_reduce)

BLOCK_KERNEL = "ds.paged_decode_block"
# the device's line of whole programs, one event an execution, and the
# block pass's program (`InferenceEngine._decode_fn` of a block model)
MODULE_LINE, BLOCK_PROGRAM = "XLA Modules", "jit_planned_block_decode"


def _block(rec):
    """The block length the engine ran, or None where the cell is of no
    block model."""
    family = rec["spec"]["config"].get("family")
    if family != "sdar_moe":
        return None
    return harness.load_module(rec["spec"]["root"], "families",
                               family).GENERATION["block"]


def kv_token_bytes(conf, itemsize=2):
    """K and V bytes of one token and layer: 2,048."""
    return 2 * conf["num_key_value_heads"] * conf["head_dim"] * itemsize


def layer_fixed_params(conf):
    """A layer's attention and router weights: 19,136,512."""
    h, d = conf["hidden_size"], conf["head_dim"]
    return 2 * h * conf["num_attention_heads"] * d + \
        2 * h * conf["num_key_value_heads"] * d + h * conf["num_experts"]


def expert_params(conf):
    """One expert's three matrices: 4,718,592."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def experts_touched(rows, conf):
    """The expected number of a layer's experts that `rows` token rows
    touch under uniform routing, k distinct a row: E (1 - (1 - k/E)^rows)
    (127.97 of 128 at 128 rows; seeded random weights route near
    uniformly)."""
    E, k = conf["num_experts"], conf["num_experts_per_tok"]
    return E * (1.0 - (1.0 - k / E) ** rows)


def block_decode(row_passes, kv_tokens, block, conf, itemsize=2):
    """(flops, bytes) of ONE call of the paged kernel (one layer) over
    `row_passes` sequences' blocks that attend `kv_tokens` positions in
    all: K and V of the KV heads read once, the block's query rows read
    and written once, `4 x block x heads x d` flops a position."""
    heads, d = conf["num_attention_heads"], conf["head_dim"]
    flops = 4 * kv_tokens * block * heads * d
    bytes_ = kv_tokens * kv_token_bytes(conf, itemsize) + \
        2 * row_passes * block * heads * d * itemsize
    return flops, bytes_


def block_step(row_passes, kv_tokens, block, conf, itemsize=2):
    """(flops, bytes) one block-pass program of `row_passes` sequences
    over `kv_tokens` attended positions cannot avoid. Bytes: every layer's
    attention and router weights and every TOUCHED expert's once, the head
    once (the embedding is a gather of a few rows), the attended K and V
    of every layer, the block's written rows. Flops: two a weight and
    token row (8 experts a row), the head on every row, and the
    attention's 4 a position, block row, head and feature."""
    L, h = conf["num_hidden_layers"], conf["hidden_size"]
    rows = row_passes * block
    head = conf["vocab_size"] * h
    per_row = layer_fixed_params(conf) + \
        conf["num_experts_per_tok"] * expert_params(conf)
    bytes_ = (L * (layer_fixed_params(conf) +
                   experts_touched(rows, conf) * expert_params(conf)) +
              head) * itemsize + \
        L * (kv_tokens + rows) * kv_token_bytes(conf, itemsize)
    flops = 2 * rows * (L * per_row + head) + \
        L * block_decode(row_passes, kv_tokens, block, conf)[0]
    return flops, bytes_


@functools.lru_cache(maxsize=2)
def _block_programs(path, device_plane, op_line):  # noqa: ARG001 - cache keys
    """Seconds of every block-pass program of the trace's first device
    that lies wholly inside the traced window, but for the first and the
    last."""
    planes = trace_reduce.load(path)
    window = scope_reduce.window_of(planes)
    for pname, lines in planes:
        if not trace_reduce.DEVICE_PLANE.match(pname):
            continue
        by_line = dict(lines)
        ops = [ev for lname, evs in lines
               if trace_reduce.OP_LINE.match(lname) for ev in evs]
        if not ops:
            continue
        lo, hi = window if window else (min(e[1] for e in ops),
                                        max(e[2] for e in ops))
        steps = [e - s for name, s, e in by_line.get(MODULE_LINE, [])
                 if name.startswith(BLOCK_PROGRAM) and lo <= s and e <= hi]
        # the profiler's own start and stop cut the program in flight
        return steps[1:-1]
    return []


def block_programs(rec):
    path = rec.get("trace_path")
    if not path:
        return []
    return _block_programs(path, trace_reduce.DEVICE_PLANE.pattern,
                           trace_reduce.OP_LINE.pattern)


def _edges(rec):
    """(row-passes, attended positions) a dispatched program of the traced
    stretch, as means, or None."""
    edges = rec.get("traced_stats") or {}
    if _block(rec) is None or not edges.get("decode_steps") or \
            not edges.get("block_passes") or \
            not edges.get("decode_kv_tokens_block"):
        return None
    n = edges["decode_steps"]
    return edges["block_passes"] / n, edges["decode_kv_tokens_block"] / n


def block_decode_roofline(rec):
    """100 * the least time one call of the paged kernel at a block's
    rows could take (`block_decode`, at the stretch's mean row-passes and
    attended positions a program) over the mean time of the stretch's
    calls of `ds.paged_decode_block`."""
    per_program = _edges(rec)
    if per_program is None:
        return None
    seconds = scope_reduce.seconds_per_call(rec, [BLOCK_KERNEL],
                                            per=[BLOCK_KERNEL])
    if seconds is None:
        return None
    return scope_reduce.roofline(
        rec, *block_decode(*per_program, _block(rec), rec["spec"]["config"]),
        seconds)


def block_step_roofline(rec):
    """100 * the least time a block-pass program of the traced stretch
    could take by its bytes and flops (`block_step`) over the mean device
    time of the stretch's whole programs."""
    per_program = _edges(rec)
    steps = block_programs(rec) if per_program else []
    if not steps:
        return None
    flops, bytes_ = block_step(*per_program, _block(rec),
                               rec["spec"]["config"])
    peaks = harness.peaks_for(rec["spec"], rec["device_kind"])
    return 100.0 * kernel_costs.least_seconds(flops, bytes_, peaks)[0] / \
        (sum(steps) / len(steps))


def grouped_matmul_roofline(rec):
    """100 * the least time the traced stretch's `ds.grouped_matmul`
    calls could take over the time they took: `moe_costs.roofline`'s
    calls, costs and prefill buckets, with a BLOCK PASS's useful rows:
    the stretch's mean row-passes a program x the block x the experts a
    token (1,024 of the 2,944-row buffer at 32 full rows), where
    `moe_costs.useful_rows_by_buffer` reckons a token step's batch x the
    experts a token. (A prefill's useful rows stay its prompts' mean
    length: a block model caches the prompt's whole blocks, up to 3
    tokens fewer.)"""
    per_program = _edges(rec)
    traced = moe_costs.calls(rec) if per_program else []
    if not traced:
        return None
    spec, block = rec["spec"], _block(rec)
    conf = spec["config"]
    top_k, experts = conf["num_experts_per_tok"], conf["num_experts"]
    useful = moe_costs.useful_rows_by_buffer(spec)
    for batch in spec["cell"]["engine"]["inference"]["decode_batch_sizes"]:
        useful.pop(moe_costs.buffer_rows(batch, top_k, experts), None)
        useful[moe_costs.buffer_rows(batch * block, top_k, experts)] = \
            per_program[0] * block * top_k
    peaks = harness.peaks_for(spec, rec["device_kind"])
    least = took = 0.0
    for rows, k, n, n_experts, seconds in traced:
        if rows not in useful:
            continue
        flops, bytes_ = moe_costs.grouped_matmul(useful[rows], k, n,
                                                 n_experts)
        least += kernel_costs.least_seconds(flops, bytes_, peaks)[0]
        took += seconds
    return 100.0 * least / took if took else None


def _stat_ratio(rec, over, under):
    stats = rec.get("stats") or {}
    if _block(rec) is None or not stats.get(under) or over not in stats:
        return None
    return stats[over] / stats[under]


def tokens_per_pass(rec):
    """Rows the window's passes unmasked over the row-passes it
    dispatched. Under the floor of one row a denoising pass a block of 4
    masked rows takes 4 such passes and a commit: 0.8. Two edges of a
    request move the cell's reading off it by under a hundredth: its
    FIRST block holds the prompt's last P % 4 tokens and reads (4 - P %
    4) / (5 - P % 4); its LAST block is never committed (the request ends
    when its last token is delivered) and reads 1. A reading well under
    0.8 is a fault; well over it, the threshold fired."""
    return _stat_ratio(rec, "block_tokens_final", "block_passes")


def commit_share(rec):
    """Row-passes that were commits over the row-passes dispatched: 1 of
    5 under the floor: 0.2, less the last block of each request, which is
    not committed (63 commits in 319 passes of an answer of 256 tokens:
    0.197)."""
    return _stat_ratio(rec, "block_commit_passes", "block_passes")


def pass_occupancy(rec):
    """100 * the row-passes the window dispatched over the rows its pass
    programs had (`decode_steps` programs of the compiled batch): the
    share of a pass program's rows that held a sequence's block. What
    `serve_batch_occupancy` is to a token step; that reader divides
    DELIVERED tokens, which a block pass yields 0.8 of a row at best."""
    share = _stat_ratio(rec, "block_passes", "decode_steps")
    if share is None or not rec.get("max_batch_size"):
        return None
    return 100.0 * share / rec["max_batch_size"]


def first_unmask_ms(rec):
    """Mean milliseconds from a request's submit to the read-back of the
    pass that first unmasked one of its rows, over the requests whose
    first row the window unmasked (`Request.first_unmask_at`): a prefill
    step, the first pass, its read-back one step late. The answer begins
    to exist then; its first TOKEN is the block's leftmost row, final 1
    to `block` passes later as the seed's weights draw the confidences,
    which is why the window's median TTFT is no steady number here."""
    mean = _stat_ratio(rec, "block_first_unmask_s", "block_first_unmasks")
    return None if mean is None else 1e3 * mean
