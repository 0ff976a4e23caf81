"""What a LOOPED model's decode step needs, for the cell that serves one
(Ouro-2.6B: 48 layers run 4 times over the same weights, a KV cache a
pass): the decode steps of a traced stretch and the kernel calls inside
them (`serve_loop_passes_per_step`), the least time of a step by its
bytes (`serve_loop_step_roofline`) and the pool bytes a context token
holds (`serve_loop_kv_bytes_per_token`). Beside `kernel_costs.py`, which
is left as it is. No new kernel: per call the paged decode, the row write
and the flash forward run at Pythia-1.4b's shapes.

    least_s = max(flops / peak_flops, bytes / peak_bytes_per_s)
    roofline share = least_s / the time the SAME steps took

Every reader returns None where the program has no such scope or counter
(a commit from before this configuration), and raises nothing.
"""

import functools

from benchmarks import (harness, kernel_costs, scope_reduce, trace_reduce,
                        xplane_meta)

DECODE_KERNEL = "ds.paged_decode"
# the device's line of whole programs, one event an execution, and the
# decode step's program (`InferenceEngine._decode_fn` of a planned model)
MODULE_LINE, DECODE_PROGRAM = "XLA Modules", "jit_planned_decode"


def passes(conf):
    return conf["total_ut_steps"]


def cache_layers(conf):
    """Cache layers a token holds: one a pass and layer (192)."""
    return passes(conf) * conf["num_hidden_layers"]


def kv_token_bytes(conf, itemsize=2):
    """K and V bytes of one token over every cache layer: 1,572,864."""
    return 2 * cache_layers(conf) * conf["num_key_value_heads"] * \
        conf["head_dim"] * itemsize


def block_matmul_params(conf):
    """The 48 layers' matmul weights, counted once: 2,466,250,752."""
    h, d = conf["hidden_size"], conf["head_dim"]
    heads, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    return conf["num_hidden_layers"] * (
        2 * h * heads * d + 2 * h * kv * d +
        3 * h * conf["intermediate_size"])


def decode_step(rows, kv_tokens, conf, itemsize=2):
    """(flops, bytes) one decode step of `rows` sequences over
    `kv_tokens` context tokens in all cannot avoid. Bytes: the block
    weights once a PASS (a layer's 103 MB cannot stay on the chip between
    two passes: its fast memory is 128 MiB in all and the 47 other layers
    pass through it in between, so `T x` is the true count, not a
    re-read a better schedule would save), the head once, and every
    attended token's K and V of every cache layer once. Flops: two a
    weight and row, and the attention's 4 a context token, head, feature
    and cache layer."""
    T = passes(conf)
    head = conf["vocab_size"] * conf["hidden_size"]
    bytes_ = (T * block_matmul_params(conf) + head) * itemsize + \
        kv_tokens * kv_token_bytes(conf, itemsize)
    flops = 2 * rows * (T * block_matmul_params(conf) + head) + \
        4 * kv_tokens * conf["num_attention_heads"] * conf["head_dim"] * \
        cache_layers(conf)
    return flops, bytes_


@functools.lru_cache(maxsize=2)
def _decode_steps(path, device_plane, op_line):  # noqa: ARG001 - cache keys
    """For the trace's first device that ran something: [(seconds, calls
    of the paged decode kernel inside)] of every decode program that lies
    wholly inside the traced window, but for the first and the last."""
    planes = trace_reduce.load(path)
    meta = dict(xplane_meta.load(path))
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
        table = meta.get(pname, {})
        kernel = sorted(
            s for n, s, e in ops if trace_reduce.MOSAIC in n and
            scope_reduce.innermost(table.get(n, {}).get("tf_op", ""))
            == DECODE_KERNEL)
        steps = []
        for name, s, e in by_line.get(MODULE_LINE, []):
            if name.startswith(DECODE_PROGRAM) and lo <= s and e <= hi:
                steps.append((e - s, sum(1 for k in kernel if s <= k < e)))
        # the profiler's own start and stop cut the program in flight
        # (the last is recorded with no length): the inner ones are whole
        return steps[1:-1]
    return []


def decode_steps(rec):
    path = rec.get("trace_path")
    if not path:
        return []
    return _decode_steps(path, trace_reduce.DEVICE_PLANE.pattern,
                         trace_reduce.OP_LINE.pattern)


def _looped(rec):
    return "total_ut_steps" in rec["spec"]["config"]


def loop_passes_per_step(rec):
    """Calls of the paged decode kernel inside the traced stretch's
    decode programs, over those programs, over the model's layers: the
    passes of the stack a decode step ran. An INVARIANT, not a quantity
    to raise: `total_ut_steps` (4.0 here) is right and any other reading
    is a fault; `BENCHMARK.json` says `better: higher` because its schema
    asks a direction and the fault that can happen, a pass skipped or
    one whose attention leaves the kernel, reads lower."""
    steps = decode_steps(rec) if _looped(rec) else []
    calls = sum(n for _, n in steps)
    if not calls:
        return None
    return calls / len(steps) / rec["spec"]["config"]["num_hidden_layers"]


def loop_step_roofline(rec):
    """100 * the least time a decode step of the traced stretch could
    take by its bytes and flops (`decode_step`) over the mean device time
    of the stretch's decode programs. The rows and the attended tokens
    are the program's own counts BETWEEN THE STRETCH'S EDGES
    (`rec["traced_stats"]`, which `drivers/closed_loop_probed.py` notes
    when the profiler starts and stops), as means a dispatched step."""
    edges = rec.get("traced_stats") or {}
    if not _looped(rec) or not edges.get("decode_steps") or \
            not edges.get("decode_kv_tokens"):
        return None
    steps = decode_steps(rec)
    if not steps:
        return None
    n = edges["decode_steps"]
    flops, bytes_ = decode_step(edges["decode_tokens"] / n,
                                edges["decode_kv_tokens"] / n,
                                rec["spec"]["config"])
    peaks = harness.peaks_for(rec["spec"], rec["device_kind"])
    return 100.0 * kernel_costs.least_seconds(flops, bytes_, peaks)[0] / \
        (sum(s for s, _ in steps) / len(steps))


def loop_kv_bytes_per_token(rec):
    """Bytes of the page pool in use a context token attended, over the
    window's decode steps: the pages that held a step's context
    (`stats["kv_page_steps_full"]`) times a page's bytes over all the
    cache layers, over `stats["decode_kv_tokens"]`: `kv_token_bytes`
    (1,572,864) plus the last page's unused slots, as
    `laguna_costs.kv_bytes_per_token` counts them."""
    stats = rec.get("stats") or {}
    if not _looped(rec) or not stats.get("decode_kv_tokens") or \
            not stats.get("kv_page_steps_full") or \
            "loop_passes" not in stats:
        return None
    conf = rec["spec"]["config"]
    page = rec["spec"]["cell"]["engine"]["inference"]["page_size"]
    return stats["kv_page_steps_full"] * page * kv_token_bytes(conf) / \
        stats["decode_kv_tokens"]
