"""What a latent-attention model's kernels need, for the cell that serves
one (GLM-4.7-Flash: 20 query heads over head-less latent pages): the
numerators of `serve_latent_decode_roofline`,
`serve_latent_prefill_roofline` and `serve_routed_matmul_roofline`, and
the bytes of `serve_latent_kv_bytes_per_token`. Beside `kernel_costs.py`,
which is left as it is (its paged decode takes a K and a V row a head).

    least_s = max(flops / peak_flops, bytes / peak_bytes_per_s)
    roofline share = least_s / the time the SAME calls took

Every reader returns None where the program has no such scope or counter
(a commit from before this configuration), and raises nothing.
"""

import re

from benchmarks import (harness, kernel_costs, laguna_costs, moe_costs,
                        scope_reduce, trace_reduce)

DECODE, PREFILL = "ds.paged_decode_latent", "ds.flash_fwd"
LANES = 128
_ARRAY = re.compile(r"[a-z]+[0-9]*\[([0-9,]*)\]")


def widths(conf):
    """(a latent row's features, of which the value's): 576 and 512."""
    return (conf["kv_lora_rank"] + conf["qk_rope_head_dim"],
            conf["kv_lora_rank"])


def latent_decode(rows, kv_tokens, heads, width, v_width, itemsize=2):
    """(flops, bytes) of one decode step of one layer, absorbed: every
    head meets every attended latent row twice (`width` features for the
    score, `v_width` for the value): `2 * kv_tokens * heads * (width +
    v_width)` flops; each attended row's `width` features read ONCE,
    whatever the heads; the `rows` queries read and their outputs
    written."""
    flops = 2 * kv_tokens * heads * (width + v_width)
    bytes_ = kv_tokens * width * itemsize + \
        rows * heads * (width + v_width) * itemsize
    return flops, bytes_


def latent_decode_roofline(rec):
    """100 * the least time the traced stretch's calls of the latent
    kernel could take over the time those calls took. The attended rows
    are the program's own count BETWEEN THE STRETCH'S EDGES
    (`rec["traced_stats"]`, which `drivers/closed_loop_probed.py` notes
    when the profiler starts and stops), every layer attending them once:
    not the window's mean, which a prefill-heavy or decode-heavy stretch
    does not share. (A step is counted where it is dispatched and traced
    where it runs: the two differ by a step in a hundred.)"""
    edges = rec.get("traced_stats") or {}
    if not edges.get("decode_kv_tokens_latent"):
        return None
    reduced = scope_reduce.of_run(rec)
    count, seconds = (reduced or {"calls": {}})["calls"].get(DECODE, (0, 0))
    if not count:
        return None
    conf = rec["spec"]["config"]
    flops, bytes_ = latent_decode(
        edges["decode_tokens"], edges["decode_kv_tokens_latent"],
        conf["num_attention_heads"], *widths(conf))
    layers = conf["num_hidden_layers"]
    return scope_reduce.roofline(rec, layers * flops, layers * bytes_,
                                 seconds)


def routed_matmul_roofline(rec):
    """100 * the least time the traced stretch's grouped matmuls could
    take / the time they took, for a configuration that spells its
    experts `n_routed_experts` (`moe_costs.roofline` reads `num_experts`).
    A call's buffer rows (from its HLO text, `moe_costs.calls`) tell a
    decode step from each prefill bucket; its useful rows are that
    program's tokens times the experts a token; each expert its rows
    touch is read once, as many as rows that fall on the experts alike
    reach (`laguna_costs.touched_experts`: 55.5 of 64 for a decode step's
    128 rows, all 64 for a prefill), which is the most, so the share errs
    high where the routing is less even than that."""
    stats = rec.get("stats") or {}
    conf = rec["spec"]["config"]
    if not stats.get("moe_rows_routed") or "n_routed_experts" not in conf:
        return None
    traced = moe_costs.calls(rec)
    if not traced:
        return None
    peaks = harness.peaks_for(rec["spec"], rec["device_kind"])
    useful = moe_costs.useful_rows_by_buffer(dict(rec["spec"], config=dict(
        conf, num_experts=conf["n_routed_experts"])))
    least = took = 0.0
    for rows, k, n, experts, seconds in traced:
        if rows not in useful:
            continue
        least += kernel_costs.least_seconds(*laguna_costs.expert_share(
            useful[rows], k, n, experts), peaks)[0]
        took += seconds
    return 100.0 * least / took if took else None


def flash_calls(rec):
    """[(sequence length S, seconds)] of the `ds.flash_fwd` custom calls
    that lie wholly inside the traced stretch, first device. A device
    event's name is its HLO instruction, whose result is the kernel's
    output [batch * heads, S, head dim]."""
    path = rec.get("trace_path")
    if not path:
        return []
    out = []
    for tf_op, _, events, (lo, hi) in scope_reduce.device_operations(
            trace_reduce.load(path), scope_reduce.xplane_meta.load(path)):
        for name, start, end in events:
            if trace_reduce.MOSAIC in name and lo <= start and end <= hi \
                    and scope_reduce.innermost(tf_op[name]) == PREFILL:
                head = name.partition("custom-call(")[0].partition(" = ")[2]
                dims = [[int(d) for d in m.group(1).split(",") if d]
                        for m in _ARRAY.finditer(head)]
                if dims and len(dims[0]) == 3:
                    out.append((dims[0][1], end - start))
        break
    return out


def prompt_tokens_by_bucket(spec):
    """{prefill bucket: mean length of the prompts of the traffic's
    (fixed, cyclic) population that land in it}: a bucket's pad tokens
    are the program's choice and are not counted."""
    traffic = spec["traffic"]
    driver = harness.load_module(spec["root"], "drivers", traffic["kind"])
    prompts = driver.quantile_lengths(traffic["prompt_len"],
                                      traffic["population"])
    buckets = sorted(spec["cell"]["engine"]["inference"]["prefill_lengths"])
    out = {}
    for i, bucket in enumerate(buckets):
        mine = [int(p) for p in prompts
                if (buckets[i - 1] if i else 0) < p <= bucket]
        out[bucket] = sum(mine) / len(mine) if mine else bucket
    return out


def latent_prefill_roofline(rec):
    """100 * the least time the traced stretch's prefill attention could
    take / the time it took: the segmented flash forward at the expanded
    form's head dim (nope + rope for q.k, and the same for v), causal,
    each call at its own bucket's mean prompt length."""
    stats = rec.get("stats") or {}
    if "decode_kv_tokens_latent" not in stats:
        return None
    traced = flash_calls(rec)
    if not traced:
        return None
    conf = rec["spec"]["config"]
    tokens = prompt_tokens_by_bucket(rec["spec"])
    peaks = harness.peaks_for(rec["spec"], rec["device_kind"])
    head_dim = conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]
    least = took = 0.0
    for bucket, seconds in traced:
        if bucket not in tokens:
            continue
        least += kernel_costs.least_seconds(*kernel_costs.flash_fwd(
            1, conf["num_attention_heads"], tokens[bucket], head_dim),
            peaks)[0]
        took += seconds
    return 100.0 * least / took if took else None


def latent_kv_bytes_per_token(rec):
    """Bytes of the latent pool in use a context token attended, over the
    window's decode steps: the pages that held a step's context
    (`stats["kv_page_steps_latent"]`) times a page's bytes in all the
    layers, over `stats["decode_kv_tokens_latent"]`. A page's row is the
    latent row rounded up to whole lane tiles (576 -> 640 features), as
    the pool holds it: 7,680 bytes a token over 6 layers plus the last
    page's unused slots, where heads of 256 + 256 would hold 122,880."""
    stats = rec.get("stats") or {}
    if not stats.get("decode_kv_tokens_latent"):
        return None
    conf = rec["spec"]["config"]
    page = rec["spec"]["cell"]["engine"]["inference"]["page_size"]
    row = -(-widths(conf)[0] // LANES) * LANES
    page_bytes = conf["num_hidden_layers"] * page * row * 2     # bf16
    return stats["kv_page_steps_latent"] * page_bytes / \
        stats["decode_kv_tokens_latent"]
