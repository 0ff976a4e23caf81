from benchmarks import kernel_costs, scope_reduce


def read(rec):
    """The least time one call of the paged decode kernel could take over
    the mean time of one call in the traced stretch. The bytes are the
    program's own count: `stats["decode_kv_tokens"]`, the context tokens
    the window's decode steps attended over, as a mean per step (the
    closed loop's population is fixed, so the window's mean stands for
    the traced stretch's)."""
    stats, steps = rec.get("stats") or {}, rec.get("decode_steps")
    if not steps or "decode_kv_tokens" not in stats:
        return None
    seconds = scope_reduce.seconds_per_call(
        rec, ["ds.paged_decode"], per=["ds.paged_decode"])
    if seconds is None:
        return None
    conf = rec["spec"]["config"]
    heads = conf["num_attention_heads"]
    rows = max(1, round(stats["decode_tokens"] / steps))
    context = [stats["decode_kv_tokens"] / steps / rows] * rows
    return scope_reduce.roofline(
        rec, *kernel_costs.paged_decode(
            context, heads, conf["hidden_size"] // heads), seconds)
