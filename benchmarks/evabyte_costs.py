"""What the evabyte cell's mechanism needs (EvaByte: every layer's
attention exact inside a window of 2,048 bytes and one pooled key / value
for each 16 bytes behind it, in ONE page pool): the bytes and operations
of the decode read over both populations (`ds.paged_decode`), of a decode
step's pooling (`ds.eva_summarize`) and of a prefill's attention
(`ds.eva_prefill`, the flash forward inside it), the numerators of
`serve_eva_decode_roofline`, `serve_eva_summarize_roofline` and
`serve_eva_prefill_roofline`, and the counter readers. Beside
`kernel_costs.py`, which is left as it is.

    least_s = max(flops / peak_flops, bytes / peak_bytes_per_s)
    roofline share = least_s of the traced stretch's calls / their time

The work is counted from the program's own COUNTERS over the traced
stretch (`traced_stats`, the engine's counters at the stretch's two
edges), never from the contexts: a decode step reads
`decode_kv_tokens_eva_window` exact rows and `decode_kv_tokens_eva_summary`
pooled rows a layer (a row of either is K and V of 32 heads of 128:
16,384 B in bf16), pools `eva_chunks_pooled` chunks a layer (16 rows of K
and V read, one of each written), and a prefill scores
`eva_prefill_pairs` (query, key) pairs a head and layer over its REAL
rows, whatever its bucket.

Every reader returns None where the program has no such scope or counter
(a commit from before this configuration), and raises nothing.
"""

from benchmarks import harness, kernel_costs, scope_reduce

DECODE, SUMMARIZE = "ds.paged_decode", "ds.eva_summarize"
PREFILL, FLASH = "ds.eva_prefill", "ds.flash_fwd"
WINDOW_ROWS, SUMMARY_ROWS = ("decode_kv_tokens_eva_window",
                             "decode_kv_tokens_eva_summary")


def row_bytes(conf, itemsize=2):
    """K and V of one row (exact or pooled) in one layer."""
    return 2 * conf["hidden_size"] * itemsize


def decode_read(rows, queries, conf):
    """(flops, bytes) of ONE layer's paged decode of `queries` rows over
    `rows` attended rows of either population: their K and V read once,
    4 flops an attended row, head and feature; q read and o written."""
    h = conf["hidden_size"]
    return 4 * rows * h, rows * row_bytes(conf) + 2 * queries * h * 2


def summarize(chunks, conf):
    """(flops, bytes) of ONE layer's pooling of `chunks` chunks: the
    chunk's K and V rows read, one pooled row of each written; a dot with
    phi, a softmax and two weighted sums a row and feature."""
    h, C = conf["hidden_size"], conf["chunk_size"]
    return 6 * chunks * C * h, chunks * (C + 1) * row_bytes(conf)


def prefill_attention(pairs, tokens, conf):
    """(flops, bytes) of ONE layer's prefill attention that scores `pairs`
    (query, key) pairs a head over `tokens` real rows: QK^T and PV, q, k,
    v read and the output written once, and a pooled K and V row a chunk
    written and read back."""
    h, C = conf["hidden_size"], conf["chunk_size"]
    return 4 * pairs * h, 4 * tokens * h * 2 + 2 * (tokens // C) * \
        row_bytes(conf)


def _traced(rec, *keys):
    stats = rec.get("traced_stats") or {}
    reduced = scope_reduce.of_run(rec)
    if reduced is None or any(k not in stats for k in keys):
        return None, None
    return stats, reduced


def _share(rec, flops, bytes_, seconds):
    if not seconds:
        return None
    peaks = harness.peaks_for(rec["spec"], rec["device_kind"])
    return 100.0 * kernel_costs.least_seconds(flops, bytes_, peaks)[0] / \
        seconds


def _layers(rec):
    return rec["spec"]["config"]["num_hidden_layers"]


def decode_roofline(rec):
    stats, reduced = _traced(rec, WINDOW_ROWS, SUMMARY_ROWS)
    calls, seconds = (reduced or {}).get("calls", {}).get(DECODE, (0, 0.0))
    if not calls:
        return None
    rows = stats[WINDOW_ROWS] + stats[SUMMARY_ROWS]
    flops, bytes_ = decode_read(rows, stats["decode_tokens"],
                                rec["spec"]["config"])
    return _share(rec, _layers(rec) * flops, _layers(rec) * bytes_, seconds)


def decode_time_share(rec):
    """The paged decode's share of device busy time; None where the
    program counts no chunk-pooled rows (another model's decode)."""
    if WINDOW_ROWS not in (rec.get("stats") or {}):
        return None
    return scope_reduce.share(rec, [DECODE])


def summarize_roofline(rec):
    """The decode steps' pooling kernel alone (a prefill's pooling is a
    fusion under the same scope, and no kernel call)."""
    stats, reduced = _traced(rec, "eva_chunks_pooled")
    calls, seconds = (reduced or {}).get("calls", {}).get(SUMMARIZE,
                                                          (0, 0.0))
    if not calls or not stats["eva_chunks_pooled"]:
        return None
    flops, bytes_ = summarize(stats["eva_chunks_pooled"],
                              rec["spec"]["config"])
    return _share(rec, _layers(rec) * flops, _layers(rec) * bytes_, seconds)


def summarize_time_share(rec):
    return scope_reduce.share(rec, [SUMMARIZE])


def _prefill_seconds(reduced):
    if reduced is None or PREFILL not in reduced["scopes"]:
        return None
    return reduced["scopes"][PREFILL] + reduced["scopes"].get(FLASH, 0.0)


def prefill_roofline(rec):
    """A prefill's attention: the region's own operations and the flash
    forward it calls (every layer of this model is chunk-pooled, so all of
    that kernel's time is this region's)."""
    stats, reduced = _traced(rec, "eva_prefill_pairs", "prefill_tokens")
    seconds = _prefill_seconds(reduced)
    if not seconds or not stats["eva_prefill_pairs"]:
        return None
    flops, bytes_ = prefill_attention(stats["eva_prefill_pairs"],
                                      stats["prefill_tokens"],
                                      rec["spec"]["config"])
    return _share(rec, _layers(rec) * flops, _layers(rec) * bytes_, seconds)


def prefill_time_share(rec):
    reduced = scope_reduce.of_run(rec)
    if reduced is None or PREFILL not in reduced["scopes"]:
        return None
    return scope_reduce.share(rec, [PREFILL, FLASH])


def rows_per_token(rec):
    """Rows the window's decode steps read over the live contexts those
    stand for: the compression the cell runs at."""
    stats = rec.get("stats") or {}
    if not stats.get("decode_context_tokens_eva"):
        return None
    return (stats[WINDOW_ROWS] + stats[SUMMARY_ROWS]) / \
        stats["decode_context_tokens_eva"]


def rolls_in_window(rec):
    stats = rec.get("stats") or {}
    return stats.get("eva_windows_rolled")
