"""What the phi4flash cell's new kernels need (Phi-4-mini-flash-reasoning:
Mamba-1, window and ONE full differential-attention layer whose K and V
seven cross layers read): the bytes and operations of `ds.ssm_step`,
`ds.ssm_scan` and of the paged decode over the full layer's pages
(`ds.paged_decode_cross` + the full layer's own `ds.paged_decode`), the
numerators of `serve_ssm_step_roofline`, `serve_ssm_scan_roofline` and
`serve_cross_decode_roofline`, and the two counter readers. Beside
`kernel_costs.py`, which is left as it is.

    least_s = max(flops / peak_flops, bytes / peak_bytes_per_s)
    roofline share = least_s of the traced stretch's calls / their time

The work is counted whatever implements it: a recurrent state is read and
written once a token and layer, a scan reads its step, its input and its
B and C and writes its output once a token, an attended row's K and V are
read once a reading layer. The rows and tokens are the program's own
counts over the traced stretch (`traced_stats`, the engine's counters at
the stretch's two edges). `peaks.json` has a peak for the matrix unit and
for memory: both scans are elementwise work on the VECTOR unit (some 8
operations and an exponential a channel, state and token), which has no
entry there, so their shares are of the memory floor alone and a scan
that the vector unit bounds reads low however well it is written.

Every reader returns None where the program has no such scope or counter
(a commit from before this configuration), and raises nothing.
"""

from benchmarks import harness, kernel_costs, scope_reduce

STEP, SCAN = "ds.ssm_step", "ds.ssm_scan"
CROSS, FULL = "ds.paged_decode_cross", "ds.paged_decode"
STATE, CONV, EXPAND = 16, 4, 2


def inner(conf):
    return EXPAND * conf["hidden_size"]


def layer_counts(conf):
    """(Mamba layers, layers that read the full layer's pages)."""
    L = conf["num_hidden_layers"]
    return L // 4 + 1, L // 4 - 1


def ssm_step(rows, conf, calls):
    """(flops, bytes) of `calls` one-token steps over `rows` live rows in
    all: each row's float32 state [N, d_i] read and written, its step and
    input read and its output written, B and C; a call's A and D once."""
    d = inner(conf)
    flops = rows * STATE * d * 8
    bytes_ = rows * (2 * STATE * d * 4 + 3 * d * 4 + 2 * STATE * 4) + \
        calls * (STATE * d + d) * 4
    return flops, bytes_


def ssm_scan(tokens, conf, calls):
    """(flops, bytes) of `calls` scans over `tokens` real prompt tokens in
    all: a token's step, input, B and C read and its output written; a
    call's A and D read and its final state written."""
    d = inner(conf)
    flops = tokens * STATE * d * 8
    bytes_ = tokens * (3 * d * 4 + 2 * STATE * 4) + \
        calls * (2 * STATE * d + d) * 4
    return flops, bytes_


def cross_decode(rows, kv_tokens, conf, itemsize=2):
    """(flops, bytes) of ONE layer's paged decode of `rows` queries over
    `kv_tokens` attended rows of the full layer's pages: their K and V
    (`num_key_value_heads` x the published head size each) read once,
    4 flops an attended row, head and feature."""
    e = conf["hidden_size"] // conf["num_attention_heads"]
    kv = conf["num_key_value_heads"] * e
    q = conf["num_attention_heads"] * e
    return 4 * kv_tokens * q, \
        2 * kv_tokens * kv * itemsize + 2 * rows * 2 * q * itemsize


def _traced(rec, *keys):
    stats = rec.get("traced_stats") or {}
    reduced = scope_reduce.of_run(rec)
    if reduced is None or any(k not in stats for k in keys):
        return None, None
    return stats, reduced


def _share(rec, flops, bytes_, seconds):
    peaks = harness.peaks_for(rec["spec"], rec["device_kind"])
    if not seconds:
        return None
    return 100.0 * kernel_costs.least_seconds(flops, bytes_, peaks)[0] / \
        seconds


def ssm_step_roofline(rec):
    stats, reduced = _traced(rec, "state_slot_steps")
    calls, seconds = (reduced or {}).get("calls", {}).get(STEP, (0, 0.0))
    if not calls:
        return None
    conf = rec["spec"]["config"]
    rows = stats["state_slot_steps"] * layer_counts(conf)[0]
    return _share(rec, *ssm_step(rows, conf, calls), seconds)


def ssm_scan_roofline(rec):
    stats, reduced = _traced(rec, "prefill_tokens", "state_slot_steps")
    calls, seconds = (reduced or {}).get("calls", {}).get(SCAN, (0, 0.0))
    if not calls:
        return None
    conf = rec["spec"]["config"]
    tokens = stats["prefill_tokens"] * layer_counts(conf)[0]
    return _share(rec, *ssm_scan(tokens, conf, calls), seconds)


def cross_decode_roofline(rec):
    """The full layer's pages read by its own decode and by every cross
    layer: the traced stretch's decode steps attended
    `decode_kv_tokens` rows and its prefills' last rows `prefill_tokens`,
    each in 1 + the cross layers."""
    stats, reduced = _traced(rec, "decode_kv_tokens", "state_slot_steps")
    if reduced is None:
        return None
    calls = [reduced["calls"].get(k, (0, 0.0)) for k in (CROSS, FULL)]
    if not calls[0][0]:
        return None
    conf = rec["spec"]["config"]
    layers = 1 + layer_counts(conf)[1]
    rows = stats["decode_tokens"] + stats["prefill_requests"]
    attended = stats["decode_kv_tokens"] + stats["prefill_tokens"]
    flops, bytes_ = cross_decode(rows, attended, conf)
    return _share(rec, layers * flops, layers * bytes_,
                  sum(seconds for _, seconds in calls))


def cross_decode_time_share(rec):
    """The share of device busy time under both names; None where no
    cross layer ran (the full layer's own name is every model's)."""
    reduced = scope_reduce.of_run(rec)
    if reduced is None or CROSS not in reduced["scopes"]:
        return None
    return scope_reduce.share(rec, [CROSS, FULL])


def state_bytes_per_seq(rec):
    """Bytes of recurrent state a live sequence held, over the window's
    decode steps."""
    stats = rec.get("stats") or {}
    if not stats.get("state_slot_steps"):
        return None
    return stats["state_byte_steps"] / stats["state_slot_steps"]


def prefill_cross_row_share(rec):
    """Rows the last layer of the window's prefills computed over the
    rows their first layer did: 1 / the bucket where the cross half runs
    on the last row alone."""
    stats = rec.get("stats") or {}
    if not stats.get("prefill_rows"):
        return None
    return stats["prefill_rows_cross"] / stats["prefill_rows"]
