"""An MoE layer's grouped matmuls: the operations and bytes one call
needs, and the calls a traced stretch made, each with its own shapes.

Beside `kernel_costs.py` (which it leaves as it is): the numerators of
`serve_grouped_matmul_roofline`.

    least_s(call) = max(flops / peak_flops, bytes / peak_bytes_per_s)
    roofline share = sum of least_s over the calls / sum of their times

What counts, for a call that multiplies `rows` useful rows [rows, K] by
the weights of the experts they chose, [E, K, N]: `2 * rows * K * N`
flops; every expert's [K, N] weights read once, or one expert a row where
the rows are fewer than the experts (at 8 experts a token and a batch of
32 nearly every one of 64 experts has rows: an expert is empty with
probability 1.8%, which is not subtracted), plus the useful rows read and
written once. Useful rows are real tokens times experts a
token: the padding that rounds a group up to a row tile, the buffer rows
no group owns and a prefill bucket's pad tokens are the program's choice
and are not counted.

A call's shapes are read from the trace, not from the window's counters:
a device event's name is its HLO instruction, and a Mosaic call's text
carries the shapes of its result and of its operands,

    %ds.grouped_matmul.3 = bf16[R,N]{..} custom-call(s32[M] .., s32[M] ..,
        s32[1] .., bf16[R,K]{..} %x, bf16[L,E,K,N]{..} %w), custom_call_..

so a decode step's call and each prefill bucket's are told apart by `R`,
the rows of the buffer. `R` is the program's buffer for `T` token rows
(`buffer_rows`, the dropless layout as the program builds it); which `T`
the cell's engine compiles is in the cell's file. A bucket's useful rows
are its tokens times the experts a token: for a decode step the batch,
and for a prefill bucket the mean length of the prompts of the traffic's
(fixed, cyclic) population that land in it. A call whose `R` fits no
program of the cell is left out of both sums.
"""

import re

from benchmarks import harness, kernel_costs, scope_reduce, trace_reduce

SCOPE = "ds.grouped_matmul"
_ARRAY = re.compile(r"[a-z]+[0-9]*\[([0-9,]*)\]")
# the dropless layout's row tile: the power of two at or above the mean
# group, between bf16's 16-row tile and the MXU's 128 rows
MIN_TILE, MAX_TILE = 16, 128


def grouped_matmul(rows, k, n, experts, itemsize=2):
    """(flops, bytes) of one call: `rows` useful rows through [K, N]
    weights of `experts` experts, each expert that has rows read once."""
    flops = 2 * rows * k * n
    read = min(experts, rows)       # an expert without a row is not read
    bytes_ = read * k * n * itemsize + rows * (k + n) * itemsize
    return flops, bytes_


def buffer_rows(tokens, top_k, experts):
    """Rows of the buffer the program sorts `tokens` tokens' rows into:
    every group padded to a whole row tile."""
    rows = tokens * top_k
    mean = max(1, -(-rows // experts))
    tile = min(MAX_TILE, max(MIN_TILE, 1 << (mean - 1).bit_length()))
    return -(-(rows + experts * (tile - 1)) // tile) * tile


def call_shapes(name):
    """(R, K, N, E) of a grouped matmul's HLO text, or None."""
    head, _, operands = name.partition("custom-call(")
    result = _ARRAY.search(head.partition(" = ")[2])
    arrays = [[int(d) for d in m.group(1).split(",") if d]
              for m in _ARRAY.finditer(operands.partition("), ")[0])]
    # the weights: [E, K, N], or the layers' stack [L, E, K, N] that a
    # serving program's kernel indexes
    weights = [a for a in arrays if len(a) in (3, 4)]
    if result is None or not weights:
        return None
    r_n = [int(d) for d in result.group(1).split(",") if d]
    if len(r_n) != 2:
        return None
    experts, k, n = weights[-1][-3:]
    if n != r_n[1]:                 # the backward's dx reads w as [E, N, K]
        k, n = n, k
    return r_n[0], k, n, experts


def calls(rec):
    """[(R, K, N, E, seconds)] of the `ds.grouped_matmul` custom calls
    that lie wholly inside the traced stretch, first device."""
    path = rec.get("trace_path")
    if not path:
        return []
    out = []
    for tf_op, _, events, (lo, hi) in scope_reduce.device_operations(
            trace_reduce.load(path), scope_reduce.xplane_meta.load(path)):
        for name, start, end in events:
            if trace_reduce.MOSAIC in name and lo <= start and end <= hi \
                    and scope_reduce.innermost(tf_op[name]) == SCOPE:
                shapes = call_shapes(name)
                if shapes is not None:
                    out.append((*shapes, end - start))
        break
    return out


def useful_rows_by_buffer(spec):
    """{buffer rows R: useful rows of a call with that buffer} for the
    programs the cell's engine compiles."""
    conf, traffic = spec["config"], spec["traffic"]
    engine = spec["cell"]["engine"]["inference"]
    top_k, experts = conf["num_experts_per_tok"], conf["num_experts"]
    out = {}
    for batch in engine["decode_batch_sizes"]:
        out[buffer_rows(batch, top_k, experts)] = batch * top_k
    driver = harness.load_module(spec["root"], "drivers", traffic["kind"])
    prompts = driver.quantile_lengths(traffic["prompt_len"],
                                      traffic["population"])
    buckets = sorted(engine["prefill_lengths"])
    for i, bucket in enumerate(buckets):
        below = buckets[i - 1] if i else 0
        mine = [int(p) for p in prompts if below < p <= bucket]
        for batch in engine["prefill_batch_sizes"]:
            tokens = batch * (sum(mine) / len(mine) if mine else bucket)
            out[buffer_rows(batch * bucket, top_k, experts)] = \
                tokens * top_k
    return out


def roofline(rec):
    """100 * the least time the traced stretch's calls could take / the
    time they took; None where the trace holds no such call."""
    traced = calls(rec)
    if not traced:
        return None
    peaks = harness.peaks_for(rec["spec"], rec["device_kind"])
    useful = useful_rows_by_buffer(rec["spec"])
    least = took = 0.0
    for rows, k, n, experts, seconds in traced:
        if rows not in useful:
            continue
        flops, bytes_ = grouped_matmul(useful[rows], k, n, experts)
        least += kernel_costs.least_seconds(flops, bytes_, peaks)[0]
        took += seconds
    return 100.0 * least / took if took else None
