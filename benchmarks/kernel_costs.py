"""Operations and bytes the algorithm needs for one call of each kernel of
the main path, from its shapes: the numerators of a roofline share.

No metric reads these yet: the device trace gives the kernels no stable
names (PERF.md section 7, the list for the `tracing` issue). They are
written here, with the benchmark, so that the issue only has to name the
kernels and add one reader per `<kernel>_roofline` metric:

    least_s = max(flops / peak_flops, bytes / peak_bytes_per_s)
    roofline share = least_s / measured kernel time

What counts: the arithmetic and the traffic the algorithm cannot avoid.
Recomputation a kernel chooses to do (flash backward recomputing the
scores) is counted, because the algorithm as published needs it; padding,
masked-out blocks a grid still visits, and re-reads caused by a block
choice are not.
"""


def flash_fwd(batch, heads, seq, head_dim, causal=True, itemsize=2):
    """Attention forward: QK^T and PV, 2 * 2 * s^2 * d a head, half of it
    under a causal mask. Reads q, k, v and writes out once each, plus the
    float32 log-sum-exp row."""
    flops = 4 * batch * heads * seq * seq * head_dim * (0.5 if causal else 1)
    bytes_ = 4 * batch * heads * seq * head_dim * itemsize + \
        4 * batch * heads * seq
    return flops, bytes_


def flash_bwd(batch, heads, seq, head_dim, causal=True, itemsize=2):
    """Attention backward (dq, dk, dv): the scores are recomputed (one
    matmul) and four more produce dv, dp, dq and dk: 2.5 times the
    forward. Reads q, k, v, out, dout and lse, writes dq, dk, dv."""
    flops = 10 * batch * heads * seq * seq * head_dim * \
        (0.5 if causal else 1)
    bytes_ = 8 * batch * heads * seq * head_dim * itemsize + \
        8 * batch * heads * seq
    return flops, bytes_


def paged_decode(context_lens, heads, head_dim, itemsize=2):
    """One decode step of paged attention over sequences of
    `context_lens` tokens: 4 * len * d a head, and every live K and V
    element read once. Memory-bound at any batch."""
    tokens = sum(context_lens)
    flops = 4 * tokens * heads * head_dim
    bytes_ = 2 * tokens * heads * head_dim * itemsize + \
        2 * len(context_lens) * heads * head_dim * itemsize
    return flops, bytes_


def ce_head(rows, hidden, vocab, backward=True, itemsize=2):
    """The output head fused with cross entropy over `rows` positions:
    one [rows, hidden] x [hidden, vocab] matmul forward, two more
    backward. Reads the hidden states and the head's weights, writes
    their gradients; the logits need never reach memory."""
    flops = 2 * rows * hidden * vocab * (3 if backward else 1)
    bytes_ = (rows * hidden + hidden * vocab) * itemsize * \
        (2 if backward else 1)
    return flops, bytes_


def adam_update(n_params, master_itemsize=4, grad_itemsize=4,
                param_itemsize=2):
    """One Adam step: about a dozen flops an element, and the traffic is
    what matters: read the gradient, master and both moments, write
    master, both moments and the compute-type weight."""
    flops = 12 * n_params
    bytes_ = n_params * (grad_itemsize + 3 * master_itemsize      # reads
                         + 3 * master_itemsize + param_itemsize)  # writes
    return flops, bytes_


def least_seconds(flops, bytes_, peaks):
    """The least time one chip could take, and which peak bounds it."""
    compute = flops / peaks["bf16_flops_per_s"]
    memory = bytes_ / peaks["hbm_bytes_per_s"]
    return max(compute, memory), "compute" if compute >= memory else "memory"
