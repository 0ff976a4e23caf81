"""Mixture-of-Experts FFN with expert parallelism.

The reference (DeepSpeed v0.3.15) predates MoE; this layer exists so the
framework covers the modern 4th parallel axis alongside dp/pp/tp/sp. The
design is the GShard/Switch pattern, TPU-first:

- **top-1 gating with capacity**: each token routes to its argmax expert;
  an expert accepts at most `capacity` tokens (position-ordered).
  Overflow tokens combine to an exact-zero output — the surrounding
  transformer block's residual connection is what carries them through
  unchanged (standard Switch/GShard usage; this layer does NOT add the
  residual itself). Static shapes throughout.
- **two dispatch engines** (`dispatch=`):

  * ``"einsum"`` (default — the reference numerics): the dense GShard
    [T, E, C] one-hot dispatch/combine einsum pair, exactly the
    formulation GShard lowers to XLA. Pure MXU work, no scatter — but at
    top-2/cf=1.25 most of those flops multiply zeros.
  * ``"sort"``: argsort the routed token copies by expert (stable sort
    == GShard queue order: all first choices in token order, then all
    second choices), compact them into per-expert contiguous spans with
    capacity enforced by position-in-expert, run the expert FFN as ONE
    Pallas grouped matmul over the packed buffer
    (`ops/pallas/grouped_matmul.py` — ragged per-expert sizes, masked
    tails, XLA-fallback off-TPU), and combine by gathering each token's
    surviving rows with a weighted add. Same routing decisions, same
    capacity semantics, numerical parity with the einsum engine — at a
    fraction of the matmul flops.

- **token groups** (`groups`): GShard's G dimension. Tokens split into
  `g` independent routing groups with per-group capacity C/g, shrinking
  the dispatch/combine tensors from O(T·E·C) to O(T·E·C/g). `groups=1`
  is the exact ungrouped oracle; `groups=0` ("auto") picks the divisor
  of T whose group size is NEAREST `_AUTO_GROUP_TOKENS` (1024) and at
  least 128. The sort engine folds groups into E·g "virtual experts"
  (expert-major) so grouping costs nothing extra there.
- **expert parallelism**: experts shard over an ``expert`` mesh axis
  inside `shard_map`; token shards are exchanged with `all_to_all`
  (dispatch) and returned (combine), both riding ICI. With the sort
  engine, `a2a_overlap_chunks > 1` splits the exchange along the local-
  expert axis and software-pipelines `all_to_all(chunk i+1)` against
  expert-FFN(chunk i), hiding ICI time under MXU time (decorrelated
  jitter and the pmean'd aux loss are unchanged).
- Gate math in fp32; an auxiliary load-balancing loss (mean_prob ×
  mean_assignment per expert, scaled by E) is returned for the trainer.
- **top-2 combine weights**: `renorm_kept_choices=False` (default) keeps
  the GShard paper normalization — over the pair *before* capacity —
  which silently leaks the probability mass of an overflowed second
  choice. `True` renormalizes over the choices that actually survived
  capacity, so a token whose second choice overflowed carries full
  weight on its first. Off by default: the legacy einsum path stays
  bit-identical.

`moe_ffn_dense` is the single-device reference semantics;
`moe_ffn_expert_parallel` runs inside `shard_map` and matches it
exactly (tested on the 8-device mesh), for either engine.
"""

import functools
import threading

import numpy as np

import jax
import jax.numpy as jnp

# auto-grouping target: the largest per-group token count. 1024 keeps the
# per-layer dispatch+combine pair ≈ E·C·T·4B ≈ tens of MB at GPT scales
# while each group is still large enough for balanced routing statistics.
_AUTO_GROUP_TOKENS = 1024

DISPATCH_MODES = ("einsum", "sort")


class _RoutingStatsCollector:
    """Host-side sink for the sort engine's in-jit routing statistics
    (``moe.observability``): per-expert load fractions and the
    capacity-drop fraction land here via `jax.debug.callback`
    (unordered — the callback runs when the device values materialize,
    so the hot path never syncs) and the engine drains them into
    ``Train/MoE/*`` scalars at its step-record boundary.

    Samples are AVERAGED across everything that accumulated since the
    last drain: one entry per MoE layer per step, plus duplicates when
    rematerialization re-runs a layer's forward in the backward pass —
    duplicate values are identical, so the averages are unbiased."""

    # un-drained cap: with no monitor attached nothing ever drains —
    # keep the most recent window instead of growing forever
    MAX_PENDING = 1024

    def __init__(self):
        self._lock = threading.Lock()
        self._load = []          # [E] load fraction per emission
        self._drop = []          # capacity-drop fraction per emission

    def _record(self, load, drop):
        load = np.asarray(load, np.float64)
        drop = float(np.asarray(drop))
        with self._lock:
            self._load.append(load)
            self._drop.append(drop)
            if len(self._load) > self.MAX_PENDING:
                del self._load[:-self.MAX_PENDING]
                del self._drop[:-self.MAX_PENDING]

    def drain(self):
        """Averaged scalars since the last drain, or None when nothing
        was emitted (observability off, or the callbacks have not
        materialized yet)."""
        with self._lock:
            load, self._load = self._load, []
            drop, self._drop = self._drop, []
        if not load:
            return None
        mean_load = np.mean(np.stack(load), axis=0)     # [E]
        mean = float(mean_load.mean())
        return {
            "Train/MoE/expert_load_min": float(mean_load.min()),
            "Train/MoE/expert_load_max": float(mean_load.max()),
            # coefficient of variation: 0 = perfectly balanced; the
            # single-number imbalance series worth alerting on
            "Train/MoE/expert_load_cv":
                float(mean_load.std() / max(mean, 1e-12)),
            "Train/MoE/capacity_drop_fraction": float(np.mean(drop)),
        }


ROUTING_STATS = _RoutingStatsCollector()


def _emit_routing_stats(route, capacity, E, g):
    """Emit one routing observation from inside the compiled step (sort
    engine only — `route.counts`/`route.pos` already hold the
    position-in-expert bookkeeping, so the stats cost two reductions).
    The virtual-expert counts fold back to real experts
    (virtual id = expert·g + group)."""
    kT = route.pos.shape[0]                      # routed copies (T·top_k)
    counts_e = route.counts.reshape(E, g).sum(axis=1)
    load = counts_e.astype(jnp.float32) / max(kT, 1)
    kept = jnp.sum(jnp.minimum(route.counts, capacity))
    drop = 1.0 - kept.astype(jnp.float32) / max(kT, 1)
    jax.debug.callback(ROUTING_STATS._record, load, drop, ordered=False)


@functools.lru_cache(maxsize=None)
def _resolve_groups(groups, tokens):
    """0/'auto' → the divisor of `tokens` whose group size is nearest
    `_AUTO_GROUP_TOKENS` (never below 128: a token count with only tiny
    divisors near the target — e.g. 2·1031 — would otherwise shrink
    capacity to ~1 and silently drop routed tokens); otherwise validate
    the explicit count. Memoized per (groups, tokens): the O(√T) divisor
    search used to run on every trace."""
    if groups in (0, None, "auto"):
        best_g, best_cost = 1, abs(tokens - _AUTO_GROUP_TOKENS)
        d = 1
        while d * d <= tokens:
            if tokens % d == 0:
                for g in (d, tokens // d):
                    size = tokens // g
                    if size < 128:
                        continue
                    cost = abs(size - _AUTO_GROUP_TOKENS)
                    if cost < best_cost or (cost == best_cost
                                            and g > best_g):
                        best_g, best_cost = g, cost
            d += 1
        return best_g
    groups = int(groups)
    if groups < 1 or tokens % groups:
        raise ValueError(f"groups={groups} must be ≥1 and divide the "
                         f"token count {tokens}")
    return groups


def _choice_dispatch(onehot, capacity, base_counts=None):
    """Per-choice capacity bookkeeping: position-ordered slots within
    each expert's queue, offset by `base_counts` (earlier choices'
    occupancy — GShard queues second choices AFTER all first choices).
    Returns (dispatch [T, E, C], counts [E])."""
    T, E = onehot.shape
    pos = jnp.cumsum(onehot, axis=0) * onehot               # [T, E]
    if base_counts is not None:
        pos = pos + base_counts[None, :] * onehot
    pos_in_expert = jnp.sum(pos, axis=-1) - 1.0             # [T]
    keep = pos_in_expert < capacity                         # [T]
    slot = jax.nn.one_hot(pos_in_expert.astype(jnp.int32), capacity,
                          dtype=jnp.float32)                # [T, C]
    dispatch = onehot[:, :, None] * slot[:, None, :] * \
        keep[:, None, None]                                 # [T, E, C]
    return dispatch, jnp.sum(onehot, axis=0)


def _one_hot_dispatch(gate_logits, capacity, top_k=1, rng=None,
                      jitter_eps=0.0, renorm_kept_choices=False):
    """Top-k capacity routing (GShard: k=2 is the paper default; k=1 is
    Switch).

    gate_logits [T, E] fp32 → (dispatch [T, E, C] bool-ish float,
    combine [T, E, C] float = normalized gate prob on the kept slot,
    aux_loss). With `rng` and `jitter_eps`, logits get GShard's
    multiplicative uniform jitter (training-time exploration).
    `renorm_kept_choices` normalizes the top-2 pair over the choices
    that SURVIVED capacity instead of the pre-capacity pair (see module
    docstring); False keeps the legacy math bit-identical.
    """
    T, E = gate_logits.shape
    if rng is not None and jitter_eps > 0.0:
        noise = jax.random.uniform(rng, gate_logits.shape,
                                   minval=1.0 - jitter_eps,
                                   maxval=1.0 + jitter_eps)
        gate_logits = gate_logits * noise
    probs = jax.nn.softmax(gate_logits, axis=-1)

    expert1 = jnp.argmax(probs, axis=-1)                    # [T]
    onehot1 = jax.nn.one_hot(expert1, E, dtype=jnp.float32)
    g1 = jnp.take_along_axis(probs, expert1[:, None], axis=-1)[:, 0]

    # GShard aux loss uses the FIRST choice's assignment statistics
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(onehot1, axis=0)
    aux = E * jnp.sum(me * ce)

    dispatch1, counts1 = _choice_dispatch(onehot1, capacity)
    if top_k == 1:
        return dispatch1, dispatch1 * g1[:, None, None], aux

    if top_k != 2:
        raise ValueError(f"top_k must be 1 or 2, got {top_k}")
    probs2 = probs * (1.0 - onehot1)                        # mask top-1
    expert2 = jnp.argmax(probs2, axis=-1)
    onehot2 = jax.nn.one_hot(expert2, E, dtype=jnp.float32)
    g2 = jnp.take_along_axis(probs, expert2[:, None], axis=-1)[:, 0]
    dispatch2, _ = _choice_dispatch(onehot2, capacity,
                                    base_counts=counts1)
    if renorm_kept_choices:
        # normalize over the kept pair: an overflowed second choice's
        # mass moves to the surviving first choice instead of leaking
        kept1 = jnp.sum(dispatch1, axis=(1, 2))             # 1.0 or 0.0
        kept2 = jnp.sum(dispatch2, axis=(1, 2))
        w1, w2 = g1 * kept1, g2 * kept2
        denom = w1 + w2 + 1e-9
        g1n, g2n = w1 / denom, w2 / denom
    else:
        # normalize the pair (GShard combine weights)
        denom = g1 + g2 + 1e-9
        g1n, g2n = g1 / denom, g2 / denom
    dispatch = dispatch1 + dispatch2
    combine = dispatch1 * g1n[:, None, None] + \
        dispatch2 * g2n[:, None, None]
    return dispatch, combine, aux


def _expert_ffn(w_in, b_in, w_out, b_out, x):
    """One expert's FFN on [C, H] (weights [H, I]/[I, H])."""
    h = jax.nn.gelu(x @ w_in.astype(x.dtype) + b_in.astype(x.dtype))
    return h @ w_out.astype(x.dtype) + b_out.astype(x.dtype)


def _route_groups(gate, xg, capacity, top_k, rng, jitter_eps,
                  renorm_kept_choices=False):
    """Route each group independently: xg [g, Tg, H] →
    (dispatch [g, Tg, E, C], combine [g, Tg, E, C], aux mean-over-groups).
    Dispatch/combine are cast to the compute dtype — dispatch is exactly
    0/1 (lossless); combine rounds like every other activation."""
    logits = (xg @ gate.astype(xg.dtype)).astype(jnp.float32)
    if rng is not None and jitter_eps > 0.0:
        route = jax.vmap(lambda lg, r: _one_hot_dispatch(
            lg, capacity, top_k=top_k, rng=r, jitter_eps=jitter_eps,
            renorm_kept_choices=renorm_kept_choices))
        dispatch, combine, aux = route(logits,
                                       jax.random.split(rng, xg.shape[0]))
    else:
        route = jax.vmap(lambda lg: _one_hot_dispatch(
            lg, capacity, top_k=top_k,
            renorm_kept_choices=renorm_kept_choices))
        dispatch, combine, aux = route(logits)
    return (dispatch.astype(xg.dtype), combine.astype(xg.dtype),
            jnp.mean(aux))


# ---------------------------------------------------------------------------
# sort-based dispatch engine
# ---------------------------------------------------------------------------

class _SortRoute:
    """Routing plan over V = E·g virtual experts (expert-major:
    v = expert·g + group). Copy-major arrays are [k·T]: copy c < T is
    token c's first choice, copy c ≥ T its second."""

    def __init__(self, experts_v, pos, weights, counts, starts, order,
                 aux):
        self.experts_v = experts_v   # [kT] virtual expert per copy
        self.pos = pos               # [kT] position-in-expert per copy
        self.weights = weights       # tuple of [T] combine weights
        self.counts = counts         # [V] routed copies per virtual expert
        self.starts = starts         # [V] exclusive prefix of counts
        self.order = order           # [kT] stable sort permutation
        self.aux = aux


def _jittered_probs(gate, xg, rng, jitter_eps):
    """Gate probabilities [g, Tg, E] with the SAME per-group jitter
    construction as the einsum engine (vmapped per-group key split) —
    the two dispatch engines must draw identical noise so they route
    identically."""
    logits = (xg @ gate.astype(xg.dtype)).astype(jnp.float32)
    if rng is not None and jitter_eps > 0.0:
        keys = jax.random.split(rng, xg.shape[0])
        noise = jax.vmap(lambda r: jax.random.uniform(
            r, logits.shape[1:], minval=1.0 - jitter_eps,
            maxval=1.0 + jitter_eps))(keys)
        logits = logits * noise
    return jax.nn.softmax(logits, axis=-1)


def _sort_route(probs, capacity, top_k, renorm_kept_choices):
    """probs [g, Tg, E] fp32 → _SortRoute.

    The stable argsort over (virtual-)expert ids reproduces the GShard
    queue exactly: copies are enumerated choice-major (all first choices
    in token order, then all second choices), so within each expert the
    sorted order is first-choices-then-second-choices — identical
    position-in-expert bookkeeping to `_choice_dispatch`'s cumsum +
    base_counts offset, without the [T, E, C] one-hot tensors."""
    g, tg, E = probs.shape
    T = g * tg
    p2 = probs.reshape(T, E)
    gi = (jnp.arange(T, dtype=jnp.int32) // tg)

    expert1 = jnp.argmax(p2, axis=-1)
    onehot1 = jax.nn.one_hot(expert1, E, dtype=jnp.float32)
    g1 = jnp.take_along_axis(p2, expert1[:, None], axis=-1)[:, 0]
    # GShard aux loss, per group then averaged (matches _route_groups)
    me = jnp.mean(probs, axis=1)                            # [g, E]
    ce = jnp.mean(onehot1.reshape(g, tg, E), axis=1)        # [g, E]
    aux = jnp.mean(E * jnp.sum(me * ce, axis=-1))

    v1 = expert1.astype(jnp.int32) * g + gi
    if top_k == 1:
        experts_v = v1
        gates = (g1,)
    elif top_k == 2:
        probs2 = p2 * (1.0 - onehot1)                       # mask top-1
        expert2 = jnp.argmax(probs2, axis=-1)
        g2 = jnp.take_along_axis(p2, expert2[:, None], axis=-1)[:, 0]
        experts_v = jnp.concatenate([v1, expert2.astype(jnp.int32) * g + gi])
        gates = (g1, g2)
    else:
        raise ValueError(f"top_k must be 1 or 2, got {top_k}")

    kT = experts_v.shape[0]
    V = E * g
    order = jnp.argsort(experts_v)          # stable → GShard queue order
    counts = jnp.zeros((V,), jnp.int32).at[experts_v].add(1)
    starts = jnp.cumsum(counts) - counts    # exclusive prefix, no concat
    pos_sorted = jnp.arange(kT, dtype=jnp.int32) - starts[experts_v[order]]
    pos = jnp.zeros((kT,), jnp.int32).at[order].set(pos_sorted)
    kept = pos < capacity

    k1 = kept[:T].astype(jnp.float32)
    if top_k == 1:
        weights = (gates[0] * k1,)
    else:
        k2 = kept[T:].astype(jnp.float32)
        g1, g2 = gates
        if renorm_kept_choices:
            w1, w2 = g1 * k1, g2 * k2
            denom = w1 + w2 + 1e-9
            weights = (w1 / denom, w2 / denom)
        else:
            denom = g1 + g2 + 1e-9
            weights = (g1 / denom * k1, g2 / denom * k2)
    return _SortRoute(experts_v, pos, weights, counts, starts, order, aux)


def _pick_span(capacity, block_m=None):
    from ..ops.pallas.grouped_matmul import pick_span
    return pick_span(capacity, block_m)


def _fill_buffer(x, route, capacity, span):
    """Compact routed copies into the [V·span, H] expert-major buffer by
    GATHER: buffer row (v, p) holds the p-th surviving copy of virtual
    expert v (sorted order), zero when p ≥ min(count, capacity). Returns
    (buffer, group_sizes [V])."""
    T, H = x.shape
    kT = route.order.shape[0]
    V = route.counts.shape[0]
    tok_sorted = route.order % T            # sorted copy → source token
    p = jnp.arange(span, dtype=jnp.int32)
    src = route.starts[:, None] + p[None, :]                # [V, span]
    sizes = jnp.minimum(route.counts, capacity)
    valid = p[None, :] < sizes[:, None]
    tok = tok_sorted[jnp.clip(src, 0, kT - 1)]
    buf = jnp.where(valid[..., None], x[tok], 0)            # [V, span, H]
    return buf.reshape(V * span, H), sizes


def _sort_ffn(params, buf, sizes, span, lut, n_w, rows_per_w, block_m,
              block_n, backend, ffn_quant=None):
    """Expert FFN over the packed buffer as two grouped matmuls. Biases
    ride a [n_w, rows, ·] reshape (no per-row gather). Masked tail rows
    come out of the second matmul as exact zeros plus a bias term; the
    combine never gathers them.

    `ffn_quant` = (recipe, margin, amax_row [4, H]) runs both grouped
    matmuls with delayed-scaling fake-quantized operands
    (`ops.pallas.quant_matmul.grouped_scaled_operands`) and makes the
    return (out, new_amax_row)."""
    from ..ops.pallas.grouped_matmul import grouped_matmul
    dt = buf.dtype
    w_in = params["w_in"].astype(dt)
    b_in = params["b_in"].astype(dt)
    w_out = params["w_out"].astype(dt)
    b_out = params["b_out"].astype(dt)
    inter = w_in.shape[-1]
    new_row = None
    if ffn_quant is not None:
        from ..ops.pallas.quant_matmul import grouped_scaled_operands
        recipe, margin, amax_row = ffn_quant
        buf, w_in, hx_in, hw_in = grouped_scaled_operands(
            buf, w_in, amax_row[0], amax_row[1], recipe, margin)
    h = grouped_matmul(buf, w_in, sizes, span, lut, block_m, block_n,
                       backend)
    h = jax.nn.gelu(h.reshape(n_w, rows_per_w, inter) + b_in[:, None, :])
    h = h.reshape(-1, inter)
    if ffn_quant is not None:
        from ..ops.pallas.quant_matmul import grouped_scaled_operands
        h, w_out, hx_out, hw_out = grouped_scaled_operands(
            h, w_out, amax_row[2], amax_row[3], recipe, margin)
        new_row = jnp.stack([hx_in, hw_in, hx_out, hw_out])
    out = grouped_matmul(h, w_out, sizes, span, lut,
                         block_m, block_n, backend)
    hidden = w_out.shape[-1]
    out = out.reshape(n_w, rows_per_w, hidden) + b_out[:, None, :]
    out = out.reshape(-1, hidden)
    if ffn_quant is not None:
        return out, new_row
    return out


def _sort_combine(out_buf, route, span, T, dtype):
    """y[t] = Σ_k weight_k[t] · out_buf[row of copy k] — the gather +
    weighted-add replacement for the [T, E, C] combine einsum. Dropped
    copies carry weight 0 (their clipped row gather is a no-op)."""
    R = out_buf.shape[0]
    rows = jnp.clip(route.experts_v * span + route.pos, 0, R - 1)
    y = None
    for c, wk in enumerate(route.weights):
        term = wk.astype(dtype)[:, None] * out_buf[rows[c * T:(c + 1) * T]]
        y = term if y is None else y + term
    return y


def _gmm_geometry(capacity, k_dim, n_dim, dtype, block_m, block_n,
                  backend):
    """Resolve (span, block_m, block_n): `ops.autotune`'s VMEM-screened
    pick on a TPU when the Pallas backend is in play, the kernel's
    defaults otherwise."""
    if (block_m is None or block_n is None) and backend != "xla":
        from ..ops.autotune import grouped_matmul_blocks
        from ..ops.pallas.grouped_matmul import _interpret
        if not _interpret():
            bm, bn = grouped_matmul_blocks(k_dim, n_dim, dtype)
            block_m = block_m or bm
            block_n = block_n or bn
    span, bm = _pick_span(capacity, block_m)
    return span, bm, block_n


def moe_ffn_dense(params, x, capacity_factor=1.25, top_k=1, rng=None,
                  jitter_eps=0.0, groups=1, dispatch="einsum",
                  renorm_kept_choices=False, gmm_block_m=None,
                  gmm_block_n=None, gmm_backend=None, observe=False,
                  ffn_quant=None):
    """Reference semantics on one device. params: stacked expert weights
    {"w_in" [E, H, I], "b_in" [E, I], "w_out" [E, I, H], "b_out" [E, H],
    "gate" [H, E]}; x [T, H] → (y [T, H], aux_loss). `groups` splits the
    tokens into independent routing groups (GShard's G dim) — capacity
    becomes per-group, dispatch memory drops by the group factor.
    `dispatch` picks the engine (module docstring); both route
    identically."""
    if dispatch not in DISPATCH_MODES:
        raise ValueError(f"dispatch must be one of {DISPATCH_MODES}, "
                         f"got {dispatch!r}")
    if observe and dispatch != "sort":
        raise ValueError(
            "observe=True requires dispatch='sort': the routing stats "
            "come from the sort engine's position-in-expert bookkeeping")
    if ffn_quant is not None and dispatch != "sort":
        raise ValueError(
            "quantization.ffn on MoE blocks requires dispatch='sort': "
            "the delayed-scaling path quantizes the grouped expert "
            "matmul operands (the einsum engine spends its flops on the "
            "one-hot dispatch tensor, which quantization cannot help)")
    T, H = x.shape
    E = params["w_in"].shape[0]
    g = _resolve_groups(groups, T)
    tg = T // g
    capacity = max(1, int(capacity_factor * top_k * tg / E))
    xg = x.reshape(g, tg, H)

    if dispatch == "einsum":
        dispatch_t, combine, aux = _route_groups(
            params["gate"], xg, capacity, top_k, rng, jitter_eps,
            renorm_kept_choices=renorm_kept_choices)
        expert_in = jnp.einsum("gtec,gth->egch", dispatch_t, xg)
        expert_out = jax.vmap(_expert_ffn)(
            params["w_in"], params["b_in"], params["w_out"],
            params["b_out"],
            expert_in.reshape(E, g * capacity, H))          # [E, g*C, H]
        y = jnp.einsum("gtec,egch->gth", combine,
                       expert_out.reshape(E, g, capacity, H))
        return y.reshape(T, H), aux

    probs = _jittered_probs(params["gate"], xg, rng, jitter_eps)
    route = _sort_route(probs, capacity, top_k, renorm_kept_choices)
    if observe:
        _emit_routing_stats(route, capacity, E, g)
    span, bm, bn = _gmm_geometry(capacity, H, params["w_in"].shape[-1],
                                 x.dtype, gmm_block_m, gmm_block_n,
                                 gmm_backend)
    buf, sizes = _fill_buffer(x, route, capacity, span)
    lut = tuple(np.repeat(np.arange(E), g))
    out_buf = _sort_ffn(params, buf, sizes, span, lut, E, g * span,
                        bm, bn, gmm_backend, ffn_quant=ffn_quant)
    if ffn_quant is not None:
        out_buf, new_amax_row = out_buf
        return (_sort_combine(out_buf, route, span, T, x.dtype),
                route.aux, new_amax_row)
    return _sort_combine(out_buf, route, span, T, x.dtype), route.aux


# ---------------------------------------------------------------------------
# dropless top-k routing (no capacity: every token keeps all its experts)
# ---------------------------------------------------------------------------

def dropless_geometry(tokens, top_k, n_experts):
    """(buffer rows, row tile) of the dropless dispatch of `tokens`
    tokens: static, from shapes alone, so the serving engine can count
    them on the host (`stats["moe_buffer_rows"]`)."""
    from ..ops.pallas.grouped_matmul import (ragged_block_m,
                                             ragged_buffer_rows)
    bm = ragged_block_m(tokens * top_k, n_experts)
    return ragged_buffer_rows(tokens * top_k, n_experts, bm), bm


# pairs a block of the running count: a pair meets the 127 before it in
# one compare, the blocks' totals run down a short cumsum
_RANK_BLOCK = 128


def dropless_plan(pair_expert, n_experts, block_m, rows):
    """The ragged layout's plan, COUNTED: the pairs are never sorted by
    expert.

    pair_expert [P] int32: the held expert of pair p, or the sentinel
    `n_experts` for a pair that owns no row (a padded token's, an absent
    expert's). The caller numbers the pairs; the layer does so
    choice-major, p = j * T + t for token t's j-th choice
    (`moe_ffn_dropless`). Returns (counts [E], tile_expert, tile_rows,
    starts [E] as `ragged_tile_maps` gives them, pair_row [P]: the
    buffer row of each pair (`rows` for a sentinel pair), src [rows]:
    the pair of each buffer row (P for a padding row)).

    Inside a group the rows keep pair order, which is the order a stable
    sort by expert gives: a pair's row is its group's start plus its
    RANK, the number of earlier pairs of the same expert. The rank is
    counted in two levels: inside a block of `_RANK_BLOCK` pairs by
    comparing the block with itself, and across blocks by the running
    sum of the blocks' per-expert totals, [P / 128, E].

    `src` inverts `pair_row`, and is the one operation left that moves
    integers by index: ONE sort of the pairs' rows together with the
    padding rows' own numbers, after which every buffer row is named
    once, in order. (A scatter of the pairs' numbers at their rows takes
    one dynamic index a pair, which a TPU runs one after the other: it
    is 0-6 us of 55 ahead of the sort at a decode step's pairs and costs
    1.4-1.7x the sort at a prefill's, PERF.md section 6, PR 45.)"""
    from ..ops.pallas.grouped_matmul import _PLANS_TRACED, ragged_tile_maps
    P, E, B = pair_expert.shape[0], int(n_experts), _RANK_BLOCK
    nb = -(-P // B)
    pe = pair_expert
    if nb * B != P:
        pe = jnp.concatenate([pe, jnp.full((nb * B - P,), E, jnp.int32)])
    pe = pe.reshape(nb, B)
    i = jnp.arange(B, dtype=jnp.int32)
    earlier = (i[None, :] < i[:, None])[None]                 # [1, i, j<i]
    rank = jnp.sum((pe[:, :, None] == pe[:, None, :]) & earlier, axis=2,
                   dtype=jnp.int32)                           # [nb, B]
    # compared where it is used, twice: a [P, E] one-hot is never stored
    onehot = pe[:, :, None] == jnp.arange(E, dtype=jnp.int32)
    totals = jnp.sum(onehot, axis=1, dtype=jnp.int32)         # [nb, E]
    before = jnp.cumsum(totals, axis=0) - totals
    counts = before[-1] + totals[-1]
    tile_expert, tile_rows, starts = ragged_tile_maps(counts, block_m,
                                                      rows // block_m)
    first = (starts[None, :] + before)[:, None, :]            # [nb, 1, E]
    row = jnp.sum(jnp.where(onehot, first, 0), axis=2) + rank
    pair_row = jnp.where(pe < E, row, rows).reshape(nb * B)[:P]
    # a row past its tile's live rows is padding and names itself; the
    # keys under `rows` are then 0 .. rows - 1, each once
    lane = jnp.arange(block_m, dtype=jnp.int32)
    padding = (lane[None, :] >= tile_rows[:, None]).reshape(rows)
    keys = jnp.concatenate([pair_row, jnp.where(
        padding, jnp.arange(rows, dtype=jnp.int32), rows)])
    values = jnp.concatenate([jnp.arange(P, dtype=jnp.int32),
                              jnp.full((rows,), P, jnp.int32)])
    src = jax.lax.sort((keys, values), num_keys=1, is_stable=False)[1][:rows]
    _PLANS_TRACED["counted"] = _PLANS_TRACED.get("counted", 0) + 1
    return counts, tile_expert, tile_rows, starts, pair_row, src


def moe_ffn_dropless(params, x, top_k, norm_topk_prob=False,
                     activation=jax.nn.silu, token_mask=None,
                     gmm_backend=None, held=None, scale=1.0,
                     score="softmax"):
    """Top-k routing that drops nothing, through the grouped matmul.

    params: {"gate" [H, E] (the router), "w_in" [E, H, 2I] (each
    expert's gate and up projections, [gate | up] along the last dim),
    "w_out" [E, I, H]}; no biases. x [T, H] -> (y [T, H], stats [2, E]).
    A serving layer loop hands `w_in` / `w_out` as `LayerOf` the stacked
    weights (`ops.pallas.grouped_matmul`).

        p = softmax(x @ gate) in float32 over all E experts
        (p_j, e_j) = the top_k largest, renormalised over the kept ones
                     only if `norm_topk_prob`
        y = sum_j p_j * (act(x Wgate[e_j]) * (x Wup[e_j])) Wdown[e_j]

    The T*top_k (token, expert) rows are laid out by expert in ONE
    buffer, a group's rows in pair order (`dropless_plan`: counted, not
    sorted), whose groups have their real lengths, each padded to a whole
    row tile (`ops.pallas.grouped_matmul`, ragged layout): no capacity,
    no `capacity_factor`. The pairs are numbered CHOICE-MAJOR, p = j * T
    + t for token t's j-th choice, so each routed row moves once each
    way: the fill is one gather of x's rows (a padding row names one
    zero row appended to x: no pass that zeroes the buffer), and the
    combine's gather `out[pair_row]` IS [top_k, T, H], summed over its
    major axis where it lies (token-major, [T, top_k, H] puts top_k on
    the tiled second-minor dimension: a copy of every gathered row at
    top_k = 10 and 4). `token_mask` [T] marks real tokens: a padded
    row (a prefill bucket's tail, an inactive decode row) is routed to no
    expert, takes no buffer row and no part in the statistics, and comes
    out zero. So a token's result does not depend on its batch
    neighbours.

    stats = [f, P]: f_e the share of the routed (token, choice) pairs
    that chose e, P_e the mean router probability of e over the routed
    tokens. The load-balancing loss E * sum_e f_e P_e is taken by the
    model over ALL layers' routed tokens (`GPTNeoX._head_loss`), so the
    layer returns its means, not a scalar.

    `held` = (first, past-the-last): WHICH of the router's experts this
    layer holds (`w_in` / `w_out` then hold ``past - first`` experts, in
    order; one chip's share of an expert-parallel layer). The router
    still scores all E and keeps `top_k`; a (token, choice) pair that
    fell on an absent expert takes no buffer row, and the result is the
    held experts' part of the sum: what the absent ones would have added
    is left out, and the shares of all the holders add up to the whole
    layer. stats then has a third row, c: the raw count of routed pairs
    on each of the E experts (exact in float32), from which a caller
    counts the pairs that were held. `scale` multiplies the kept
    weights (after the renormalisation).

    `score="sigmoid"` (the published `noaux_tc` router): s = sigmoid(x @
    gate) in float32, each expert alone; the top_k are the largest of
    s + params["gate_bias"] ([E], a learned correction that balances the
    load), and the kept weights are those experts' s, WITHOUT the bias,
    renormalised and scaled as above. P of the statistics is then the
    mean of s / sum_e s.
    """
    from .. import scopes
    from ..ops.pallas.grouped_matmul import _PLANS_TRACED, ragged_matmul
    T, H = x.shape
    E_all = params["gate"].shape[1]          # the experts the router scores
    lo, hi = held if held is not None else (0, E_all)
    E = hi - lo                              # the experts held here
    k = int(top_k)
    if not 1 <= k <= E_all:
        raise ValueError(f"dropless routing needs 1 <= top_k <= {E_all} "
                         f"experts, got top_k={top_k}")
    if params["w_in"].shape[0] != E:
        raise ValueError(
            f"w_in holds {params['w_in'].shape[0]} experts; the held range "
            f"{(lo, hi)} of the router's {E_all} names {E}")
    R, bm = dropless_geometry(T, k, E)
    live = jnp.ones((T,), jnp.bool_) if token_mask is None \
        else token_mask.reshape(T).astype(jnp.bool_)

    with scopes.scope("ds.moe_route"):
        # float32 at full precision: a near-tie at the k-th probability
        # is decided by the accumulated sum, not by a bf16 pass
        logits = jnp.dot(x.astype(jnp.float32),
                         params["gate"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        if score == "sigmoid":
            scores = jax.nn.sigmoid(logits)                   # [T, E]
            # the bias picks; what it picked is weighed by its own score
            _, experts = jax.lax.top_k(
                scores + params["gate_bias"].astype(jnp.float32), k)
            weights = jnp.take_along_axis(scores, experts, axis=-1)
            probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
        elif score == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)           # [T, E]
            weights, experts = jax.lax.top_k(probs, k)        # [T, k]
        else:
            raise ValueError(f"router score {score!r}: 'softmax' or "
                             f"'sigmoid'")
        if norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        if scale != 1.0:
            weights = weights * scale
        n_live = jnp.maximum(jnp.sum(live), 1).astype(jnp.float32)
        mean_prob = jnp.sum(jnp.where(live[:, None], probs, 0.0),
                            axis=0) / n_live                  # P [E]

    with scopes.scope("ds.moe_dispatch"):
        # pair p = j*T + t; a padded token's pairs go to the sentinel E,
        # which owns no buffer row
        experts = experts.astype(jnp.int32)
        here = live[:, None] & (experts >= lo) & (experts < hi)
        pair_expert = jnp.where(here, experts - lo, E).T.reshape(k * T)
        counts, tile_expert, tile_rows, _, pair_row, src = dropless_plan(
            pair_expert, E, bm, R)
        _PLANS_TRACED["choice_major"] = \
            _PLANS_TRACED.get("choice_major", 0) + 1
        # one gather: a padding row (src == T * k) names x's zero row
        zero_row = jnp.zeros((1, H), x.dtype)
        buf = jnp.concatenate([x, zero_row])[
            jnp.where(src < T * k, src % T, T)]
        if held is None:
            stats = jnp.stack([counts.astype(jnp.float32) /
                               jnp.maximum(jnp.sum(counts), 1), mean_prob])
        else:
            routed = jnp.sum(
                (jnp.where(live[:, None], experts, E_all).reshape(T * k, 1)
                 == jnp.arange(E_all, dtype=jnp.int32)),
                axis=0, dtype=jnp.int32).astype(jnp.float32)
            stats = jnp.stack([routed / jnp.maximum(jnp.sum(routed), 1.0),
                               mean_prob, routed])

    dt = x.dtype
    inter = params["w_out"].shape[1]
    h = ragged_matmul(buf, params["w_in"].astype(dt), tile_expert,
                      tile_rows, bm, backend=gmm_backend)     # [R, 2I]
    h = activation(h[:, :inter]) * h[:, inter:]
    out = ragged_matmul(h, params["w_out"].astype(dt), tile_expert,
                        tile_rows, bm, backend=gmm_backend)   # [R, H]

    with scopes.scope("ds.moe_combine"):
        rows = out[jnp.minimum(pair_row, R - 1)].reshape(k, T, H)
        w = jnp.where(here, weights, 0.0).astype(dt).T        # [k, T]
        y = jnp.sum(w[:, :, None] * rows, axis=0)
    return y, stats


def _a2a(t, axis_name):
    return jax.lax.all_to_all(t, axis_name, 0, 0, tiled=False)


def _overlap_chunks(requested, e_local):
    """Largest divisor of the local expert count ≤ the requested chunk
    count (1 = no pipelining)."""
    n = max(1, min(int(requested), e_local))
    while e_local % n:
        n -= 1
    return n


def moe_ffn_expert_parallel(params, x, axis_name, ep, capacity_factor=1.25,
                            top_k=1, rng=None, jitter_eps=0.0, groups=1,
                            dispatch="einsum", renorm_kept_choices=False,
                            a2a_overlap_chunks=1, gmm_block_m=None,
                            gmm_block_n=None, gmm_backend=None,
                            observe=False):
    """Inside shard_map: x is this rank's token shard [T_local, H];
    params carry this rank's experts ({"w_in" [E/ep, H, I], ...}) with
    the gate replicated. all_to_all exchanges expert-major token blocks
    so each rank runs only its own experts; a second all_to_all returns
    the outputs. Matches `moe_ffn_dense` run per-shard exactly (with the
    same `groups`: capacity is per local routing group).

    With `dispatch="sort"` and `a2a_overlap_chunks > 1` the exchange is
    chunked along the local-expert axis and software-pipelined: the
    all_to_all for chunk i+1 is issued before the expert FFN of chunk i,
    so XLA's scheduler can hide the ICI transfer under the grouped
    matmul. Results are bit-identical to the unchunked exchange (pure
    reordering of independent slices)."""
    if dispatch not in DISPATCH_MODES:
        raise ValueError(f"dispatch must be one of {DISPATCH_MODES}, "
                         f"got {dispatch!r}")
    if observe and dispatch != "sort":
        raise ValueError(
            "observe=True requires dispatch='sort': the routing stats "
            "come from the sort engine's position-in-expert bookkeeping")
    T, H = x.shape
    e_local = params["w_in"].shape[0]
    E = e_local * ep
    g = _resolve_groups(groups, T)
    tg = T // g
    capacity = max(1, int(capacity_factor * top_k * tg / E))
    if rng is not None:
        # decorrelate jitter across ranks: a replicated key would give
        # every rank's tokens identical noise (1/ep of the exploration)
        rng = jax.random.fold_in(rng, jax.lax.axis_index(axis_name))
    xg = x.reshape(g, tg, H)

    if dispatch == "einsum":
        dispatch_t, combine, aux = _route_groups(
            params["gate"], xg, capacity, top_k, rng, jitter_eps,
            renorm_kept_choices=renorm_kept_choices)

        # [g, Tg, E, C] → [E, g·C, H] expert-major buffers, then exchange:
        # split E = ep × e_local; all_to_all gives [ep, e_local, g·C, H]
        # where dim 0 is the source rank.
        expert_in = jnp.einsum("gtec,gth->egch", dispatch_t, xg)
        expert_in = expert_in.reshape(ep, e_local, g * capacity, H)
        expert_in = _a2a(expert_in, axis_name)           # [ep, eL, g·C, H]

        flat_in = jnp.moveaxis(expert_in, 0, 1).reshape(
            e_local, ep * g * capacity, H)
        expert_out = jax.vmap(_expert_ffn)(
            params["w_in"], params["b_in"], params["w_out"],
            params["b_out"], flat_in)                    # [eL, ep·g·C, H]
        expert_out = jnp.moveaxis(
            expert_out.reshape(e_local, ep, g * capacity, H), 1, 0)

        expert_out = _a2a(expert_out, axis_name)         # [ep, eL, g·C, H]
        expert_out = expert_out.reshape(E, g, capacity, H)
        y = jnp.einsum("gtec,egch->gth", combine, expert_out)
        # aux is per-shard; average over the expert(-data) axis
        return y.reshape(T, H), jax.lax.pmean(aux, axis_name)

    # ---- sort engine -----------------------------------------------------
    probs = _jittered_probs(params["gate"], xg, rng, jitter_eps)
    route = _sort_route(probs, capacity, top_k, renorm_kept_choices)
    if observe:
        # per-rank stats over this rank's token shard (each rank routes
        # its own tokens to all E global experts); the host collector
        # averages across ranks' emissions
        _emit_routing_stats(route, capacity, E, g)
    span, bm, bn = _gmm_geometry(capacity, H, params["w_in"].shape[-1],
                                 x.dtype, gmm_block_m, gmm_block_n,
                                 gmm_backend)
    buf, sizes = _fill_buffer(x, route, capacity, span)  # [E·g·span, H]

    n_ch = _overlap_chunks(a2a_overlap_chunks, e_local)
    e_chunk = e_local // n_ch
    send = buf.reshape(ep, e_local, g * span, H)
    sz_send = sizes.reshape(ep, e_local, g)

    def ffn_chunk(ci, rbuf, rsz):
        # rbuf [ep, e_chunk, g·span, H] (dim 0 = source rank); the
        # span layout makes the received sizes the RAGGED group sizes
        # the kernel was built for — ep·g spans per local expert.
        flat = jnp.moveaxis(rbuf, 0, 1).reshape(
            e_chunk * ep * g * span, H)
        fsz = jnp.moveaxis(rsz, 0, 1).reshape(e_chunk * ep * g)
        lut = tuple(np.repeat(np.arange(e_chunk), ep * g))
        sl = slice(ci * e_chunk, (ci + 1) * e_chunk)
        pchunk = {k: params[k][sl]
                  for k in ("w_in", "b_in", "w_out", "b_out")}
        out = _sort_ffn(pchunk, flat, fsz, span, lut, e_chunk,
                        ep * g * span, bm, bn, gmm_backend)
        return jnp.moveaxis(out.reshape(e_chunk, ep, g * span, H), 1, 0)

    chunk = lambda t, ci: t[:, ci * e_chunk:(ci + 1) * e_chunk]  # noqa: E731
    # software pipeline: exchange chunk i+1 concurrently with FFN(i)
    recv = [(_a2a(chunk(send, 0), axis_name),
             _a2a(chunk(sz_send, 0), axis_name))]
    outs = []
    for ci in range(n_ch):
        if ci + 1 < n_ch:
            recv.append((_a2a(chunk(send, ci + 1), axis_name),
                         _a2a(chunk(sz_send, ci + 1), axis_name)))
        rbuf, rsz = recv[ci]
        outs.append(_a2a(ffn_chunk(ci, rbuf, rsz), axis_name))
    out_full = outs[0] if n_ch == 1 else jnp.concatenate(outs, axis=1)
    out_buf = out_full.reshape(E * g * span, H)
    y = _sort_combine(out_buf, route, span, T, x.dtype)
    return y, jax.lax.pmean(route.aux, axis_name)


class MoELayer:
    """Engine-protocol MoE FFN layer (init/apply), expert-parallel when a
    mesh with an ``expert`` axis is supplied."""

    def __init__(self, hidden_size, intermediate_size, num_experts,
                 capacity_factor=1.25, mesh=None, axis_name="expert",
                 param_dtype=jnp.float32, top_k=1, jitter_eps=0.0,
                 groups=1, dispatch="einsum", renorm_kept_choices=False,
                 a2a_overlap_chunks=1):
        if dispatch not in DISPATCH_MODES:
            raise ValueError(f"dispatch must be one of {DISPATCH_MODES}, "
                             f"got {dispatch!r}")
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.top_k = top_k          # 1 = Switch, 2 = GShard default
        self.jitter_eps = jitter_eps
        self.groups = groups        # 0 = auto (per-call token count)
        self.dispatch = dispatch
        self.renorm_kept_choices = renorm_kept_choices
        self.a2a_overlap_chunks = a2a_overlap_chunks
        self.axis_name = axis_name
        self.ep = int(mesh.shape[axis_name]) \
            if mesh is not None and axis_name in mesh.axis_names else 1
        if num_experts % max(self.ep, 1) != 0:
            raise ValueError(f"num_experts {num_experts} must divide over "
                             f"expert-parallel size {self.ep}")
        self.param_dtype = param_dtype

    def init(self, rng, x=None):
        E, H, I = self.num_experts, self.hidden_size, self.intermediate_size
        k1, k2, k3 = jax.random.split(rng, 3)
        dt = self.param_dtype
        return {
            "gate": (jax.random.normal(k1, (H, E)) * 0.02).astype(dt),
            "w_in": (jax.random.normal(k2, (E, H, I)) * 0.02).astype(dt),
            "b_in": jnp.zeros((E, I), dt),
            "w_out": (jax.random.normal(k3, (E, I, H)) * 0.02).astype(dt),
            "b_out": jnp.zeros((E, H), dt),
        }

    def param_specs(self):
        """Expert dim sharded over the expert axis; gate replicated."""
        from jax.sharding import PartitionSpec as P
        ax = self.axis_name if self.ep > 1 else None
        return {"gate": P(), "w_in": P(ax), "b_in": P(ax),
                "w_out": P(ax), "b_out": P(ax)}

    def apply(self, params, x, rng=None):
        """x [..., H] → (y [..., H], aux_loss); dense or inside
        shard_map depending on construction."""
        lead = x.shape[:-1]
        flat = x.reshape(-1, self.hidden_size)
        kw = dict(capacity_factor=self.capacity_factor, top_k=self.top_k,
                  rng=rng, jitter_eps=self.jitter_eps if rng is not None
                  else 0.0, groups=self.groups, dispatch=self.dispatch,
                  renorm_kept_choices=self.renorm_kept_choices)
        if self.ep > 1:
            y, aux = moe_ffn_expert_parallel(
                params, flat, self.axis_name, self.ep,
                a2a_overlap_chunks=self.a2a_overlap_chunks, **kw)
        else:
            y, aux = moe_ffn_dense(params, flat, **kw)
        return y.reshape(*lead, self.hidden_size), aux
