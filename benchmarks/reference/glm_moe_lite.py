"""Plain reference for the GLM-MoE-lite architecture (zai-org,
GLM-4.7-Flash; `model_type: glm4_moe_lite`).

Straightforward `jax.numpy` in float32 at `highest` matmul precision,
written from the public `config.json` (the model-configs catalog's row):
EXPANDED attention only (every head's keys and values are made from the
latent rows; nothing is absorbed), no kernel, no cache, no sorting, no
batching, and nothing imported from `deeperspeed_tpu`. `RMS(x; g) = x /
sqrt(mean(x^2) + eps) * g`; no bias anywhere; H = `num_attention_heads`,
a head's dims n = `qk_nope_head_dim`, r = `qk_rope_head_dim`, v =
`v_head_dim`, ranks `q_lora_rank` and c = `kv_lora_rank`.

    a      = RMS(x; g_attn)
    c_q    = RMS(a Wqa; g_qa)                    [q_lora_rank]
    q      = c_q Wqb -> [T, H, n + r]            a head's [q_nope | q_rope]
    c_kv | k_r = a Wkva                          [c | r]
    c_kv   = RMS(c_kv; g_kva)                    k_r is NOT normed
    kv     = c_kv Wkvb -> [T, H, n + v]          a head's [k_nope | v]
    rotary, rotate-half over all r features, inv_freq = theta^(-2i/r):
        q_rope of every head, and k_r ONCE, for all heads
    s_h,ij = (q_nope_h,i . k_nope_h,j + rot(q_rope_h,i) . rot(k_r,j))
             / sqrt(n + r);  key j is visible to query i iff j <= i
    o_h    = softmax_j(s_h) v_h                  [v]
    x      = x + concat_h(o_h) Wo
    m      = RMS(x; g_mlp)
    layer < first_k_dense_replace:
             x = x + (silu(m Wgate) * (m Wup)) Wdown
    else:    s = sigmoid(m Wr) over all n_routed_experts
             e_j = the num_experts_per_tok largest of s + b   (b: the
                   correction bias, which chooses and does not weigh)
             w_j = routed_scaling_factor * s_{e_j} / sum_j s_{e_j}
             x = x + sum_{j: e_j in held} w_j E_{e_j}(m) + E_shared(m)
    logits = RMS(x_L; g_f) Whead^T
    next-token prediction (`num_nextn_predict_layers` 1), position i:
             h' = [RMS(x_L,i; g_h) | RMS(emb(t_{i+1}); g_e)] Wp
             one expert layer as above on h' (positions 0..T-2), the
             block's own final norm, the SAME head -> logits for t_{i+2}
    loss   = CE(logits_i, t_{i+1}) + mtp_loss_weight * CE(mtp_i, t_{i+2})

`held` (a range of expert ids, default all) is WHICH experts' part of the
routed sum is computed, for the share test: the shares of disjoint ranges
add up, the shared expert counted once, to the whole layer.

What the public file leaves open is the configuration file's `assumed`
(the rotary pairing, where the two low-rank norms sit, the order of the
two halves under Wp and which hidden state it reads, the loss weight).

It reads the weights in the tree the program keeps them in (the one
thing the two must share), one stack a layer kind, a kind named
`latent<H>.<dense|experts>`, every leaf with the kind's layers leading:

    embed.wte [V, h]; embed_out.wte [V, h]; final_ln.scale [h];
    stacks[kind].ln_attn.scale, .ln_mlp.scale [L, h];
    stacks[kind].attn.{q_a [L, h, q_rank], q_a_norm [L, q_rank],
        q_b [L, q_rank, H*(n+r)], kv_a [L, h, c+r], kv_a_norm [L, c],
        kv_b [L, c, H*(n+v)], out_w [L, H*v, h]};
    dense:   stacks[kind].mlp.{in_w [L, h, 2i], out_w [L, i, h]}
    experts: stacks[kind].mlp.{gate [L, h, E] (the router Wr), gate_bias
             [L, E] (b), w_in [L, E, h, 2w], w_out [L, E, w, h],
             shared_in [L, h, 2s], shared_out [L, s, h]}
    mtp.{hnorm.scale, enorm.scale, final_ln.scale [h], proj [2h, h],
         block: a stack of ONE layer of kind latent<H>.experts}

Departures from the published layout, on purpose (with random weights a
layout is a convention):
- a gated FFN's gate and up projections are one matrix [Wgate | Wup],
  and `n_shared_experts` shared experts are one MLP of their summed width.
- Attention runs a block of queries at a time, the dense FFN a block of
  rows at a time, and the experts one at a time, each over the rows routed
  to it (gathered, in chunks of 512, so that a row of 16,896 tokens fits
  beside the served state on the chip): the same sums as the published
  gather/scatter. An expert's weights are widened to float32 when it is
  its turn, the head's an eighth of the vocabulary at a time.
"""

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 128
ROW_BLOCK = 512


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * \
        _f32(scale)


def layer_kinds(conf):
    """[(stack name, index within the stack, FFN kind)] a layer."""
    H, dense = conf["num_attention_heads"], conf["first_k_dense_replace"]
    return [(f"latent{H}.dense", i, "dense") if i < dense else
            (f"latent{H}.experts", i - dense, "experts")
            for i in range(conf["num_hidden_layers"])]


def _rotate(x, theta):
    """x [S, ..., r]: rotate-half over all r features at positions
    0..S-1."""
    r = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    freqs = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    emb = emb.reshape(x.shape[0], *(1,) * (x.ndim - 2), r)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(emb) + jnp.concatenate([-x2, x1], -1) * jnp.sin(emb)


def _block(n, most):
    return max(b for b in range(1, min(n, most) + 1) if n % b == 0)


def _attention(q, k, v):
    """q, k [S, H, d], v [S, H, dv] -> [S, H, dv], causal, a block of
    queries at a time against every key."""
    S, H, d = q.shape
    blk = _block(S, QUERY_BLOCK)
    keys = jnp.arange(S)[None, :]

    def one(args):
        qb, first = args                                    # [blk, H, d]
        seen = keys <= first + jnp.arange(blk)[:, None]
        s = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(one, (q.reshape(S // blk, blk, H, d),
                            jnp.arange(0, S, blk)))
    return out.reshape(S, H, -1)


def latent_row(conf, attn, a):
    """What a layer keeps of each token, from its normed input a [S, h]:
    (c_kv [S, c], rot(k_r) [S, r]), the compressed keys-and-values and
    the ONE rotary key row all heads read. A cache of this architecture
    holds them side by side."""
    c = conf["kv_lora_rank"]
    ckv = a @ _f32(attn["kv_a"])
    return (_rms(ckv[:, :c], attn["kv_a_norm"], conf["rms_norm_eps"]),
            _rotate(ckv[:, c:], conf["rope_theta"]))


def latent_attention(conf, attn, a):
    """The attention of one layer on the normed input a [S, h] ->
    [S, H * v], expanded."""
    S = a.shape[0]
    H, eps = conf["num_attention_heads"], conf["rms_norm_eps"]
    n = conf["qk_nope_head_dim"]
    c_q = _rms(a @ _f32(attn["q_a"]), attn["q_a_norm"], eps)
    q = (c_q @ _f32(attn["q_b"])).reshape(S, H, -1)
    c_kv, k_r = latent_row(conf, attn, a)
    kv = (c_kv @ _f32(attn["kv_b"])).reshape(S, H, -1)
    q = jnp.concatenate(
        [q[..., :n], _rotate(q[..., n:], conf["rope_theta"])], axis=-1)
    k = jnp.concatenate(
        [kv[..., :n], jnp.broadcast_to(k_r[:, None, :],
                                       (S, H, k_r.shape[-1]))], axis=-1)
    return _attention(q, k, kv[..., n:]).reshape(S, -1)


def _gated(m, w_in, w_out):
    h = m @ _f32(w_in)
    inter = h.shape[-1] // 2
    return (jax.nn.silu(h[:, :inter]) * h[:, inter:]) @ _f32(w_out)


def _gated_rows(m, w_in, w_out):
    """`_gated`, ROW_BLOCK rows at a time (a dense layer's hidden row of
    20,480 features times 16,896 tokens would be 1.4 GB)."""
    blk = _block(m.shape[0], ROW_BLOCK)
    return jax.lax.map(lambda rows: _gated(rows, w_in, w_out),
                       m.reshape(-1, blk, m.shape[1])).reshape(m.shape)


def route(conf, mlp, m):
    """m [T, h] -> (experts [T, k], weights [T, k]): sigmoid scores; the
    k largest of score + bias; their scores, without the bias,
    renormalised and scaled."""
    scores = jax.nn.sigmoid(m @ _f32(mlp["gate"]))
    _, top_e = jax.lax.top_k(scores + _f32(mlp["gate_bias"]),
                             conf["num_experts_per_tok"])
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    if conf["norm_topk_prob"]:
        top_s = top_s / top_s.sum(-1, keepdims=True)
    return top_e, top_s * conf["routed_scaling_factor"]


def experts_sum(w_in, w_out, m, top_e, top_w, held, base=0):
    """sum_{j: e_j in held} w_j E_{e_j}(m), `held` = (first, past). One
    expert at a time (`w_in` [.., h, 2w] / `w_out` [.., w, h] hold expert
    e at row `base + e`), over the rows routed to it, gathered ROW_BLOCK
    at a time."""
    T = m.shape[0]
    C = min(T, ROW_BLOCK)

    def one(e, acc):
        sel = top_e == e
        mask = sel.any(-1)
        w_tok = (sel * top_w).sum(-1)
        order = jnp.cumsum(mask) - 1

        def chunk(j, acc):
            pick = mask & (order >= j * C) & (order < (j + 1) * C)
            idx = jnp.nonzero(pick, size=C, fill_value=T)[0]
            rows = m.at[idx].get(mode="fill", fill_value=0.0)
            wt = w_tok.at[idx].get(mode="fill", fill_value=0.0)
            return acc.at[idx].add(
                wt[:, None] * _gated(rows, w_in[base + e], w_out[base + e]),
                mode="drop")

        # every chunk the rows could fill, the empty ones skipped: loops
        # of a fixed length, so that `jax.grad` runs through them
        return jax.lax.fori_loop(
            0, -(-T // C), lambda j, acc: jax.lax.cond(
                j * C < mask.sum(), chunk, lambda j, acc: acc, j, acc), acc)

    return jax.lax.fori_loop(held[0], held[1], one, jnp.zeros_like(m))


def moe_layer(conf, mlp, m, held=None, shared=True):
    """An expert layer's FFN on m [T, h]: the `held` experts' part of the
    routed sum (default: all), plus the shared expert (`shared`: whether
    to count it; a sum over several holders counts it once)."""
    top_e, top_w = route(conf, mlp, m)
    y = experts_sum(mlp["w_in"], mlp["w_out"], m, top_e, top_w,
                    held or (0, conf["n_routed_experts"]),
                    mlp.get("expert_base", 0))
    if shared:
        y = y + _gated(m, mlp["shared_in"], mlp["shared_out"])
    return y


def _layer(conf, ffn, p, x, held=None):
    """One layer on x [S, h]; `p` that layer's leaves."""
    eps = conf["rms_norm_eps"]
    a = _rms(x, p["ln_attn"]["scale"], eps)
    x = x + latent_attention(conf, p["attn"], a) @ _f32(p["attn"]["out_w"])
    m = _rms(x, p["ln_mlp"]["scale"], eps)
    if ffn == "dense":
        return x + _gated_rows(m, p["mlp"]["in_w"], p["mlp"]["out_w"])
    return x + moe_layer(conf, p["mlp"], m, held)


def _layer_leaves(stack, i):
    """Layer `i` of a kind's stack. The experts stay whole, the kind's
    layers' experts in one row of matrices with this layer's from row
    `expert_base` on: they are indexed one at a time (a layer's are 1.2
    GB, and slicing them out would copy them)."""
    p = {group: {k: v[i] for k, v in leaves.items()
                 if k not in ("w_in", "w_out")}
         for group, leaves in stack.items()}
    for k in ("w_in", "w_out"):
        if k in stack["mlp"]:
            w = stack["mlp"][k]
            p["mlp"][k] = w.reshape(-1, *w.shape[2:])
            p["mlp"]["expert_base"] = i * w.shape[1]
    return p


def _last_hidden(conf, params, row, held=None):
    """One row of tokens [S] -> the last layer's hidden states [S, h],
    before the final norm."""
    x = _f32(params["embed"]["wte"][row])
    for name, i, ffn in layer_kinds(conf):
        x = _layer(conf, ffn, _layer_leaves(params["stacks"][name], i), x,
                   held)
    return x


def cache_rows(conf, params, row):
    """One row of tokens [S] -> [layers, S, c + r], float32: every
    layer's `[c_kv | rot(k_r)]` of every token, which is what a cache of
    this architecture holds (the comparison of `closed_loop_probed`)."""
    with jax.default_matmul_precision("highest"):
        x, rows = _f32(params["embed"]["wte"][row]), []
        for name, i, ffn in layer_kinds(conf):
            p = _layer_leaves(params["stacks"][name], i)
            rows.append(jnp.concatenate(latent_row(conf, p["attn"], _rms(
                x, p["ln_attn"]["scale"], conf["rms_norm_eps"])), axis=-1))
            x = _layer(conf, ffn, p, x)
        return jnp.stack(rows)


def hidden_states(conf, params, tokens, held=None):
    """tokens [B, S] -> final-norm hidden states [B, S, h], float32."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _rms(_last_hidden(conf, params, row, held),
                 params["final_ln"]["scale"], conf["rms_norm_eps"])
            for row in tokens])


def _head(params, hidden):
    """hidden [..., h] -> logits [..., V], the head widened an eighth of
    the vocabulary at a time."""
    wte = params["embed_out"]["wte"]
    V = wte.shape[0]
    parts = 8 if V % 8 == 0 else 1
    out = jax.lax.map(lambda w: hidden @ _f32(w).T,
                      wte.reshape(parts, V // parts, -1))
    return jnp.moveaxis(out, 0, -2).reshape(*hidden.shape[:-1], V)


def logits(conf, params, tokens, held=None):
    """tokens [B, S] -> logits [B, S, V], float32."""
    with jax.default_matmul_precision("highest"):
        return _head(params, hidden_states(conf, params, tokens, held))


def logits_at(conf, params, tokens, positions):
    """Logits [B, T, V] at `positions` [B, T] only."""
    with jax.default_matmul_precision("highest"):
        hidden = hidden_states(conf, params, tokens)
        picked = jnp.take_along_axis(hidden, positions[:, :, None], axis=1)
        return _head(params, picked)


def _mtp_hidden(conf, params, row, last):
    """The next-token-prediction block on one row of tokens [S] and the
    last layer's hidden states of that row `last` [S, h]: final-norm
    hidden states [S - 1, h], position i from `last[i]` and token i + 1."""
    eps, mtp = conf["rms_norm_eps"], params["mtp"]
    emb = _f32(params["embed"]["wte"][row[1:]])
    x = jnp.concatenate([_rms(last[:-1], mtp["hnorm"]["scale"], eps),
                         _rms(emb, mtp["enorm"]["scale"], eps)],
                        axis=-1) @ _f32(mtp["proj"])
    x = _layer(conf, "experts", _layer_leaves(mtp["block"], 0), x)
    return _rms(x, mtp["final_ln"]["scale"], eps)


def mtp_logits(conf, params, tokens):
    """The next-token-prediction block's logits [B, S - 1, V]: position
    i, from the last layer's hidden state at i and token i + 1, predicts
    token i + 2."""
    with jax.default_matmul_precision("highest"):
        return _head(params, jnp.stack([
            _mtp_hidden(conf, params, row, _last_hidden(conf, params, row))
            for row in tokens]))


def _cross_entropy(lg, targets):
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()


def loss(conf, params, tokens, mtp_loss_weight):
    """Next-token cross entropy, plus (with a next-token-prediction
    block) `mtp_loss_weight` times that of the block's logits against
    the tokens two ahead. `jax.grad` of it is the gradient reference."""
    nextn = conf["num_nextn_predict_layers"]
    with jax.default_matmul_precision("highest"):
        hidden, ahead = [], []
        for row in tokens:
            last = _last_hidden(conf, params, row)
            hidden.append(_rms(last, params["final_ln"]["scale"],
                               conf["rms_norm_eps"]))
            if nextn:
                ahead.append(_mtp_hidden(conf, params, row, last))
        total = _cross_entropy(_head(params, jnp.stack(hidden))[:, :-1],
                               tokens[:, 1:])
        if nextn:
            total = total + mtp_loss_weight * _cross_entropy(
                _head(params, jnp.stack(ahead))[:, :-1], tokens[:, 2:])
        return total


def num_params(conf):
    """Parameters of the configuration, by layer kind, with its
    next-token-prediction blocks."""
    h, H = conf["hidden_size"], conf["num_attention_heads"]
    n, r, v = (conf[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim",
                                 "v_head_dim"))
    qr, c = conf["q_lora_rank"], conf["kv_lora_rank"]
    attn = h * qr + qr + qr * H * (n + r) + h * (c + r) + c + \
        c * H * (n + v) + H * v * h
    w, E = conf["moe_intermediate_size"], conf["n_routed_experts"]
    experts = h * E + E + 3 * h * w * (E + conf["n_shared_experts"])
    dense = min(conf["first_k_dense_replace"], conf["num_hidden_layers"])
    total = 2 * conf["vocab_size"] * h + h
    total += dense * (attn + 3 * h * conf["intermediate_size"] + 2 * h)
    total += (conf["num_hidden_layers"] - dense) * (attn + experts + 2 * h)
    total += conf["num_nextn_predict_layers"] * (
        2 * h + 2 * h * h + attn + experts + 2 * h + h)
    return total
