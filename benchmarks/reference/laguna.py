"""Plain reference for the Laguna architecture (poolside, Laguna-S-2.1).

Straightforward `jax.numpy` in float32 at `highest` matmul precision,
written from the public `config.json` (the model-configs catalog's row):
no kernel, no cache, no sorting, no batching, and nothing imported from
`deeperspeed_tpu`. `RMS(x; g) = x / sqrt(mean(x^2) + eps) * g`; no bias
anywhere; layer l has H_l query heads (`num_attention_heads_per_layer`),
G = `num_key_value_heads` KV heads, d = `head_dim`.

    a   = RMS(x; g_attn)
    q   = a Wq_l -> [T, H_l, d];  k = a Wk -> [T, G, d];  v = a Wv
    rotary, rotate-half, on the first r*d features of each head:
        full_attention:    r = 0.5, inv_freq by YaRN (below), cos and sin
                           times attention_factor
        sliding_attention: r = 1,   inv_freq = theta^(-2i/d)
    query head h reads KV head h // (H_l / G)
    s_ij = q_i . k_j / sqrt(d); key j is visible to query i iff j <= i,
           and on a sliding layer also i - j < sliding_window
    o_h  = softmax_j(s) v
    gate = sigmoid(a Wg_l) -> [T, H_l]      one scalar a head and token
    x    = x + concat_h(gate_h * o_h) Wo_l
    m    = RMS(x; g_mlp)
    dense layer:  x = x + (silu(m Wgate) * (m Wup)) Wdown
    sparse layer: p = softmax(m Wr) over all published experts
                  (p_j, e_j) = the num_experts_per_tok largest
                  w_j = moe_routed_scaling_factor * p_j / sum_j p_j
                  x = x + sum_{j: e_j in held} w_j E_{e_j}(m) + E_shared(m)
    logits = RMS(x_L; g_f) Whead^T

`held` (a range of expert ids, default the configuration's
`held_experts`) is WHICH experts' part of the sum is computed: one chip's
share of an expert-parallel layer. What the absent experts would have
added is left out, and that partial result goes on to the next layer.
With every published expert held it is the uncut model.

YaRN as Hugging Face's `rope_type: yarn` computes it, in float32:
    pos_i = theta^(2i/rot), rot = r*d;  c(b) = rot ln(orig / (2 pi b))
                                               / (2 ln theta)
    low = max(floor(c(beta_fast)), 0); high = min(ceil(c(beta_slow)),
    rot - 1); ramp_i = clip((i - low) / (high - low), 0, 1)
    inv_freq_i = ramp_i / (factor pos_i) + (1 - ramp_i) / pos_i

What the public file leaves open is the configuration file's `assumed`
(softmax router scores, the headwise sigmoid gate from the normed input,
no norm on q or k, silu, an ungated shared expert, pre-norm with two
norms a layer, this YaRN).

It reads the weights in the tree the program keeps them in (the one
thing the two must share), one stack a layer kind, a kind named
`<full|window><H_l>.<dense|experts>`, every leaf with the kind's layers
leading:

    embed.wte [V, h]; embed_out.wte [V, h]; final_ln.scale [h];
    stacks[kind].ln_attn.scale, .ln_mlp.scale [n, h];
    stacks[kind].attn.{q_w [n, h, H_l*d], kv_w [n, h, 2*G*d],
                       out_w [n, H_l*d, h], gate_w [n, h, H_l]};
    dense:   stacks[kind].mlp.{in_w [n, h, 2i], out_w [n, i, h]}
    experts: stacks[kind].mlp.{gate [n, h, E published] (the router Wr),
             w_in [n, E held, h, 2w], w_out [n, E held, w, h],
             shared_in [n, h, 2s], shared_out [n, s, h]}

Departures from the published layout, on purpose (with random weights a
layout is a convention):
- Wk and Wv are one matrix [K | V] (each G heads of d features), and a
  gated FFN's gate and up projections are one matrix [Wgate | Wup].
- Attention runs a block of queries at a time and the experts one at a
  time, each over the rows routed to it (gathered, in chunks of 512, so
  that a row of 8,704 tokens fits beside the served state on the chip):
  the same sums as the published gather/scatter.
- The head's weights are widened to float32 an eighth of the vocabulary
  at a time.
- The router's logits are float32 here, as everything.
"""

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 128
EXPERT_ROWS = 512

_ATTN = {"full_attention": "full", "sliding_attention": "window"}
_FFN = {"dense": "dense", "sparse": "experts"}


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * \
        _f32(scale)


def held_range(conf):
    """(first, past-the-last) of the experts the parameters hold."""
    lo, hi = (int(t) for t in conf["held_experts"].split("-"))
    return lo, hi + 1


def layer_kinds(conf):
    """[(stack name, index within the stack, attention kind, query heads,
    FFN kind)] a layer, in order."""
    seen, out = {}, []
    for attn, heads, ffn in zip(conf["layer_types"],
                                conf["num_attention_heads_per_layer"],
                                conf["mlp_layer_types"]):
        attn, ffn = _ATTN[attn], _FFN[ffn]
        name = f"{attn}{heads}.{ffn}"
        out.append((name, seen.get(name, 0), attn, heads, ffn))
        seen[name] = seen.get(name, 0) + 1
    return out


def yarn_inv_freq(rope, head_dim):
    """(inv_freq [rot / 2], the factor on cos and sin) of one entry of
    `rope_parameters`."""
    rot = int(head_dim * rope["partial_rotary_factor"])
    theta = rope["rope_theta"]
    pos = theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    if rope["rope_type"] == "default":
        return 1.0 / pos, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")

    def c(rotations):
        return rot * math.log(rope["original_max_position_embeddings"] /
                              (rotations * 2 * math.pi)) / \
            (2 * math.log(theta))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - low) /
                    (high - low), 0.0, 1.0)
    return ramp / (rope["factor"] * pos) + (1.0 - ramp) / pos, \
        rope["attention_factor"]


def _rotate(x, inv_freq, factor):
    """x [S, H, d]: rotate-half over the first 2 * len(inv_freq)
    features of every head, at positions 0..S-1."""
    rot = 2 * inv_freq.shape[0]
    freqs = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    cos, sin = jnp.cos(emb) * factor, jnp.sin(emb) * factor
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(xr, 2, axis=-1)
    xr = xr * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
    return jnp.concatenate([xr, xp], axis=-1)


def _query_block(n):
    return max(b for b in range(1, min(n, QUERY_BLOCK) + 1) if n % b == 0)


def _attention(q, k, v, window):
    """q [S, H, d], k / v [S, G, d] -> [S, H, d], a block of queries at a
    time against every key."""
    S, H, d = q.shape
    G = k.shape[1]
    blk = _query_block(S)
    keys = jnp.arange(S)[None, :]

    def one(args):
        qb, first = args                                 # [blk, G, r, d]
        rows = first + jnp.arange(blk)[:, None]
        seen = keys <= rows
        if window is not None:
            seen = seen & (rows - keys < window)
        s = jnp.einsum("qgrd,kgd->grqk", qb, k) / math.sqrt(d)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(one, (q.reshape(S // blk, blk, G, H // G, d),
                            jnp.arange(0, S, blk)))
    return out.reshape(S, H, d)


def _gated(m, w_in, w_out):
    h = m @ _f32(w_in)
    inter = h.shape[-1] // 2
    return (jax.nn.silu(h[:, :inter]) * h[:, inter:]) @ _f32(w_out)


def route(conf, gate, m):
    """m [T, h] -> (experts [T, k], weights [T, k]): the k largest of the
    softmax over all published experts, renormalised and scaled."""
    probs = jax.nn.softmax(m @ _f32(gate), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, conf["num_experts_per_tok"])
    if conf["norm_topk_prob"]:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    return top_e, top_p * conf["moe_routed_scaling_factor"]


def experts_sum(conf, w_in, w_out, m, top_e, top_w, held, holds, base=0):
    """sum_{j: e_j in held} w_j E_{e_j}(m). `w_in` [.., h, 2w] / `w_out`
    [.., w, h] hold, from row `base` on, the experts `holds` = (first,
    past) of the published ones; `held` the range whose part is wanted.
    One expert at a time, over the rows routed to it, gathered
    EXPERT_ROWS at a time."""
    T = m.shape[0]
    C = min(T, EXPERT_ROWS)
    lo, hi = held
    if not holds[0] <= lo < hi <= holds[1]:
        raise ValueError(f"held {held} is not among the experts the "
                         f"parameters hold, {holds}")

    def one(e, acc):
        sel = top_e == e
        mask = sel.any(-1)
        w_tok = (sel * top_w).sum(-1)
        order = jnp.cumsum(mask) - 1
        we_in = w_in[base + e - holds[0]]
        we_out = w_out[base + e - holds[0]]

        def chunk(j, acc):
            pick = mask & (order >= j * C) & (order < (j + 1) * C)
            idx = jnp.nonzero(pick, size=C, fill_value=T)[0]
            rows = m.at[idx].get(mode="fill", fill_value=0.0)
            wt = w_tok.at[idx].get(mode="fill", fill_value=0.0)
            return acc.at[idx].add(wt[:, None] * _gated(rows, we_in, we_out),
                                   mode="drop")

        return jax.lax.fori_loop(0, (mask.sum() + C - 1) // C, chunk, acc)

    return jax.lax.fori_loop(lo, hi, one, jnp.zeros_like(m))


def moe_layer(conf, mlp, m, held=None, shared=True):
    """A sparse layer's FFN on m [T, h]: the `held` experts' part of the
    routed sum, plus the shared expert (`shared`: whether to count it; a
    sum over several holders counts it once)."""
    top_e, top_w = route(conf, mlp["gate"], m)
    y = experts_sum(conf, mlp["w_in"], mlp["w_out"], m, top_e, top_w,
                    held or held_range(conf), held_range(conf),
                    mlp.get("expert_base", 0))
    if shared:
        y = y + _gated(m, mlp["shared_in"], mlp["shared_out"])
    return y


def _layer(conf, kind, p, x, held):
    """One layer on x [S, h]; `p` that layer's leaves."""
    _, _, attn, heads, ffn = kind
    S, h = x.shape
    d, G = conf["head_dim"], conf["num_key_value_heads"]
    eps = conf["rms_norm_eps"]
    rope = conf["rope_parameters"][
        "full_attention" if attn == "full" else "sliding_attention"]
    a = _rms(x, p["ln_attn"]["scale"], eps)
    q = (a @ _f32(p["attn"]["q_w"])).reshape(S, heads, d)
    kv = (a @ _f32(p["attn"]["kv_w"])).reshape(S, 2, G, d)
    inv_freq, factor = yarn_inv_freq(rope, d)
    q = _rotate(q, inv_freq, factor)
    k = _rotate(kv[:, 0], inv_freq, factor)
    o = _attention(q, k, kv[:, 1],
                   conf["sliding_window"] if attn == "window" else None)
    if conf["gating"] != "per-head":
        raise ValueError(f"gating {conf['gating']!r}")
    gate = jax.nn.sigmoid(a @ _f32(p["attn"]["gate_w"]))       # [S, H_l]
    x = x + (o * gate[:, :, None]).reshape(S, heads * d) @ \
        _f32(p["attn"]["out_w"])
    m = _rms(x, p["ln_mlp"]["scale"], eps)
    if ffn == "dense":
        return x + _gated(m, p["mlp"]["in_w"], p["mlp"]["out_w"])
    return x + moe_layer(conf, p["mlp"], m, held)


def _layer_leaves(stack, i):
    """Layer `i` of a kind's stack. The experts stay whole, the kind's
    layers' experts in one row of matrices with this layer's from row
    `expert_base` on: they are indexed one at a time (a layer's are 2.4
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


def hidden_states(conf, params, tokens, held=None):
    """tokens [B, S] -> final-norm hidden states [B, S, h], float32."""
    with jax.default_matmul_precision("highest"):
        rows = []
        for row in tokens:
            x = _f32(params["embed"]["wte"][row])
            for kind in layer_kinds(conf):
                p = _layer_leaves(params["stacks"][kind[0]], kind[1])
                x = _layer(conf, kind, p, x, held)
            rows.append(_rms(x, params["final_ln"]["scale"],
                             conf["rms_norm_eps"]))
        return jnp.stack(rows)


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


def logits_at(conf, params, tokens, positions, held=None):
    """Logits [B, T, V] at `positions` [B, T] only."""
    with jax.default_matmul_precision("highest"):
        hidden = hidden_states(conf, params, tokens, held)
        picked = jnp.take_along_axis(hidden, positions[:, :, None], axis=1)
        return _head(params, picked)


def num_params(conf, held=True):
    """Parameters by layer kind: of what is held here, or (`held` False)
    of the published model at this depth."""
    h, d, G = conf["hidden_size"], conf["head_dim"], \
        conf["num_key_value_heads"]
    E = conf["num_experts"] if held else conf["num_experts_published"]
    total = 2 * conf["vocab_size"] * h + h
    for _, _, _, heads, ffn in layer_kinds(conf):
        total += 2 * h * heads * d + 2 * h * G * d + h * heads + 2 * h
        if ffn == "dense":
            total += 3 * h * conf["intermediate_size"]
        else:
            total += h * conf["num_experts_published"] + 3 * h * (
                E * conf["moe_intermediate_size"] +
                conf["shared_expert_intermediate_size"])
    return total
