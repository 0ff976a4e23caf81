"""Plain reference for the qwen3_next architecture (Qwen3-Next-80B-A3B):
Gated DeltaNet layers beside output-gated softmax attention, every FFN
routed experts with a gated shared expert.

Straightforward `jax.numpy` in float32 at `highest` matmul precision,
written from the public `config.json` (the model-configs catalog's row)
and the Gated DeltaNet paper (arXiv:2412.06464): no kernel, no cache, no
chunked form, no batching, and nothing imported from `deeperspeed_tpu`.
With H = `hidden_size`, eps = `rms_norm_eps`:

    RMS(u; w) = u / sqrt(mean(u^2) + eps) * (1 + w)
    every layer l:  x' = x + Mixer_l(RMS(x; w1));  y = x' + MoE(RMS(x'; w2))
    logits = RMS(x_L; w_f) W_head^T  (untied), no bias anywhere
    layer l is `full` where (l + 1) % full_attention_interval == 0, else `gdn`

gdn (n_k key heads, n_v value heads, d_k, d_v, K taps):
    [q | k | v | z] = a W_in   (H -> 2 n_k d_k + 2 n_v d_v);  [b | a'] = a W_ba
    c_t = silu(sum_j w_c[j] * [q|k|v]_{t-K+1+j}), zeros before the row;
    split c back into q, k, v; a key head serves n_v / n_k CONSECUTIVE
    value heads;  q = l2norm(q) / sqrt(d_k),  k = l2norm(k)  (eps 1e-6)
    a value head h:  beta_t = sigmoid(b_t),
                     g_t = -exp(A_log[h]) softplus(a'_t + dt_bias[h])
    S'  = exp(g_t) S_{t-1};  delta_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t delta_t^T   ([d_k, d_v], zero at the start)
    o_t = S_t^T q_t
    out = ((o_t / sqrt(mean(o_t^2) + eps) * w_n) * silu(z_t)) W_o
    (w_n a plain scale, NOT 1 + w): the recurrence itself, a `lax.scan`.
full (heads Hq of d, G KV heads, rotary on the first `partial_rotary_factor`
of the head at `rope_theta`, rotate-half):
    q = a W_q -> [T, Hq, d];  gate = a W_gate -> [T, Hq d]
    [k | v] = a W_kv -> [T, 2, G, d];  q = RMS(q; w_q), k = RMS(k; w_k) a head
    causal softmax attention at d^-0.5, Hq / G query heads a KV head
    out = (attn * sigmoid(gate)) W_o
MoE (E published experts, `held_experts` of them here, width w):
    p = softmax(u W_r) over ALL E; the `num_experts_per_tok` largest,
    weights p_i / sum p (norm_topk_prob);  expert i: (silu(u Wg) * (u Wu)) Wd
    + sigmoid(u w_s) * shared(u).  The absent experts' part is left out.

It reads the weights in the tree the program keeps them in (the one thing
the two must share), one stack a layer kind, named `gdn0.experts` and
`full<Hq>.experts`, every leaf with the kind's layers leading.

Departures from the published layout, on purpose (with random weights a
layout is a convention): the checkpoint interleaves `in_proj_qkvz` and
`in_proj_ba` a KEY head at a time (a key head's q, k, its value heads' v,
z; b, a) where `in_w` here is [q | k | v | z] and `ba_w` [b | a] whole; the
query projection's second half a head (the gate) is a matrix of its own,
`gate_w`; W_k and W_v are one matrix [K | V]; an expert's gate and up are
one matrix [Wg | Wu]; the convolution's weight is [K, channels]. `states`
stops the recurrence after `n` rows of a longer row (by causality what
lies behind moves nothing before it), so that one compiled program serves
every length. The multi-token-prediction block is not built.
"""

import jax
import jax.numpy as jnp

HEAD_ROWS = 256
QUERY_BLOCK = 256
EXPERT_ROWS = 512


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * \
        (1.0 + _f32(w))


def held_range(conf):
    """(first, past-the-last) of the published experts held here."""
    published = conf.get("num_experts_published", conf["num_experts"])
    lo, hi = (int(t) for t in conf.get(
        "held_experts", f"0-{published - 1}").split("-"))
    return lo, hi + 1


def layer_kinds(conf):
    every = conf["full_attention_interval"]
    return ["full" if (i + 1) % every == 0 else "gdn"
            for i in range(conf["num_hidden_layers"])]


def _layer_leaves(stack, j):
    """Layer `j` of a kind's stack. The experts stay whole, the kind's
    layers' experts in one row of matrices with this layer's from row
    `expert_base` on: they are indexed one at a time (a layer's are 1.6
    GB, and slicing them out would copy them)."""
    p = {group: {k: v[j] for k, v in leaves.items()
                 if k not in ("w_in", "w_out")}
         for group, leaves in stack.items()}
    for k in ("w_in", "w_out"):
        w = stack["mlp"][k]
        p["mlp"][k] = w.reshape(-1, *w.shape[2:])
    p["mlp"]["expert_base"] = j * stack["mlp"]["w_in"].shape[1]
    return p


def layers_of(conf, params):
    """[(kind, that layer's leaves)] in order."""
    names = {"gdn": "gdn0.experts",
             "full": f"full{conf['num_attention_heads']}.experts"}
    at, out = {}, []
    for kind in layer_kinds(conf):
        j = at.get(kind, 0)
        at[kind] = j + 1
        out.append((kind, _layer_leaves(params["stacks"][names[kind]], j)))
    return out


def delta_rule(q, k, v, g, beta, n=None):
    """The gated delta rule over T rows from a zero state: q, k [T, n_v,
    d_k] (a key head's already repeated), v [T, n_v, d_v], g, beta
    [T, n_v] -> (o [T, n_v, d_v], S after row n - 1 [n_v, d_k, d_v])."""
    T = q.shape[0]
    n = T if n is None else n

    def one(S, t):
        i, q_t, k_t, v_t, g_t, b_t = t
        decayed = jnp.exp(g_t)[:, None, None] * S
        delta = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", decayed, k_t))
        new = decayed + k_t[:, :, None] * delta[:, None, :]
        return jnp.where(i < n, new, S), jnp.einsum("hkv,hk->hv", new, q_t)

    S, o = jax.lax.scan(
        one, jnp.zeros((q.shape[1], q.shape[2], v.shape[2])),
        (jnp.arange(T), q, k, v, g, beta))
    return o, S


def gdn(conf, p, a, n=None):
    """a [T, H] -> (out [T, H], the convolution's input rows n - K + 1 ..
    n - 1 of [q | k | v] [K - 1, channels], S after row n - 1 [n_v, d_k,
    d_v]); `n` (default T): the rows that are the sequence."""
    T = a.shape[0]
    n = T if n is None else n
    nk, nv = conf["linear_num_key_heads"], conf["linear_num_value_heads"]
    dk, dv = conf["linear_key_head_dim"], conf["linear_value_head_dim"]
    K, ch = conf["linear_conv_kernel_dim"], 2 * nk * dk + nv * dv
    proj, ba = a @ _f32(p["in_w"]), a @ _f32(p["ba_w"])
    qkv, z = proj[:, :ch], proj[:, ch:]
    w_c = _f32(p["conv_w"])
    padded = jnp.concatenate([jnp.zeros((K - 1, ch)), qkv])
    c = jax.nn.silu(sum(w_c[j] * padded[j:j + T] for j in range(K)))

    def unit(t):
        return t / jnp.sqrt(jnp.square(t).sum(-1, keepdims=True) + 1e-6)

    q = unit(c[:, :nk * dk].reshape(T, nk, dk)) / jnp.sqrt(float(dk))
    k = unit(c[:, nk * dk:2 * nk * dk].reshape(T, nk, dk))
    v = c[:, 2 * nk * dk:].reshape(T, nv, dv)
    q, k = (jnp.repeat(t, nv // nk, axis=1) for t in (q, k))
    beta = jax.nn.sigmoid(ba[:, :nv])
    g = -jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(
        ba[:, nv:] + _f32(p["dt_bias"]))
    o, S = delta_rule(q, k, v, g, beta, n)
    o = o / jnp.sqrt(jnp.square(o).mean(-1, keepdims=True) +
                     conf["rms_norm_eps"]) * _f32(p["norm"])
    out = (o.reshape(T, nv * dv) * jax.nn.silu(z)) @ _f32(p["out_w"])
    return out, jax.lax.dynamic_slice_in_dim(padded, n, K - 1), S


def _rotate(x, conf):
    """Rotate-half rotary on the first `partial_rotary_factor` of the
    head's features: x [T, heads, d]."""
    T, _, d = x.shape
    rot = int(d * conf["partial_rotary_factor"])
    rot -= rot % 2
    inv = 1.0 / (float(conf["rope_theta"]) **
                 (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(xr, 2, axis=-1)
    xr = xr * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)
    return jnp.concatenate([xr, xp], axis=-1)


def keys_values(conf, p, a):
    """a [T, H] -> (k [T, G, d] normed and rotated, v [T, G, d])."""
    G, d = conf["num_key_value_heads"], conf["head_dim"]
    kv = (a @ _f32(p["kv_w"])).reshape(-1, 2, G, d)
    k = _rotate(_rms(kv[:, 0], p["k_norm"], conf["rms_norm_eps"]), conf)
    return k, kv[:, 1]


def _query_block(n):
    return max(b for b in range(1, min(n, QUERY_BLOCK) + 1) if n % b == 0)


def attention(conf, p, a, k, v):
    """The gated softmax attention of a `full` layer: a [T, H] -> [T, H]."""
    T = a.shape[0]
    Hq, G, d = conf["num_attention_heads"], k.shape[1], conf["head_dim"]
    q = _rotate(_rms((a @ _f32(p["q_w"])).reshape(T, Hq, d), p["q_norm"],
                     conf["rms_norm_eps"]), conf)
    blk = _query_block(T)
    keys = jnp.arange(T)[None, :]

    def one(args):
        qb, first = args                                 # [blk, G, r, d]
        seen = keys <= first + jnp.arange(blk)[:, None]
        s = jnp.einsum("qgrd,kgd->grqk", qb, k) * d ** -0.5
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(one, (q.reshape(T // blk, blk, G, Hq // G, d),
                          jnp.arange(0, T, blk)))
    gate = jax.nn.sigmoid(a @ _f32(p["gate_w"]))
    return (o.reshape(T, Hq * d) * gate) @ _f32(p["out_w"])


def _gated(m, w_in, w_out):
    h = m @ _f32(w_in)
    inter = h.shape[-1] // 2
    return (jax.nn.silu(h[:, :inter]) * h[:, inter:]) @ _f32(w_out)


def route(conf, gate, m):
    """m [T, H] -> (experts [T, k], weights [T, k]): the k largest of the
    softmax over ALL published experts, renormalised."""
    probs = jax.nn.softmax(m @ _f32(gate), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, conf["num_experts_per_tok"])
    if conf["norm_topk_prob"]:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    return top_e, top_p


def experts_sum(w_in, w_out, m, top_e, top_w, held, holds, base=0):
    """sum_{j: e_j in held} w_j E_{e_j}(m). `w_in` [.., H, 2w] / `w_out`
    [.., w, H] hold, from row `base` on, the experts `holds` = (first,
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
    """A layer's FFN on m [T, H]: the `held` experts' part of the routed
    sum (default: all the parameters hold), plus the gated shared expert
    (`shared`: whether to count it; a sum over several holders counts it
    once)."""
    top_e, top_w = route(conf, mlp["gate"], m)
    y = experts_sum(mlp["w_in"], mlp["w_out"], m, top_e, top_w,
                    held or held_range(conf), held_range(conf),
                    mlp.get("expert_base", 0))
    if shared:
        y = y + jax.nn.sigmoid(m @ _f32(mlp["shared_gate"])) * \
            _gated(m, mlp["shared_in"], mlp["shared_out"])
    return y


def walk(conf, params, row, n=None):
    """One row of tokens [T] -> (the last layer's hidden states [T, H],
    {"conv": [gdn layers, K - 1, channels], "state": [gdn layers, n_v,
    d_k, d_v] (after row n - 1), "full": [full layers, T, 2 G d] the full
    layers' [K | V] rows})."""
    eps = conf["rms_norm_eps"]
    x = _f32(params["embed"]["wte"][row])
    kept = {"conv": [], "state": [], "full": []}
    for kind, p in layers_of(conf, params):
        a = _rms(x, p["ln_attn"]["scale"], eps)
        if kind == "gdn":
            out, tail, S = gdn(conf, p["attn"], a, n)
            kept["conv"].append(tail)
            kept["state"].append(S)
        else:
            k, v = keys_values(conf, p["attn"], a)
            T = a.shape[0]
            kept["full"].append(jnp.concatenate(
                [k.reshape(T, -1), v.reshape(T, -1)], axis=-1))
            out = attention(conf, p["attn"], a, k, v)
        x = x + out
        x = x + moe_layer(conf, p["mlp"], _rms(x, p["ln_mlp"]["scale"], eps))
    return x, {key: jnp.stack(val) for key, val in kept.items()}


def hidden_states(conf, params, tokens):
    """tokens [B, T] -> what the head reads [B, T, H], float32."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _rms(walk(conf, params, row)[0], params["final_ln"]["scale"],
                 conf["rms_norm_eps"]) for row in tokens])


def states(conf, params, row, n):
    """What a cache of this architecture holds once rows [0, n) of `row`
    [T] went through it (`walk`'s second result): every gdn layer's
    convolution rows and matrix states after row n - 1, every full layer's
    [K | V] rows (those before n are the sequence's)."""
    with jax.default_matmul_precision("highest"):
        return walk(conf, params, row, n)[1]


def _head(params, hidden):
    """hidden [..., H] -> logits [..., V], a block of rows and an eighth
    of the vocabulary at a time."""
    wte = params["embed_out"]["wte"]
    V, d = wte.shape
    parts = 8 if V % 8 == 0 else 1
    flat = hidden.reshape(-1, d)
    rows = HEAD_ROWS if flat.shape[0] % HEAD_ROWS == 0 else flat.shape[0]

    def block(hb):
        out = jax.lax.map(lambda w: hb @ _f32(w).T,
                          wte.reshape(parts, V // parts, d))
        return jnp.moveaxis(out, 0, 1).reshape(rows, V)

    out = jax.lax.map(block, flat.reshape(-1, rows, d))
    return out.reshape(*hidden.shape[:-1], V)


def logits(conf, params, tokens):
    """tokens [B, T] -> logits [B, T, V], float32."""
    with jax.default_matmul_precision("highest"):
        return _head(params, hidden_states(conf, params, tokens))


forward = logits


def logits_at(conf, params, tokens, positions):
    """Logits [B, P, V] at `positions` [B, P] only."""
    with jax.default_matmul_precision("highest"):
        hidden = hidden_states(conf, params, tokens)
        picked = jnp.take_along_axis(hidden, positions[:, :, None], axis=1)
        return _head(params, picked)


def num_params(conf):
    """Parameters of the configuration as held here, by layer kind."""
    H, V = conf["hidden_size"], conf["vocab_size"]
    nk, nv = conf["linear_num_key_heads"], conf["linear_num_value_heads"]
    dk, dv = conf["linear_key_head_dim"], conf["linear_value_head_dim"]
    K, ch = conf["linear_conv_kernel_dim"], 2 * nk * dk + nv * dv
    Hq, G, d = conf["num_attention_heads"], conf["num_key_value_heads"], \
        conf["head_dim"]
    published = conf.get("num_experts_published", conf["num_experts"])
    w, s = conf["moe_intermediate_size"], \
        conf["shared_expert_intermediate_size"]
    moe_norms = H * published + conf["num_experts"] * 3 * H * w + \
        3 * H * s + H + 2 * H
    kinds = {"gdn": H * (ch + nv * dv) + H * 2 * nv + K * ch + 2 * nv + dv +
             nv * dv * H,
             "full": 2 * H * Hq * d + 2 * H * G * d + Hq * d * H + 2 * d}
    return sum(kinds[k] + moe_norms for k in layer_kinds(conf)) + \
        2 * V * H + H
