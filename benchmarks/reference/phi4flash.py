"""Plain reference for the phi4flash architecture (Microsoft,
Phi-4-mini-flash-reasoning; SambaY, arXiv:2507.06607): a decoder-hybrid-
decoder of Mamba-1, window and ONE full differential-attention layer,
whose second half keeps no cache of its own.

Straightforward `jax.numpy` in float32 at `highest` matmul precision,
written from the public `config.json` (the model-configs catalog's row)
and the two papers: no kernel, no cache, no padding trick, no batching,
and nothing imported from `deeperspeed_tpu`. With L = `num_hidden_layers`
(a multiple of 4), d = `hidden_size`, eps = `layer_norm_eps`:

    every layer l:  a = LN(x);  x' = x + Mixer_l(a);  y = x' + MLP(LN'(x'))
    LN is LayerNorm WITH scale and bias
    MLP(u) = (silu(u Wg) * (u Wu)) Wd, no bias
    logits = LN_f(x_L) E^T, E the embedding (tied), no position anywhere

    l < L/2, l even, and l = L/2:  Mamba-1 (layer L/2 also gives the memory m)
    l < L/2, l odd:                differential attention, window
    l = L/2 + 1:                   differential attention, full, causal
    l > L/2 + 1, l even:           Gated Memory Unit on m
    l > L/2 + 1, l odd:            differential CROSS attention: its own
                                   q, layer L/2 + 1's K and V, causal

Mamba (d_i = 2 d, N = 16, K = 4, R = ceil(d / 16)):
    [u | z] = a W_in;   c_t = silu(b_c + sum_k w_c[k] * u_{t-K+1+k})
    [dl | B | C] = c W_x;   D_t = softplus(dl W_dt + b_dt);  A = -exp(A_log)
    h_t = exp(D_t * A) * h_{t-1} + (D_t * c_t) * B_t,  h_{-1} = 0   [N, d_i]
    s_t = sum_n h_t[n] C_t[n] + D_skip * c_t;   out = (s * silu(z)) W_out
    m_t = s_t  (after the skip, before the gate)
GMU:  out = (m * silu(a W_1)) W_2, m of the SAME token
Differential attention (H = heads / 2 pairs of width 2 e, e = d / heads;
G = kv_heads / 2 groups):
    q = a Wq + bq -> [T, H, 2, e];  k = a Wk + bk -> [T, G, 2, e]
    v = a Wv + bv -> [T, G, 2 e];   pair p reads group p // (H / G)
    P_j = softmax(q_pj k_gj^T / sqrt(e) + mask),  j = 1, 2
    o_p = P_1 v - lam P_2 v, computed as the published code does, by the
          value's halves: FOUR softmax-weighted sums (P_1 v1, P_1 v2,
          P_2 v1, P_2 v2)
    o'_p = (1 - lam0_l) * RMS(o_p; gamma_l);  out = concat_p(o'_p) Wo + bo
    lam0_l = 0.8 - 0.6 exp(-0.3 l);  lam = exp(lq1 . lk1) - exp(lq2 . lk2)
    + lam0_l.  Mask: causal, and t - s < sliding_window on a window layer

It reads the weights in the tree the program keeps them in (the one
thing the two must share), one stack a layer kind, named `ssm0.dense`,
`window<H>.dense`, `full<H>.dense`, `gmu0.dense`, `cross<H>.dense`, every
leaf with the kind's layers leading.

Departures from the published layout, on purpose (with random weights a
layout is a convention): Wk and Wv are one matrix [K | V]; the MLP's gate
and up are one matrix [Wg | Wu]; the convolution's weight is [K, d_i] and
`A_log` [N, d_i] (channels last); attention runs one pair at a time; the
head is widened an eighth of the vocabulary at a time, a block of rows
at a time. `states` stops the recurrence after `n` rows of a longer row
(by causality what lies behind moves nothing before it), so that one
compiled program serves every length.
"""

import math

import jax
import jax.numpy as jnp

HEAD_ROWS = 256


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def layer_kinds(conf):
    """Each layer's mixer: ssm | window | full | gmu | cross."""
    L = conf["num_hidden_layers"]
    if L % 4 or conf["mb_per_layer"] != 2:
        raise ValueError("num_hidden_layers must be a multiple of 4 and "
                         "mb_per_layer 2")
    half, out = L // 2, []
    for i in range(L):
        if i <= half:
            out.append("window" if i % 2 else "ssm")
        elif i == half + 1:
            out.append("full")
        else:
            out.append("cross" if i % 2 else "gmu")
    return out


def stack_names(conf):
    H = conf["num_attention_heads"] // 2
    return {"ssm": "ssm0.dense", "gmu": "gmu0.dense",
            "window": f"window{H}.dense", "full": f"full{H}.dense",
            "cross": f"cross{H}.dense"}


def layers_of(conf, params):
    """[(layer index, kind, that layer's leaves)] in order."""
    names, at, out = stack_names(conf), {}, []
    for i, kind in enumerate(layer_kinds(conf)):
        j = at.get(kind, 0)
        at[kind] = j + 1
        out.append((i, kind, jax.tree_util.tree_map(
            lambda a, j=j: a[j], params["stacks"][names[kind]])))
    return out


def _ln(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * _f32(p["scale"]) + \
        _f32(p["bias"])


def lam0(layer):
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def mamba(p, a, n=None):
    """a [T, d] -> (out [T, d], m [T, d_i], the convolution's input rows
    u_{n-K+1} .. u_{n-1} [K - 1, d_i], h after row n - 1 [N, d_i]);
    `n` (default T): the rows that are the sequence."""
    T = a.shape[0]
    n = T if n is None else n
    w_c, A = _f32(p["conv_w"]), -jnp.exp(_f32(p["A_log"]))
    K, N = w_c.shape[0], A.shape[0]
    uz = a @ _f32(p["in_w"])
    d_i = uz.shape[-1] // 2
    u, z = uz[:, :d_i], uz[:, d_i:]
    padded = jnp.concatenate([jnp.zeros((K - 1, d_i)), u])
    c = _f32(p["conv_b"]) + sum(w_c[k] * padded[k:k + T] for k in range(K))
    c = jax.nn.silu(c)
    proj = c @ _f32(p["x_w"])
    R = proj.shape[-1] - 2 * N
    step = jax.nn.softplus(proj[:, :R] @ _f32(p["dt_w"]) + _f32(p["dt_b"]))

    def one(h, t):
        i, step_t, c_t, b_t, c_coef = t
        new = jnp.exp(step_t[None, :] * A) * h + \
            (step_t * c_t)[None, :] * b_t[:, None]
        s = (new * c_coef[:, None]).sum(0) + _f32(p["D"]) * c_t
        return jnp.where(i < n, new, h), s

    h, s = jax.lax.scan(one, jnp.zeros((N, d_i)),
                        (jnp.arange(T), step, c, proj[:, R:R + N],
                         proj[:, R + N:]))
    out = (s * jax.nn.silu(z)) @ _f32(p["out_w"])
    tail = jax.lax.dynamic_slice_in_dim(padded, n, K - 1)
    return out, s, tail, h


def gmu(p, a, m):
    return (m * jax.nn.silu(a @ _f32(p["in_w"]))) @ _f32(p["out_w"])


def keys_values(conf, p, a):
    """a [T, d] -> (k [T, G, 2, e], v [T, G, 2 e])."""
    G = conf["num_key_value_heads"] // 2
    e = conf["hidden_size"] // conf["num_attention_heads"]
    kv = (a @ _f32(p["kv_w"]) + _f32(p["kv_b"])).reshape(-1, 2, G, 2 * e)
    return kv[:, 0].reshape(-1, G, 2, e), kv[:, 1]


def diff_attention(conf, layer, p, a, k, v, window=None):
    """The layer's own queries from `a` [T, d] over `k`, `v` (its own or
    the full layer's) -> [T, d] before nothing: the mixer's output."""
    T = a.shape[0]
    H, G = conf["num_attention_heads"] // 2, k.shape[1]
    e = conf["hidden_size"] // conf["num_attention_heads"]
    q = (a @ _f32(p["q_w"]) + _f32(p["q_b"])).reshape(T, H, 2, e)
    pos = jnp.arange(T)
    seen = pos[None, :] <= pos[:, None]
    if window is not None:
        seen = seen & (pos[:, None] - pos[None, :] < window)
    lam_0 = lam0(layer)
    lam = jnp.exp(jnp.sum(_f32(p["lam_q1"]) * _f32(p["lam_k1"]))) - \
        jnp.exp(jnp.sum(_f32(p["lam_q2"]) * _f32(p["lam_k2"]))) + lam_0

    def pair(pi):
        g = pi // (H // G)
        qp, kg, vg = q[:, pi], k[:, g], v[:, g]

        def weights(j):
            s = qp[:, j] @ kg[:, j].T / math.sqrt(e)
            return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)

        p1, p2 = weights(0), weights(1)
        v1, v2 = vg[:, :e], vg[:, e:]
        first = jnp.concatenate([p1 @ v1, p1 @ v2], axis=-1)
        second = jnp.concatenate([p2 @ v1, p2 @ v2], axis=-1)
        o = first - lam * second
        o = o / jnp.sqrt(jnp.square(o).mean(-1, keepdims=True) +
                         conf["layer_norm_eps"]) * _f32(p["subln"])
        return (1.0 - lam_0) * o

    o = jax.lax.map(pair, jnp.arange(H))                # [H, T, 2 e]
    return jnp.moveaxis(o, 0, 1).reshape(T, -1) @ _f32(p["out_w"]) + \
        _f32(p["out_b"])


def _mlp(p, u):
    hmid = u @ _f32(p["in_w"])
    F = hmid.shape[-1] // 2
    return (jax.nn.silu(hmid[:, :F]) * hmid[:, F:]) @ _f32(p["out_w"])


def walk(conf, params, row, n=None):
    """One row of tokens [T] -> (the last layer's hidden states [T, d],
    {"conv": [ssm layers, K - 1, d_i], "ssm": [ssm layers, N, d_i] (after
    row n - 1), "full": [1, T, 2 G 2e] the full layer's [K | V] rows,
    "window": [window layers, T, ...] the window layers', "mem": [T, d_i]
    the memory})."""
    eps, W = conf["layer_norm_eps"], conf["sliding_window"]
    x = _f32(params["embed"]["wte"])[row]
    kept = {"conv": [], "ssm": [], "full": [], "window": []}
    m = kv = None
    for i, kind, p in layers_of(conf, params):
        a = _ln(x, p["ln_attn"], eps)
        if kind == "ssm":
            out, m, tail, h = mamba(p["attn"], a, n)
            kept["conv"].append(tail)
            kept["ssm"].append(h)
        elif kind == "gmu":
            out = gmu(p["attn"], a, m)
        elif kind == "cross":
            out = diff_attention(conf, i, p["attn"], a, *kv)
        else:
            k, v = keys_values(conf, p["attn"], a)
            if kind == "full":
                kv = (k, v)
            T = a.shape[0]
            kept[kind].append(jnp.concatenate(
                [k.reshape(T, -1), v.reshape(T, -1)], axis=-1))
            out = diff_attention(conf, i, p["attn"], a, k, v,
                                 W if kind == "window" else None)
        x = x + out
        x = x + _mlp(p["mlp"], _ln(x, p["ln_mlp"], eps))
    kept = {key: jnp.stack(val) for key, val in kept.items()}
    return x, dict(kept, mem=m)


def hidden_states(conf, params, tokens):
    """tokens [B, T] -> what the head reads [B, T, d], float32."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _ln(walk(conf, params, row)[0], params["final_ln"],
                conf["layer_norm_eps"]) for row in tokens])


def states(conf, params, row, n):
    """What a cache of this architecture holds once rows [0, n) of `row`
    [T] went through it (`walk`'s second result): every Mamba layer's
    convolution rows and scan state after row n - 1, the full layer's and
    the window layers' [K | V] rows (those before n are the sequence's)."""
    with jax.default_matmul_precision("highest"):
        return walk(conf, params, row, n)[1]


def _head(params, hidden):
    """hidden [..., d] -> logits [..., V] by the tied embedding, a block
    of rows and an eighth of the vocabulary at a time."""
    wte = params["embed"]["wte"]
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


def logits_at(conf, params, tokens, positions):
    """Logits [B, P, V] at `positions` [B, P] only."""
    with jax.default_matmul_precision("highest"):
        hidden = hidden_states(conf, params, tokens)
        picked = jnp.take_along_axis(hidden, positions[:, :, None], axis=1)
        return _head(params, picked)


def num_params(conf):
    """Parameters of the configuration, by layer kind."""
    d, F, V = conf["hidden_size"], conf["intermediate_size"], \
        conf["vocab_size"]
    e = d // conf["num_attention_heads"]
    kv = conf["num_key_value_heads"] * e
    d_i, N, K, R = 2 * d, 16, 4, -(-d // 16)
    mlp_norms = 3 * d * F + 4 * d
    lam = 4 * e + 2 * e
    kinds = {
        "ssm": d * 2 * d_i + (K + 1) * d_i + d_i * (R + 2 * N) +
        (R + 1) * d_i + d_i * N + d_i + d_i * d,
        "gmu": 2 * d * d_i,
        "cross": 2 * (d * d + d) + lam,
        "full": 2 * (d * d + d) + 2 * (d * kv + kv) + lam,
    }
    kinds["window"] = kinds["full"]
    return sum(kinds[k] + mlp_norms for k in layer_kinds(conf)) + \
        V * d + 2 * d
