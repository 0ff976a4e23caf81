"""Plain reference for the Ouro architecture (ByteDance's looped language
models; `model_type: ouro`, Ouro-1.4B / Ouro-2.6B).

Straightforward `jax.numpy` in float32 at `highest` matmul precision,
written from the public `config.json` (the model-configs catalog's row)
and the facts the configuration file lists under `assumed`: no kernel,
no cache, and nothing imported from `deeperspeed_tpu`. `rms(x; s) = s * x / sqrt(mean(x^2) + eps)`; H heads
of d = `head_dim`; no bias on any projection; T = `total_ut_steps`.

    layer l, pass t, input h:
      a = rms(h; s1_l);  q, k, v = a Wq_l, a Wk_l, a Wv_l
      q, k = rotary(q), rotary(k)   rotate-half over all d features,
                                    inv_freq = theta^(-2i/d)
      o = softmax(q k^T / sqrt(d), causal) v      keys and values: pass
                                    t's OWN rows of layer l
      h = h + rms(o Wo_l; s2_l)
      m = rms(h; s3_l);  h = h + rms((silu(m Wg_l) * (m Wu_l)) Wd_l; s4_l)
    model:
      z_0 = E[tokens]
      z_t = rms(layer_L(... layer_1(z_{t-1}) ...); s_f),  t = 1..T: the
            SAME weights every pass, the final norm after EVERY pass, its
            output the next pass's input
      g_t = sigmoid(z_t . w_e + b_e)
      p_t = g_t prod_{j<t}(1 - g_j) for t < T, p_T = prod_{j<T}(1 - g_j)
      c_t = p_1 + ... + p_t;  t* = the first t < T with
            c_t >= early_exit_threshold, else T
      logits = z_{t*} W_head^T

What a cache of this architecture holds is every pass's K and V of every
layer: pass t of layer l at cache layer (t - 1) L + l (`cache_rows`).

It reads the weights in the tree the program keeps them in (the one thing
the two must share), ONE stack of the L layers (kind `full<H>.dense`),
every leaf with the layers leading:

    embed.wte [V, h]; embed_out.wte [V, h]; final_ln.scale [h];
    loop_exit.w [h], loop_exit.b [1];
    stacks[kind].ln_attn.scale, .ln_attn_out.scale, .ln_mlp.scale,
        .ln_mlp_out.scale [L, h]   (s1, s2, s3, s4);
    stacks[kind].attn.{q_w [L, h, H*d], kv_w [L, h, 2*H*d] ([K | V]),
        out_w [L, H*d, h]};
    stacks[kind].mlp.{in_w [L, h, 2i] ([Wg | Wu]), out_w [L, i, h]}

Departures from the published layout, on purpose (with random weights a
layout is a convention): K and V projections are one matrix [Wk | Wv],
the MLP's gate and up one matrix [Wg | Wu]. Attention runs a block of
queries at a time against every key, and a layer's bf16 weights are
widened to float32 when it is that layer's turn (a scan over the stack's
layers inside a Python loop over the passes: 103 MB a layer, never the
9.9 GB of the whole stack), the head's an eighth of the vocabulary at a
time: the same sums, so that the reference fits beside the served state
on the chip. `one_pass` is a pass alone, for a caller that holds one
pass's rows at a time (`drivers/closed_loop_kv_probed.py`: 192 cache
layers of a 640-token row are 2 GB in float32).
"""

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 128


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * \
        _f32(scale)


def stack_of(conf, params):
    return params["stacks"][f"full{conf['num_attention_heads']}.dense"]


def _rotate(x, theta):
    """x [S, H, d]: rotate-half over all d features at positions 0..S-1."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(emb) + jnp.concatenate([-x2, x1], -1) * jnp.sin(emb)


def _block(n, most):
    return max(b for b in range(1, min(n, most) + 1) if n % b == 0)


def _attention(q, k, v):
    """q, k, v [S, H, d] -> [S, H, d], causal, a block of queries at a
    time against every key."""
    S, H, d = q.shape
    blk = _block(S, QUERY_BLOCK)
    keys = jnp.arange(S)[None, :]

    def one(args):
        qb, first = args
        seen = keys <= first + jnp.arange(blk)[:, None]
        s = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(one, (q.reshape(S // blk, blk, H, d),
                            jnp.arange(0, S, blk)))
    return out.reshape(S, H, d)


def layer(conf, p, x):
    """One layer on x [S, h] with that layer's leaves `p` -> (x, its K
    rows [S, H, d] after the rotary, its V rows [S, H, d])."""
    S = x.shape[0]
    H, eps = conf["num_attention_heads"], conf["rms_norm_eps"]
    a = _rms(x, p["ln_attn"]["scale"], eps)
    q = (a @ _f32(p["attn"]["q_w"])).reshape(S, H, -1)
    kv = (a @ _f32(p["attn"]["kv_w"])).reshape(S, 2, H, -1)
    q = _rotate(q, conf["rope_theta"])
    k, v = _rotate(kv[:, 0], conf["rope_theta"]), kv[:, 1]
    o = _attention(q, k, v).reshape(S, -1) @ _f32(p["attn"]["out_w"])
    x = x + _rms(o, p["ln_attn_out"]["scale"], eps)
    m = _rms(x, p["ln_mlp"]["scale"], eps)
    g = m @ _f32(p["mlp"]["in_w"])
    inter = g.shape[-1] // 2
    y = (jax.nn.silu(g[:, :inter]) * g[:, inter:]) @ _f32(p["mlp"]["out_w"])
    return x + _rms(y, p["ln_mlp_out"]["scale"], eps), k, v


def one_pass(conf, params, x):
    """The L layers once on x [S, h] and the final norm -> (z [S, h], the
    pass's K and V rows [L, S, 2 * H * d], a token's [K | V]). A layer's
    weights are sliced out of the stack and widened when it is that
    layer's turn."""
    S = x.shape[0]

    def step(x, p):
        x, k, v = layer(conf, p, x)
        return x, jnp.concatenate([k.reshape(S, -1), v.reshape(S, -1)],
                                  axis=-1)

    x, rows = jax.lax.scan(step, x, stack_of(conf, params))
    return _rms(x, params["final_ln"]["scale"], conf["rms_norm_eps"]), rows


def embed(params, row):
    return _f32(params["embed"]["wte"][row])


def passes(conf, params, row, with_rows=False):
    """One row of tokens [S] -> every pass's final-norm hidden states
    [T, S, h] (and with `with_rows` the K and V rows of every pass and
    layer, [T * L, S, 2 * H * d])."""
    x, out, rows = embed(params, row), [], []
    for _ in range(conf["total_ut_steps"]):
        x, kv = one_pass(conf, params, x)
        out.append(x)
        rows.append(kv)
    return (jnp.stack(out), jnp.concatenate(rows)) if with_rows \
        else jnp.stack(out)


def exit_pass(conf, params, z):
    """Every pass's hidden states z [T, S, h] -> t* [S], from 1: the
    first pass t < T whose cumulative exit probability reaches
    `early_exit_threshold`, else T."""
    gate = params["loop_exit"]
    T = z.shape[0]
    t_star = jnp.full(z.shape[1:-1], T, jnp.int32)
    stay = jnp.ones(z.shape[1:-1], jnp.float32)      # prod_{j<t}(1 - g_j)
    cum = jnp.zeros(z.shape[1:-1], jnp.float32)
    for t in range(1, T):
        g = jax.nn.sigmoid(z[t - 1] @ _f32(gate["w"]) + _f32(gate["b"])[0])
        cum = cum + g * stay
        stay = stay * (1.0 - g)
        t_star = jnp.where((cum >= conf["early_exit_threshold"]) &
                           (t_star == T), t, t_star)
    return t_star


def hidden_states(conf, params, tokens):
    """tokens [B, S] -> the hidden states the head reads [B, S, h]
    (every token's z_{t*}), float32."""
    with jax.default_matmul_precision("highest"):
        out = []
        for row in tokens:
            z = passes(conf, params, row)
            t_star = exit_pass(conf, params, z)
            out.append(jnp.take_along_axis(
                z, (t_star - 1)[None, :, None], axis=0)[0])
        return jnp.stack(out)


def exit_passes(conf, params, tokens):
    """tokens [B, S] -> t* [B, S], from 1."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([exit_pass(conf, params, passes(conf, params, row))
                          for row in tokens])


def cache_rows(conf, params, row):
    """One row of tokens [S] -> [T * L, S, 2 * H * d], float32: every
    pass's and layer's [K | V] of every token, K after the rotary, pass t
    of layer l at index (t - 1) L + l: what a cache of this architecture
    holds (the comparison of `closed_loop_kv_probed`)."""
    with jax.default_matmul_precision("highest"):
        return passes(conf, params, row, with_rows=True)[1]


def _head(params, hidden):
    """hidden [..., h] -> logits [..., V], the head widened an eighth of
    the vocabulary at a time."""
    wte = params["embed_out"]["wte"]
    V = wte.shape[0]
    parts = 8 if V % 8 == 0 else 1
    out = jax.lax.map(lambda w: hidden @ _f32(w).T,
                      wte.reshape(parts, V // parts, -1))
    return jnp.moveaxis(out, 0, -2).reshape(*hidden.shape[:-1], V)


def logits(conf, params, tokens):
    """tokens [B, S] -> logits [B, S, V], float32."""
    with jax.default_matmul_precision("highest"):
        return _head(params, hidden_states(conf, params, tokens))


def logits_at(conf, params, tokens, positions):
    """Logits [B, T, V] at `positions` [B, T] only."""
    with jax.default_matmul_precision("highest"):
        hidden = hidden_states(conf, params, tokens)
        picked = jnp.take_along_axis(hidden, positions[:, :, None], axis=1)
        return _head(params, picked)


def num_params(conf):
    """Parameters of the configuration: the L layers ONCE whatever the
    passes (four projections, the gated MLP's three, four norms), the
    embedding and the head, the final norm, the exit gate and its bias."""
    h, d = conf["hidden_size"], conf["head_dim"]
    H, G = conf["num_attention_heads"], conf["num_key_value_heads"]
    one = 2 * h * H * d + 2 * h * G * d + 3 * h * conf["intermediate_size"] \
        + 4 * h
    return conf["num_hidden_layers"] * one + 2 * conf["vocab_size"] * h + \
        h + h + 1
