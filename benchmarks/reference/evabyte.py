"""Plain reference for the EvaByte architecture (`model_type: evabyte`; a
byte-level decoder whose attention is EVA, arXiv:2302.04542, as the public
`config.json` names it: `attention_class: "eva"`, `window_size`,
`chunk_size`).

Straightforward `jax.numpy` in float32 at `highest` matmul precision,
written from the public `config.json` (the model-configs catalog's row)
and the equations of ISSUE 50: no kernel, no cache, no page, no batching,
and nothing imported from `deeperspeed_tpu`. With h = `hidden_size`,
H = `num_attention_heads` heads of D = h / H, s = D ** -0.5,
W = `window_size`, C = `chunk_size`, eps = `rms_norm_eps`, and position t
in window j(t) = t // W and chunk c(t) = t // C:

    RMS(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)    (`norm_add_unit_offset`)
    every layer:  u = RMS(x; w1);  q, k, v = u Wq, u Wk, u Wv   (no bias)
                  q, k <- rotary over the whole head, theta `rope_theta`
    pooling of a whole chunk c (rows 16c .. 16c + 15, per head):
                  a_i = s (k_i . phi);  p = softmax_i(a)
                  K~_c = sum_i p_i k_i + mu;   V~_c = sum_i p_i v_i
    attention of query t: ONE softmax at scale s over the keys
                  {k_i : W j(t) <= i <= t}  and  {K~_c : c < (W / C) j(t)}
                  (every chunk of every EARLIER window; the current
                  window's own chunks are not read)
                  o_t = sum_i P_i v_i + sum_c P_c V~_c;   x' = x + o Wo
    then          y = x' + (silu(u' Wg) * (u' Wu)) Wd,  u' = RMS(x'; w2)
    logits = RMS(x_L; wf) Wh,  Wh [h, num_pred_heads * vocab], float32:
    head m (columns m * vocab ..) is the distribution of byte t + 1 + m.

ASSUMED (the public file has no key for them; the sandbox has no network,
nothing was read from the source; each is ONE place here and one in the
program, `benchmarks/families/evabyte.py` names it):

1. the pooling logits carry the scale s and read the keys AFTER the
   rotary (`pool`, and `layer`'s call of it).
2. a chunk's pooled row becomes visible when its WINDOW ends, not when its
   chunk ends (`attend`'s `seen`).
3. the rotary pairs a feature with the one D / 2 behind it (Llama's
   `rotate_half`; `rotary`).

`mu` added before or after the pooling is the same number (the weights sum
to 1): no assumption. DEPARTURES, on purpose: the program keeps its
residual stream in bfloat16 where the public `fp32_skip_add` says float32
(as every model served here; this reference is float32 throughout, so the
departure is inside the cell's tolerances); the eight heads' use in
self-speculative decoding is not built (every head's logits are computed
and compared, greedy decoding reads head 0).

It reads the weights in the tree the program keeps them in (the one thing
the two must share): ONE stack `eva<H>.dense`, every leaf with the layers
leading: `ln_attn.scale`, `ln_mlp.scale` [L, h] (w of 1 + w); `attn.q_w`
[L, h, H D], `attn.kv_w` [L, h, 2 H D] ([K | V], each H heads of D),
`attn.out_w` [L, H D, h], `attn.eva_phi`, `attn.eva_mu` [L, H, D]; `mlp.in_w`
[L, h, 2 F] ([gate | up]), `mlp.out_w` [L, F, h]; `embed.wte` [vocab, h],
`embed_out.wte` [num_pred_heads * vocab, h], `final_ln.scale` [h]. Wk and
Wv as one matrix and the MLP's gate and up as one are layout, not
mathematics.

A row is computed a WINDOW at a time (the attention of window j reads the
rows of window j and the pooled rows before it, the MLP its own rows), so
that a row of 20,480 bytes at the published widths fits beside a full
chip. A row whose length is no whole number of windows is padded behind:
by causality the padding moves nothing before it.
"""

import jax
import jax.numpy as jnp


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def stack_name(conf):
    return f"eva{conf['num_attention_heads']}.dense"


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * \
        (1.0 + _f32(w))


def rotary(x, positions, theta):
    """x [T, H, D] at `positions` [T]: feature i turns with feature
    i + D / 2 by the angle positions * theta ** (-2 i / D)."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def pool(k, v, phi, mu, scale, chunk):
    """k (after the rotary), v [T, H, D] -> (K~, V~) [T / C, H, D]."""
    T, H, D = k.shape
    kc, vc = k.reshape(-1, chunk, H, D), v.reshape(-1, chunk, H, D)
    a = scale * jnp.einsum("nchd,hd->nch", kc, _f32(phi))
    p = jax.nn.softmax(a, axis=1)[..., None]
    return (p * kc).sum(1) + _f32(mu), (p * vc).sum(1)


def attend(q, k, v, pk, pv, window, per_window, scale):
    """One window's queries q [W, H, D] over its own rows k, v [W, H, D]
    (causal) and the pooled rows pk, pv [N, H, D] of which the first
    `per_window * window` (a scalar: chunks of the EARLIER windows) are
    visible, one softmax. -> [W, H, D]."""
    Wn, N = q.shape[0], pk.shape[0]
    causal = jnp.arange(Wn)[None, :] <= jnp.arange(Wn)[:, None]
    seen = jnp.arange(N)[None, :] < per_window * window

    def head(t):
        qh, kh, vh, pkh, pvh = t
        s = jnp.concatenate(
            [jnp.where(seen, qh @ pkh.T * scale, -jnp.inf),
             jnp.where(causal, qh @ kh.T * scale, -jnp.inf)], axis=-1)
        P = jax.nn.softmax(s, axis=-1)
        return P[:, :N] @ pvh + P[:, N:] @ vh

    out = jax.lax.map(head, tuple(jnp.moveaxis(t, 1, 0)
                                  for t in (q, k, v, pk, pv)))
    return jnp.moveaxis(out, 0, 1)


def layer(conf, p, x):
    """x [T, h] (T whole windows) -> (y [T, h], the layer's rows [T, 2 H D]
    ([K after the rotary | V]), its pooled rows [T / C, 2 H D])."""
    T, h = x.shape
    H = conf["num_attention_heads"]
    D = h // H
    W, C = conf["window_size"], conf["chunk_size"]
    eps, scale = conf["rms_norm_eps"], D ** -0.5
    a = p["attn"]
    xw = x.reshape(-1, W, h)
    starts = jnp.arange(xw.shape[0]) * W

    def keys_values(t):
        rows, start = t
        u = _rms(rows, p["ln_attn"]["scale"], eps)
        kv = (u @ _f32(a["kv_w"])).reshape(W, 2, H, D)
        return rotary(kv[:, 0], start + jnp.arange(W),
                      conf["rope_theta"]), kv[:, 1]

    k, v = jax.lax.map(keys_values, (xw, starts))       # [nW, W, H, D]
    pk, pv = pool(k.reshape(T, H, D), v.reshape(T, H, D), a["eva_phi"],
                  a["eva_mu"], scale, C)

    def one_window(t):
        rows, kw, vw, j = t
        u = _rms(rows, p["ln_attn"]["scale"], eps)
        q = rotary((u @ _f32(a["q_w"])).reshape(W, H, D),
                   j * W + jnp.arange(W), conf["rope_theta"])
        o = attend(q, kw, vw, pk, pv, j, W // C, scale)
        rows = rows + o.reshape(W, H * D) @ _f32(a["out_w"])
        u = _rms(rows, p["ln_mlp"]["scale"], eps)
        mid = u @ _f32(p["mlp"]["in_w"])
        F = mid.shape[-1] // 2
        return rows + (jax.nn.silu(mid[:, :F]) * mid[:, F:]) @ \
            _f32(p["mlp"]["out_w"])

    y = jax.lax.map(one_window, (xw, k, v, jnp.arange(xw.shape[0])))
    return (y.reshape(T, h),
            jnp.concatenate([k.reshape(T, -1), v.reshape(T, -1)], -1),
            jnp.concatenate([pk.reshape(T // C, -1),
                             pv.reshape(T // C, -1)], -1))


def walk(conf, params, row, n=None):
    """One row of tokens [T] -> the last layer's hidden states [T', h],
    T' = T padded to whole windows; with `n` (a position, may be traced)
    also what a cache is made from once rows [0, n) went through:
    (hidden states, rows [L, W, 2 H D]: every layer's [K after the rotary
    | V] of the window that holds position n, of which the first n % W
    are the sequence's, pooled rows [L, T' / C, 2 H D]: every chunk's
    [K~ | V~])."""
    W = conf["window_size"]
    row = jnp.pad(row, (0, -row.shape[0] % W))
    stack = params["stacks"][stack_name(conf)]
    x = _f32(params["embed"]["wte"])[row]
    if n is None:
        return jax.lax.scan(
            lambda x, p: (layer(conf, p, x)[0], None), x, stack)[0]
    start = jnp.minimum(n // W * W, row.shape[0] - W)

    def body(x, p):
        y, rows, pooled = layer(conf, p, x)
        return y, (jax.lax.dynamic_slice_in_dim(rows, start, W), pooled)

    x, (rows, pooled) = jax.lax.scan(body, x, stack)
    return x, rows, pooled


def hidden_states(conf, params, tokens):
    """tokens [B, T] -> what the head reads [B, T, h], float32."""
    T = tokens.shape[1]
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _rms(walk(conf, params, row)[:T], params["final_ln"]["scale"],
                 conf["rms_norm_eps"]) for row in tokens])


def states(conf, params, row, n):
    """What a cache of this architecture holds once rows [0, n) of `row`
    [T] went through it: {"rows": [L, W, 2 H D], the exact rows of the
    window that holds position n (the first n % W are live), "pooled":
    [L, T' / C, 2 H D], of which the chunks under (W / C) (n // W) are
    VISIBLE and those from there to under n // C PENDING}."""
    with jax.default_matmul_precision("highest"):
        _, rows, pooled = walk(conf, params, row, n)
    return {"rows": rows, "pooled": pooled}


def _head(params, hidden):
    return hidden @ _f32(params["embed_out"]["wte"]).T


def logits(conf, params, tokens):
    """tokens [B, T] -> logits of every prediction head [B, T,
    num_pred_heads * vocab], float32."""
    with jax.default_matmul_precision("highest"):
        return _head(params, hidden_states(conf, params, tokens))


def all_heads_at(conf, params, tokens, positions):
    """Logits [B, P, num_pred_heads * vocab] at `positions` [B, P] only."""
    with jax.default_matmul_precision("highest"):
        hidden = hidden_states(conf, params, tokens)
        picked = jnp.take_along_axis(hidden, positions[:, :, None], axis=1)
        return _head(params, picked)


def logits_at(conf, params, tokens, positions):
    """HEAD 0's logits [B, P, vocab] at `positions`: the next byte's, what
    greedy decoding reads."""
    return all_heads_at(conf, params, tokens,
                        positions)[..., :conf["vocab_size"]]


def num_params(conf):
    h, F, V = conf["hidden_size"], conf["intermediate_size"], \
        conf["vocab_size"]
    per_layer = 4 * h * h + 3 * h * F + 2 * h + 2 * h   # phi, mu: H D = h
    return conf["num_hidden_layers"] * per_layer + V * h + \
        conf["num_pred_heads"] * V * h + h
