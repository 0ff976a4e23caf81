"""Plain reference for the SDAR-MoE architecture (`model_type: sdar_moe`;
JetLM's SDAR-30B-A3B-Chat: a block-diffusion language model over a
mixture of experts).

Straightforward `jax.numpy` in float32 at `highest` matmul precision: no
kernel, no cache, no sorting, and nothing imported from `deeperspeed_tpu`.
`rms(x; s) = s * x / sqrt(mean(x^2, -1) + eps)`; no bias anywhere; `B` the
block length.

    layer, input h [T, hidden]:
      a = rms(h; s1);  q = a Wq [T, H, d],  k = a Wk [T, G, d],  v = a Wv
      q = rms(q; sq), k = rms(k; sk)    over the d features of EACH head;
                                        sq, sk are [d], shared by the heads
      q, k = rotary(q), rotary(k)       rotate-half over all d features,
                                        inv_freq = theta^(-2i/d), absolute
                                        positions
      o_i = sum_j softmax_j(q_i . k_j / sqrt(d)) v_j
                over j with j // B <= i // B          (BLOCK-causal; KV
                                        head g serves query heads
                                        g H/G .. (g + 1) H/G - 1)
      h = h + o Wo
      m = rms(h; s2);  p = softmax(m Wr) over all experts, float32
      (w, e) = the num_experts_per_tok largest of p;  w = w / sum(w)
                                        (norm_topk_prob)
      h = h + sum_c w_c (silu(m Wg[e_c]) * (m Wu[e_c])) Wd[e_c]
                                        (width moe_intermediate_size; no
                                        shared expert; every layer)
    logits = rms(h_L; s_f) W_head^T     (untied)

    generation (greedy; one sequence, prompt of P tokens, at most N new):
      blocks are aligned to absolute multiples of B, so the first
      generated block holds the prompt's last P % B tokens, unmasked; the
      rest of it and every later block starts as MASK tokens. For each
      block from P // B on, pass after pass until no row of it is MASK:
        logits_r = the model on the whole prefix and the block's B rows
        t_r = argmax logits_r;  c_r = softmax(logits_r)[t_r]  (float32)
        unmask every masked row with c_r > threshold, and if those are
        fewer than n = B / steps, the n most confident masked rows (ties:
        the lower position); an unmasked row takes t_r and stays
      stop once N new tokens are final (or an eos token among them);
      tokens past N are dropped.

The reference has no cache, so it has no commit pass: `generate` runs the
whole prefix and the current block for every pass.

It reads the configuration under the public `config.json` keys, the facts
the public file has no key for as the dict `gen` its caller brings
(`benchmarks/families/sdar_moe.py::GENERATION`: `block`, `mask_token_id`,
`denoising_steps`, `confidence_threshold`), and the weights in the tree
the program keeps them in (the one thing the two must share), one stack
for all layers (`stacks["full<H>.experts"]`, every leaf with the leading
dim L):

    embed.wte [V, h]; embed_out.wte [V, h]; final_ln.scale [h];
    ln_attn.scale, ln_mlp.scale [L, h];
    attn.{q_w [L, h, H d], kv_w [L, h, 2 G d] ([K | V], each G heads of
          d), out_w [L, H d, h], q_norm [L, d], k_norm [L, d]};
    mlp.{gate [L, h, E] (the router Wr), w_in [L, E, h, 2 w] (each
         expert's [Wgate | Wup]), w_out [L, E, w, h] (Wdown)}

Departures from the published description, on purpose:
- Wk and Wv are one matrix [K | V] and an expert's gate and up projections
  one matrix [Wgate | Wup]: with random weights a layout is a convention.
- The experts are looped over (a scan), each applied to every token and
  weighted by the token's weight for it (zero where it was not chosen):
  the same sum as the published gather/scatter, and one expert's float32
  copy at a time fits beside the served model on the chip. The attention
  is computed a query head at a time for the same reason.
- Which rows of a block are masked is a flag a row, not `token == MASK`:
  a row whose argmax IS the mask token's id is final like any other (the
  published loop would denoise it again and, greedy, never end).
- `replay_stats` computes a request's prefix ONCE for all of its recorded
  passes: under the block-causal mask no row of the prefix sees the
  current block, so the prefix's keys and values are the same in every
  pass's forward, and a pass's block rows ride behind the finished
  sequence with a mask that shows them the earlier blocks and each other.
  Every row's arithmetic is that of the separate forward `generate` runs.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * \
        _f32(scale)


def _check(conf):
    if conf["hidden_act"] != "silu" or conf["attention_bias"] or \
            conf["rope_scaling"] is not None or \
            conf["use_sliding_window"] or conf["tie_word_embeddings"] or \
            conf["mlp_only_layers"] or conf["decoder_sparse_step"] != 1 or \
            not conf["norm_topk_prob"]:
        raise ValueError("this reference computes silu experts in every "
                         "layer with renormalised top-k weights, no "
                         "attention bias, plain rotary, full attention and "
                         "an untied head")


def stack(conf, params):
    return params["stacks"][f"full{conf['num_attention_heads']}.experts"]


def _rotary(conf, positions):
    """positions [T] -> cos, sin [T, d]."""
    d = conf["head_dim"]
    inv_freq = 1.0 / (conf["rope_theta"] **
                      (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = positions.astype(jnp.float32)[:, None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def _rotate(x, cos, sin):
    """x [T, heads, d]: rotate-half over the whole head dim."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos[:, None, :] + \
        jnp.concatenate([-x2, x1], axis=-1) * sin[:, None, :]


def block_causal(positions, block):
    """seen[i, j]: the row at `positions[i]` sees the row at
    `positions[j]`: j's block is not after i's."""
    b = positions // block
    return b[None, :] <= b[:, None]


def router(conf, gate, m):
    """m [T, h] -> weights [T, E]: a token's weight for each expert, zero
    where it was not chosen."""
    probs = jax.nn.softmax(m @ _f32(gate), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, conf["num_experts_per_tok"])
    top_p = top_p / top_p.sum(-1, keepdims=True)
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, top_e].set(top_p)


def _experts(conf, p, m, weights):
    """sum_e weights[:, e] * FFN_e(m), one expert at a time."""
    w = conf["moe_intermediate_size"]

    def one(acc, ew):
        w_in, w_out, w_e = ew
        h = m @ _f32(w_in)
        h = jax.nn.silu(h[:, :w]) * h[:, w:]
        return acc + w_e[:, None] * (h @ _f32(w_out)), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(m),
                          (p["mlp"]["w_in"], p["mlp"]["w_out"], weights.T))
    return acc


def layer(conf, p, x, cos, sin, seen):
    """One layer on rows x [T, h] under the mask `seen` [T, T] ->
    (x, [K | V] rows [T, 2 G d], K after the rotary)."""
    T, h = x.shape
    H, G, d = conf["num_attention_heads"], conf["num_key_value_heads"], \
        conf["head_dim"]
    eps = conf["rms_norm_eps"]
    a = _rms(x, p["ln_attn"]["scale"], eps)
    q = (a @ _f32(p["attn"]["q_w"])).reshape(T, H, d)
    kv = (a @ _f32(p["attn"]["kv_w"])).reshape(T, 2, G, d)
    k, v = kv[:, 0], kv[:, 1]
    q = _rms(q, p["attn"]["q_norm"], eps)
    k = _rms(k, p["attn"]["k_norm"], eps)
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)

    def head(i):
        g = i // (H // G)
        s = (q[:, i] @ k[:, g].T) / math.sqrt(d)
        s = jnp.where(seen, s, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ v[:, g]

    o = jax.lax.map(head, jnp.arange(H))                    # [H, T, d]
    x = x + jnp.moveaxis(o, 0, 1).reshape(T, H * d) @ _f32(p["attn"]["out_w"])
    m = _rms(x, p["ln_mlp"]["scale"], eps)
    x = x + _experts(conf, p, m, router(conf, p["mlp"]["gate"], m))
    return x, jnp.concatenate([k.reshape(T, -1), v.reshape(T, -1)], axis=-1)


def _layers(conf, params, x, positions, seen):
    """All layers -> (final-norm hidden [T, h], cache rows [L, T, 2 G d])."""
    _check(conf)
    cos, sin = _rotary(conf, positions)

    def one(x, p):
        return layer(conf, p, x, cos, sin, seen)

    x, rows = jax.lax.scan(one, x, stack(conf, params))
    return _rms(x, params["final_ln"]["scale"], conf["rms_norm_eps"]), rows


def embed(params, tokens):
    return _f32(params["embed"]["wte"][tokens])


def hidden_states(conf, params, tokens, block):
    """tokens [S] -> final-norm hidden states [S, h] under the
    block-causal mask."""
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        return _layers(conf, params, embed(params, tokens), pos,
                       block_causal(pos, block))[0]


def forward(conf, params, tokens, block):
    """tokens [S] -> logits [S, V], float32: the model's forward under the
    block-causal mask."""
    with jax.default_matmul_precision("highest"):
        return hidden_states(conf, params, tokens, block) @ \
            _f32(params["embed_out"]["wte"]).T


def cache_rows(conf, params, tokens, block):
    """tokens [S] -> what a cache of the finished sequence holds: [L, S,
    2 G d], a token's [K | V] of every layer, K after the rotary."""
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(tokens.shape[0])
        return _layers(conf, params, embed(params, tokens), pos,
                       block_causal(pos, block))[1]


def choose(conf_rows, masked, gen):
    """Which rows of a block a pass unmasks, from each row's confidence
    (numpy [B]) and which are masked: every masked row over the
    threshold, and never fewer than block / steps of the most confident
    masked rows (ties: the lower position)."""
    floor = max(1, gen["block"] // gen["denoising_steps"])
    conf_rows = np.where(masked, conf_rows, -np.inf)
    order = sorted(range(len(masked)), key=lambda r: (-conf_rows[r], r))
    return [r for rank, r in enumerate(order) if masked[r] and (
        conf_rows[r] > gen["confidence_threshold"] or rank < floor)]


def confidence(logits):
    """logits [R, V] -> (argmax [R], its softmax probability [R])."""
    lg = _f32(logits)
    return jnp.argmax(lg, -1), jnp.exp(
        lg.max(-1) - jax.scipy.special.logsumexp(lg, axis=-1))


def generate(conf, params, prompt, max_new_tokens, gen, eos_token_id=None):
    """Greedy block generation as the module docstring writes it. Returns
    (the new tokens, at most `max_new_tokens`; the passes: a list of
    (block's first position, rows unmasked in order of choice, their
    tokens))."""
    B, mask_id = gen["block"], gen["mask_token_id"]
    P = len(prompt)
    n_blocks = -(-(P + max_new_tokens) // B)
    x = list(prompt) + [mask_id] * (n_blocks * B - P)
    masked = [False] * P + [True] * (n_blocks * B - P)
    passes = []

    def final():
        """The new tokens that are final: the unmasked prefix past the
        prompt, up to N and an eos."""
        out = []
        for t, m in zip(x[P:P + max_new_tokens], masked[P:]):
            if m:
                break
            out.append(int(t))
            if t == eos_token_id:
                break
        return out

    def done(out):
        return len(out) >= max_new_tokens or \
            (out and out[-1] == eos_token_id)

    for b in range(P // B, n_blocks):
        lo = b * B
        while any(masked[lo:lo + B]) and not done(final()):
            lg = forward(conf, params, jnp.asarray(x[:lo + B], jnp.int32),
                         B)[lo:]
            best, c = (np.asarray(t) for t in confidence(lg))
            rows = choose(c, np.asarray(masked[lo:lo + B]), gen)
            for r in rows:
                x[lo + r], masked[lo + r] = int(best[r]), False
            passes.append((lo, rows, [int(best[r]) for r in rows]))
        if done(final()):
            break
    return final(), passes


def replay_stats(conf, params, tokens, n, starts, states, served, block,
                 head_rows=256):
    """One request's recorded passes against its finished sequence, in
    ONE forward (the module docstring says why the arithmetic is that of
    the separate forwards).

    `tokens` [W]: the finished sequence in its first `n` entries (prompt,
    then what was served), anything behind; `starts` [S]: a recorded
    pass's block's first position (-1: padding); `states` [S, block]: the
    block going IN, the mask token at its masked rows; `served` [S,
    block]: the token the engine put at each row (anything where it
    unmasked none). Returns a dict of [S, block] arrays: `best` (the
    reference's best logit at the row), `argmax`, `confidence` (of the
    argmax) and `served` (the reference's logit of the served token), and
    `cache` [L, W, 2 G d], the finished sequence's cache rows (rows at and
    past `n` are of the padding)."""
    W, (S, B) = tokens.shape[0], states.shape
    with jax.default_matmul_precision("highest"):
        pos = jnp.arange(W)
        state_pos = (jnp.maximum(starts, 0)[:, None] +
                     jnp.arange(B)[None, :]).reshape(-1)
        positions = jnp.concatenate([pos, state_pos])
        # the finished sequence among itself: block-causal, padding apart
        live = pos < n
        seq = block_causal(pos, block) & live[None, :]
        # a state row sees the finished blocks before its own, and the
        # rows of its own state (no row of the finished sequence sees it)
        sees_seq = (pos[None, :] // block < state_pos[:, None] // block) & \
            live[None, :]
        group = jnp.repeat(jnp.arange(S), B)
        own = group[:, None] == group[None, :]
        seen = jnp.concatenate([
            jnp.concatenate([seq, jnp.zeros((W, S * B), bool)], axis=1),
            jnp.concatenate([sees_seq, own], axis=1)], axis=0)
        x = jnp.concatenate([embed(params, tokens),
                             embed(params, states.reshape(-1))])
        hidden, rows = _layers(conf, params, x, positions, seen)
        head = _f32(params["embed_out"]["wte"])

        def stats(chunk):
            h, tok = chunk
            lg = h @ head.T
            best, c = confidence(lg)
            return (lg.max(-1), best, c,
                    jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0])

        n_rows = S * B
        pad = -n_rows % head_rows
        h = jnp.pad(hidden[W:], ((0, pad), (0, 0)))
        tok = jnp.pad(served.reshape(-1), (0, pad))
        out = jax.lax.map(stats, (h.reshape(-1, head_rows, h.shape[-1]),
                                  tok.reshape(-1, head_rows)))
        best, arg, c, got = (t.reshape(-1)[:n_rows].reshape(S, B)
                             for t in out)
    return {"best": best, "argmax": arg, "confidence": c, "served": got,
            "cache": rows[:, :W]}
