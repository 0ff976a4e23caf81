"""Plain reference for the OLMoE architecture (OLMoE-1B-7B).

Straightforward `jax.numpy` in float32 at `highest` matmul precision,
following `OlmoeForCausalLM` (Muennighoff et al. 2024, "OLMoE: Open
Mixture-of-Experts Language Models", and the public `config.json` the
code is written for): no kernel, no cache, no sorting, no batching
tricks, and nothing imported from `deeperspeed_tpu`.
`RMS(x; g) = x / sqrt(mean(x^2, -1) + eps) * g`; no bias anywhere.

    a = RMS(x; g_in);  q = a Wq, k = a Wk, v = a Wv
    q = RMS(q; g_q), k = RMS(k; g_k)     over all hidden_size features,
                                         BEFORE the split into heads
    heads; rotary over the whole head dim (rotate-half); causal
    softmax(q k^T / sqrt(d)) v;  x = x + (..) Wo
    m = RMS(x; g_post);  p = softmax(m Wr) over all experts
    (p_j, e_j) = the num_experts_per_tok largest of p, renormalised over
                 the kept ones only if norm_topk_prob
    x = x + sum_j p_j * (silu(m Wgate[e_j]) * (m Wup[e_j])) Wdown[e_j]
    logits = RMS(x_L; g_f) Whead^T
    loss = CE + router_aux_loss_coef * E * sum_e f_e P_e
        f_e: the share of all layers' (token, choice) pairs that chose e
        P_e: the mean router probability of e over all layers' tokens

It reads the configuration under the public `config.json` keys and the
weights in the tree the program keeps them in (the one thing the two must
share):

    embed.wte [V, h]; embed_out.wte [V, h]; final_ln.scale [h];
    blocks[i].ln_attn.scale, blocks[i].ln_mlp.scale [h];
    blocks[i].attn.{qkv_w [h, 3h], out_w [h, h],
                    q_norm.scale [h], k_norm.scale [h]};
    blocks[i].mlp.{gate [h, E] (the router Wr),
                   w_in [E, h, 2i] (each expert's [Wgate | Wup]),
                   w_out [E, i, h] (Wdown)}

Departures from the published code, on purpose:
- Wq, Wk, Wv are one fused matrix laid out per head as [q | k | v]
  (GPT-NeoX's layout, which the program's block shares with Pythia), and
  an expert's gate and up projections are one matrix [Wgate | Wup]. With
  random weights a layout is a convention; q's features are in head-major
  order either way, so g_q and g_k index them as published.
- The experts are looped over, each applied to every token and weighted
  by the token's weight for it (zero where it was not chosen): the same
  sum as the published gather/scatter, and it fits beside the engine on
  the chip (all 64 experts of all tokens at once would be 1.6 GB of
  float32 a layer at 1,600 tokens).
- The published load-balancing loss sums over the k choice slots, which
  is k times `E * sum_e f_e P_e` with f_e the share of PAIRS; the
  equation above is the one this repository was given, and the constant
  k is the coefficient's.
- The router's logits are float32 here, as everything; the published code
  takes the router matmul in the model's type and the softmax in float32.

Also here, because they are arithmetic about this architecture and part of
the yardstick: the model flops per trained token, on ACTIVE parameters.
"""

import math

import jax
import jax.numpy as jnp

IGNORE_INDEX = -100


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _rms(x, p, eps):
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * \
        _f32(p["scale"])


def _rotary(conf, seq_len):
    head = conf["hidden_size"] // conf["num_attention_heads"]
    inv_freq = 1.0 / (conf["rope_theta"] **
                      (jnp.arange(0, head, 2, dtype=jnp.float32) / head))
    freqs = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)           # [S, head]
    return jnp.cos(emb), jnp.sin(emb)


def _rotate(x, cos, sin):
    """x [B, S, H, D]: rotate-half over the whole head dim."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    half = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos[None, :, None, :] + half * sin[None, :, None, :]


def router(conf, p, m):
    """m [T, h] -> (probabilities [T, E], weights [T, E]: a token's
    weight for each expert, zero where it was not chosen)."""
    probs = jax.nn.softmax(m @ _f32(p["mlp"]["gate"]), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, conf["num_experts_per_tok"])
    if conf["norm_topk_prob"]:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    rows = jnp.arange(m.shape[0])[:, None]
    return probs, jnp.zeros_like(probs).at[rows, top_e].set(top_p)


def _experts(conf, p, m, weights):
    """sum_e weights[:, e] * FFN_e(m), one expert at a time."""
    inter = conf["intermediate_size"]

    def one(acc, ew):
        w_in, w_out, w_e = ew
        h = m @ _f32(w_in)
        h = jax.nn.silu(h[:, :inter]) * h[:, inter:]
        return acc + w_e[:, None] * (h @ _f32(w_out)), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(m),
                          (p["mlp"]["w_in"], p["mlp"]["w_out"], weights.T))
    return acc


def _block(conf, p, x, cos, sin):
    """-> (x, router probabilities [B*S, E], chosen mask [B*S, E])."""
    B, S, h = x.shape
    nh = conf["num_attention_heads"]
    hd = h // nh
    eps = conf["rms_norm_eps"]
    a = _rms(x, p["ln_attn"], eps)
    qkv = (a @ _f32(p["attn"]["qkv_w"])).reshape(B, S, nh, 3 * hd)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = _rms(q.reshape(B, S, h), p["attn"]["q_norm"], eps).reshape(q.shape)
    k = _rms(k.reshape(B, S, h), p["attn"]["k_norm"], eps).reshape(k.shape)
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(B, S, h) @ _f32(p["attn"]["out_w"])
    m = _rms(x, p["ln_mlp"], eps).reshape(B * S, h)
    probs, weights = router(conf, p, m)
    x = x + _experts(conf, p, m, weights).reshape(B, S, h)
    return x, probs, weights > 0


def _forward(conf, params, tokens):
    """-> (final-norm hidden [B, S, h], [(probs, chosen)] per layer)."""
    if conf["hidden_act"] != "silu" or conf.get("attention_bias") or \
            conf.get("clip_qkv") is not None or \
            conf.get("rope_scaling") is not None:
        raise ValueError("this reference computes silu experts, no "
                         "attention bias, no qkv clipping and plain "
                         "rotary, with one KV head a query head (the "
                         "published num_key_value_heads, 16 of 16)")
    x = _f32(params["embed"]["wte"][tokens])
    cos, sin = _rotary(conf, tokens.shape[1])
    routed = []
    for p in params["blocks"]:
        x, probs, chosen = _block(conf, p, x, cos, sin)
        routed.append((probs, chosen))
    return _rms(x, params["final_ln"], conf["rms_norm_eps"]), routed


def hidden_states(conf, params, tokens):
    """tokens [B, S] -> final-norm hidden states [B, S, h], float32."""
    with jax.default_matmul_precision("highest"):
        return _forward(conf, params, tokens)[0]


def _head(params):
    return _f32(params["embed_out"]["wte"])


def logits(conf, params, tokens):
    """tokens [B, S] -> logits [B, S, V], float32."""
    with jax.default_matmul_precision("highest"):
        return hidden_states(conf, params, tokens) @ _head(params).T


def logits_at(conf, params, tokens, positions):
    """Logits [B, T, V] at `positions` [B, T] only (the head is the large
    part at a 50k vocabulary)."""
    with jax.default_matmul_precision("highest"):
        hidden = hidden_states(conf, params, tokens)
        picked = jnp.take_along_axis(hidden, positions[:, :, None], axis=1)
        return picked @ _head(params).T


def aux_loss(conf, routed):
    """E * sum_e f_e P_e over all layers' routed tokens."""
    probs = jnp.concatenate([p for p, _ in routed])          # [L*T, E]
    chosen = jnp.concatenate([c for _, c in routed])
    f = chosen.sum(0) / chosen.sum()
    return conf["num_experts"] * (f * probs.mean(0)).sum()


def loss(conf, params, tokens, labels):
    """Mean next-token cross entropy over the targets that are not
    `IGNORE_INDEX` (`labels` [B, S] is shifted here, as the program's
    loss does: position t predicts labels[t + 1]), plus
    `router_aux_loss_coef` times the load-balancing loss."""
    with jax.default_matmul_precision("highest"):
        hidden, routed = _forward(conf, params, tokens)
        lg = (hidden @ _head(params).T)[:, :-1]
        targets = labels[:, 1:]
        valid = targets != IGNORE_INDEX
        logp = jax.nn.log_softmax(lg, axis=-1)
        picked = jnp.take_along_axis(
            logp, jnp.where(valid, targets, 0)[..., None], axis=-1)[..., 0]
        ce = -(picked * valid).sum() / jnp.maximum(valid.sum(), 1)
        return ce + conf["router_aux_loss_coef"] * aux_loss(conf, routed)


def matmul_params(conf):
    """ACTIVE parameters that are matmul operands, for one token: the
    attention's four matrices, the router, num_experts_per_tok experts
    of three matrices each, and the output head. The input embedding is
    a gather and the norms are not matmuls."""
    h, i = conf["hidden_size"], conf["intermediate_size"]
    per_layer = 4 * h * h + h * conf["num_experts"] + \
        conf["num_experts_per_tok"] * 3 * h * i
    return conf["num_hidden_layers"] * per_layer + conf["vocab_size"] * h


def train_flops_per_token(conf, seq_len):
    """Model flops one trained token needs, forward and backward: six per
    active matmul parameter, and causal attention's two matmuls (6*L*h*s,
    as `reference/gpt_neox.py` counts them). Recomputation does not
    count."""
    return 6 * matmul_params(conf) + \
        6 * conf["num_hidden_layers"] * conf["hidden_size"] * seq_len
