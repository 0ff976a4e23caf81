"""Plain reference for the GPT-NeoX architecture (Pythia, GPT-NeoX-20B).

Straightforward `jax.numpy` in float32 at `highest` matmul precision,
following the published model (Black et al. 2022, "GPT-NeoX-20B", and the
`GPTNeoXForCausalLM` code the public `config.json` files are written for):
no kernel, no cache, no batching tricks, and nothing imported from
`deeperspeed_tpu`. It reads the configuration under the public
`config.json` keys and the weights in the tree the program keeps them in
(the one thing the two must share):

    embed.wte [V, h]; embed_out.wte [V, h] (absent when tied);
    final_ln.{scale,bias}; blocks[i].ln_attn / ln_mlp .{scale,bias};
    blocks[i].attn.{qkv_w [h, 3h], qkv_b, out_w [h, h], out_b};
    blocks[i].mlp.{in_w [h, i], in_b, out_w [i, h], out_b}

with the fused QKV laid out per head as [q | k | v] (GPT-NeoX's own
layout). Departure from the program, on purpose: `hidden_act: "gelu"` is
the exact erf form here, as published; the program uses the tanh form.

Also here, because they are arithmetic about this architecture and part of
the yardstick: the model flops per trained token.
"""

import functools
import math

import jax
import jax.numpy as jnp

IGNORE_INDEX = -100
_ACTIVATIONS = {
    "gelu": functools.partial(jax.nn.gelu, approximate=False),
    "gelu_new": functools.partial(jax.nn.gelu, approximate=True),
    "gelu_fast": functools.partial(jax.nn.gelu, approximate=True),
    "relu": jax.nn.relu,
}


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * _f32(p["scale"]) + \
        _f32(p["bias"])


def _rotary(conf, seq_len):
    head = conf["hidden_size"] // conf["num_attention_heads"]
    rot = int(head * conf["rotary_pct"])
    rot -= rot % 2
    inv_freq = 1.0 / (conf["rotary_emb_base"] **
                      (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    freqs = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)           # [S, rot]
    return jnp.cos(emb), jnp.sin(emb), rot


def _rotate(x, cos, sin, rot):
    """x [B, S, H, D]: rotate the first `rot` dims of every head."""
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(xr, 2, axis=-1)
    half = jnp.concatenate([-x2, x1], axis=-1)
    xr = xr * cos[None, :, None, :] + half * sin[None, :, None, :]
    return jnp.concatenate([xr, xp], axis=-1)


def _block(conf, p, x, cos, sin, rot):
    B, S, h = x.shape
    nh = conf["num_attention_heads"]
    hd = h // nh
    eps = conf["layer_norm_eps"]
    a = _layer_norm(x, p["ln_attn"], eps)
    qkv = a @ _f32(p["attn"]["qkv_w"]) + _f32(p["attn"]["qkv_b"])
    q, k, v = jnp.split(qkv.reshape(B, S, nh, 3 * hd), 3, axis=-1)
    q, k = _rotate(q, cos, sin, rot), _rotate(k, cos, sin, rot)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, S, h)
    attn = attn @ _f32(p["attn"]["out_w"]) + _f32(p["attn"]["out_b"])
    m_in = x if conf["use_parallel_residual"] else x + attn
    m = _layer_norm(m_in, p["ln_mlp"], eps)
    m = m @ _f32(p["mlp"]["in_w"]) + _f32(p["mlp"]["in_b"])
    m = _ACTIVATIONS[conf["hidden_act"]](m)
    m = m @ _f32(p["mlp"]["out_w"]) + _f32(p["mlp"]["out_b"])
    return m_in + m + attn if conf["use_parallel_residual"] else m_in + m


def hidden_states(conf, params, tokens):
    """tokens [B, S] -> final-norm hidden states [B, S, h], float32."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"]["wte"][tokens])
        cos, sin, rot = _rotary(conf, tokens.shape[1])
        for p in params["blocks"]:
            x = _block(conf, p, x, cos, sin, rot)
        return _layer_norm(x, params["final_ln"], conf["layer_norm_eps"])


def _head(params):
    return _f32(params.get("embed_out", params["embed"])["wte"])


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


def loss(conf, params, tokens, labels):
    """Mean next-token cross entropy over the targets that are not
    `IGNORE_INDEX`; `labels` [B, S] is shifted here, as the program's
    loss does (position t predicts labels[t + 1])."""
    lg = logits(conf, params, tokens)[:, :-1]
    targets = labels[:, 1:]
    valid = targets != IGNORE_INDEX
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.where(valid, targets, 0)[..., None], axis=-1)[..., 0]
    return -(picked * valid).sum() / jnp.maximum(valid.sum(), 1)


def matmul_params(conf):
    """Parameters that are matmul operands: the blocks' four weight
    matrices and the output head. The input embedding is a gather and
    the biases and norms are not matmuls."""
    h, i = conf["hidden_size"], conf["intermediate_size"]
    return conf["num_hidden_layers"] * (4 * h * h + 2 * h * i) + \
        conf["vocab_size"] * h


def train_flops_per_token(conf, seq_len):
    """Model flops one trained token needs, forward and backward: six per
    matmul parameter, and causal attention's two matmuls (QK^T and PV:
    4*s*h forward per layer if dense, half of that causal, times three
    for forward plus backward = 6*L*h*s). Recomputation does not count.
    With intermediate = 4h this is 6*(12*L*h^2 + V*h) + 6*L*h*s."""
    return 6 * matmul_params(conf) + \
        6 * conf["num_hidden_layers"] * conf["hidden_size"] * seq_len
