"""The plain references the serving tests hold an engine to.

One program a reference, not one a token (or one a request): a causal
forward runs under `jax.jit` at ONE padded length, and every position's
logits are read from the row a forward of that length would have ended on.
Rows `0..t-1` of a padded causal forward see nothing behind them. They are
the unpadded eager forward's rows to float32's last bits and not bit for
bit (2e-7 on the tiny NeoX and GPT-2, which is also what one `jax.jit` at
the SAME length differs from the eager forward by:
`tests/test_references.py`), so a greedy token is taken only where its
margin over the runner-up is far outside that.
"""

import jax
import jax.numpy as jnp
import numpy as np

# (fn, static arguments) -> fn under jit (jit keeps a program a shape)
_PROGRAMS = {}
# two programs of one forward differ by 2e-7 in a logit: a greedy token
# that wins by less than this is not one to hold an engine to
MARGIN = 1e-5


def jitted(fn, *static, **static_kw):
    """`fn(*static, ..., **static_kw)` under one `jax.jit` a `(fn, static)`:
    a forward called bare dispatches, and compiles, every operation on its
    own. The static values are configs, whose repr names every field."""
    key = (fn, repr(static), repr(static_kw))
    if key not in _PROGRAMS:
        _PROGRAMS[key] = jax.jit(
            lambda *args: fn(*static, *args, **static_kw))
    return _PROGRAMS[key]


def padded_length(n, limit):
    """The next multiple of 64 that holds `n` rows, inside `limit`."""
    return min(limit, -(-n // 64) * 64)


def padded_rows(program, params, tokens, length):
    """Rows `[len(tokens), ...]` of `program(params, tokens [1, length])`
    (a `jitted` forward), the tokens zero-padded behind."""
    padded = np.zeros((1, length), np.int32)
    padded[0, :len(tokens)] = tokens
    out = program(params, jnp.asarray(padded))
    return np.asarray(out[0, :len(tokens)])


def model_rows(model, params, tokens):
    """`model.apply` over one row of tokens: logits `[len(tokens), V]`."""
    return padded_rows(
        jitted(model.apply), params, tokens,
        padded_length(len(tokens), model.config.max_seq_len))


def teacher_forced(cfg, params, forward_fn, prompt, n, use_pallas=False):
    """`n` greedy tokens behind `prompt` by the model's full forward."""
    length = padded_length(len(prompt) + n, cfg.max_seq_len)
    forward = jitted(forward_fn, cfg, use_pallas=use_pallas)
    toks = list(prompt)
    for _ in range(n):
        row = padded_rows(forward, params, toks, length)[-1]
        nxt = int(row.argmax())
        assert row[nxt] - np.partition(row, -2)[-2] > MARGIN, (
            "a reference token decided inside two programs' rounding")
        toks.append(nxt)
    return toks[len(prompt):]


def reference_rows(reference, conf, params, tokens, limit):
    """`reference.logits(conf, params, .)` (a module of
    `benchmarks/reference/`) over one row of tokens: `[len(tokens), V]`."""
    return padded_rows(jitted(reference.logits, conf), params, tokens,
                       padded_length(len(tokens), limit))
