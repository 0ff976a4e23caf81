"""The shared references of the serving tests (`tests/model/references.py`):
a padded causal forward is the growing loop's forward to the last bits
of float32, and its greedy tokens are the growing loop's."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.models.gpt2 import GPT2, GPT2Config
from deeperspeed_tpu.models.gpt2 import forward as gpt2_forward
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.models.gpt_neox import forward as neox_forward
from tests.model.references import (MARGIN, jitted, model_rows,
                                    padded_length, padded_rows,
                                    teacher_forced)

MODELS = {"neox": (GPTNeoX, GPTNeoXConfig, neox_forward),
          "gpt2": (GPT2, GPT2Config, gpt2_forward)}


@pytest.fixture(scope="module", params=sorted(MODELS))
def tiny(request):
    model_cls, cfg_cls, forward_fn = MODELS[request.param]
    cfg = cfg_cls.tiny()
    params = model_cls(config=cfg, use_pallas=False).init_params(
        jax.random.PRNGKey(1))
    return cfg, params, forward_fn


@pytest.mark.parametrize("n", [5, 40])
def test_padded_rows_are_the_unpadded_forwards(tiny, n):
    """Not bit for bit: the eager forward, one jit at the same length and
    one jit at the padded length each differ from the others by 1-2e-7
    (fusion reorders float32 sums), two orders under `references.MARGIN`."""
    cfg, params, forward_fn = tiny
    toks = np.random.default_rng(n).integers(1, cfg.vocab_size, size=n)
    plain = forward_fn(cfg, params, jnp.asarray([toks], jnp.int32),
                       use_pallas=False)
    program = jitted(forward_fn, cfg, use_pallas=False)
    got = padded_rows(program, params, toks,
                      padded_length(n, cfg.max_seq_len))
    same_length = program(params, jnp.asarray([toks], jnp.int32))
    for other in (got, same_length[0]):
        np.testing.assert_allclose(np.asarray(other), np.asarray(plain[0]),
                                   rtol=0, atol=MARGIN / 10)


def test_teacher_forced_is_the_growing_loop(tiny):
    cfg, params, forward_fn = tiny
    prompt = list(np.random.default_rng(0).integers(1, cfg.vocab_size,
                                                    size=11))
    toks, want = list(prompt), []
    for _ in range(2):      # bare: a program an operation at each length
        logits = forward_fn(cfg, params, jnp.asarray([toks], jnp.int32),
                            use_pallas=False)
        want.append(int(jnp.argmax(logits[0, -1])))
        toks.append(want[-1])
    assert teacher_forced(cfg, params, forward_fn, prompt, 2) == want


def test_model_rows_are_the_models_apply():
    cfg = GPTNeoXConfig.tiny()
    model = GPTNeoX(config=cfg, use_pallas=False)
    params = model.init_params(jax.random.PRNGKey(1))
    toks = np.random.default_rng(3).integers(1, cfg.vocab_size, size=23)
    np.testing.assert_allclose(
        model_rows(model, params, toks),
        np.asarray(model.apply(params, jnp.asarray([toks], jnp.int32)))[0],
        rtol=0, atol=MARGIN / 10)


def test_padded_length_stays_inside_the_model():
    assert padded_length(5, 64) == 64 == padded_length(64, 64)
    assert padded_length(65, 128) == 128 == padded_length(65, 2048)
    assert padded_length(70, 100) == 100
