"""The CE head (`fused_lm_head_loss`): a `custom_vjp` whose forward rule
takes the loss AND both gradients from the one logits tile a chunk holds,
and whose backward rule only scales them.

Held against `lm_loss(einsum(x, wte))` differentiated plainly, in float32
and bfloat16: the padded tail, ignored rows, an all-ignored batch, tied
weights (GPT-2's call), an upstream scale (a float16 loss scale), the
call under `jax.checkpoint` and under `shard_map` with a `pmean`, what
the differentiated jaxpr holds, and the counter
(`ops.dispatch_report()["ce_head"]`).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import deeperspeed_tpu
from deeperspeed_tpu.compat import shard_map
from deeperspeed_tpu.models.gpt_neox import (GPTNeoX, GPTNeoXConfig,
                                             fused_lm_head_loss, lm_loss)
from deeperspeed_tpu.ops import dispatch_report

B, S, H, V = 3, 17, 32, 97
DTYPES = [jnp.float32, jnp.bfloat16]
# share of a gradient's largest entry the head may differ by from the
# plain gradient: float32 differs by summation order; in bfloat16 the
# head rounds `softmax - onehot` to the operands' dtype before its
# matmuls and `dx` once more where the scale meets it
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2, jnp.float16: 4e-3}


def inputs(dtype, seed=0, ignored=()):
    kx, kw, kl = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (B, S, H), dtype)
    wte = (0.3 * jax.random.normal(kw, (V, H))).astype(dtype)
    labels = jax.random.randint(kl, (B, S), 0, V)
    for b, s in ignored:
        labels = labels.at[b, s].set(-100)
    return x, wte, labels


def plain(x, wte, labels):
    logits = jnp.einsum("bsh,vh->bsv", x, wte,
                        preferred_element_type=jnp.float32)
    return lm_loss(logits, labels)


def close(got, want, dtype):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    scale = max(np.abs(want).max(), 1e-30)
    assert np.abs(got - want).max() <= TOL[dtype] * scale, \
        (np.abs(got - want).max(), scale)


# chunk_rows against the 48 rows of B x (S - 1): whole chunks, a padded
# tail, one chunk larger than the batch (the default at a tiny model)
@pytest.mark.parametrize("chunk_rows", [16, 20, 4096])
@pytest.mark.parametrize("ignored", [(), ((1, 5), (2, 16), (0, 1))],
                         ids=["all_valid", "ignored_rows"])
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_loss_and_both_gradients_match_the_plain_head(dtype, ignored,
                                                      chunk_rows):
    x, wte, labels = inputs(dtype, ignored=ignored)

    def head(x, wte):
        return fused_lm_head_loss(x, wte, labels, chunk_rows=chunk_rows)

    want, (want_dx, want_dw) = jax.value_and_grad(
        lambda x, w: plain(x, w, labels), (0, 1))(x, wte)
    got, (dx, dw) = jax.value_and_grad(head, (0, 1))(x, wte)
    assert dx.dtype == x.dtype and dw.dtype == wte.dtype
    np.testing.assert_allclose(got, want, rtol=2e-6 if dtype == jnp.float32
                               else 1e-3)
    # undifferentiated, the primal computes the same loss
    np.testing.assert_allclose(head(x, wte), got, rtol=1e-6)
    close(dx, want_dx, dtype)
    close(dw, want_dw, dtype)
    # the last position predicts nothing
    assert not np.asarray(dx, np.float32)[:, -1].any()
    for b, s in ignored:
        if s:
            assert not np.asarray(dx, np.float32)[b, s - 1].any()


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_an_all_ignored_batch_gives_zero_gradients(dtype):
    x, wte, _ = inputs(dtype)
    labels = jnp.full((B, S), -100)
    loss, (dx, dw) = jax.value_and_grad(
        lambda x, w: fused_lm_head_loss(x, w, labels, chunk_rows=16),
        (0, 1))(x, wte)
    assert float(loss) == 0.0
    assert not np.asarray(dx, np.float32).any()
    assert not np.asarray(dw, np.float32).any()


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_tied_weights_add_the_heads_gradient_to_the_embeddings(dtype):
    """GPT-2's call: `wte` is the embedding AND the head."""
    _, wte, labels = inputs(dtype)
    tokens = jnp.clip(labels, 0)

    def tied(head):
        return lambda wte: head(jnp.tanh(wte[tokens]), wte, labels)

    want = jax.grad(tied(plain))(wte)
    got = jax.grad(tied(lambda x, w, l: fused_lm_head_loss(
        x, w, l, chunk_rows=16)))(wte)
    close(got, want, dtype)


@pytest.mark.parametrize("dtype", DTYPES + [jnp.float16],
                         ids=lambda d: d.__name__)
def test_an_upstream_scale_meets_the_sums_after_the_matmuls(dtype):
    """A loss scale of 2**15: the gradients are the unscaled ones times
    the scale, finite in float16, where a scale multiplied into the tile
    before a float16 matmul could overflow and one left out underflow."""
    x, wte, labels = inputs(dtype)
    scale = 2.0 ** 15

    def head(x, w):
        return fused_lm_head_loss(x, w, labels, chunk_rows=16)

    want = jax.grad(lambda x, w: plain(x, w, labels), (0, 1))(
        x.astype(jnp.float32), wte.astype(jnp.float32))
    got = jax.grad(lambda x, w: head(x, w) * scale, (0, 1))(x, wte)
    for g, w in zip(got, want):
        assert g.dtype == dtype
        close(g, w * scale, jnp.bfloat16 if dtype == jnp.bfloat16
              else jnp.float16)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_under_checkpoint_the_forward_rule_reruns(dtype):
    x, wte, labels = inputs(dtype)

    def head(x, w):
        return fused_lm_head_loss(x, w, labels, chunk_rows=16)

    want = jax.grad(head, (0, 1))(x, wte)
    got = jax.grad(jax.checkpoint(head), (0, 1))(x, wte)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_under_shard_map_with_a_pmean(dtype, devices):
    """The explicit ZeRO-3 schedule's head: each rank's rows against the
    whole `wte`, the loss a `pmean` (every rank holds as many valid rows
    here, so the mean of means is the batch's mean)."""
    kx, kw, kl = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(kx, (4, S, H), dtype)
    wte = (0.3 * jax.random.normal(kw, (V, H))).astype(dtype)
    labels = jax.random.randint(kl, (4, S), 0, V)
    mesh = Mesh(np.asarray(devices[:2]), ("data",))

    def local(x, wte, labels):
        loss, (dx, dw) = jax.value_and_grad(
            lambda x, w: fused_lm_head_loss(x, w, labels, chunk_rows=16),
            (0, 1))(x, wte)
        return (jax.lax.pmean(loss, "data"), dx / 2,
                jax.lax.pmean(dw, "data"))

    # as the schedule maps it: gradients taken inside, `check_vma` off
    mapped = jax.jit(shard_map(
        local, mesh=mesh, in_specs=(P("data"), P(), P("data")),
        out_specs=(P(), P("data"), P()), check_vma=False))
    want, want_grads = jax.value_and_grad(
        lambda x, w: plain(x, w, labels), (0, 1))(x, wte)
    got, *grads = mapped(x, wte, labels)
    np.testing.assert_allclose(got, want, rtol=2e-6 if dtype == jnp.float32
                               else 1e-3)
    for g, w in zip(grads, want_grads):
        close(g, w, dtype)


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_the_differentiated_head_recomputes_and_scatters_nothing(dtype):
    """No `checkpoint` / `remat` equation and no `scatter-add` under the
    head, and three matmuls over the vocabulary a chunk: the tile, `dx`
    and `dW`. Undifferentiated: one."""
    x, wte, labels = inputs(dtype)

    def head(x, w):
        return fused_lm_head_loss(x, w, labels, chunk_rows=16)

    def vocab_matmuls(jaxpr):
        return [e for e in equations(jaxpr) if e.primitive.name ==
                "dot_general" and V in (*e.invars[0].aval.shape,
                                        *e.invars[1].aval.shape)]

    grad = jax.make_jaxpr(jax.value_and_grad(head, (0, 1)))(x, wte).jaxpr
    names = {e.primitive.name for e in equations(grad)}
    assert not {n for n in names if "remat" in n or "checkpoint" in n}, names
    assert not {n for n in names if n.startswith("scatter")}, names
    assert len(vocab_matmuls(grad)) == 3
    assert len(vocab_matmuls(jax.make_jaxpr(head)(x, wte).jaxpr)) == 1


def test_no_forward_mode_rule():
    x, wte, labels = inputs(jnp.float32)
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(lambda x: fused_lm_head_loss(x, wte, labels), (x,), (x,))


def test_the_counter_tells_a_train_step_from_an_evaluation(devices):
    """`dispatch_report()["ce_head"]`: a tiny Pythia's train step traces
    the forward rule, its evaluation the primal alone."""
    model = GPTNeoX(GPTNeoXConfig.tiny(), use_pallas=False)
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(
            jax.random.PRNGKey(0)),
        config_params={"train_batch_size": 8, "steps_per_print": 1000,
                       "optimizer": {"type": "Adam",
                                     "params": {"lr": 1e-3}}})
    tokens = np.random.default_rng(0).integers(
        0, model.config.vocab_size, (1, 8, 16), np.int32)
    before = dispatch_report()["ce_head"]
    loss = float(engine.train_batch(batch=(tokens, tokens)))
    trained = dispatch_report()["ce_head"]
    assert np.isfinite(loss)
    assert trained["loss_and_grads"] > before["loss_and_grads"]
    evaluated = float(engine.eval_batch((tokens[0], tokens[0])))
    after = dispatch_report()["ce_head"]
    assert np.isfinite(evaluated)
    assert after["loss_only"] > trained["loss_only"]
    assert after["loss_and_grads"] == trained["loss_and_grads"]
