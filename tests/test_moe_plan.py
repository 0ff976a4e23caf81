"""The dropless MoE layer's plan is COUNTED (`moe.layer.dropless_plan`):
against the plain form kept here (a stable argsort and scatters, the
lines the layer ran until PR 45) every integer of the plan is the same,
at the serving cells' own shapes and at the edges; the layer's outputs
and gradients are bit-equal to a copy of that layer; and the traced
program holds ONE sort (of the buffer's rows, for `src`) and no scatter
or scatter-add, so that a later edit cannot bring the others back
unseen."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu import ops
from deeperspeed_tpu.moe.layer import (dropless_geometry, dropless_plan,
                                       moe_ffn_dropless)
from deeperspeed_tpu.ops.pallas.grouped_matmul import ragged_matmul


# --- the plain form ---------------------------------------------------------

def plain_tile_maps(counts, block_m, n_tiles):
    counts = counts.astype(jnp.int32)
    tiles = (counts + block_m - 1) // block_m
    ends = jnp.cumsum(tiles)
    first = ends - tiles
    m = jnp.arange(n_tiles, dtype=jnp.int32)
    live = m < ends[-1]
    last_live = jnp.maximum(ends[-1] - 1, 0)
    owner = jnp.searchsorted(ends, jnp.where(live, m, last_live),
                             side="right").astype(jnp.int32)
    owner = jnp.minimum(owner, counts.shape[0] - 1)
    rows = jnp.clip(counts[owner] - (m - first[owner]) * block_m, 0, block_m)
    return owner, jnp.where(live, rows, 0), first * block_m


def plain_plan(pair_expert, E, bm, R):
    P = pair_expert.shape[0]
    order = jnp.argsort(pair_expert)                          # stable
    counts = jnp.zeros((E + 1,), jnp.int32).at[pair_expert].add(1)[:E]
    tile_expert, tile_rows, starts = plain_tile_maps(counts, bm, R // bm)
    sorted_expert = pair_expert[order]
    begin = jnp.cumsum(counts) - counts
    e_safe = jnp.minimum(sorted_expert, E - 1)
    dest = starts[e_safe] + jnp.arange(P, dtype=jnp.int32) - begin[e_safe]
    dest = jnp.where(sorted_expert < E, dest, R)
    src = jnp.full((R,), P, jnp.int32).at[dest].set(
        order.astype(jnp.int32), mode="drop")
    pair_row = jnp.zeros((P,), jnp.int32).at[order].set(dest)
    return counts, tile_expert, tile_rows, starts, pair_row, src


def plain_moe_ffn_dropless(params, x, top_k, norm_topk_prob=False,
                           activation=jax.nn.silu, token_mask=None,
                           gmm_backend=None, held=None, scale=1.0,
                           score="softmax"):
    """`moe_ffn_dropless` as PR 44 left it."""
    T, H = x.shape
    E_all = params["gate"].shape[1]
    lo, hi = held if held is not None else (0, E_all)
    E = hi - lo
    k = int(top_k)
    R, bm = dropless_geometry(T, k, E)
    live = jnp.ones((T,), jnp.bool_) if token_mask is None \
        else token_mask.reshape(T).astype(jnp.bool_)
    logits = jnp.dot(x.astype(jnp.float32),
                     params["gate"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(
            scores + params["gate_bias"].astype(jnp.float32), k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, k)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    n_live = jnp.maximum(jnp.sum(live), 1).astype(jnp.float32)
    mean_prob = jnp.sum(jnp.where(live[:, None], probs, 0.0),
                        axis=0) / n_live
    experts = experts.astype(jnp.int32)
    here = live[:, None] & (experts >= lo) & (experts < hi)
    pair_expert = jnp.where(here, experts - lo, E).reshape(T * k)
    counts, tile_expert, tile_rows, _, pair_row, src = plain_plan(
        pair_expert, E, bm, R)
    buf = jnp.where((src < T * k)[:, None],
                    x[jnp.minimum(src, T * k - 1) // k], 0)
    if held is None:
        stats = jnp.stack([counts.astype(jnp.float32) /
                           jnp.maximum(jnp.sum(counts), 1), mean_prob])
    else:
        routed = jnp.zeros((E_all + 1,), jnp.float32).at[
            jnp.where(live[:, None], experts, E_all).reshape(T * k)
        ].add(1.0)[:E_all]
        stats = jnp.stack([routed / jnp.maximum(jnp.sum(routed), 1.0),
                           mean_prob, routed])
    dt = x.dtype
    inter = params["w_out"].shape[1]
    h = ragged_matmul(buf, params["w_in"].astype(dt), tile_expert,
                      tile_rows, bm, backend=gmm_backend)
    h = activation(h[:, :inter]) * h[:, inter:]
    out = ragged_matmul(h, params["w_out"].astype(dt), tile_expert,
                        tile_rows, bm, backend=gmm_backend)
    rows = out[jnp.minimum(pair_row, R - 1)].reshape(T, k, H)
    w = jnp.where(here, weights, 0.0).astype(dt)
    return jnp.sum(w[:, :, None] * rows, axis=1), stats


# --- the plan, integer for integer -------------------------------------------

def routed_pairs(seed, T, k, E_all, held=None, live_share=0.9,
                 one_expert=False, used=None):
    """[T * k] pair experts as the layer builds them: the top k of
    random scores a token, some tokens masked, absent experts out."""
    rng = np.random.default_rng(seed)
    scores = rng.standard_normal((T, E_all)).astype(np.float32)
    if used is not None:                 # the other experts get no pair
        scores[:, used:] = -np.inf
    experts = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    if one_expert:
        experts = np.full((T, k), min(3, E_all - 1))
    lo, hi = held if held else (0, E_all)
    live = rng.random(T) < live_share
    here = live[:, None] & (experts >= lo) & (experts < hi)
    return jnp.asarray(np.where(here, experts - lo, hi - lo).reshape(T * k),
                       jnp.int32), hi - lo


NAMES = ("counts", "tile_expert", "tile_rows", "starts", "pair_row", "src")

PLANS = {
    # the serving cells' shapes (ISSUE 45's table): tokens, k, the
    # router's experts, the held range
    "olmoe-decode": dict(T=32, k=8, E_all=64),
    "olmoe-prefill": dict(T=1536, k=8, E_all=64),
    "laguna-decode": dict(T=32, k=10, E_all=256, held=(0, 128)),
    "laguna-prefill": dict(T=8192, k=10, E_all=256, held=(0, 128)),
    "glm-decode": dict(T=32, k=4, E_all=64),
    "glm-prefill": dict(T=16384, k=4, E_all=64),
    "sdar-decode": dict(T=128, k=8, E_all=128),
    "sdar-prefill": dict(T=2048, k=8, E_all=128),
    # the edges
    "pairs-not-whole-blocks": dict(T=5, k=3, E_all=8),
    "one-pair-over-a-block": dict(T=43, k=3, E_all=8),
    "every-token-masked": dict(T=24, k=2, E_all=8, live_share=0.0),
    "no-token-masked": dict(T=24, k=2, E_all=8, live_share=1.1),
    "every-pair-on-one-expert": dict(T=200, k=1, E_all=8, one_expert=True),
    "experts-with-no-pair": dict(T=64, k=2, E_all=16, used=5),
    "held-upper-half": dict(T=96, k=4, E_all=16, held=(8, 16)),
    "held-middle-none-chosen": dict(T=16, k=2, E_all=16, held=(4, 8),
                                    used=3),
    "one-expert-held": dict(T=40, k=2, E_all=4, held=(2, 3)),
}


@pytest.mark.parametrize("case", sorted(PLANS))
def test_counted_plan_is_the_sorted_plan(case):
    spec = dict(PLANS[case])
    T, k = spec["T"], spec["k"]
    pairs, E = routed_pairs(20260930 + T, **spec)
    R, bm = dropless_geometry(T, k, E)
    got = jax.jit(dropless_plan, static_argnums=(1, 2, 3))(pairs, E, bm, R)
    want = jax.jit(plain_plan, static_argnums=(1, 2, 3))(pairs, E, bm, R)
    for name, g, w in zip(NAMES, got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"{case}: {name}")
    # what the layout promises, whatever built it: a live pair's row
    # names it back, a group's rows are in pair order
    pair_row, src = np.asarray(got[4]), np.asarray(got[5])
    owned = np.asarray(pairs) < E
    assert (pair_row[~owned] == R).all()
    assert (src[pair_row[owned]] == np.flatnonzero(owned)).all()
    assert (np.asarray(got[0]).sum() == owned.sum() ==
            (src < T * k).sum())


# --- the layer, bit for bit -------------------------------------------------

def layer_params(seed, H, inter, E_all, held=None, sigmoid=False):
    lo, hi = held if held else (0, E_all)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    params = {
        "gate": jax.random.normal(keys[0], (H, E_all), jnp.float32),
        "w_in": jax.random.normal(keys[1], (hi - lo, H, 2 * inter),
                                  jnp.float32) / np.sqrt(H),
        "w_out": jax.random.normal(keys[2], (hi - lo, inter, H),
                                   jnp.float32) / np.sqrt(inter)}
    if sigmoid:
        params["gate_bias"] = 0.1 * jax.random.normal(keys[3], (E_all,))
    return params


LAYERS = {
    "softmax-masked": dict(T=37, H=16, inter=24, E_all=8, k=2,
                           mask=True, kw={}),
    "softmax-renormalised-over-a-block": dict(
        T=150, H=32, inter=16, E_all=16, k=3, mask=False,
        kw=dict(norm_topk_prob=True)),
    "held-share-scaled": dict(T=48, H=16, inter=8, E_all=16, k=4, mask=True,
                              kw=dict(held=(4, 12), norm_topk_prob=True,
                                      scale=2.5)),
    "sigmoid-biased": dict(T=29, H=16, inter=8, E_all=8, k=2, mask=True,
                           kw=dict(score="sigmoid", norm_topk_prob=True,
                                   scale=1.8)),
}


def _layer_case(case):
    spec = LAYERS[case]
    kw = dict(spec["kw"], gmm_backend="xla")
    params = layer_params(7, spec["H"], spec["inter"], spec["E_all"],
                          held=kw.get("held"),
                          sigmoid=kw.get("score") == "sigmoid")
    x = jax.random.normal(jax.random.PRNGKey(11), (spec["T"], spec["H"]),
                          jnp.float32)
    mask = (jnp.arange(spec["T"]) % 5 != 3) if spec["mask"] else None
    return params, x, spec["k"], dict(kw, token_mask=mask)


@pytest.mark.parametrize("case", sorted(LAYERS))
def test_layer_outputs_are_the_plain_layers_bit_for_bit(case):
    params, x, k, kw = _layer_case(case)
    y, stats = jax.jit(lambda p, v: moe_ffn_dropless(p, v, k, **kw))(
        params, x)
    y0, stats0 = jax.jit(
        lambda p, v: plain_moe_ffn_dropless(p, v, k, **kw))(params, x)
    assert stats.shape == (3 if "held" in kw else 2, params["gate"].shape[1])
    assert (np.asarray(y) == np.asarray(y0)).all()
    assert (np.asarray(stats) == np.asarray(stats0)).all()
    assert np.asarray(y).any()


@pytest.mark.parametrize("case", ["softmax-masked", "held-share-scaled"])
def test_layer_gradients_are_the_plain_layers_bit_for_bit(case):
    params, x, k, kw = _layer_case(case)
    probe = jax.random.normal(jax.random.PRNGKey(3), x.shape, jnp.float32)

    def loss(fn):
        def scalar(p, v):
            y, stats = fn(p, v, k, **kw)
            return jnp.sum(y * probe) + jnp.sum(stats[1] * stats[0])
        return jax.jit(jax.grad(scalar, argnums=(0, 1)))

    (gp, gx) = loss(moe_ffn_dropless)(params, x)
    (gp0, gx0) = loss(plain_moe_ffn_dropless)(params, x)
    assert (np.asarray(gx) == np.asarray(gx0)).all() and np.asarray(gx).any()
    for name in ("w_in", "w_out", "gate"):
        assert (np.asarray(gp[name]) == np.asarray(gp0[name])).all(), name
        assert np.asarray(gp[name]).any(), name


# --- the traced program's budget -------------------------------------------

def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


@pytest.mark.parametrize("case", sorted(LAYERS))
def test_the_traced_layer_moves_integers_by_index_once(case):
    params, x, k, kw = _layer_case(case)
    names = list(_primitives(jax.make_jaxpr(
        lambda p, v: moe_ffn_dropless(p, v, k, **kw))(params, x).jaxpr))
    moved = [n for n in names if n == "sort" or n.startswith("scatter")]
    assert moved == ["sort"], names
    # the plain layer is what the budget is there to keep out
    plain = [n for n in _primitives(jax.make_jaxpr(
        lambda p, v: plain_moe_ffn_dropless(p, v, k, **kw))(params, x).jaxpr)
        if n == "sort" or n.startswith("scatter")]
    assert sorted(plain) == sorted(
        ["sort", "scatter", "scatter"] +
        ["scatter-add"] * (2 if "held" in kw else 1))


def test_a_trace_is_counted_by_the_form_of_its_plan():
    params, x, k, kw = _layer_case("softmax-masked")
    before = ops.dispatch_report()["moe"]["plan"]
    jax.make_jaxpr(lambda p, v: moe_ffn_dropless(p, v, k, **kw))(params, x)
    assert ops.dispatch_report()["moe"]["plan"] == \
        {"counted": before.get("counted", 0) + 1}
