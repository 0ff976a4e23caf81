"""The dropless MoE layer's plan is COUNTED (`moe.layer.dropless_plan`):
against the plain form kept here (a stable argsort and scatters, the
lines the layer ran until PR 45) every integer of the plan is the same,
at the serving cells' own shapes and at the edges; the layer's outputs
and gradients are bit-equal to a copy of that layer (which numbers the
pairs token-major, as the layer did until PR 64, or choice-major, as it
does since: `pair_major`); the fill leaves exact zeros in the buffer's
padding rows; and the traced
program holds ONE sort (of the buffer's rows, for `src`) and no scatter
or scatter-add, so that a later edit cannot bring the others back
unseen."""

import importlib

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
                           score="softmax", pair_major="token"):
    """`moe_ffn_dropless` as PR 44 left it: a stable sort by expert, a
    `where` over the buffer, the combine over `[T, k, H]`.

    `pair_major="choice"` moves the rows as the layer does since PR 64,
    under the same plain plan: the pairs numbered p = j * T + t before
    the stable sort (the same rows in the same groups, a group's rows in
    (choice, token) order, which is the order `dw` sums them in), and
    the fill one gather from x with a zero row appended (`dx` is then
    the slice of a scatter-add, to which XLA adds the router's part
    AFTER the rows' and not before: the last digit of `dx` follows)."""
    choice_major = pair_major == "choice"
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
    pair_expert = jnp.where(here, experts - lo, E)
    pair_expert = (pair_expert.T if choice_major else pair_expert).reshape(
        T * k)
    counts, tile_expert, tile_rows, _, pair_row, src = plain_plan(
        pair_expert, E, bm, R)
    if choice_major:
        buf = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])[
            jnp.where(src < T * k, src % T, T)]
    else:
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
    if choice_major:
        pair_row = pair_row.reshape(k, T).T.reshape(T * k)
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
    # the serving cells' top_k: 4 (GLM), 8 (OLMoE, SDAR), 10 (Laguna,
    # Qwen3-Next: the k the token-major combine copied)
    "sigmoid-k4-masked": dict(T=45, H=16, inter=8, E_all=16, k=4, mask=True,
                              kw=dict(score="sigmoid", norm_topk_prob=True,
                                      scale=1.8)),
    "softmax-k8-masked": dict(T=40, H=16, inter=8, E_all=16, k=8, mask=True,
                              kw={}),
    "softmax-k8-held": dict(T=24, H=16, inter=8, E_all=32, k=8, mask=False,
                            kw=dict(held=(8, 24))),
    "softmax-k10-masked": dict(T=33, H=16, inter=8, E_all=32, k=10,
                               mask=True, kw=dict(norm_topk_prob=True)),
    "held-k10-masked-bf16": dict(T=52, H=16, inter=8, E_all=32, k=10,
                                 mask=True, dtype=jnp.bfloat16,
                                 kw=dict(held=(0, 16), norm_topk_prob=True)),
}


def _layer_case(case):
    spec = LAYERS[case]
    kw = dict(spec["kw"], gmm_backend="xla")
    params = layer_params(7, spec["H"], spec["inter"], spec["E_all"],
                          held=kw.get("held"),
                          sigmoid=kw.get("score") == "sigmoid")
    dtype = spec.get("dtype", jnp.float32)
    params = {name: leaf if name.startswith("gate") else leaf.astype(dtype)
              for name, leaf in params.items()}
    x = jax.random.normal(jax.random.PRNGKey(11), (spec["T"], spec["H"]),
                          jnp.float32).astype(dtype)
    mask = (jnp.arange(spec["T"]) % 5 != 3) if spec["mask"] else None
    return params, x, spec["k"], dict(kw, token_mask=mask)


@pytest.mark.parametrize("case", sorted(LAYERS))
def test_layer_outputs_are_the_plain_layers_bit_for_bit(case):
    """Against the plain layer under BOTH pair numberings: the
    token-major one is the layer every PR up to 63 ran, so the result of
    a token did not change with the order of its group's rows."""
    params, x, k, kw = _layer_case(case)
    y, stats = jax.jit(lambda p, v: moe_ffn_dropless(p, v, k, **kw))(
        params, x)
    assert stats.shape == (3 if "held" in kw else 2, params["gate"].shape[1])
    assert np.asarray(y).any()
    for pair_major in ("token", "choice"):
        y0, stats0 = jax.jit(lambda p, v: plain_moe_ffn_dropless(
            p, v, k, pair_major=pair_major, **kw))(params, x)
        assert y.dtype == y0.dtype
        assert (np.asarray(y) == np.asarray(y0)).all(), pair_major
        assert (np.asarray(stats) == np.asarray(stats0)).all(), pair_major


@pytest.mark.parametrize("case", ["softmax-masked", "held-share-scaled",
                                  "softmax-k10-masked"])
def test_layer_gradients_are_the_plain_layers_bit_for_bit(case):
    """`dw` sums a group's rows in the buffer's order, so the plain layer
    is given the layer's pair numbering (choice-major) before its stable
    sort, and the layer's fill: every gradient is then the same float.
    Against the token-major layer of PR 44 the router's gradient is the
    same float too; `dx` and `dw` are the same sums in another order."""
    params, x, k, kw = _layer_case(case)
    probe = jax.random.normal(jax.random.PRNGKey(3), x.shape, jnp.float32)

    def loss(fn, **more):
        def scalar(p, v):
            y, stats = fn(p, v, k, **kw, **more)
            return jnp.sum(y * probe) + jnp.sum(stats[1] * stats[0])
        return jax.jit(jax.grad(scalar, argnums=(0, 1)))

    (gp, gx) = loss(moe_ffn_dropless)(params, x)
    (gp0, gx0) = loss(plain_moe_ffn_dropless, pair_major="choice")(params, x)
    assert (np.asarray(gx) == np.asarray(gx0)).all() and np.asarray(gx).any()
    for name in ("w_in", "w_out", "gate"):
        assert (np.asarray(gp[name]) == np.asarray(gp0[name])).all(), name
        assert np.asarray(gp[name]).any(), name
    (gp1, gx1) = loss(plain_moe_ffn_dropless)(params, x)
    assert (np.asarray(gp["gate"]) == np.asarray(gp1["gate"])).all()
    for got, want in ((gx, gx1), (gp["w_in"], gp1["w_in"]),
                      (gp["w_out"], gp1["w_out"])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


# --- the fill: one gather, padding rows exact zeros ------------------------

@pytest.mark.parametrize("case", ["softmax-masked", "held-share-scaled",
                                  "held-k10-masked-bf16"])
def test_the_fill_leaves_exact_zeros_in_the_padding_rows(monkeypatch, case):
    """The buffer the first grouped matmul is handed: a padding row (past
    its tile's live rows) names the zero row appended to x, a live row
    holds its token's x, in (choice, token) order inside a group. No
    `where` zeroes the buffer afterwards: the rows are zeros as they are
    gathered."""
    # (`ops.pallas` re-exports a function under the module's own name)
    gmm = importlib.import_module("deeperspeed_tpu.ops.pallas.grouped_matmul")
    params, x, k, kw = _layer_case(case)
    seen = []
    real = gmm.ragged_matmul

    def watched(buf, w, tile_expert, tile_rows, block_m, **more):
        seen.append((np.asarray(buf.astype(jnp.float32)),
                     np.asarray(tile_expert), np.asarray(tile_rows),
                     block_m))
        return real(buf, w, tile_expert, tile_rows, block_m, **more)

    monkeypatch.setattr(gmm, "ragged_matmul", watched)
    moe_ffn_dropless(params, x, k, **kw)                # eager: concrete
    buf, tile_expert, tile_rows, bm = seen[0]
    T, E = x.shape[0], params["w_in"].shape[0]
    assert buf.shape[0] == dropless_geometry(T, k, E)[0]
    lane = np.arange(bm)[None, :]
    padding = (lane >= tile_rows[:, None]).reshape(-1)
    assert padding.any() and not padding.all()
    assert (buf[padding] == 0).all() and not np.signbit(buf[padding]).any()
    # the live rows: each expert's tokens, choice by choice
    logits = np.asarray(x, np.float32) @ np.asarray(params["gate"])
    lo = kw["held"][0] if "held" in kw else 0
    chosen = np.asarray(jax.lax.top_k(jnp.asarray(logits), k)[1]) - lo
    live = np.ones(T, bool) if kw["token_mask"] is None \
        else np.asarray(kw["token_mask"])
    xs = np.asarray(x.astype(jnp.float32))
    tile_of = np.repeat(np.arange(tile_rows.size), bm)
    for e in range(E):
        want = [t for j in range(k) for t in range(T)
                if live[t] and chosen[t, j] == e]
        got = buf[~padding & (tile_expert[tile_of] == e)]
        assert (got == xs[want]).all(), e


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
    # the plan's form and the pairs' numbering, one count each a trace
    assert ops.dispatch_report()["moe"]["plan"] == \
        {"counted": before.get("counted", 0) + 1,
         "choice_major": before.get("choice_major", 0) + 1}
