"""Sort-based MoE dispatch engine: parity with the einsum engine on the
dense and 8-device expert-parallel paths, a2a-overlap trajectory parity,
top-2 combine-weight renormalization, and auto-group memoization.

Fast lane on purpose (acceptance: sort-vs-einsum parity runs in the
<3-min lane) — shapes are tiny and jits are shared where possible."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from deeperspeed_tpu.compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deeperspeed_tpu.moe import (DISPATCH_MODES, MoELayer, moe_ffn_dense,
                                 moe_ffn_expert_parallel)
from deeperspeed_tpu.moe.layer import _pick_span, _resolve_groups

H, I, E = 16, 32, 8


def dense(params, x, rng=None, **static):
    """`moe_ffn_dense` under one `jax.jit`: called bare, every operation
    of the dispatch is dispatched, and compiled, on its own."""
    return jax.jit(lambda p, x, rng: moe_ffn_dense(p, x, rng=rng, **static))(
        params, x, rng)


def _params(rng, E=E):
    return MoELayer(H, I, E).init(rng)


# --- dense parity ---------------------------------------------------------

@pytest.mark.parametrize("top_k", [1, 2])
@pytest.mark.parametrize("groups", [1, 4])
def test_sort_matches_einsum_dense(top_k, groups):
    params = _params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, H), jnp.float32)
    y_e, aux_e = dense(params, x, top_k=top_k, groups=groups)
    y_s, aux_s = dense(params, x, top_k=top_k, groups=groups,
                               dispatch="sort")
    np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_e),
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(float(aux_s), float(aux_e), rtol=1e-6)


def test_sort_matches_einsum_capacity_overflow():
    """All tokens forced to one expert at capacity 1: the sort engine
    must drop exactly the tokens the cumsum bookkeeping drops."""
    params = _params(jax.random.PRNGKey(0))
    params["gate"] = jnp.zeros_like(params["gate"]).at[:, 0].set(1.0)
    x = jnp.ones((16, H), jnp.float32)
    y_e, _ = dense(params, x, capacity_factor=E / 16)
    y_s, _ = dense(params, x, capacity_factor=E / 16,
                           dispatch="sort")
    np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_e),
                               rtol=2e-6, atol=2e-6)
    norms = np.linalg.norm(np.asarray(y_s), axis=-1)
    assert norms[0] > 1e-3 and np.all(norms[1:] < 1e-6)


def test_sort_matches_einsum_with_jitter():
    """Both engines must draw IDENTICAL gate jitter (same per-group key
    split) so they route identically under exploration noise."""
    params = _params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(3), (32, H), jnp.float32)
    kw = dict(top_k=2, groups=2, rng=jax.random.PRNGKey(7),
              jitter_eps=0.3)
    y_e, aux_e = dense(params, x, **kw)
    y_s, aux_s = dense(params, x, dispatch="sort", **kw)
    np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_e),
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(float(aux_s), float(aux_e), rtol=1e-6)


def test_sort_grads_match_einsum():
    params = _params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(4), (24, H), jnp.float32)

    def loss(p, dispatch):
        y, aux = dense(p, x, top_k=2, dispatch=dispatch)
        return jnp.sum(y ** 2) + 0.01 * aux

    g_e = jax.grad(lambda p: loss(p, "einsum"))(params)
    g_s = jax.grad(lambda p: loss(p, "sort"))(params)
    for k in g_e:
        np.testing.assert_allclose(np.asarray(g_s[k]), np.asarray(g_e[k]),
                                   rtol=2e-5, atol=2e-5, err_msg=k)


def test_sort_interpret_kernel_path():
    """Force the Pallas kernel (interpret mode on CPU) through the full
    layer — the exact code path a TPU run takes."""
    params = _params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(5), (16, H), jnp.float32)
    y_e, _ = dense(params, x, top_k=2)
    y_k, _ = dense(params, x, top_k=2, dispatch="sort",
                           gmm_backend="pallas")
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_e),
                               rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("dispatch", DISPATCH_MODES)
def test_capacity_router_is_top_1_or_2_and_dropless_takes_any_k(dispatch):
    """The rule "top_k must be 1 or 2" is the GShard capacity router's,
    in both its engines; routing that drops nothing
    (`moe_ffn_dropless`) takes any 1 <= k <= E and names what it
    refuses."""
    from deeperspeed_tpu.moe.layer import moe_ffn_dropless
    params = _params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, H), jnp.float32)
    with pytest.raises(ValueError, match="top_k must be 1 or 2"):
        moe_ffn_dense(params, x, top_k=3, dispatch=dispatch)
    gated = {"gate": params["gate"],
             "w_in": jnp.concatenate([params["w_in"]] * 2, axis=-1),
             "w_out": params["w_out"]}
    y, stats = moe_ffn_dropless(gated, x, 3)
    assert y.shape == x.shape and stats.shape == (2, E)
    np.testing.assert_allclose(float(stats[0].sum()), 1.0, atol=1e-6)
    with pytest.raises(ValueError, match="1 <= top_k <="):
        moe_ffn_dropless(gated, x, E + 1)


def test_unknown_dispatch_raises():
    params = _params(jax.random.PRNGKey(0))
    x = jnp.ones((8, H), jnp.float32)
    with pytest.raises(ValueError, match="dispatch"):
        moe_ffn_dense(params, x, dispatch="scatter")
    with pytest.raises(ValueError, match="dispatch"):
        MoELayer(H, I, E, dispatch="scatter")
    assert DISPATCH_MODES == ("einsum", "sort")


# --- top-2 combine-weight renormalization (capacity leak fix) -------------

# Routing pattern where SECOND choices overflow while first choices
# survive: tokens 0-1 route e0→e2, tokens 2-3 route e1→e2. At capacity 2
# expert2 (second choices only) keeps tokens 0-1's and drops tokens
# 2-3's — tokens 2-3 keep their first choice but lose the second.
_LOGIT_ROWS = np.asarray([[2.0, -5.0, 1.0, -5.0],
                          [2.0, -5.0, 1.0, -5.0],
                          [-5.0, 2.0, 1.0, -5.0],
                          [-5.0, 2.0, 1.0, -5.0]], np.float32)


@pytest.mark.parametrize("dispatch", ["einsum", "sort"])
def test_renorm_kept_choices_restores_leaked_mass(dispatch):
    """A token whose second choice overflows keeps weight g1/(g1+g2) < 1
    under the legacy pair normalization — the g2 mass silently leaks.
    renorm_kept_choices renormalizes over the surviving choices, so the
    token carries full weight on its kept first choice."""
    from deeperspeed_tpu.moe.layer import _one_hot_dispatch
    logits = jnp.asarray(_LOGIT_ROWS)
    _, combine, _ = _one_hot_dispatch(logits, capacity=2, top_k=2)
    per_token = np.asarray(jnp.sum(combine, axis=(1, 2)))
    probs = np.asarray(jax.nn.softmax(_LOGIT_ROWS[2]))
    g1n = probs[1] / (probs[1] + probs[2])
    # tokens 0-1 keep both choices (sum 1); tokens 2-3 lose choice 2 and
    # LEAK its mass (sum g1n < 1)
    np.testing.assert_allclose(per_token[:2], 1.0, atol=1e-5)
    np.testing.assert_allclose(per_token[2:], g1n, atol=1e-5)

    _, combine_r, _ = _one_hot_dispatch(logits, capacity=2, top_k=2,
                                        renorm_kept_choices=True)
    per_token_r = np.asarray(jnp.sum(combine_r, axis=(1, 2)))
    np.testing.assert_allclose(per_token_r, 1.0, atol=1e-5)

    # end-to-end through both engines: gate reads token dims 0/1 so x
    # rows reproduce the logit pattern above; capacity_factor 1.0 at
    # T=4/E=4/top2 → capacity 2
    params = _params(jax.random.PRNGKey(0), E=4)
    gate = jnp.zeros_like(params["gate"])
    gate = gate.at[0].set(jnp.asarray(_LOGIT_ROWS[0]))
    gate = gate.at[1].set(jnp.asarray(_LOGIT_ROWS[2]))
    params["gate"] = gate
    x = jnp.zeros((4, H), jnp.float32)
    x = x.at[0, 0].set(1.0).at[1, 0].set(1.0)
    x = x.at[2, 1].set(1.0).at[3, 1].set(1.0)
    y_r, _ = dense(params, x, top_k=2, capacity_factor=1.0,
                           renorm_kept_choices=True, dispatch=dispatch)
    y_l, _ = dense(params, x, top_k=2, capacity_factor=1.0,
                           dispatch=dispatch)
    # tokens 2-3 (overflowed second choice) change; tokens 0-1 don't
    diff = np.abs(np.asarray(y_r) - np.asarray(y_l)).max(axis=-1)
    assert diff[2] > 1e-6 and diff[3] > 1e-6
    assert diff[0] < 1e-7 and diff[1] < 1e-7
    y_ref, _ = dense(params, x, top_k=2, capacity_factor=1.0,
                             renorm_kept_choices=True, dispatch="einsum")
    np.testing.assert_allclose(np.asarray(y_r), np.asarray(y_ref),
                               rtol=2e-6, atol=2e-6)


def test_renorm_off_is_legacy_bitwise():
    """Default off: the einsum path must stay bit-identical to the
    legacy pair normalization."""
    from deeperspeed_tpu.moe.layer import _one_hot_dispatch
    logits = jax.random.normal(jax.random.PRNGKey(8), (16, 4), jnp.float32)
    d1, c1, a1 = _one_hot_dispatch(logits, capacity=2, top_k=2)
    d2, c2, a2 = _one_hot_dispatch(logits, capacity=2, top_k=2,
                                   renorm_kept_choices=False)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


# --- auto-group memoization ----------------------------------------------

def test_resolve_groups_memoized():
    _resolve_groups.cache_clear()
    assert _resolve_groups(0, 2500) == 2     # 2500 → group size 1250
    hits0 = _resolve_groups.cache_info().hits
    assert _resolve_groups(0, 2500) == 2
    assert _resolve_groups.cache_info().hits == hits0 + 1
    # explicit counts validate (and errors are not cached)
    with pytest.raises(ValueError):
        _resolve_groups(3, 10)
    with pytest.raises(ValueError):
        _resolve_groups(3, 10)
    assert _resolve_groups("auto", 3 * 1024) == 3


# --- span / block geometry ------------------------------------------------

def test_pick_span_bounds_padding():
    for cap in (1, 7, 64, 320, 2560, 4096):
        span, bm = _pick_span(cap)
        assert span >= cap and span % bm == 0
        # padding bounded: ≤ 12.5% (+ the 8-row floor for tiny spans)
        assert span - cap <= max(cap // 8, 7)


# --- expert-parallel parity (8-device mesh) -------------------------------

def test_sort_matches_einsum_expert_parallel(devices):
    ep = 4
    mesh = Mesh(np.asarray(devices[:ep]), ("expert",))
    layer = MoELayer(H, I, E, mesh=mesh, top_k=2, groups=2)
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(9), (ep * 8, H), jnp.float32)

    def build(**kw):
        return jax.jit(shard_map(
            lambda p, x: moe_ffn_expert_parallel(
                p, x, "expert", ep, top_k=2, groups=2, **kw),
            mesh=mesh, in_specs=(layer.param_specs(), P("expert")),
            out_specs=(P("expert"), P()), check_vma=False))

    y_e, aux_e = build()(params, x)
    y_s, aux_s = build(dispatch="sort")(params, x)
    np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_e),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(aux_s), float(aux_e), rtol=1e-6)

    # and both match the per-shard dense reference
    ref = jnp.concatenate([
        dense(params, x[r * 8:(r + 1) * 8], top_k=2, groups=2,
                      dispatch="sort")[0] for r in range(ep)])
    np.testing.assert_allclose(np.asarray(y_s), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_a2a_overlap_chunking_parity(devices):
    """Chunked a2a software pipelining is a pure reordering: outputs
    identical to the unchunked exchange for every chunk count."""
    ep = 4
    mesh = Mesh(np.asarray(devices[:ep]), ("expert",))
    layer = MoELayer(H, I, E, mesh=mesh, top_k=2)
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(10), (ep * 8, H),
                          jnp.float32)

    def run(chunks):
        return jax.jit(shard_map(
            lambda p, x: moe_ffn_expert_parallel(
                p, x, "expert", ep, top_k=2, dispatch="sort",
                a2a_overlap_chunks=chunks),
            mesh=mesh, in_specs=(layer.param_specs(), P("expert")),
            out_specs=(P("expert"), P()), check_vma=False))(params, x)

    y1, _ = run(1)
    y2, _ = run(2)
    # e_local = 2 → a request of 3 degrades to the largest divisor (1)
    y3, _ = run(3)
    np.testing.assert_array_equal(np.asarray(y2), np.asarray(y1))
    np.testing.assert_array_equal(np.asarray(y3), np.asarray(y1))


def test_a2a_overlap_training_trajectory_parity(devices):
    """Short training trajectory (manual SGD through the EP layer):
    chunked and unchunked runs must track each other step for step."""
    ep = 4
    mesh = Mesh(np.asarray(devices[:ep]), ("expert",))
    layer = MoELayer(H, I, E, mesh=mesh, top_k=2)
    x = jax.random.normal(jax.random.PRNGKey(11), (ep * 8, H),
                          jnp.float32)
    tgt = jax.random.normal(jax.random.PRNGKey(12), (ep * 8, H),
                            jnp.float32) * 0.1

    def trajectory(chunks, steps=3, lr=0.1):
        params = layer.init(jax.random.PRNGKey(0))
        mapped = shard_map(
            lambda p, x: moe_ffn_expert_parallel(
                p, x, "expert", ep, top_k=2, dispatch="sort",
                a2a_overlap_chunks=chunks),
            mesh=mesh, in_specs=(layer.param_specs(), P("expert")),
            out_specs=(P("expert"), P()), check_vma=False)

        @jax.jit
        def step(p):
            def loss(p):
                y, aux = mapped(p, x)
                return jnp.mean((y - tgt) ** 2) + 0.01 * aux
            val, g = jax.value_and_grad(loss)(p)
            return jax.tree_util.tree_map(
                lambda w, gw: w - lr * gw, p, g), val

        losses = []
        for _ in range(steps):
            params, val = step(params)
            losses.append(float(val))
        return losses

    base = trajectory(1)
    ovl = trajectory(2)
    np.testing.assert_allclose(ovl, base, rtol=1e-6, atol=1e-7)
    assert base[-1] < base[0]


# --- config plumb-through -------------------------------------------------

def test_gpt_neox_config_plumbs_dispatch_keys():
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
    from deeperspeed_tpu.runtime.config import DeepSpeedConfig

    cfg = DeepSpeedConfig({
        "train_batch_size": 8,
        "moe": {"num_experts": 4, "top_k": 2, "dispatch": "sort",
                "a2a_overlap_chunks": 2, "renorm_kept_choices": True},
    }, world_size=1)
    model = GPTNeoX(GPTNeoXConfig.tiny(), use_pallas=False)
    model.apply_ds_config(cfg)
    assert model.config.moe_dispatch == "sort"
    assert model.config.moe_a2a_overlap_chunks == 2
    assert model.config.moe_renorm_kept_choices is True
    assert model.config.moe_num_experts == 4
