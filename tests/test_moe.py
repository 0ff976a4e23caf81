"""MoE / expert-parallelism tests: the all_to_all dispatch must
reproduce the dense routing exactly, and training through the engine
must converge."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from deeperspeed_tpu.compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import deeperspeed_tpu
from deeperspeed_tpu.moe import (MoELayer, moe_ffn_dense,
                                 moe_ffn_expert_parallel)

H, I, E = 16, 32, 4


def _params(rng):
    layer = MoELayer(H, I, E)
    return layer.init(rng)


def test_dense_moe_routes_and_shapes():
    params = _params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (24, H), jnp.float32)
    y, aux = moe_ffn_dense(params, x)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    assert float(aux) > 0


def test_dense_moe_capacity_overflow_drops_tokens():
    """With capacity 1 and all tokens forced to one expert, only the
    first token per expert gets output (the rest combine to zero)."""
    params = _params(jax.random.PRNGKey(0))
    # bias the gate so everything routes to expert 0
    params["gate"] = jnp.zeros_like(params["gate"]).at[:, 0].set(1.0)
    x = jnp.ones((8, H), jnp.float32)
    y, _ = moe_ffn_dense(params, x, capacity_factor=E / 8)  # capacity 1
    norms = np.linalg.norm(np.asarray(y), axis=-1)
    assert norms[0] > 1e-3          # first token processed
    assert np.all(norms[1:] < 1e-6)  # overflow dropped


def test_expert_parallel_matches_dense(devices):
    """EP over 4 ranks == per-shard dense routing, token-exact."""
    ep = 4
    mesh = Mesh(np.asarray(devices[:ep]), ("expert",))
    params = _params(jax.random.PRNGKey(0))
    T_local = 12
    x = jax.random.normal(jax.random.PRNGKey(2), (ep * T_local, H),
                          jnp.float32)

    # dense per shard (each rank routes its tokens over all experts)
    ref = []
    for r in range(ep):
        y, _ = moe_ffn_dense(params, x[r * T_local:(r + 1) * T_local])
        ref.append(np.asarray(y))
    ref = np.concatenate(ref, axis=0)

    e_local = E // ep
    sharded_specs = {"gate": P(), "w_in": P("expert"), "b_in": P("expert"),
                     "w_out": P("expert"), "b_out": P("expert")}
    mapped = shard_map(
        lambda p, x: moe_ffn_expert_parallel(p, x, "expert", ep),
        mesh=mesh, in_specs=(sharded_specs, P("expert")),
        out_specs=(P("expert"), P()), check_vma=False)
    y, aux = jax.jit(mapped)(params, x)
    np.testing.assert_allclose(np.asarray(y), ref, atol=1e-5, rtol=1e-5)


def test_moe_layer_trains_through_engine(devices):
    """An MoE FFN model converges through the standard engine, with the
    aux loss added."""
    layer = MoELayer(H, I, E)

    class MoEModel:
        def init_params(self, rng):
            k1, k2 = jax.random.split(rng)
            return {"moe": layer.init(k1),
                    "out": (jax.random.normal(k2, (H, H)) * 0.1)}

        def loss_fn(self, params, batch, rng=None):
            x, y = batch
            h, aux = layer.apply(params["moe"], x)
            pred = h @ params["out"]
            return jnp.mean((pred - y) ** 2) + 0.01 * aux

    model = MoEModel()
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(
            jax.random.PRNGKey(0)),
        config_params={"train_batch_size": 16,
                       "optimizer": {"type": "Adam",
                                     "params": {"lr": 3e-3}},
                       "steps_per_print": 1000})
    rng = np.random.default_rng(0)
    x = rng.normal(size=(1, 16, H)).astype(np.float32)
    y = rng.normal(size=(1, 16, H)).astype(np.float32) * 0.1
    losses = [float(engine.train_batch(batch=(x, y))) for _ in range(25)]
    assert losses[-1] < losses[0] * 0.8, losses


# --- top-2 gating (GShard default) ----------------------------------------

def test_top2_dense_routes_two_experts():
    params = _params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(2), (24, H), jnp.float32)
    y1, _ = moe_ffn_dense(params, x, top_k=1)
    y2, _ = moe_ffn_dense(params, x, top_k=2)
    assert y2.shape == x.shape
    assert np.isfinite(np.asarray(y2)).all()
    # top-2 output differs from top-1 (second expert contributes)
    assert np.abs(np.asarray(y2) - np.asarray(y1)).max() > 1e-6


def test_top2_combine_weights_normalized():
    """With ample capacity, each token's combine weights over its two
    experts sum to ~1 (GShard normalization)."""
    from deeperspeed_tpu.moe.layer import _one_hot_dispatch
    logits = jax.random.normal(jax.random.PRNGKey(3), (16, E),
                               jnp.float32)
    dispatch, combine, _ = _one_hot_dispatch(logits, capacity=16, top_k=2)
    per_token = np.asarray(jnp.sum(combine, axis=(1, 2)))
    np.testing.assert_allclose(per_token, 1.0, atol=1e-5)
    # and each token occupies exactly two slots
    slots = np.asarray(jnp.sum(dispatch, axis=(1, 2)))
    np.testing.assert_allclose(slots, 2.0, atol=1e-6)


def test_top2_second_choices_queue_after_first():
    """Capacity is consumed by first choices before any second choice
    (GShard queueing): with capacity == exact top-1 load, second choices
    overflow."""
    from deeperspeed_tpu.moe.layer import _one_hot_dispatch
    # all tokens: top1 = expert 0, top2 = expert 1
    logits = jnp.tile(jnp.asarray([[2.0, 1.0, -5.0, -5.0]]), (4, 1))
    dispatch, combine, _ = _one_hot_dispatch(logits, capacity=4, top_k=2)
    d = np.asarray(dispatch)
    assert d[:, 0].sum() == 4          # all first choices kept
    assert d[:, 1].sum() == 4          # second choices fill expert 1
    dispatch, _, _ = _one_hot_dispatch(logits, capacity=2, top_k=2)
    d = np.asarray(dispatch)
    assert d[:, 0].sum() == 2          # first two tokens keep expert 0
    assert d[:, 1].sum() == 2


def test_top2_expert_parallel_matches_dense(devices):
    ep = 4
    mesh = Mesh(np.asarray(devices[:ep]), ("expert",))
    layer = MoELayer(H, I, E, mesh=mesh, top_k=2)
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(4), (ep * 8, H), jnp.float32)

    # per-shard dense reference (each rank routes its own tokens)
    refs = [moe_ffn_dense(params, x[r * 8:(r + 1) * 8], top_k=2)[0]
            for r in range(ep)]
    ref = jnp.concatenate(refs, axis=0)

    mapped = shard_map(
        lambda p, x: moe_ffn_expert_parallel(p, x, "expert", ep, top_k=2),
        mesh=mesh, in_specs=(layer.param_specs(), P("expert")),
        out_specs=(P("expert"), P()), check_vma=False)
    y, aux = jax.jit(mapped)(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_gate_jitter_changes_routing_only_with_rng():
    layer = MoELayer(H, I, E, top_k=2, jitter_eps=0.3)
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(5), (32, H), jnp.float32)
    y_det, _ = layer.apply(params, x)            # no rng → no jitter
    y_det2, _ = layer.apply(params, x)
    np.testing.assert_array_equal(np.asarray(y_det), np.asarray(y_det2))
    y_a, _ = layer.apply(params, x, rng=jax.random.PRNGKey(1))
    y_b, _ = layer.apply(params, x, rng=jax.random.PRNGKey(2))
    assert np.abs(np.asarray(y_a) - np.asarray(y_b)).max() > 1e-8


# --- config-drivable MoE / SP (VERDICT round-2 #9) -----------------------

def test_moe_config_drivable(devices):
    """A user JSON config alone (no library imports) turns on the MoE
    FFN: the engine applies the `moe` block before param init, expert
    weights appear, and training on a fixed batch decreases the loss."""
    import deeperspeed_tpu
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    model = GPTNeoX(GPTNeoXConfig.tiny(), use_pallas=False)
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=None,
        config_params={
            "train_batch_size": 16,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "steps_per_print": 1000,
            "moe": {"num_experts": 4, "top_k": 2, "jitter_eps": 0.01},
        }, rng=jax.random.PRNGKey(0))
    mlp = engine.state.params["blocks"][0]["mlp"]
    assert mlp["w_in"].shape[0] == 4, "expert weights missing"
    assert model.config.moe_top_k == 2
    rng = np.random.default_rng(0)
    toks = rng.integers(0, model.config.vocab_size, (1, 16, 32), np.int32)
    losses = [float(engine.train_batch(batch=(toks, toks)))
              for _ in range(8)]
    assert losses[-1] < losses[0], losses


def test_sequence_parallel_config_drivable(devices):
    """The `sequence_parallel` JSON block swaps in ring attention over
    the mesh's sp axis — trajectory parity with the dense engine."""
    import deeperspeed_tpu
    from jax.sharding import Mesh
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    cfg_json = {
        "train_batch_size": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "steps_per_print": 1000,
    }

    def run(sp_mesh):
        model = GPTNeoX(GPTNeoXConfig.tiny(), use_pallas=False)
        extra = dict(cfg_json)
        mesh = None
        if sp_mesh:
            mesh = Mesh(np.asarray(devices).reshape(2, 4),
                        ("data", "sp"))
            extra["sequence_parallel"] = {"enabled": True,
                                          "mode": "ring", "axis": "sp"}
        engine, *_ = deeperspeed_tpu.initialize(
            model=model, model_parameters=None, config_params=extra,
            mesh=mesh, rng=jax.random.PRNGKey(0))
        rng = np.random.default_rng(2)
        toks = rng.integers(0, model.config.vocab_size, (1, 8, 128),
                            np.int32)
        return [float(engine.train_batch(batch=(toks, toks)))
                for _ in range(4)]

    base = run(False)
    got = run(True)
    np.testing.assert_allclose(got, base, rtol=2e-4, atol=2e-4)


def test_moe_pipeline_guarded(devices):
    """MoE × pipeline is rejected loudly at every entry (round-4 VERDICT
    #4): no valid config may silently drop the expert aux loss."""
    from deeperspeed_tpu.models.gpt_neox import (GPTNeoXConfig,
                                                 to_layer_specs)
    from deeperspeed_tpu.parallel.pipeline_spmd import GPTNeoXPipeSPMD

    moe_cfg = GPTNeoXConfig.tiny(moe_num_experts=4)
    with pytest.raises(NotImplementedError, match="aux loss"):
        to_layer_specs(moe_cfg)

    mesh = Mesh(np.asarray(devices[:4]).reshape(4), ("pipe",))
    with pytest.raises(NotImplementedError, match="aux loss"):
        GPTNeoXPipeSPMD(moe_cfg, mesh, n_micro=2)


def test_moe_pipeline_json_config_guarded(devices):
    """A JSON config with both `moe` and a PipelineModule model raises a
    DeepSpeedConfigError before any training is possible."""
    from deeperspeed_tpu import LayerSpec, PipelineModule
    from deeperspeed_tpu.runtime.config import DeepSpeedConfigError

    class Tiny:
        def init(self, rng, x=None):
            return {"w": jnp.ones((4, 4))}

        def apply(self, params, x, rng=None):
            return x @ params["w"]

    module = PipelineModule([LayerSpec(Tiny)], num_stages=1,
                            loss_fn=lambda y, t: jnp.mean((y - t) ** 2))
    with pytest.raises(DeepSpeedConfigError, match="moe"):
        deeperspeed_tpu.initialize(
            model=module, model_parameters=None,
            config_params={
                "train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "moe": {"num_experts": 4},
            }, rng=jax.random.PRNGKey(0))


# --- grouped dispatch (GShard G dim; VERDICT round-4 #5) ------------------

def test_grouped_dense_matches_ungrouped_with_ample_capacity():
    """With non-binding capacity, grouping only changes bookkeeping:
    every token still reaches its top-k experts with the same combine
    weights, so grouped == ungrouped output."""
    params = _params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(6), (32, H), jnp.float32)
    y1, aux1 = moe_ffn_dense(params, x, capacity_factor=float(E),
                             top_k=2, groups=1)
    y4, aux4 = moe_ffn_dense(params, x, capacity_factor=float(E),
                             top_k=2, groups=4)
    np.testing.assert_allclose(np.asarray(y4), np.asarray(y1),
                               rtol=1e-5, atol=1e-5)
    # aux statistics are per-group means of the same assignment counts
    assert np.isfinite(float(aux4))


def test_grouped_capacity_is_per_group():
    """groups=T makes every token its own group with capacity ≥ 1:
    nothing can overflow even at tiny capacity_factor (the degenerate
    proof that capacity became per-group)."""
    params = _params(jax.random.PRNGKey(0))
    params["gate"] = jnp.zeros_like(params["gate"]).at[:, 0].set(1.0)
    x = jnp.ones((8, H), jnp.float32)
    # ungrouped with capacity 1 drops 7 of 8 tokens (proved elsewhere);
    # fully grouped keeps them all
    y, _ = moe_ffn_dense(params, x, capacity_factor=E / 8, groups=8)
    norms = np.linalg.norm(np.asarray(y), axis=-1)
    assert np.all(norms > 1e-3)


def test_groups_must_divide_tokens():
    params = _params(jax.random.PRNGKey(0))
    x = jnp.ones((10, H), jnp.float32)
    with pytest.raises(ValueError):
        moe_ffn_dense(params, x, groups=3)


def test_auto_groups_picks_divisor():
    from deeperspeed_tpu.moe.layer import _resolve_groups
    assert _resolve_groups(0, 512) == 1
    assert _resolve_groups(0, 4096) == 4
    assert _resolve_groups("auto", 3 * 1024) == 3
    # non-power-of-two token counts still get a divisor near the target
    g = _resolve_groups(0, 6000)
    assert 6000 % g == 0 and 128 <= 6000 // g <= 2048
    # awkward factorizations never produce tiny groups (2062 = 2*1031:
    # group size 1031, NOT 2 — tiny groups shrink capacity to ~1 and
    # silently drop routed tokens)
    assert _resolve_groups(0, 2062) == 2
    assert _resolve_groups(0, 127) == 1   # below the floor: one group


def test_grouped_expert_parallel_matches_grouped_dense(devices):
    """EP with groups == per-shard grouped dense routing."""
    ep = 4
    mesh = Mesh(np.asarray(devices[:ep]), ("expert",))
    layer = MoELayer(H, I, E, mesh=mesh, top_k=2, groups=2)
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(7), (ep * 8, H), jnp.float32)

    refs = [moe_ffn_dense(params, x[r * 8:(r + 1) * 8], top_k=2,
                          groups=2)[0] for r in range(ep)]
    ref = jnp.concatenate(refs, axis=0)

    mapped = shard_map(
        lambda p, x: moe_ffn_expert_parallel(p, x, "expert", ep, top_k=2,
                                             groups=2),
        mesh=mesh, in_specs=(layer.param_specs(), P("expert")),
        out_specs=(P("expert"), P()), check_vma=False)
    y, _ = jax.jit(mapped)(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# --- sort dispatch engine (PR 5) ------------------------------------------

def test_moe_sort_dispatch_config_drivable_trajectory_parity(devices):
    """`moe.dispatch = "sort"` via JSON config alone: the engine trains
    through the sort engine and tracks the einsum engine's loss
    trajectory step for step."""
    import deeperspeed_tpu
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    def run(dispatch):
        model = GPTNeoX(GPTNeoXConfig.tiny(), use_pallas=False)
        engine, *_ = deeperspeed_tpu.initialize(
            model=model, model_parameters=None,
            config_params={
                "train_batch_size": 16,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "steps_per_print": 1000,
                "moe": {"num_experts": 4, "top_k": 2,
                        "dispatch": dispatch},
            }, rng=jax.random.PRNGKey(0))
        assert model.config.moe_dispatch == dispatch
        rng = np.random.default_rng(0)
        toks = rng.integers(0, model.config.vocab_size, (1, 16, 32),
                            np.int32)
        return [float(engine.train_batch(batch=(toks, toks)))
                for _ in range(6)]

    base = run("einsum")
    got = run("sort")
    np.testing.assert_allclose(got, base, rtol=2e-4, atol=2e-4)
    assert got[-1] < got[0]
