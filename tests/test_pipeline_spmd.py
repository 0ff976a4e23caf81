"""SPMD pipeline executor tests: the compiled ppermute pipeline must
reproduce sequential execution exactly, forward and backward."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeperspeed_tpu.models.gpt_neox import GPTNeoXConfig
from deeperspeed_tpu.parallel.pipeline_spmd import (GPTNeoXPipeSPMD,
                                                    last_stage_value,
                                                    pipeline_loss_fn,
                                                    spmd_pipeline)

# passes and fits tier-1's rule (`pyproject.toml`, `slow`); `slow` for the
# whole run's budget alone, with the mechanisms no cell runs (ROADMAP D19)
pytestmark = pytest.mark.slow

DIM = 16


@pytest.fixture
def pipe_mesh(devices):
    import numpy as np
    return Mesh(np.asarray(devices[:4]), ("pipe",))


def test_spmd_pipeline_matches_sequential(pipe_mesh):
    """8 linear layers over 4 stages, 4 microbatches: pipelined forward ==
    sequential forward."""
    n_stages, n_layers, n_micro = 4, 8, 4
    rng = np.random.default_rng(0)
    ws = rng.normal(size=(n_layers, DIM, DIM)).astype(np.float32) * 0.3
    x = rng.normal(size=(n_micro, 2, DIM)).astype(np.float32)

    def stage_fn(w_local, x):
        def one(x, w):
            return jnp.tanh(x @ w), None
        y, _ = jax.lax.scan(one, x, w_local)
        return y

    from deeperspeed_tpu.compat import shard_map

    def run(ws, x_micro):
        outputs = spmd_pipeline(stage_fn, ws, x_micro, "pipe", n_stages,
                                n_micro)
        # Broadcast last stage's outputs so the result is well-defined.
        return last_stage_value(outputs, "pipe", n_stages)

    mapped = shard_map(run, mesh=pipe_mesh,
                       in_specs=(P("pipe"), P()), out_specs=P(),
                       check_vma=False)
    out = mapped(jnp.asarray(ws), jnp.asarray(x))

    # Sequential reference.
    ref = jnp.asarray(x)
    for i in range(n_layers):
        ref = jnp.tanh(ref @ ws[i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_gpt_neox_pipelined_loss_matches_monolithic(pipe_mesh):
    cfg = GPTNeoXConfig(vocab_size=128, hidden_size=32, num_layers=4,
                        num_heads=2, max_seq_len=32)
    model = GPTNeoXPipeSPMD(cfg, pipe_mesh, n_micro=2)
    params = model.init_params(jax.random.PRNGKey(0))
    # Shard blocks over pipe as the engine would.
    specs = model.param_specs(params, pipe_mesh)
    params = jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, NamedSharding(pipe_mesh, s)),
        params, specs, is_leaf=lambda x: isinstance(x, P))

    rng = np.random.default_rng(1)
    tokens = rng.integers(0, cfg.vocab_size, size=(4, 16), dtype=np.int32)
    loss_pipe = float(model.loss_fn(params, (tokens, tokens)))

    # Monolithic reference with the same parameters.
    from deeperspeed_tpu.models import gpt_neox as M

    def mono_loss(params, tokens):
        x = params["embed"]["wte"][tokens]
        cos_sin = M._rotary_cache(cfg, tokens.shape[1])
        for i in range(cfg.num_layers):
            bp = jax.tree_util.tree_map(lambda l: l[i], params["blocks"])
            x = M.block_forward(cfg, bp, x, cos_sin)
        x = M.layer_norm(x, params["head"]["final_ln"]["scale"],
                         params["head"]["final_ln"]["bias"],
                         cfg.layernorm_eps)
        logits = jnp.einsum("bsh,vh->bsv", x, params["head"]["wte"],
                            preferred_element_type=jnp.float32)
        return M.lm_loss(logits, tokens)

    host_params = jax.tree_util.tree_map(np.asarray, params)
    loss_ref = float(mono_loss(host_params, tokens))
    np.testing.assert_allclose(loss_pipe, loss_ref, rtol=1e-5)


def test_gpt_neox_pipelined_grads_flow(pipe_mesh):
    cfg = GPTNeoXConfig(vocab_size=64, hidden_size=32, num_layers=4,
                        num_heads=2, max_seq_len=16)
    model = GPTNeoXPipeSPMD(cfg, pipe_mesh, n_micro=2)
    params = model.init_params(jax.random.PRNGKey(0))
    specs = model.param_specs(params, pipe_mesh)
    params = jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, NamedSharding(pipe_mesh, s)),
        params, specs, is_leaf=lambda x: isinstance(x, P))

    rng = np.random.default_rng(2)
    tokens = rng.integers(0, cfg.vocab_size, size=(4, 8), dtype=np.int32)

    grads = jax.jit(
        jax.grad(lambda p: model.loss_fn(p, (tokens, tokens))))(params)
    # Every block layer must receive gradient signal.
    gblocks = grads["blocks"]["attn"]["qkv_w"]
    per_layer = np.asarray(jnp.sum(jnp.abs(gblocks), axis=(1, 2)))
    assert (per_layer > 0).all(), per_layer
    assert float(jnp.abs(grads["embed"]["wte"]).sum()) > 0
    assert float(jnp.abs(grads["head"]["wte"]).sum()) > 0


def test_engine_with_spmd_pipeline(pipe_mesh):
    """The SPMD-pipelined model trains through the standard engine."""
    import deeperspeed_tpu

    cfg = GPTNeoXConfig(vocab_size=64, hidden_size=32, num_layers=4,
                        num_heads=2, max_seq_len=16)
    model = GPTNeoXPipeSPMD(cfg, pipe_mesh, n_micro=2)
    params = model.init_params(jax.random.PRNGKey(0))
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=params, mesh=pipe_mesh,
        config_params={
            "train_batch_size": 4,
            "steps_per_print": 1000,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        })
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, cfg.vocab_size, size=(4, 8), dtype=np.int32)
    batch = (tokens[None], tokens[None])
    losses = [float(engine.train_batch(batch=batch)) for _ in range(6)]
    assert losses[-1] < losses[0]


def test_block_forward_tp_matches_dense(devices):
    """Megatron TP block (explicit psum inside shard_map) == dense block."""
    from deeperspeed_tpu.compat import shard_map
    from deeperspeed_tpu.models import gpt_neox as M

    cfg = GPTNeoXConfig(vocab_size=64, hidden_size=32, num_layers=1,
                        num_heads=4, max_seq_len=16)
    mesh = Mesh(np.asarray(devices[:2]).reshape(2), ("model",))
    bp = M.init_block_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32), jnp.float32)
    cs = M._rotary_cache(cfg, 16)

    ref = M.block_forward(cfg, bp, x, cs, use_pallas=False)

    specs = M.block_param_specs_tp()
    tp = shard_map(
        lambda bp, x: M.block_forward_tp(cfg, bp, x, cs, "model", 2,
                                         use_pallas=False),
        mesh=mesh, in_specs=(specs, P()), out_specs=P(),
        check_vma=False)
    out = tp(bp, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_engine_3d_dp_pipe_tp(devices):
    """Full 3D: ZeRO over data x SPMD pipeline x Megatron TP in one jit."""
    import deeperspeed_tpu

    mesh = Mesh(np.asarray(devices[:8]).reshape(2, 2, 2),
                ("data", "pipe", "model"))
    cfg = GPTNeoXConfig(vocab_size=64, hidden_size=32, num_layers=4,
                        num_heads=2, max_seq_len=16)
    model = GPTNeoXPipeSPMD(cfg, mesh, n_micro=2, use_pallas=False)
    params = model.init_params(jax.random.PRNGKey(0))
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=params, mesh=mesh,
        config_params={
            "train_batch_size": 8,
            "steps_per_print": 1000,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        })
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, cfg.vocab_size, size=(8, 16), dtype=np.int32)
    batch = (tokens[None], tokens[None])
    losses = [float(engine.train_batch(batch=batch)) for _ in range(6)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_pipeline_dp_mean_matches_single(devices):
    """dp x pipe loss == the same batch's loss on a pipe-only mesh."""
    from deeperspeed_tpu.models import gpt_neox as M

    cfg = GPTNeoXConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=16)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(4, 16), dtype=np.int32)

    mesh_p = Mesh(np.asarray(devices[:2]).reshape(2), ("pipe",))
    m1 = GPTNeoXPipeSPMD(cfg, mesh_p, n_micro=2, use_pallas=False)
    p1 = m1.init_params(jax.random.PRNGKey(0))
    l_ref = float(m1.loss_fn(p1, (tokens, tokens)))

    mesh_dp = Mesh(np.asarray(devices[:4]).reshape(2, 2),
                   ("data", "pipe"))
    m2 = GPTNeoXPipeSPMD(cfg, mesh_dp, n_micro=2, use_pallas=False)
    l_dp = float(m2.loss_fn(p1, (tokens, tokens)))
    # the dp mean over two half-batches == the full-batch token mean here
    # (equal token counts per shard)
    np.testing.assert_allclose(l_dp, l_ref, atol=1e-5, rtol=1e-5)


def test_pipeline_tp_vocab_parallel_loss_matches(devices):
    """pipe x model (vocab-parallel embed + parallel xent) == pipe-only."""
    cfg = GPTNeoXConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=16)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(4, 16), dtype=np.int32)

    mesh_p = Mesh(np.asarray(devices[:2]).reshape(2), ("pipe",))
    m1 = GPTNeoXPipeSPMD(cfg, mesh_p, n_micro=2, use_pallas=False)
    p1 = m1.init_params(jax.random.PRNGKey(0))
    l_ref = float(jax.jit(m1.loss_fn)(p1, (tokens, tokens)))

    mesh_tp = Mesh(np.asarray(devices[:4]).reshape(2, 2),
                   ("pipe", "model"))
    m2 = GPTNeoXPipeSPMD(cfg, mesh_tp, n_micro=2, use_pallas=False)
    l_tp = float(jax.jit(m2.loss_fn)(p1, (tokens, tokens)))
    np.testing.assert_allclose(l_tp, l_ref, atol=1e-4, rtol=1e-4)

    # grads flow through the vocab-parallel embedding and head
    g = jax.jit(jax.grad(lambda p: m2.loss_fn(p, (tokens, tokens))))(p1)
    assert np.abs(np.asarray(g["embed"]["wte"])).sum() > 0
    assert np.abs(np.asarray(g["head"]["wte"])).sum() > 0


def test_engine_legacy_path_profiles(devices):
    """forward/backward/step training also triggers the flops profiler."""
    import deeperspeed_tpu
    from tests.simple_model import SimpleModel

    model = SimpleModel(hidden_dim=8, num_layers=1)
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(
            jax.random.PRNGKey(0)),
        config_params={"train_batch_size": len(devices),
                       "optimizer": {"type": "Adam",
                                     "params": {"lr": 1e-3}},
                       "flops_profiler": {"enabled": True,
                                          "profile_step": 0},
                       "steps_per_print": 100})
    x = np.ones((len(devices), 8), np.float32)
    loss = engine.forward((x, x))
    engine.backward(loss)
    engine.step()
    assert engine.flops_profiler.get_total_flops() > 0
