"""1-bit Adam / 1-bit LAMB tests (parity with reference
`tests/onebit/test_onebit.py` NCCL/MPI compressed-allreduce correctness:
warmup == plain Adam, post-freeze compression preserves convergence, and
the error-feedback identity holds).
"""

import numpy as np

import jax
import jax.numpy as jnp
import pytest
from deeperspeed_tpu.compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deeperspeed_tpu.ops.adam.fused_adam import FusedAdam
from deeperspeed_tpu.runtime.comm.compressed import (
    compressed_allreduce_dense, compressed_allreduce_two_phase,
    compressed_allreduce_two_phase_host, pack_signs, unpack_signs,
    wire_pad)
from deeperspeed_tpu.runtime.fp16.onebit import OnebitAdam, OnebitLamb

# passes and fits tier-1's rule (`pyproject.toml`, `slow`); `slow` for the
# whole run's budget alone, with the mechanisms no cell runs (ROADMAP D19)
pytestmark = pytest.mark.slow


def params8():
    return {"w": jax.random.normal(jax.random.PRNGKey(0), (16, 16),
                                   jnp.float32) * 0.1}


def test_compressed_allreduce_error_feedback_identity():
    """scale*sign(x+err) + new_err == x + err (lossless decomposition)."""
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 32), jnp.float32)
    err = jnp.zeros((8, 32), jnp.float32)

    def body(x, err):
        return compressed_allreduce_dense(x, err, "data")

    out, new_err = shard_map(body, mesh=mesh,
                             in_specs=(P("data"), P("data")),
                             out_specs=(P("data"), P("data")))(x, err)
    assert out.shape == (8, 32)
    x_np, out_np = np.asarray(x), np.asarray(out)
    # Reconstruct each shard's quantized value from the identity
    # q = (x + err) - new_err (err was zero here) and check it has the
    # sign+scale form: per-shard constant magnitude = mean|x|, signs of x.
    q = x_np - np.asarray(new_err)
    for r in range(8):
        np.testing.assert_allclose(np.abs(q[r]), np.abs(x_np[r]).mean(),
                                   rtol=1e-5)
        np.testing.assert_array_equal(np.sign(q[r]),
                                      np.where(x_np[r] >= 0, 1.0, -1.0))
    # The allreduced output is the cross-shard mean of the quantized values.
    np.testing.assert_allclose(
        out_np, np.broadcast_to(q.mean(axis=0), (8, 32)), rtol=1e-5)


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4, 64)).astype(np.float32)
    signs = x >= 0
    packed = pack_signs(jnp.asarray(signs))
    assert packed.dtype == jnp.uint8 and packed.shape == (4, 8)
    vals = unpack_signs(packed)
    np.testing.assert_array_equal(np.asarray(vals),
                                  np.where(signs, 1.0, -1.0))


def test_wire_pad():
    assert wire_pad(100, 8) == 128
    assert wire_pad(64, 8) == 64
    assert wire_pad(1, 4) == 32


def test_two_phase_packed_matches_host_reference():
    """The in-mesh packed transport (all_to_all sign bytes + allgather)
    computes exactly the two-phase error-feedback math of the host
    oracle (reference `comm/nccl.py:47-186` semantics)."""
    world = 8
    n = 256
    mesh = Mesh(np.asarray(jax.devices()[:world]), ("data",))
    rng = np.random.default_rng(2)
    xs = rng.normal(size=(world, n)).astype(np.float32)
    werr = rng.normal(size=(world, n)).astype(np.float32) * 0.1
    serr = rng.normal(size=(world, n // world)).astype(np.float32) * 0.1

    def body(x, we, se):
        return compressed_allreduce_two_phase(x[0], we[0], se[0],
                                              "data", world)

    out, new_we, new_se = shard_map(
        body, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data")),
        out_specs=(P("data"), P("data"), P("data")),
        check_vma=False)(xs, werr, serr)
    out = np.asarray(out).reshape(world, n)
    new_we = np.asarray(new_we).reshape(world, n)
    new_se = np.asarray(new_se).reshape(world, n // world)
    ref_outs, ref_we, ref_se = compressed_allreduce_two_phase_host(
        list(jnp.asarray(xs)), list(jnp.asarray(werr)),
        list(jnp.asarray(serr)))
    # every rank reconstructs the same full result
    np.testing.assert_allclose(out, np.broadcast_to(
        np.asarray(ref_outs[0]), (world, n)), rtol=1e-6, atol=1e-6)
    for r in range(world):
        np.testing.assert_allclose(new_we[r], np.asarray(ref_we[r]),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(new_se[r], np.asarray(ref_se[r]),
                                   rtol=1e-6, atol=1e-6)


def test_two_phase_packed_matches_host_reference_ragged():
    """n_valid < n (zero-padded ragged tail): transport and oracle must
    still agree — scales normalized by valid counts, pad lanes pinned to
    0 in outputs and both error buffers."""
    world = 8
    n = 256
    n_valid = 231  # tail spans part of the last server chunk
    mesh = Mesh(np.asarray(jax.devices()[:world]), ("data",))
    rng = np.random.default_rng(3)
    mask = (np.arange(n) < n_valid)
    xs = rng.normal(size=(world, n)).astype(np.float32) * mask
    werr = rng.normal(size=(world, n)).astype(np.float32) * 0.1 * mask
    serr = (rng.normal(size=(world, n // world)).astype(np.float32) * 0.1
            * mask.reshape(world, n // world))

    def body(x, we, se):
        return compressed_allreduce_two_phase(x[0], we[0], se[0],
                                              "data", world,
                                              n_valid=n_valid)

    out, new_we, new_se = shard_map(
        body, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data")),
        out_specs=(P("data"), P("data"), P("data")),
        check_vma=False)(xs, werr, serr)
    out = np.asarray(out).reshape(world, n)
    new_we = np.asarray(new_we).reshape(world, n)
    new_se = np.asarray(new_se).reshape(world, n // world)
    ref_outs, ref_we, ref_se = compressed_allreduce_two_phase_host(
        list(jnp.asarray(xs)), list(jnp.asarray(werr)),
        list(jnp.asarray(serr)), n_valid=n_valid)
    np.testing.assert_allclose(out, np.broadcast_to(
        np.asarray(ref_outs[0]), (world, n)), rtol=1e-6, atol=1e-6)
    assert np.all(out[:, n_valid:] == 0)
    for r in range(world):
        np.testing.assert_allclose(new_we[r], np.asarray(ref_we[r]),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(new_se[r], np.asarray(ref_se[r]),
                                   rtol=1e-6, atol=1e-6)
    assert np.all(new_we[:, n_valid:] == 0)


def test_two_phase_packed_wire_volume():
    """Measured bytes on the wire: the compiled packed transport moves
    sign BYTES (u8), beating an fp32 allreduce by >=4x (VERDICT target;
    analytically ~16x for large n)."""
    import re

    world = 8
    n = 32768
    mesh = Mesh(np.asarray(jax.devices()[:world]), ("data",))

    def packed_body(x, we, se):
        return compressed_allreduce_two_phase(x, we, se, "data", world)

    mapped = shard_map(packed_body, mesh=mesh,
                       in_specs=(P(), P(), P("data")),
                       out_specs=(P(), P(), P("data")),
                       check_vma=False)
    hlo = jax.jit(mapped).lower(
        jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32),
        jnp.zeros((n,), jnp.float32)).compile().as_text()

    def wire_bytes(hlo):
        total = 0
        for line in hlo.splitlines():
            if re.search(r"=\s*\S*\s*(all-to-all|all-gather)", line):
                m = re.search(r"(u8|f32|s32|bf16)\[([\d,]*)\]", line)
                if not m:
                    continue
                dtype, dims = m.groups()
                sz = int(np.prod([int(d) for d in dims.split(",") if d]))
                total += sz * {"u8": 1, "bf16": 2, "f32": 4, "s32": 4}[dtype]
        return total

    packed_bytes = wire_bytes(hlo)
    assert packed_bytes > 0, "no collectives found in HLO"

    def dense_body(x):
        return jax.lax.pmean(x, "data")

    dense = shard_map(dense_body, mesh=mesh, in_specs=P(), out_specs=P(),
                      check_vma=False)
    dense_hlo = jax.jit(dense).lower(
        jnp.zeros((n,), jnp.float32)).compile().as_text()
    # fp32 allreduce payload: at least the full buffer in fp32
    dense_bytes = max(n * 4, wire_bytes(dense_hlo))
    assert packed_bytes * 4 <= dense_bytes, (packed_bytes, dense_bytes)


def test_onebit_adam_warmup_matches_fused_adam():
    """During freeze_step warmup the update is exactly FusedAdam
    (adam_w_mode=False / classic L2)."""
    params = params8()
    g = jax.tree_util.tree_map(lambda p: jnp.ones_like(p) * 0.01, params)

    ob = OnebitAdam(lr=1e-2, freeze_step=100)
    ob_state = ob.init_state(params)
    ob_p, ob_state = ob.update(g, ob_state, params)

    # OnebitAdam's update is m / (sqrt(v) + eps) with no bias correction
    # (reference onebit/adam.py applies the raw moments), so the matching
    # dense reference is FusedAdam(bias_correction=False, classic L2).
    ref = FusedAdam(lr=1e-2, adam_w_mode=False, bias_correction=False)
    ref_state = ref.init_state(params)
    ref_p, ref_state = ref.update(g, ref_state, params)

    np.testing.assert_allclose(np.asarray(ob_state.exp_avg["w"]),
                               np.asarray(ref_state.exp_avg["w"]),
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(ob_p["w"]),
                               np.asarray(ref_p["w"]), atol=1e-7)


@pytest.mark.parametrize("cls", [OnebitAdam, OnebitLamb])
def test_onebit_converges_after_freeze(cls):
    """Training continues to converge after compression kicks in."""
    params = params8()
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16), jnp.float32)
    y = jax.random.normal(jax.random.PRNGKey(2), (8, 16), jnp.float32)

    def loss_fn(p):
        return jnp.mean(jnp.square(x @ p["w"] - y))

    opt = cls(lr=1e-2, freeze_step=5)
    state = opt.init_state(params)
    p = params
    losses = []
    for i in range(120):
        g = jax.grad(loss_fn)(p)
        p, state = opt.update(g, state, p)
        losses.append(float(loss_fn(p)))
    # sign-magnitude updates oscillate near the optimum (quantized steps
    # have a fixed per-step magnitude), so assert on the best loss and
    # that the tail stays in the converged basin, not on the final step
    assert min(losses) < losses[0] * 0.5
    assert losses[-1] < losses[0]
    assert np.isfinite(losses[-1])
    assert int(state.step) == 120


def test_onebit_adam_variance_frozen_after_freeze_step():
    params = params8()
    opt = OnebitAdam(lr=1e-2, freeze_step=2)
    state = opt.init_state(params)
    p = params
    g = jax.tree_util.tree_map(lambda q: jnp.ones_like(q) * 0.1, params)
    for _ in range(2):
        p, state = opt.update(g, state, p)
    v_frozen = np.asarray(state.exp_avg_sq["w"]).copy()
    g2 = jax.tree_util.tree_map(lambda q: jnp.ones_like(q) * 5.0, params)
    p, state = opt.update(g2, state, p)
    np.testing.assert_array_equal(np.asarray(state.exp_avg_sq["w"]),
                                  v_frozen)


def test_onebit_adam_engine_config():
    """'OneBitAdam' optimizer type wires through deeperspeed_tpu.initialize."""
    import deeperspeed_tpu
    from tests.simple_model import SimpleModel

    model = SimpleModel(hidden_dim=16)
    engine, opt, _, _ = deeperspeed_tpu.initialize(
        model=model,
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "OneBitAdam",
                          "params": {"lr": 1e-2, "freeze_step": 3}},
        })
    assert isinstance(opt, OnebitAdam) or isinstance(engine.optimizer,
                                                     OnebitAdam)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(1, 8, 16)).astype(np.float32)
    y = rng.normal(size=(1, 8, 16)).astype(np.float32)
    losses = [float(engine.train_batch(batch=(x, y))) for _ in range(10)]
    assert losses[-1] < losses[0]


# --- packed transport inside the ENGINE's step (VERDICT round-2 #5) ------

def _packed_engine(freeze_step, packed=True, seed=0, dp=8):
    import deeperspeed_tpu
    D = 16

    def loss_fn(params, batch, rng):
        x, y = batch
        pred = jnp.tanh(x @ params["w1"]) @ params["w2"]
        return jnp.mean((pred - y) ** 2)

    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    params = {"w1": jax.random.normal(k1, (D, D)) * 0.3,
              "w2": jax.random.normal(k2, (D, D)) * 0.3}
    opt_params = {"lr": 1e-2, "freeze_step": freeze_step}
    if packed:
        opt_params["packed_transport"] = True
    engine, *_ = deeperspeed_tpu.initialize(
        model=loss_fn, model_parameters=params,
        config_params={"train_batch_size": 16,
                       "optimizer": {"type": "OneBitAdam",
                                     "params": opt_params},
                       "steps_per_print": 1000})
    return engine


def _run_engine(engine, steps, seed=3, fixed=False):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(1, 16, 16)).astype(np.float32)
    y = rng.normal(size=(1, 16, 16)).astype(np.float32)
    out = []
    for _ in range(steps):
        if not fixed:
            x = rng.normal(size=(1, 16, 16)).astype(np.float32)
            y = rng.normal(size=(1, 16, 16)).astype(np.float32)
        out.append(float(engine.train_batch(batch=(x, y))))
    return np.asarray(out)


def test_packed_engine_warmup_matches_dense(devices):
    """During freeze_step warmup the packed engine runs plain Adam on the
    dp-mean gradient — identical trajectory to the default path."""
    ref = _run_engine(_packed_engine(100, packed=False), 4)
    got = _run_engine(_packed_engine(100, packed=True), 4)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-6)


def test_packed_engine_post_freeze_converges(devices):
    """After freeze_step the compressed-momentum step keeps training:
    loss decreases and error-feedback buffers become active."""
    engine = _packed_engine(2)
    losses = _run_engine(engine, 20, fixed=True)
    assert losses[-1] < losses[0] * 0.5, losses
    we = jax.tree_util.tree_leaves(engine.state.opt_state.worker_error)
    assert any(float(jnp.abs(w).sum()) > 0 for w in we), \
        "compression never engaged"


def test_packed_engine_wire_bytes(devices):
    """The VERDICT 'done' criterion: the ENGINE's post-freeze compiled
    step contains no fp32 gradient allreduce — its gradient-sync wire
    volume (packed u8 all_to_all/all_gather + scales) is >=4x smaller
    than the dense program's fp32 pmean traffic."""
    import re

    def wire_bytes(hlo, ops):
        """Sum payload bytes of matching collectives; variadic ops carry
        a result TUPLE, so every dtype[dims] before the op name counts."""
        total = 0
        pat = re.compile(r"=\s*(.*?)\s*(" + "|".join(ops) + r")\(")
        for line in hlo.splitlines():
            mt = pat.search(line)
            if not mt:
                continue
            for dtype, dims in re.findall(
                    r"(u8|f32|s32|bf16)\[([\d,]*)\]", mt.group(1)):
                sz = int(np.prod([int(d) for d in dims.split(",") if d]))
                total += sz * {"u8": 1, "bf16": 2, "f32": 4,
                               "s32": 4}[dtype]
        return total

    def step_hlo(engine, post):
        engine._onebit_post_phase = post
        step = engine._train_step_body(1)
        rng = np.random.default_rng(0)
        x = rng.normal(size=(1, 16, 16)).astype(np.float32)
        batch = jax.tree_util.tree_map(
            lambda b: engine._shard_stacked_batch(b), (x, x))
        return jax.jit(step).lower(
            engine.state, batch, jax.random.PRNGKey(0),
            jnp.asarray(1e-2)).compile().as_text()

    engine = _packed_engine(2)
    post_hlo = step_hlo(engine, post=True)
    warm_hlo = step_hlo(engine, post=False)
    post_bytes = wire_bytes(post_hlo,
                            ["all-to-all", "all-gather", "all-reduce"])
    warm_bytes = wire_bytes(warm_hlo, ["all-reduce"])
    n_params = 2 * 16 * 16
    assert warm_bytes >= n_params * 4, (warm_bytes,)
    assert post_bytes > 0
    assert post_bytes * 4 <= warm_bytes, (post_bytes, warm_bytes)


def test_packed_engine_single_wire_pair(devices):
    """Round-4 VERDICT #7: the post-freeze program carries ONE packed
    sign wire for the whole step — one u8 all_to_all + u8 all-gather
    pair (plus scalar scale gathers), not one pair per gradient leaf."""
    import re
    engine = _packed_engine(2)
    engine._onebit_post_phase = True
    step = engine._train_step_body(1)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(1, 16, 16)).astype(np.float32)
    batch = jax.tree_util.tree_map(
        lambda b: engine._shard_stacked_batch(b), (x, x))
    hlo = jax.jit(step).lower(
        engine.state, batch, jax.random.PRNGKey(0),
        jnp.asarray(1e-2)).compile().as_text()
    u8_collectives = [
        ln for ln in hlo.splitlines()
        if re.search(r"=\s*[^=]*u8\[[\d,]*\][^=]*\b"
                     r"(all-to-all|all-gather)\(", ln)]
    # one u8 all-to-all + one u8 all-gather for the WHOLE 2-leaf model;
    # per-leaf wiring would show 4 op definitions
    assert len(u8_collectives) == 2, "\n".join(u8_collectives)
