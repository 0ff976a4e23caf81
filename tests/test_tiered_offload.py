"""Tiered parameter/optimizer offload on the explicit schedule
(`runtime/zero/offload_engine.py` + `offload_param` ×
``zero_optimization.schedule.mode = "explicit"``).

Fast-lane coverage: host row-layout round trips; trajectory parity of
the tiered executor vs the wired ZeRO-Offload host tier (same host
CPU-Adam — parity must hold to float tolerance) across prefetch depths,
group geometries and grad accumulation; the NVMe row tier with
crash-consistent committed files; offload-tier save → resume bit-exact
vs uninterrupted (params AND Adam moments); Train/Offload/* +
param_wait + MFU telemetry (including the host-offload MFU fix); and
the parse/config rejection surface.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import deeperspeed_tpu
from deeperspeed_tpu.compat import shard_map
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.parallel.schedule import (offload_layer_plan,
                                               pack_plan_rows,
                                               unpack_plan_row)
from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError
from tests.simple_model import price_mfu_against_v5e

pytestmark = pytest.mark.offload

STEPS = 3
SEQ = 32
BATCH = 16


class Recorder:
    def __init__(self):
        self.records = []

    def record(self, sample, scalars):
        self.records.append((int(sample), dict(scalars)))

    def series(self, key):
        return [s[key] for _, s in self.records if key in s]


def tiny_cfg(num_layers=4):
    return GPTNeoXConfig(vocab_size=128, hidden_size=32,
                         num_layers=num_layers, num_heads=4,
                         max_seq_len=64)


def _engine(overrides, num_layers=4, seed=0, gas=1):
    cfg = tiny_cfg(num_layers)
    model = GPTNeoX(cfg, use_pallas=False)
    params = model.init_params(jax.random.PRNGKey(seed))
    config = {"train_batch_size": BATCH,
              "gradient_accumulation_steps": gas,
              "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
              "steps_per_print": 10_000}
    config.update(overrides)
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=params, config_params=config)
    return engine


def _train(engine, steps=STEPS, gas=1, seed=1):
    rng = np.random.default_rng(seed)
    losses = []
    for _ in range(steps):
        toks = rng.integers(0, 128, (gas, BATCH // gas, SEQ), np.int32)
        losses.append(float(engine.train_batch(batch=(toks, toks))))
    return np.asarray(losses)


def tiered(depth=2, group=2, param=None, opt=None, **extra):
    z = {"stage": 3,
         "offload_optimizer": opt or {"device": "cpu"},
         "offload_param": param or {"device": "cpu"},
         "schedule": {"mode": "explicit", "prefetch_depth": depth,
                      "group_layers": group}}
    out = {"zero_optimization": z}
    out.update(extra)
    return out


OFFLOAD_BASE = {"zero_optimization": {
    "stage": 2, "offload_optimizer": {"device": "cpu"}}}
TOL = dict(rtol=2e-4, atol=2e-4)


@pytest.fixture(scope="module")
def offload_baseline():
    """ZeRO-Offload host tier: the SAME host CPU-Adam the tiered
    executor steps — parity isolates the streaming/row machinery."""
    return _train(_engine(OFFLOAD_BASE))


# ---------------------------------------------------------------------------
# row-layout units
# ---------------------------------------------------------------------------

class TestRowLayout:
    def test_pack_unpack_roundtrip(self):
        tmpl = {"a": np.arange(24, dtype=np.float32).reshape(4, 6),
                "b": np.arange(7, dtype=np.float32),
                "c": np.arange(30, dtype=np.float32).reshape(5, 6)}
        plan = offload_layer_plan(tmpl, "data", 8, 1 << 20)
        leaves = jax.tree_util.tree_leaves(tmpl)
        row = pack_plan_rows(plan, leaves)
        assert row.shape == (8 * plan.shard_size,)
        for orig, back in zip(leaves, unpack_plan_row(plan, row)):
            np.testing.assert_array_equal(orig, back)

    def test_device_gather_matches_host_layout(self, devices):
        """Uploading a packed row with P(data) must reproduce the
        natural leaves through the schedule's gather_row/rebuild — the
        invariant the whole tier rests on."""
        mesh = Mesh(np.asarray(devices[:8]), ("data",))
        tmpl = {"w": np.arange(40, dtype=np.float32).reshape(8, 5),
                "b": np.arange(3, dtype=np.float32)}
        plan = offload_layer_plan(tmpl, "data", 8, 16)  # tiny buckets too
        row = pack_plan_rows(plan, jax.tree_util.tree_leaves(tmpl))
        placed = jax.device_put(row, NamedSharding(mesh, P("data")))

        def body(local):
            return plan.rebuild(plan.gather_row(local), [])

        out = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("data"),),
                                out_specs=P(), check_vma=False))(placed)
        for k, v in tmpl.items():
            np.testing.assert_array_equal(np.asarray(out[k]), v)

    def test_pack_requires_offload_plan(self):
        from deeperspeed_tpu.parallel.schedule import LayerPlan
        tmpl = {"w": np.zeros((8, 4), np.float32)}
        plan = LayerPlan(tmpl, {"w": P()}, {"w": False}, "data", 8, 1 << 20)
        with pytest.raises(ValueError, match="offload_layer_plan"):
            pack_plan_rows(plan, jax.tree_util.tree_leaves(tmpl))


# ---------------------------------------------------------------------------
# trajectory parity
# ---------------------------------------------------------------------------

class TestTieredParity:
    def test_matches_offload_baseline(self, offload_baseline, devices):
        engine = _engine(tiered())
        got = _train(engine)
        np.testing.assert_allclose(got, offload_baseline, **TOL)
        # params really rest off-device: the engine state holds only
        # zero-strided placeholder views
        leaf = jax.tree_util.tree_leaves(engine.state.params)[0]
        assert isinstance(leaf, np.ndarray) and 0 in leaf.strides

    def test_prefetch_depth_exceeds_layers(self, offload_baseline):
        got = _train(_engine(tiered(depth=64, group=1)))
        np.testing.assert_allclose(got, offload_baseline, **TOL)

    def test_single_group_whole_model(self, offload_baseline):
        got = _train(_engine(tiered(depth=1, group=4)))
        np.testing.assert_allclose(got, offload_baseline, **TOL)

    def test_ragged_groups(self, offload_baseline):
        """4 layers in groups of 3 -> [3, 1]: two program shapes."""
        got = _train(_engine(tiered(group=3)))
        np.testing.assert_allclose(got, offload_baseline, **TOL)

    def test_grad_accumulation(self, devices):
        base = _train(_engine(OFFLOAD_BASE, gas=2), gas=2)
        got = _train(_engine(tiered(), gas=2), gas=2)
        np.testing.assert_allclose(got, base, **TOL)

    def test_tiny_buckets(self, offload_baseline):
        # 0.001 MB buckets exercise ragged bucket tails inside the
        # group programs' gathers
        cfg = tiered()
        cfg["zero_optimization"]["schedule"]["bucket_mb"] = 0.001
        got = _train(_engine(cfg))
        np.testing.assert_allclose(got, offload_baseline, **TOL)

    def test_eval_batch(self, devices):
        e = _engine(tiered())
        b = _engine(OFFLOAD_BASE)
        toks = np.random.default_rng(3).integers(0, 128, (BATCH, SEQ),
                                                 np.int32)
        assert abs(float(e.eval_batch((toks, toks)))
                   - float(b.eval_batch((toks, toks)))) < 2e-4

    def test_train_steps_rejected(self, devices):
        e = _engine(tiered())
        with pytest.raises(RuntimeError, match="train_batch"):
            e.train_steps(np.zeros((2, 1, BATCH, SEQ), np.int32))


# ---------------------------------------------------------------------------
# NVMe row tier
# ---------------------------------------------------------------------------

class TestNvmeTier:
    def test_trains_with_committed_rows(self, tmp_path, offload_baseline):
        from deeperspeed_tpu.runtime.swap_tensor.aio_engine import \
            AsyncIOEngine
        if not AsyncIOEngine.available():
            pytest.skip("aio engine unavailable")
        e = _engine(tiered(param={"device": "nvme",
                                  "nvme_path": str(tmp_path)}))
        got = _train(e)
        np.testing.assert_allclose(got, offload_baseline, **TOL)
        store = os.path.join(str(tmp_path), "zero_stage_3")
        names = os.listdir(store)
        assert [f for f in names if f.endswith(".swp")], names
        # every write committed — no staging orphans after the fence
        assert not [f for f in names if f.endswith(".staging")], names

    def test_nvme_requires_path(self, devices):
        with pytest.raises(DeepSpeedConfigError, match="nvme_path"):
            _engine(tiered(param={"device": "nvme"}))

    def test_deep_prefetch_does_not_exhaust_pool(self, tmp_path,
                                                 offload_baseline):
        """prefetch_depth deeper than the default buffer pool: the
        swapper must be sized to the whole prefetch window (depth+1
        reads in flight), not crash mid-step with 'no free swap
        buffers'."""
        from deeperspeed_tpu.runtime.swap_tensor.aio_engine import \
            AsyncIOEngine
        if not AsyncIOEngine.available():
            pytest.skip("aio engine unavailable")
        e = _engine(tiered(depth=5, group=1,
                           param={"device": "nvme",
                                  "nvme_path": str(tmp_path)}))
        got = _train(e)
        np.testing.assert_allclose(got, offload_baseline, **TOL)

    def test_optimizer_nvme_tier(self, tmp_path, offload_baseline):
        """fp32 masters/moments on NVMe (pipelined optimizer swapper)
        under the tiered executor: the emit branch must compose with
        the swapper's load->step->store cycle."""
        from deeperspeed_tpu.runtime.swap_tensor.aio_engine import \
            AsyncIOEngine
        if not AsyncIOEngine.available():
            pytest.skip("aio engine unavailable")
        e = _engine(tiered(opt={"device": "nvme",
                                "nvme_path": str(tmp_path)}))
        got = _train(e)
        np.testing.assert_allclose(got, offload_baseline, **TOL)


# ---------------------------------------------------------------------------
# checkpoint: offloaded state rides save/resume bit-exact
# ---------------------------------------------------------------------------

class TestTieredCheckpoint:
    def test_save_resume_bit_exact(self, tmp_path, devices):
        e = _engine(tiered())
        _train(e, steps=2)
        e.save_checkpoint(str(tmp_path), tag="t2")
        cont = _train(e, steps=2, seed=9)

        e2 = _engine(tiered(), seed=5)   # different init — must not matter
        e2.load_checkpoint(str(tmp_path), tag="t2")
        cont2 = _train(e2, steps=2, seed=9)
        np.testing.assert_array_equal(cont, cont2)
        # masters AND Adam moments bit-exact after the resumed steps
        for field in ("master", "m", "v"):
            np.testing.assert_array_equal(
                np.concatenate([x.ravel()
                                for x in e._host_state[field]]),
                np.concatenate([x.ravel()
                                for x in e2._host_state[field]]))

    def test_gathered_parameters_updates_store(self, devices):
        e = _engine(tiered())
        before = _train(e, steps=1)
        with e.gathered_parameters() as view:
            view["embed"]["wte"][:] = 0.0
        natural = e.params_to_natural(e.state.params)
        np.testing.assert_array_equal(
            np.asarray(natural["embed"]["wte"]), 0.0)
        # and training continues from the edited weights
        _train(e, steps=1)


# ---------------------------------------------------------------------------
# telemetry: Train/Offload/* + param_wait + MFU for the offload tiers
# ---------------------------------------------------------------------------

TEL = {"telemetry": {"enabled": True, "goodput": True, "mfu": True}}


class TestTieredTelemetry:
    def test_offload_scalars_and_mfu(self, devices):
        e = _engine({**tiered(), **TEL})
        price_mfu_against_v5e(e)
        rec = Recorder()
        e.telemetry.monitor = rec
        _train(e, steps=2)
        h2d = rec.series("Train/Offload/bytes_h2d")
        d2h = rec.series("Train/Offload/bytes_d2h")
        stall = rec.series("Train/Offload/prefetch_stall_ms")
        assert h2d and h2d[0] > 0
        assert d2h and d2h[0] > 0
        assert stall and stall[0] >= 0.0
        # fwd uploads + bwd re-uploads + head: h2d exceeds one model copy
        model_bytes = sum(
            int(np.prod(np.shape(l))) * 4
            for l in jax.tree_util.tree_leaves(e.params_natural_like()))
        assert h2d[0] > model_bytes
        mfu = rec.series("Train/Samples/mfu")
        assert mfu and mfu[0] > 0
        # prefetch stalls land in the param_wait goodput bucket
        assert rec.series("Train/Goodput/param_wait_s")

    def test_eval_does_not_inflate_next_step_scalars(self, devices):
        """An eval_batch between train steps must not leak its flops /
        wire bytes into the next train step's MFU and Train/Offload/*
        scalars."""
        e = _engine({**tiered(), **TEL})
        rec = Recorder()
        e.telemetry.monitor = rec
        _train(e, steps=2)
        h2d_clean = rec.series("Train/Offload/bytes_h2d")[-1]
        toks = np.random.default_rng(4).integers(0, 128, (BATCH, SEQ),
                                                 np.int32)
        e.eval_batch((toks, toks))
        _train(e, steps=1)
        h2d_after_eval = rec.series("Train/Offload/bytes_h2d")[-1]
        assert h2d_after_eval == h2d_clean

    def test_host_offload_tier_reports_mfu(self, devices):
        """PR 6 left host-offload tiers at MFU `none`; the grads-step
        AOT harvest fixes the bench comparability gap."""
        e = _engine({**OFFLOAD_BASE, **TEL})
        price_mfu_against_v5e(e)
        rec = Recorder()
        e.telemetry.monitor = rec
        _train(e, steps=2)
        mfu = rec.series("Train/Samples/mfu")
        assert mfu and mfu[0] > 0

    def test_streamed_tier_reports_mfu(self, devices):
        e = _engine({"zero_optimization": {
            "stage": 3, "offload_optimizer": {"device": "cpu"},
            "offload_param": {"device": "cpu"}}, **TEL})
        price_mfu_against_v5e(e)
        rec = Recorder()
        e.telemetry.monitor = rec
        _train(e, steps=2)
        mfu = rec.series("Train/Samples/mfu")
        assert mfu and mfu[0] > 0


# ---------------------------------------------------------------------------
# config / engine rejection surface
# ---------------------------------------------------------------------------

class TestTieredRejects:
    def test_explicit_with_optimizer_only_offload(self, devices):
        with pytest.raises(DeepSpeedConfigError, match="offload_param"):
            _engine({"zero_optimization": {
                "stage": 3, "offload_optimizer": {"device": "cpu"},
                "schedule": {"mode": "explicit"}}})

    def test_model_without_hook(self, devices):
        def loss_fn(params, batch, rng):
            toks = batch[0] if isinstance(batch, tuple) else batch
            return jnp.mean(params["w"] * toks.sum())

        with pytest.raises(DeepSpeedConfigError,
                           match="build_tiered_offload_step"):
            deeperspeed_tpu.initialize(
                model=loss_fn,
                model_parameters={"w": np.ones((4,), np.float32)},
                config_params={
                    "train_batch_size": BATCH,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    **tiered()})

    @pytest.mark.parametrize("block,msg", [
        ({"device": "cpu", "bogus": 1}, "Unknown"),
        ({"device": "dram"}, "must be one of"),
        ({"device": "cpu", "buffer_count": 0}, "positive"),
        ({"device": "cpu", "buffer_size": -5}, "positive"),
        ({"device": "cpu", "pin_memory": "yes"}, "boolean"),
        ("cpu", "dict"),
    ])
    def test_offload_param_block_strict(self, block, msg):
        from deeperspeed_tpu.runtime.config import DeepSpeedConfig
        with pytest.raises(DeepSpeedConfigError, match=msg):
            DeepSpeedConfig(None, param_dict={
                "train_batch_size": 8,
                "zero_optimization": {"stage": 3,
                                      "offload_param": block}})

    @pytest.mark.parametrize("block,msg", [
        ({"device": "cpu", "nope": True}, "Unknown"),
        ({"device": 3}, "must be one of"),
        ({"device": "cpu", "buffer_count": -1}, "positive"),
        ({"device": "cpu", "pipeline_read": "on"}, "boolean"),
    ])
    def test_offload_optimizer_block_strict(self, block, msg):
        from deeperspeed_tpu.runtime.config import DeepSpeedConfig
        with pytest.raises(DeepSpeedConfigError, match=msg):
            DeepSpeedConfig(None, param_dict={
                "train_batch_size": 8,
                "zero_optimization": {"stage": 3,
                                      "offload_optimizer": block}})
