"""Multi-slice training over DCN (docs/multislice.md): slice topology +
config validation, the DCN-aware wire policy (packed sign-byte EF
transport, fp32-over-DCN refusal), slice-granular heartbeat escalation,
the dcn_delay/slice_kill fault kinds, the supervisor's re-partition exit
code, the KV-transport capped-backoff re-probe, and the two-slice chaos
drill: slice_kill -> SliceLostError -> in-process checkpoint
re-partition with surviving slices never restarted and losses matching
an unfaulted reference from the resume point (ISSUE 19 acceptance)."""

import copy
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import deeperspeed_tpu
from deeperspeed_tpu.compat import shard_map
from deeperspeed_tpu.checkpoint import manifest as mf
from deeperspeed_tpu.elasticity import (SliceLostError,
                                        repartition_after_slice_loss)
from deeperspeed_tpu.elasticity import constants as ec
from deeperspeed_tpu.elasticity.config import (PoisonStepError,
                                               RestartBudgetExceededError)
from deeperspeed_tpu.elasticity.heartbeat import (InMemoryTransport,
                                                  PeerHealthMonitor)
from deeperspeed_tpu.elasticity.supervisor import (Supervisor,
                                                   write_progress)
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.parallel.multislice import (SliceTopology,
                                                 surviving_raw_config)
from deeperspeed_tpu.parallel.schedule import dcn_exposed_crossings
from deeperspeed_tpu.runtime.comm import compressed
from deeperspeed_tpu.runtime.config import DeepSpeedConfig
from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError
from deeperspeed_tpu.runtime.pipe import p2p
from deeperspeed_tpu.utils.kv_retry import RetryingKVTransport
from tests.simple_model import SimpleModel

pytestmark = pytest.mark.multislice

WORLD = 8
BATCH = 16
SEQ = 32


def tiny_cfg(num_layers=4):
    return GPTNeoXConfig(vocab_size=128, hidden_size=32,
                         num_layers=num_layers, num_heads=4,
                         max_seq_len=64)


def _hb(interval=0.05, warn=0.1, fail=0.18):
    return {"enabled": True, "interval_s": interval,
            "warn_after_s": warn, "fail_after_s": fail}


class FakeMonitor:
    def __init__(self):
        self.records = []

    def record(self, sample_count, scalars):
        self.records.append((sample_count, dict(scalars)))

    def scalar_series(self, key):
        return [s[key] for _, s in self.records if key in s]


def make_config(d):
    return DeepSpeedConfig(d)


def base_conf(**overrides):
    conf = {
        "train_batch_size": BATCH,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "steps_per_print": 10_000,
    }
    conf.update(overrides)
    return conf


def pipe_ms_conf(stages=4, slices=2, **overrides):
    return base_conf(
        pipeline={"stages": stages, "micro_batches": 4},
        multislice={"slices": slices}, **overrides)


def make_pipe_engine(conf, num_layers=4, seed=0):
    model = GPTNeoX(tiny_cfg(num_layers), use_pallas=False)
    params = model.init_params(jax.random.PRNGKey(seed))
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=params, config_params=conf)
    return engine


# ---------------------------------------------------------------------------
# config validation (checkpoint-block strictness)
# ---------------------------------------------------------------------------

class TestMultisliceConfig:
    def test_parses_defaults(self):
        cfg = make_config(pipe_ms_conf())
        ms = cfg.multislice_config
        assert ms["slices"] == 2
        assert ms["axis"] == "pipe"
        assert ms["names"] == ["slice0", "slice1"]
        assert ms["slice_peers"] is None
        assert ms["dcn"] == {"fp32_comm": False, "packed_wire": True,
                             "compress_dp_reduce": True}
        assert ms["survive_slice_loss"] is True

    def test_absent_block_is_none(self):
        assert make_config(base_conf()).multislice_config is None

    def test_parses_names_and_peers(self):
        conf = pipe_ms_conf()
        conf["multislice"].update(
            names=["east", "west"],
            slice_peers={"east": ["h0", "h1"], "west": ["h2"]},
            dcn={"fp32_comm": True}, survive_slice_loss=False)
        ms = make_config(conf).multislice_config
        assert ms["names"] == ["east", "west"]
        assert ms["slice_peers"] == {"east": ["h0", "h1"],
                                     "west": ["h2"]}
        assert ms["dcn"]["fp32_comm"] is True
        assert ms["survive_slice_loss"] is False

    @pytest.mark.parametrize("mutate,match", [
        (lambda m: m.update(slicez=2), "Unknown"),
        (lambda m: m.pop("slices"), "required"),
        (lambda m: m.update(slices=1), ">= 2"),
        (lambda m: m.update(axis="model"), "axis"),
        (lambda m: m.update(names=["a"]), "every slice"),
        (lambda m: m.update(names=["a", "a"]), "unique"),
        (lambda m: m.update(names=["a", ""]), "non-empty"),
        (lambda m: m.update(slice_peers={"nope": ["h"]}), "unknown"),
        (lambda m: m.update(names=["a", "b"],
                            slice_peers={"a": []}), "non-empty"),
        (lambda m: m.update(names=["a", "b"],
                            slice_peers={"a": ["h"], "b": ["h"]}),
         "exactly one"),
        (lambda m: m.update(dcn={"fp32": True}), "Unknown"),
        (lambda m: m.update(dcn={"fp32_comm": "yes"}), "boolean"),
        (lambda m: m.update(survive_slice_loss=1), "boolean"),
    ])
    def test_rejects_block_shape(self, mutate, match):
        conf = pipe_ms_conf()
        mutate(conf["multislice"])
        with pytest.raises(DeepSpeedConfigError, match=match):
            make_config(conf)

    def test_axis_pipe_needs_pipeline_block(self):
        with pytest.raises(DeepSpeedConfigError, match="pipeline"):
            make_config(base_conf(multislice={"slices": 2}))

    def test_slices_must_divide_stages(self):
        with pytest.raises(DeepSpeedConfigError, match="divide"):
            make_config(pipe_ms_conf(stages=4, slices=3))

    def test_survive_needs_two_stages_per_slice(self):
        """Losing a slice must leave a >= 2-stage pipeline — the
        checkpoint layout guard rejects pipeline -> sequential."""
        with pytest.raises(DeepSpeedConfigError, match=">= 2"):
            make_config(pipe_ms_conf(stages=2, slices=2))
        ok = pipe_ms_conf(stages=2, slices=2)
        ok["multislice"]["survive_slice_loss"] = False
        assert make_config(ok).multislice_config["slices"] == 2

    def test_axis_data_rejects_pipeline(self):
        conf = pipe_ms_conf()
        conf["multislice"]["axis"] = "data"
        with pytest.raises(DeepSpeedConfigError, match="unsupported"):
            make_config(conf)

    def test_axis_data_compress_needs_gradient_compression(self):
        conf = base_conf(multislice={"slices": 2, "axis": "data"})
        with pytest.raises(DeepSpeedConfigError,
                           match="gradient_compression"):
            make_config(conf)
        conf["quantization"] = {
            "gradient_compression": {"enabled": True}}
        assert make_config(conf).multislice_config["axis"] == "data"
        # compress off: no EF wire needed, plain dp reduction over DCN
        plain = base_conf(multislice={
            "slices": 2, "axis": "data",
            "dcn": {"compress_dp_reduce": False}})
        assert make_config(plain).multislice_config["axis"] == "data"

    def test_quantization_packed_wire_key(self):
        conf = base_conf(quantization={"gradient_compression": {
            "enabled": True, "packed_wire": True}})
        qz = make_config(conf).quantization_config
        assert qz["gradient_compression_packed"] is True
        off = base_conf(quantization={"gradient_compression": {
            "enabled": True}})
        assert make_config(off).quantization_config[
            "gradient_compression_packed"] is False


# ---------------------------------------------------------------------------
# SliceTopology + the exposed-crossing model (pure units)
# ---------------------------------------------------------------------------

class TestSliceTopology:
    def test_spans_and_boundaries(self):
        t = SliceTopology(["s0", "s1"], "pipe", n_stages=4)
        assert t.stage_spans == {"s0": (0, 2), "s1": (2, 4)}
        assert t.stage_boundaries == (1,)
        assert t.n_boundaries == 1
        assert t.slice_of_stage(0) == "s0"
        assert t.slice_of_stage(3) == "s1"
        with pytest.raises(ValueError):
            t.slice_of_stage(4)

    def test_three_way(self):
        t = SliceTopology(["a", "b", "c"], "pipe", n_stages=6)
        assert t.stage_boundaries == (1, 3)
        assert t.surviving(["b"]) == (["a", "c"], 4)

    def test_needs_divisible_stages(self):
        with pytest.raises(ValueError, match="divide"):
            SliceTopology(["a", "b"], "pipe", n_stages=5)

    def test_from_config_peer_map(self):
        ms = {"slices": 2, "axis": "pipe", "names": ["s0", "s1"],
              "slice_peers": {"s0": ["hA"], "s1": ["hB", "hC"]},
              "dcn": {}, "survive_slice_loss": True}
        t = SliceTopology.from_config(ms, {"stages": 4})
        assert t.slice_of_peer("hB") == "s1"
        assert t.slice_of_peer("COORDINATOR") is None
        assert t.peers_of("s1") == ["hB", "hC"]

    def test_surviving_errors(self):
        t = SliceTopology(["s0", "s1"], "pipe", n_stages=4)
        with pytest.raises(ValueError, match="unknown"):
            t.surviving(["s9"])
        with pytest.raises(ValueError, match="all slices"):
            t.surviving(["s0", "s1"])

    def test_exposed_crossings(self):
        t = SliceTopology(["s0", "s1"], "pipe", n_stages=4)
        # classic wire: every micro-batch's fwd+bwd hop is exposed
        assert t.exposed_crossings(8, 1) == 16
        # overlapped wire hides steady-state hops: one fill + one drain
        assert t.exposed_crossings(8, 2) == 2
        d = SliceTopology(["s0", "s1", "s2"], "data")
        assert d.exposed_crossings(8, 1) == 4

    def test_dcn_exposed_crossings_values(self):
        assert dcn_exposed_crossings(0, 8, 1, True) == 0
        assert dcn_exposed_crossings(1, 8, 1, True) == 16
        assert dcn_exposed_crossings(2, 4, 1, True) == 16
        assert dcn_exposed_crossings(1, 8, 2, True) == 2
        assert dcn_exposed_crossings(1, 8, 1, False) == 2

    def test_cross_slice_p2p_bytes(self):
        t = SliceTopology(["s0", "s1"], "pipe", n_stages=4)
        assert t.cross_slice_p2p_bytes(1000, 4) == 8000
        d = SliceTopology(["s0", "s1"], "data")
        assert d.cross_slice_p2p_bytes(1000, 4) == 0


class TestSurvivingRawConfig:
    def _conf(self):
        return pipe_ms_conf(
            training_health={"fault_injection": {"faults": [
                {"kind": "slice_kill", "step": 2, "slice": "slice1"},
                {"kind": "nan_grads", "step": 5}]}})

    def test_drop_to_single_slice(self):
        conf = self._conf()
        topo = SliceTopology(["slice0", "slice1"], "pipe", n_stages=4)
        surv = surviving_raw_config(conf, topo, ["slice1"])
        assert surv["pipeline"]["stages"] == 2
        assert "multislice" not in surv
        # multislice fault kinds pruned with the block; others kept
        faults = surv["training_health"]["fault_injection"]["faults"]
        assert faults == [{"kind": "nan_grads", "step": 5}]
        # the lost config is untouched (deep copy)
        assert conf["pipeline"]["stages"] == 4
        assert "multislice" in conf
        assert len(conf["training_health"]["fault_injection"]
                   ["faults"]) == 2

    def test_shrink_three_to_two(self):
        conf = pipe_ms_conf(stages=6, slices=3)
        conf["multislice"].update(
            names=["a", "b", "c"],
            slice_peers={"a": ["h0"], "b": ["h1"], "c": ["h2"]})
        topo = SliceTopology(["a", "b", "c"], "pipe", n_stages=6,
                             peer_map={"h0": "a", "h1": "b", "h2": "c"})
        surv = surviving_raw_config(conf, topo, ["b"])
        assert surv["pipeline"]["stages"] == 4
        ms = surv["multislice"]
        assert ms["slices"] == 2 and ms["names"] == ["a", "c"]
        assert ms["slice_peers"] == {"a": ["h0"], "c": ["h2"]}
        # the surviving config re-parses cleanly
        assert make_config(surv).multislice_config["names"] == ["a", "c"]

    def test_rejects_sub_two_stage_survivor(self):
        topo = SliceTopology(["a", "b"], "pipe", n_stages=2)
        conf = pipe_ms_conf(stages=2)
        with pytest.raises(ValueError, match="2 stages"):
            surviving_raw_config(conf, topo, ["b"])


# ---------------------------------------------------------------------------
# packed sign-byte wire: parity vs the dense transport (satellite 1)
# ---------------------------------------------------------------------------

class TestPackedWire:
    def _run(self, packed, S=20, valid_rows=None, seed=0):
        rng = np.random.default_rng(seed)
        xs = np.stack([rng.normal(size=(WORLD, S)).astype(np.float32)
                       for _ in range(WORLD)])
        errs = np.stack([rng.normal(size=(WORLD, S)).astype(np.float32)
                         * 0.1 for _ in range(WORLD)])
        valid = None
        if valid_rows is not None:
            valid = jnp.asarray(valid_rows, jnp.float32)
        mesh = Mesh(np.array(jax.devices()[:WORLD]), ("data",))

        def body(x, e):
            out, new_e = compressed.compressed_reduce_scatter(
                x[0], e[0], "data", WORLD, valid=valid, packed=packed)
            return out[None], new_e[None]

        f = jax.jit(shard_map(body, mesh=mesh,
                              in_specs=(P("data"), P("data")),
                              out_specs=(P("data"), P("data")),
                              check_vma=False))
        out, new_e = f(jnp.asarray(xs), jnp.asarray(errs))
        return np.asarray(out), np.asarray(new_e), xs, errs

    def test_packed_matches_dense_and_oracle(self):
        """The 8-signs-per-byte wire reconstructs the same ±scale values
        as the dense psum_scatter: outputs agree to summation order,
        the EF buffer is bit-identical, both match the host oracle."""
        dense_o, dense_e, xs, errs = self._run(False)
        packed_o, packed_e, _, _ = self._run(True)
        np.testing.assert_allclose(packed_o, dense_o,
                                   rtol=1e-5, atol=1e-5)
        # EF state computed BEFORE the collective: exactly equal, so
        # packed and dense resume states are interchangeable
        assert np.array_equal(packed_e, dense_e)
        ref_outs, ref_errs = compressed.compressed_reduce_scatter_host(
            [jnp.asarray(x) for x in xs], [jnp.asarray(e) for e in errs])
        for r in range(WORLD):
            np.testing.assert_allclose(packed_o[r],
                                       np.asarray(ref_outs[r]),
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(packed_e[r],
                                       np.asarray(ref_errs[r]),
                                       rtol=1e-5, atol=1e-5)

    def test_packed_parity_with_valid_mask(self):
        valid = np.ones((WORLD, 24), np.float32)
        valid[:, 20:] = 0.0          # flat-pad tail
        dense_o, dense_e, _, _ = self._run(False, S=24,
                                           valid_rows=valid, seed=3)
        packed_o, packed_e, _, _ = self._run(True, S=24,
                                             valid_rows=valid, seed=3)
        np.testing.assert_allclose(packed_o, dense_o,
                                   rtol=1e-5, atol=1e-5)
        assert np.array_equal(packed_e, dense_e)
        # pad lanes pinned to exactly 0 on the packed wire too
        assert np.array_equal(packed_o[:, 20:],
                              np.zeros_like(packed_o[:, 20:]))

    def test_module_default_pin(self):
        """packed=None defers to configure_packed_wire — the engine's
        per-init pin (same discipline as p2p.configure)."""
        try:
            compressed.configure_packed_wire(True)
            assert compressed.packed_wire_enabled()
            pin_o, pin_e, _, _ = self._run(None, seed=5)
            explicit_o, explicit_e, _, _ = self._run(True, seed=5)
            np.testing.assert_allclose(pin_o, explicit_o,
                                       rtol=1e-6, atol=1e-6)
            assert np.array_equal(pin_e, explicit_e)
        finally:
            compressed.configure_packed_wire(False)
        assert not compressed.packed_wire_enabled()


# ---------------------------------------------------------------------------
# p2p wire policy: fp32-over-DCN refusal (whole-wire, one dtype)
# ---------------------------------------------------------------------------

class TestP2PDcnPolicy:
    def test_fp32_refused_over_dcn(self):
        t = jnp.ones((4,), jnp.bfloat16)
        try:
            p2p.configure_multislice(boundaries=(1,), fp32_over_dcn=False)
            assert p2p.dcn_boundaries() == (1,)
            out, orig = p2p._maybe_upcast(t, True)
            assert out.dtype == jnp.bfloat16 and orig is None
            # allowed when the config opts in
            p2p.configure_multislice(boundaries=(1,), fp32_over_dcn=True)
            out, orig = p2p._maybe_upcast(t, True)
            assert out.dtype == jnp.float32 and orig == jnp.bfloat16
        finally:
            p2p.configure_multislice()
        assert p2p.dcn_boundaries() == ()
        out, orig = p2p._maybe_upcast(t, True)
        assert out.dtype == jnp.float32    # no DCN edge: upcast normal


# ---------------------------------------------------------------------------
# heartbeat monitor at slice granularity
# ---------------------------------------------------------------------------

def _monitor(**kw):
    defaults = dict(interval_s=1.0, warn_after_s=3.0, fail_after_s=6.0,
                    transport=InMemoryTransport(), clock=lambda: 0.0)
    defaults.update(kw)
    return PeerHealthMonitor("0", **defaults)


class TestSliceGranularHeartbeat:
    def test_failed_slices_and_status(self):
        mon = _monitor(peers=["a", "b", "c"])
        mon.set_slice_map({"a": "s0", "b": "s0", "c": "s1"})
        assert mon.slice_of("a") == "s0"
        assert mon.slice_of("COORDINATOR") is None
        assert mon.peers_in_slice("s0") == ["a", "b"]
        for p in ("a", "b", "c"):
            mon.transport.publish(p, {"serial": 1, "step": 0})
        mon.poll_once(now=0.0)
        assert mon.failed_slices == []
        # only b goes silent: its whole slice is the failure unit
        for now in (3.0, 7.0):
            for p in ("a", "c"):
                mon.transport.publish(p, {"serial": int(now), "step": 1})
            mon.poll_once(now=now)
        assert list(mon.failed) == ["b"]
        assert mon.failed_slices == ["s0"]
        status = mon.slice_status(now=7.0)
        assert status["s0"]["status"] == "dead"
        assert status["s0"]["dead"] == ["b"]
        assert status["s1"]["status"] == "ok"

    def test_kill_slice_stops_simulated_members(self):
        mon = _monitor()
        mon.set_slice_map({"a": "s0", "b": "s0"})
        for p in ("a", "b"):
            mon.ensure_simulated_peer(p)
        mon.poll_once(now=0.0)
        mon.kill_slice("s0")
        mon.poll_once(now=7.0)
        assert mon.failed_slices == ["s0"]
        assert sorted(mon.failed) == ["a", "b"]

    def test_kill_slice_without_simulated_members_raises(self):
        """A silently inert kill would pass the chaos drill without
        testing anything."""
        mon = _monitor(peers=["a"])
        mon.set_slice_map({"a": "s0"})
        with pytest.raises(KeyError, match="simulated"):
            mon.kill_slice("s0")
        with pytest.raises(KeyError):
            mon.kill_slice("sX")


# ---------------------------------------------------------------------------
# KV transport: capped-backoff re-probe after degrade (satellite 2)
# ---------------------------------------------------------------------------

class _FlakyTransport:
    def __init__(self):
        self.fail = True
        self.published = []
        self.calls = 0

    def publish(self, peer, payload):
        self.calls += 1
        if self.fail:
            raise RuntimeError("grpc blip")
        self.published.append((peer, payload))

    def read_all(self):
        self.calls += 1
        if self.fail:
            raise RuntimeError("grpc blip")
        return {"peer": {"serial": 1}}


class TestKVReprobe:
    def _wrapped(self, transport, now):
        return RetryingKVTransport(
            transport, attempts=2, backoff_base_s=0.0, backoff_cap_s=0.0,
            jitter=0.0, degrade_to_local=True, name="test-kv",
            sleep=lambda s: None, reprobe_base_s=10.0,
            reprobe_cap_s=40.0, clock=lambda: now["t"])

    def test_degrade_then_promote_back(self):
        """The fleet degrade is no longer permanent: a capped-backoff
        re-probe promotes back to the real transport on first
        success."""
        t = _FlakyTransport()
        now = {"t": 0.0}
        kv = self._wrapped(t, now)
        kv.publish("0", {"serial": 1})         # exhausts -> degrades
        assert kv.degraded and kv.error_count == 2
        # inside the probe backoff window: local store only, no probe
        now["t"] = 5.0
        before = t.calls
        kv.publish("0", {"serial": 2})
        assert t.calls == before and kv.reprobe_count == 0
        # past the deadline, still failing: ONE bare probe, backoff
        # doubles (10 -> 20 -> 40 -> capped 40)
        now["t"] = 11.0
        kv.read_all()
        assert kv.reprobe_count == 1 and kv.degraded
        now["t"] = 20.0                        # next probe at 11+20=31
        kv.read_all()
        assert kv.reprobe_count == 1
        # transport heals: the next due probe promotes back
        now["t"] = 32.0
        t.fail = False
        out = kv.read_all()
        assert out == {"peer": {"serial": 1}}
        assert not kv.degraded
        assert kv.recovered_count == 1
        # subsequent ops hit the REAL transport again
        kv.publish("0", {"serial": 3})
        assert t.published == [("0", {"serial": 3})]

    def test_promotion_via_publish_returning_none(self):
        """Promotion works through ops that legitimately return None
        (publish): the degraded flag, not the return value, decides."""
        t = _FlakyTransport()
        now = {"t": 0.0}
        kv = self._wrapped(t, now)
        kv.publish("0", {"serial": 1})
        assert kv.degraded
        now["t"] = 11.0
        t.fail = False
        assert kv.publish("0", {"serial": 2}) is None
        assert not kv.degraded
        assert t.published == [("0", {"serial": 2})]

    def test_heartbeat_posture_still_raises(self):
        t = _FlakyTransport()
        kv = RetryingKVTransport(t, attempts=2, backoff_base_s=0.0,
                                 backoff_cap_s=0.0, jitter=0.0,
                                 degrade_to_local=False,
                                 sleep=lambda s: None)
        with pytest.raises(RuntimeError, match="blip"):
            kv.read_all()
        assert not kv.degraded


# ---------------------------------------------------------------------------
# supervisor: EXIT_CODE_SLICE_REPARTITION is recovery, not a crash
# (satellite 3: re-partition must not consume the poison-step count)
# ---------------------------------------------------------------------------

class _FakeChild:
    def __init__(self, rc):
        self.rc = rc

    def poll(self):
        return self.rc

    def wait(self):
        return self.rc

    def terminate(self):
        pass


def scripted_popen(script):
    calls = []

    def popen(argv, env):
        step = script[min(len(calls), len(script) - 1)]
        calls.append(dict(env))
        return _FakeChild(step(env))
    popen.calls = calls
    return popen


def make_supervisor(tmp_path, script, **kw):
    defaults = dict(max_restarts=3, backoff_base_s=0.0,
                    backoff_max_s=0.0, backoff_jitter=0.0,
                    poison_step_threshold=3,
                    popen_fn=scripted_popen(script),
                    sleep_fn=lambda s: None)
    defaults.update(kw)
    return Supervisor(["train.py"], str(tmp_path / "state"), env={},
                      **defaults)


class TestSupervisorRepartitionExit:
    def test_slice_lost_error_shape(self):
        err = SliceLostError("slice gone", lost_slices=["s1"],
                             detected_at=12.5, peers=["hB"],
                             staleness_s=0.3)
        assert err.exit_code == ec.EXIT_CODE_SLICE_REPARTITION == 77
        assert err.lost_slices == ["s1"]
        # deliberately NOT SystemExit: recovery is in-process, an
        # uncaught escape should surface as a normal traceback
        assert not isinstance(err, SystemExit)
        assert isinstance(err, Exception)

    def test_repartition_exits_never_poison(self, tmp_path):
        """Repeated rc-77 at the SAME step books restarts and crash
        steps but bypasses the poison-step detector entirely: the step
        did not fail, the topology did."""
        state = tmp_path / "state"

        def repart(env):
            os.makedirs(state, exist_ok=True)
            write_progress(str(state), 11)
            return ec.EXIT_CODE_SLICE_REPARTITION

        sup = make_supervisor(tmp_path, [repart], max_restarts=3,
                              poison_step_threshold=2)
        with pytest.raises(RestartBudgetExceededError,
                           match="re-partition"):
            sup.run()
        assert sup.crash_steps == [11, 11, 11, 11]
        assert sup.exit_codes == [77, 77, 77, 77]

    def test_genuine_crash_counts_fresh_after_repartition(self, tmp_path):
        """rc-77 exits at step 11 must not pre-charge the poison counter:
        later genuine crashes at the same step count from 1."""
        state = tmp_path / "state"

        def exiting(rc):
            def run(env):
                os.makedirs(state, exist_ok=True)
                write_progress(str(state), 11)
                return rc
            return run

        sup = make_supervisor(
            tmp_path,
            [exiting(77), exiting(77), exiting(1), exiting(1),
             exiting(1)],
            max_restarts=10, poison_step_threshold=3)
        with pytest.raises(PoisonStepError, match="step 11"):
            sup.run()
        # 2 re-partitions + 2 genuine restarts; the third genuine
        # same-step crash trips the detector
        assert sup.restarts == 4
        assert sup.exit_codes == [77, 77, 1, 1, 1]
        assert sup.crash_steps == [11, 11, 11, 11, 11]


# ---------------------------------------------------------------------------
# engine wiring: arming, scalars, fault validation, dcn_delay
# ---------------------------------------------------------------------------

def _ms_drill_conf(tmp_path=None, faults=None, peers=True,
                   heartbeat=True):
    conf = base_conf(pipeline={"stages": 4, "micro_batches": 4})
    ms = {"slices": 2, "names": ["s0", "s1"]}
    if peers:
        ms["slice_peers"] = {"s0": ["hostA"], "s1": ["hostB"]}
    conf["multislice"] = ms
    if heartbeat:
        conf["elasticity"] = {"heartbeat": _hb()}
    if tmp_path is not None:
        conf["checkpoint"] = {"save_dir": str(tmp_path),
                              "async_save": False}
    if faults:
        conf["training_health"] = {"fault_injection": {"faults": faults}}
    return conf


class TestEngineMultislice:
    def test_arms_pins_and_scalars(self):
        engine = make_pipe_engine(_ms_drill_conf(heartbeat=False))
        try:
            assert engine._multislice is not None
            assert engine._multislice.stage_boundaries == (1,)
            assert p2p.dcn_boundaries() == (1,)
            engine.monitor = FakeMonitor()
            toks = np.zeros((1, BATCH, SEQ), np.int32)
            engine.train_batch(batch=(toks, toks))
            (crossings,) = engine.monitor.scalar_series(
                "Train/Multislice/dcn_exposed_crossings")
            # classic wire, 1 boundary, 4 micro-batches: 2*1*4
            assert crossings == 8.0
        finally:
            if engine.peer_monitor is not None:
                engine.peer_monitor.stop()
        # a following NON-multislice engine resets the process pins
        model = SimpleModel(hidden_dim=16)
        plain, *_ = deeperspeed_tpu.initialize(
            model=model,
            model_parameters=model.init_params(jax.random.PRNGKey(0)),
            config_params={"train_batch_size": 8,
                           "optimizer": {"type": "Adam",
                                         "params": {"lr": 0.01}}})
        assert p2p.dcn_boundaries() == ()
        assert not compressed.packed_wire_enabled()

    def test_multislice_faults_need_block(self):
        conf = base_conf(
            pipeline={"stages": 4, "micro_batches": 4},
            training_health={"fault_injection": {"faults": [
                {"kind": "dcn_delay", "step": 1, "seconds": 0.01}]}})
        with pytest.raises(DeepSpeedConfigError, match="multislice"):
            make_pipe_engine(conf)

    def test_slice_kill_needs_heartbeat(self):
        conf = _ms_drill_conf(
            faults=[{"kind": "slice_kill", "step": 1, "slice": "s1"}],
            heartbeat=False)
        with pytest.raises(DeepSpeedConfigError, match="heartbeat"):
            make_pipe_engine(conf)

    def test_slice_kill_rejects_unknown_slice(self):
        conf = _ms_drill_conf(
            faults=[{"kind": "slice_kill", "step": 1, "slice": "sX"}])
        with pytest.raises(DeepSpeedConfigError, match="unknown"):
            make_pipe_engine(conf)

    def test_slice_kill_needs_slice_peers(self):
        conf = _ms_drill_conf(
            faults=[{"kind": "slice_kill", "step": 1, "slice": "s1"}],
            peers=False)
        with pytest.raises(DeepSpeedConfigError, match="slice_peers"):
            make_pipe_engine(conf)

    def test_dcn_delay_charges_exposed_crossings(self, monkeypatch):
        """dcn_delay is schedule-aware: `seconds` per EXPOSED crossing
        (2 * boundaries * n_micro on the classic wire), slept host-side
        on the stall path."""
        conf = _ms_drill_conf(
            faults=[{"kind": "dcn_delay", "step": 1, "seconds": 0.02}],
            heartbeat=False)
        engine = make_pipe_engine(conf)
        sleeps = []
        monkeypatch.setattr(time, "sleep",
                            lambda s: sleeps.append(float(s)))
        toks = np.zeros((1, BATCH, SEQ), np.int32)
        engine.train_batch(batch=(toks, toks))     # step 0: no fault
        assert not any(s == pytest.approx(0.16) for s in sleeps)
        engine.train_batch(batch=(toks, toks))     # step 1: charged
        assert any(s == pytest.approx(0.16) for s in sleeps)
        assert engine._pending_dcn_delay_s == 0.0


# ---------------------------------------------------------------------------
# the two-slice chaos drill (tentpole acceptance)
# ---------------------------------------------------------------------------

class TestSliceLossChaosDrill:
    def test_slice_kill_repartitions_without_restart(self, tmp_path):
        """slice_kill -> SliceLostError at a step boundary (emergency
        checkpoint committed) -> repartition_after_slice_loss resumes
        the surviving slice as a 2-stage pipeline IN-PROCESS, with
        losses matching an unfaulted reference loading the same
        checkpoint, and bounded MTTR emitted as
        Train/Elastic/slice_mttr_s."""
        conf = _ms_drill_conf(tmp_path=tmp_path, faults=[
            {"kind": "slice_kill", "step": 2, "slice": "s1"}])
        engine = make_pipe_engine(conf)
        assert engine._multislice_survive
        rng = np.random.default_rng(7)
        toks = [rng.integers(0, 128, (1, BATCH, SEQ), np.int32)
                for _ in range(60)]
        detected = None
        with pytest.raises(SliceLostError) as ei:
            for t in toks:
                engine.train_batch(batch=(t, t))
                time.sleep(0.02)
        err = ei.value
        assert err.lost_slices == ["s1"]
        assert err.peers == ["hostB"]
        assert err.exit_code == ec.EXIT_CODE_SLICE_REPARTITION
        assert err.staleness_s and err.staleness_s > 0
        detected = err.detected_at
        assert detected is not None
        # the emergency checkpoint IS the re-partition source
        tags = [t for _, t in mf.committed_tags(str(tmp_path))]
        assert tags, "slice escalation must commit an emergency save"

        def factory(surv_cfg):
            return GPTNeoX(tiny_cfg(4), use_pallas=False)

        recovered, surv = repartition_after_slice_loss(
            err, conf, factory, str(tmp_path))
        try:
            assert surv["pipeline"]["stages"] == 2
            assert "multislice" not in surv
            assert surv["training_health"]["fault_injection"][
                "faults"] == []
            assert recovered._multislice is None
            assert recovered.pipeline_schedule["stages"] == 2
            # NO restart: same process, the original config untouched
            assert conf["pipeline"]["stages"] == 4

            # unfaulted reference: fresh 2-stage engine, same
            # checkpoint, same batches -> the drill's loss-parity bar
            ref_model = GPTNeoX(tiny_cfg(4), use_pallas=False)
            reference, *_ = deeperspeed_tpu.initialize(
                model=ref_model, config_params=copy.deepcopy(surv))
            try:
                path, _ = reference.load_checkpoint(str(tmp_path))
                assert path is not None
                assert reference.global_steps == recovered.global_steps
                resume = toks[:3]
                rec_losses = [float(recovered.train_batch(batch=(t, t)))
                              for t in resume]
                ref_losses = [float(reference.train_batch(batch=(t, t)))
                              for t in resume]
                np.testing.assert_allclose(rec_losses, ref_losses,
                                           rtol=1e-6)
            finally:
                if reference.peer_monitor is not None:
                    reference.peer_monitor.stop()

            # bounded MTTR emitted once at the first step boundary
            recovered.monitor = FakeMonitor()
            t = toks[3]
            recovered.train_batch(batch=(t, t))
            (mttr,) = recovered.monitor.scalar_series(
                "Train/Elastic/slice_mttr_s")
            assert 0.0 < mttr < 600.0
            assert recovered.monitor.scalar_series(
                "Train/Elastic/lost_slices") == [1.0]
            recovered.train_batch(batch=(t, t))
            assert len(recovered.monitor.scalar_series(
                "Train/Elastic/slice_mttr_s")) == 1
        finally:
            if recovered.peer_monitor is not None:
                recovered.peer_monitor.stop()


# ---------------------------------------------------------------------------
# satellite 3: dp change coinciding with a stage change must reconcile
# ---------------------------------------------------------------------------

class TestStagePlusDpChangeResume:
    def test_reconcile_survives_simultaneous_change(self, tmp_path):
        """stages 2 -> 4 on the 8-device mesh flips dp 4 -> 2 in the
        same resume: params re-partition through the natural layout and
        the dataloader reconciles (epoch/seed kept, offset reset)
        instead of erroring."""
        rng = np.random.default_rng(0)
        dataset = [(rng.integers(0, 128, (SEQ,), np.int32),) * 2
                   for _ in range(32)]
        model = GPTNeoX(tiny_cfg(4), use_pallas=False)
        saver, *_ = deeperspeed_tpu.initialize(
            model=model,
            model_parameters=model.init_params(jax.random.PRNGKey(0)),
            config_params=base_conf(
                pipeline={"stages": 2, "micro_batches": 4}),
            training_data=dataset)
        assert saver.dp_world_size == 4
        toks = np.zeros((1, BATCH, SEQ), np.int32)
        for _ in range(2):
            saver.train_batch(batch=(toks, toks))
        saver.training_dataloader.epoch = 1      # mid-stream identity
        saver.training_dataloader._batches_yielded = 1
        saver.save_checkpoint(str(tmp_path), tag="stage-dp")
        saved = jax.tree_util.tree_map(
            np.asarray, saver.params_to_natural(saver.state.params))

        # elastic shrink: half the hosts gone -> half the global batch,
        # AND the deeper re-partition (stages 2 -> 4 flips dp 4 -> 2).
        # The smaller global batch re-chunks the loader's index stream,
        # so the exact position restore must be REFUSED and reconciled.
        shrunk = base_conf(pipeline={"stages": 4, "micro_batches": 4})
        shrunk["train_batch_size"] = BATCH // 2
        model4 = GPTNeoX(tiny_cfg(4), use_pallas=False)
        resumed, *_ = deeperspeed_tpu.initialize(
            model=model4,
            model_parameters=model4.init_params(jax.random.PRNGKey(9)),
            config_params=shrunk, training_data=dataset)
        assert resumed.dp_world_size == 2
        path, _ = resumed.load_checkpoint(str(tmp_path), tag="stage-dp")
        assert path is not None
        got = jax.tree_util.tree_map(
            np.asarray, resumed.params_to_natural(resumed.state.params))
        jax.tree_util.tree_map(np.testing.assert_array_equal, saved, got)
        loader = resumed.training_dataloader
        assert loader.epoch == 1                 # identity preserved
        assert loader._resume_offset == 0        # offset reset
        assert loader.seed == saver.training_dataloader.seed
        half = np.zeros((1, BATCH // 2, SEQ), np.int32)
        assert np.isfinite(float(resumed.train_batch(
            batch=(half, half))))
