"""Unified telemetry (runtime/telemetry.py): span tracing, goodput
buckets, in-engine MFU, trigger-driven profiler capture — plus the
satellite fixes that ride with it (monotonic timers, Train/Timers
scalars, monitor post-close behavior)."""

import glob
import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deeperspeed_tpu
from deeperspeed_tpu.runtime import telemetry as tm
from deeperspeed_tpu.runtime.config import DeepSpeedConfig
from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError
from deeperspeed_tpu.runtime.monitor import TensorBoardMonitor
from deeperspeed_tpu.utils.timer import (SynchronizedWallClockTimer,
                                         ThroughputTimer)
from tests.simple_model import (SimpleModel, price_mfu_against_v5e,
                                random_batches, random_dataset)

HIDDEN = 16
BATCH = 8

pytestmark = [pytest.mark.telemetry]


def cfg(**overrides):
    base = {
        "train_batch_size": BATCH,
        "steps_per_print": 1000,
        "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
    }
    base.update(overrides)
    return base


def tel(**overrides):
    base = {"enabled": True}
    base.update(overrides)
    return base


def make_engine(config, seed=1, training_data=None):
    model = SimpleModel(hidden_dim=HIDDEN)
    params = model.init_params(jax.random.PRNGKey(seed))
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=params, config_params=config,
        training_data=training_data)
    return engine


def stack1(batch):
    return jax.tree_util.tree_map(lambda x: x[None], batch)


def _read_scalars(log_dir):
    """{tag: [(sample, value)]} from whatever backend wrote the events."""
    tsv = os.path.join(log_dir, "events.tsv")
    out = {}
    if os.path.isfile(tsv):  # pragma: no cover - fallback backend
        with open(tsv) as f:
            next(f)
            for line in f:
                tag, sample, value = line.rstrip("\n").split("\t")
                out.setdefault(tag, []).append((int(sample), float(value)))
        return out
    from tensorboard.backend.event_processing.event_accumulator import \
        EventAccumulator
    acc = EventAccumulator(log_dir)
    acc.Reload()
    for tag in acc.Tags()["scalars"]:
        out[tag] = [(ev.step, ev.value) for ev in acc.Scalars(tag)]
    return out


# ---------------------------------------------------------------------------
# config block validation (parse-time strictness)
# ---------------------------------------------------------------------------

def test_config_defaults_off():
    config = DeepSpeedConfig(cfg(), world_size=1)
    assert config.telemetry_enabled is False
    assert config.telemetry_config["enabled"] is False
    engine = make_engine(cfg())
    assert engine.telemetry is tm.NULL_TELEMETRY


@pytest.mark.parametrize("block, match", [
    ({"enabled": True, "bogus_knob": 1}, "bogus_knob"),
    ({"enabled": "yes"}, "boolean"),
    ({"enabled": True, "goodput": 1}, "boolean"),
    ({"enabled": True, "trace_dir": 7}, "trace_dir"),
    ({"enabled": True, "trace_dir": "/tmp/x",
      "capture": [1, 2]}, "object"),
    ({"enabled": True, "trace_dir": "/tmp/x",
      "capture": {"start_step": 1, "bogus": 2}}, "bogus"),
    ({"enabled": True, "trace_dir": "/tmp/x",
      "capture": {"num_steps": 2}}, "start_step"),
    ({"enabled": True, "trace_dir": "/tmp/x",
      "capture": {"start_step": -1}}, "start_step"),
    ({"enabled": True, "trace_dir": "/tmp/x",
      "capture": {"start_step": 1, "num_steps": 0}}, "num_steps"),
    ({"enabled": True, "memory_watermark_interval_steps": -1},
     "memory_watermark"),
    ({"enabled": True, "trace_dir": "/tmp/x",
      "anomaly_capture_steps": 0}, "anomaly_capture_steps"),
    ({"enabled": True, "capture_on_anomaly": "always"}, "boolean"),
])
def test_config_rejects_bad_values(block, match):
    with pytest.raises(DeepSpeedConfigError, match=match):
        DeepSpeedConfig(cfg(telemetry=block), world_size=1)


def test_config_unknown_key_lists_choices():
    with pytest.raises(DeepSpeedConfigError, match="valid keys"):
        DeepSpeedConfig(cfg(telemetry={"enalbed": True}), world_size=1)


def test_config_capture_requires_trace_dir():
    with pytest.raises(DeepSpeedConfigError, match="trace_dir"):
        DeepSpeedConfig(cfg(telemetry=tel(
            capture={"start_step": 0})), world_size=1)
    with pytest.raises(DeepSpeedConfigError, match="trace_dir"):
        DeepSpeedConfig(cfg(telemetry=tel(capture_on_anomaly=True)),
                        world_size=1)


def test_config_valid_block_parses(tmp_path):
    config = DeepSpeedConfig(cfg(telemetry=tel(
        trace_dir=str(tmp_path), capture={"start_step": 3, "num_steps": 2},
        memory_watermark_interval_steps=5, capture_on_anomaly=True,
        anomaly_capture_steps=2)), world_size=1)
    tc = config.telemetry_config
    assert tc["capture"] == {"start_step": 3, "num_steps": 2}
    assert tc["memory_watermark_interval_steps"] == 5
    assert tc["anomaly_capture_steps"] == 2
    assert tc["goodput"] and tc["mfu"] and tc["spans"]


# ---------------------------------------------------------------------------
# span tracer: nesting + chrome-trace export
# ---------------------------------------------------------------------------

def test_span_nesting_and_chrome_export(tmp_path):
    tracer = tm.SpanTracer(mirror_annotations=False)
    tracer.start_capture()
    with tracer.span("outer"):
        time.sleep(0.002)
        with tracer.span("inner"):
            time.sleep(0.002)
    events = tracer.stop_capture()
    assert [e[0] for e in events] == ["inner", "outer"]  # close order
    by_name = {e[0]: e for e in events}
    _, o_t0, o_dur, o_depth = by_name["outer"]
    _, i_t0, i_dur, i_depth = by_name["inner"]
    assert (o_depth, i_depth) == (0, 1)
    # containment: the inner span lies inside the outer interval
    assert o_t0 <= i_t0 and i_t0 + i_dur <= o_t0 + o_dur + 1e-6

    path = tm.SpanTracer.export_chrome_trace(
        events, str(tmp_path / "spans.json"), pid=3)
    with open(path) as f:
        trace = json.load(f)
    assert len(trace["traceEvents"]) == 2
    for ev in trace["traceEvents"]:
        assert ev["ph"] == "X" and ev["pid"] == 3
        assert ev["dur"] > 0 and ev["ts"] > 0   # microseconds


def test_span_phase_accumulation_without_capture():
    tracer = tm.SpanTracer(mirror_annotations=False)
    with tracer.span("data_fetch"):
        time.sleep(0.001)
    with tracer.span("data_fetch"):
        time.sleep(0.001)
    phases = tracer.drain_phases()
    assert phases["data_fetch"] >= 0.002
    assert tracer.drain_phases() == {}          # drained
    assert tracer.stop_capture() == []          # nothing buffered


# ---------------------------------------------------------------------------
# goodput bucket arithmetic
# ---------------------------------------------------------------------------

def test_goodput_meter_buckets():
    meter = tm.GoodputMeter()
    meter.account(1.0, "ok", data_wait=0.2, ckpt_stall=0.3)
    meter.account(2.0, "quarantined")
    meter.account(1.0, "overflow")              # folds into quarantined
    meter.account(1.5, "rollback", data_wait=0.5)
    b = meter.buckets
    assert b["productive"] == pytest.approx(0.5)
    assert b["data_wait"] == pytest.approx(0.7)
    assert b["ckpt_stall"] == pytest.approx(0.3)
    assert b["quarantined"] == pytest.approx(3.0)
    assert b["rollback"] == pytest.approx(1.0)
    assert meter.total == pytest.approx(5.5)
    assert meter.fraction == pytest.approx(0.5 / 5.5)
    scalars = meter.scalars()
    assert scalars["Train/Goodput/fraction"] == meter.fraction
    assert set(scalars) == {f"Train/Goodput/{n}_s"
                            for n in tm.GOODPUT_BUCKETS} | \
        {"Train/Goodput/fraction"}


def test_goodput_meter_clamps_overlong_phases():
    meter = tm.GoodputMeter()
    # a data-fetch span longer than the step window (clock skew between
    # measurements) must not drive productive time negative
    meter.account(1.0, "ok", data_wait=5.0, ckpt_stall=5.0)
    assert meter.buckets["data_wait"] == pytest.approx(1.0)
    assert meter.buckets["ckpt_stall"] == 0.0
    assert meter.buckets["productive"] == 0.0
    assert meter.total == pytest.approx(1.0)


@pytest.mark.fault_injection
def test_goodput_scripted_sequence_quarantine(tmp_path, devices):
    """Scripted step sequence through the fault-injection harness: 3
    healthy steps, 1 quarantined (injected NaN grads under skip_batch),
    2 more healthy — bucket arithmetic must match the script."""
    engine = make_engine(cfg(
        tensorboard={"enabled": True, "output_path": str(tmp_path),
                     "job_name": "unit"},
        telemetry=tel(),
        training_health={"enabled": True, "policy": "skip_batch",
                         "warmup_steps": 100,
                         "fault_injection": {"faults": [
                             {"kind": "nan_grads", "step": 3}]}},
    ), training_data=random_dataset(64, HIDDEN))
    it = iter(engine.training_dataloader)
    for _ in range(6):
        engine.train_batch(data_iter=it)
    assert engine.sentinel.quarantined == 1

    buckets = engine.telemetry.goodput.buckets
    assert buckets["productive"] > 0
    assert buckets["quarantined"] > 0
    assert buckets["data_wait"] >= 0
    assert buckets["rollback"] == 0.0
    total = engine.telemetry.goodput.total
    assert total == pytest.approx(sum(buckets.values()))
    assert 0 < engine.telemetry.goodput.fraction < 1

    engine.monitor.flush()
    scalars = _read_scalars(os.path.join(str(tmp_path), "unit"))
    assert len(scalars["Train/Goodput/fraction"]) == 6
    # the monitor series carries the same final values as the meter
    assert scalars["Train/Goodput/quarantined_s"][-1][1] == \
        pytest.approx(buckets["quarantined"], rel=1e-5)


@pytest.mark.fault_injection
def test_goodput_rollback_bucket(tmp_path, devices):
    """A rollback step's wall time (detect + restore-checkpoint) lands
    in the rollback bucket, and the restore itself is spanned."""
    engine = make_engine(cfg(
        checkpoint={"save_dir": str(tmp_path / "ckpt")},
        telemetry=tel(),
        training_health={"enabled": True, "policy": "rollback",
                         "rollback_after": 1, "warmup_steps": 100,
                         "fault_injection": {"faults": [
                             {"kind": "nan_grads", "step": 4}]}},
    ))
    batches = list(random_batches(6, BATCH, HIDDEN, seed=3))
    for b in batches[:4]:
        engine.train_batch(batch=stack1(b))
    engine.save_checkpoint(str(tmp_path / "ckpt"))
    engine.train_batch(batch=stack1(batches[4]))   # fault -> rollback
    assert engine.sentinel.rollbacks == 1
    buckets = engine.telemetry.goodput.buckets
    assert buckets["rollback"] > 0
    assert buckets["productive"] > 0
    productive_before = float(buckets["productive"])
    engine.train_batch(batch=stack1(batches[5]))   # recovers
    assert engine.telemetry.goodput.buckets["productive"] > \
        productive_before


def test_goodput_counts_ckpt_snapshot_stall(tmp_path, devices):
    """An auto-save inside the step window charges its snapshot stall to
    the ckpt_stall bucket (read as deltas of the manager's counter)."""
    engine = make_engine(cfg(
        checkpoint={"save_dir": str(tmp_path / "ckpt"),
                    "save_interval_steps": 2},
        telemetry=tel(),
    ))
    batches = list(random_batches(5, BATCH, HIDDEN, seed=3))
    for b in batches:
        engine.train_batch(batch=stack1(b))
    engine.checkpoint_manager.wait()
    assert engine.checkpoint_manager.saves_completed >= 1
    assert engine.telemetry.goodput.buckets["ckpt_stall"] > 0


# ---------------------------------------------------------------------------
# in-engine MFU
# ---------------------------------------------------------------------------

def test_mfu_flops_match_profile_fn(tmp_path, devices):
    """The per-variant flops the telemetry layer harvests from the AOT
    executable agree with `profile_fn` cost-analyzing the same step
    body, and the emitted MFU scalar is exactly flops/step_time/peak."""
    from deeperspeed_tpu.profiling.flops_profiler.profiler import \
        profile_fn
    from deeperspeed_tpu.profiling.hardware import peak_flops_per_chip

    engine = make_engine(cfg(
        tensorboard={"enabled": True, "output_path": str(tmp_path),
                     "job_name": "unit"},
        telemetry=tel(),
    ))
    price_mfu_against_v5e(engine)
    batches = list(random_batches(3, BATCH, HIDDEN, seed=3))
    for b in batches:
        engine.train_batch(batch=stack1(b))
    flops = engine.telemetry.compiled_flops.get(1)
    assert flops and flops > 0

    sharded = engine._shard_stacked_batch(stack1(batches[0]))
    lr = jnp.asarray(0.01, jnp.float32)
    ref = profile_fn(engine._build_train_step(1).__wrapped__,
                     engine.state, sharded, jax.random.PRNGKey(0), lr,
                     n_timing_iters=1)
    assert ref["flops"] > 0
    assert abs(flops - ref["flops"]) / ref["flops"] < 0.02

    engine.monitor.flush()
    scalars = _read_scalars(os.path.join(str(tmp_path), "unit"))
    mfu = scalars["Train/Samples/mfu"]
    assert len(mfu) == 3
    assert all(v > 0 for _, v in mfu)
    # scalar consistency: mfu * peak * step_time == flops (same series)
    peak = peak_flops_per_chip("TPU v5 lite")
    tflops = scalars["Train/Samples/achieved_tflops"]
    for (_, m), (_, t) in zip(mfu, tflops):
        assert m == pytest.approx(t * 1e12 / peak, rel=1e-4)


def test_mfu_left_out_on_a_device_outside_the_table(tmp_path, devices):
    """The CPU is not in `profiling.hardware`: the lookup raises, and
    the engine emits achieved TFLOP/s without an MFU priced against
    some other chip's peak."""
    from deeperspeed_tpu.profiling.hardware import (
        ici_bandwidth_per_chip, peak_flops_per_chip)
    for lookup in (peak_flops_per_chip, ici_bandwidth_per_chip):
        with pytest.raises(ValueError, match="never a default"):
            lookup(jax.devices()[0])
        assert lookup("TPU v5 lite") > 0
    engine = make_engine(cfg(
        tensorboard={"enabled": True, "output_path": str(tmp_path),
                     "job_name": "unit"},
        telemetry=tel(),
    ))
    for b in random_batches(2, BATCH, HIDDEN, seed=3):
        engine.train_batch(batch=stack1(b))
    engine.monitor.flush()
    scalars = _read_scalars(os.path.join(str(tmp_path), "unit"))
    assert len(scalars["Train/Samples/achieved_tflops"]) == 2
    assert "Train/Samples/mfu" not in scalars


def test_mfu_aot_survives_sharding_settle(tmp_path, devices):
    """ZeRO-2 on the 8-device mesh: GSPMD may settle the donated state
    onto different output shardings than the first-call compile, and a
    checkpoint restore re-places state the same way — the AOT step must
    degrade to the jit wrapper (as the telemetry-off path would retrace)
    instead of dying on the sharding-mismatch check."""
    engine = make_engine(cfg(zero_optimization={"stage": 2},
                             telemetry=tel()))
    batches = list(random_batches(5, BATCH, HIDDEN, seed=3))
    first = float(engine.train_batch(batch=stack1(batches[0])))
    engine.train_batch(batch=stack1(batches[1]))
    engine.save_checkpoint(str(tmp_path / "ck"))
    engine.load_checkpoint(str(tmp_path / "ck"))
    for b in batches[2:]:
        engine.train_batch(batch=stack1(b))
    assert engine.global_steps == 5
    assert engine.telemetry.compiled_flops.get(1, 0) > 0
    assert np.isfinite(first)


@pytest.mark.parametrize("exc", [ValueError, TypeError])
def test_aot_step_falls_back_once_on_input_mismatch(exc):
    """The Compiled input checks raise ValueError (sharding/layout) or
    TypeError (aval mismatch) BEFORE executing; _AOTStep must degrade to
    the rebuilt jit wrapper exactly once and stay there."""
    calls = {"compiled": 0, "rebuilt": 0, "rebuild": 0}

    def compiled(*args):
        calls["compiled"] += 1
        raise exc("Argument types differ from the types for which this "
                  "computation was compiled")

    def rebuild():
        calls["rebuild"] += 1
        def jit_fn(*args):
            calls["rebuilt"] += 1
            return sum(args)
        return jit_fn

    step = tm._AOTStep(compiled, rebuild)
    assert step(1, 2) == 3
    assert step(3, 4) == 7
    assert calls == {"compiled": 1, "rebuild": 1, "rebuilt": 2}


def test_aot_step_propagates_execution_errors():
    """Errors that are not input-validation failures pass through —
    donated buffers may already be consumed, so no retry."""
    def compiled(*args):
        raise RuntimeError("device OOM")

    step = tm._AOTStep(compiled, lambda: (lambda *a: 0))
    with pytest.raises(RuntimeError, match="OOM"):
        step(1)


def test_goodput_data_wait_survives_spans_off(devices):
    """`spans: false` disables annotation mirroring/export only — the
    goodput meter's data_wait bucket must still see the data_fetch
    phase, or input-pipeline stalls silently read as productive."""
    engine = make_engine(cfg(telemetry=tel(spans=False)),
                         training_data=random_dataset(64, HIDDEN))

    def slow_iter(it):
        while True:
            time.sleep(0.01)
            yield next(it)

    it = slow_iter(iter(engine.training_dataloader))
    for _ in range(2):
        engine.train_batch(data_iter=it)
    assert engine.telemetry.goodput.buckets["data_wait"] >= 0.02
    assert engine.telemetry.exported_traces == []   # no span export


def test_close_flushes_open_window_and_releases_trace(tmp_path, devices):
    """A run ending mid-window must still export the spans, stop the
    jax trace, and release the process-wide active-trace flag for later
    engines (close() is atexit-registered, like the monitor's)."""
    trace_dir = str(tmp_path / "traces")
    engine = make_engine(cfg(telemetry=tel(
        trace_dir=trace_dir, capture={"start_step": 0,
                                      "num_steps": 100})))
    assert callable(engine.telemetry._atexit)
    engine.train_batch(batch=stack1(next(
        iter(random_batches(1, BATCH, HIDDEN, seed=3)))))
    assert engine.telemetry._window_open     # 99 steps still to go
    engine.telemetry.close()
    assert not engine.telemetry._window_open
    assert not tm._TRACE_ACTIVE
    [path] = engine.telemetry.exported_traces
    with open(path) as f:
        assert json.load(f)["traceEvents"]
    engine.telemetry.close()                 # idempotent


def test_collected_mid_window_releases_trace(tmp_path, devices):
    """A Telemetry garbage-collected with a capture window open (bench
    ladders delete failed engines and retry) must stop the jax trace it
    started and release the process-wide flag via its finalizer."""
    import gc
    tel_obj = tm.Telemetry(trace_dir=str(tmp_path / "tr"),
                           capture={"start_step": 0, "num_steps": 100})
    tel_obj.on_step_start(0)          # opens the window, starts a trace
    assert tel_obj._wstate["started_jax"] and tm._TRACE_ACTIVE
    wstate = tel_obj._wstate
    del tel_obj
    gc.collect()
    assert not tm._TRACE_ACTIVE
    assert not wstate["started_jax"]


def test_spans_off_window_skips_span_export(tmp_path, devices):
    """spans: false disables span capture/export; a scheduled window
    still drives the jax profiler trace."""
    trace_dir = str(tmp_path / "traces")
    engine = make_engine(cfg(telemetry=tel(
        spans=False, trace_dir=trace_dir,
        capture={"start_step": 0, "num_steps": 1})))
    for b in random_batches(2, BATCH, HIDDEN, seed=3):
        engine.train_batch(batch=stack1(b))
    assert engine.telemetry.exported_traces == []
    assert not glob.glob(os.path.join(trace_dir, "spans_*"))
    assert os.listdir(trace_dir)      # the jax capture landed


def test_mfu_covers_train_steps_window(tmp_path, devices):
    engine = make_engine(cfg(
        tensorboard={"enabled": True, "output_path": str(tmp_path),
                     "job_name": "unit"},
        telemetry=tel(),
    ))
    price_mfu_against_v5e(engine)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(3, 1, BATCH, HIDDEN)).astype(np.float32)
    y = rng.normal(size=(3, 1, BATCH, 1)).astype(np.float32)
    engine.train_steps((x, y))
    key = ("window", 1, 3)
    assert engine.telemetry.compiled_flops.get(key, 0) > 0
    engine.monitor.flush()
    scalars = _read_scalars(os.path.join(str(tmp_path), "unit"))
    assert len(scalars["Train/Samples/mfu"]) == 1


# ---------------------------------------------------------------------------
# trigger-driven capture
# ---------------------------------------------------------------------------

def test_scheduled_capture_window_exports(tmp_path, devices):
    trace_dir = str(tmp_path / "traces")
    engine = make_engine(cfg(telemetry=tel(
        trace_dir=trace_dir, capture={"start_step": 1, "num_steps": 1})))
    for b in random_batches(3, BATCH, HIDDEN, seed=3):
        engine.train_batch(batch=stack1(b))
    [path] = engine.telemetry.exported_traces
    assert os.path.basename(path) == "spans_step1.json"
    with open(path) as f:
        trace = json.load(f)
    names = {ev["name"] for ev in trace["traceEvents"]}
    assert "train_dispatch" in names and "h2d" in names
    # the jax profiler wrote its capture alongside the span export
    assert len(os.listdir(trace_dir)) >= 2


def test_memory_watermark_scalars(tmp_path, devices):
    engine = make_engine(cfg(
        tensorboard={"enabled": True, "output_path": str(tmp_path),
                     "job_name": "unit"},
        telemetry=tel(memory_watermark_interval_steps=2),
    ))
    for b in random_batches(4, BATCH, HIDDEN, seed=3):
        engine.train_batch(batch=stack1(b))
    engine.monitor.flush()
    scalars = _read_scalars(os.path.join(str(tmp_path), "unit"))
    # CPU devices report no memory_stats — the series may be absent, but
    # the plumbing must not crash; on TPU it carries 2 points here
    hbm = scalars.get("Train/Memory/hbm_bytes_in_use", [])
    assert len(hbm) in (0, 2)


@pytest.mark.fault_injection
def test_anomaly_capture_fires_once_per_episode(tmp_path, devices):
    """Two separate anomaly episodes -> two captures; consecutive
    anomalous steps within one episode -> one capture."""
    trace_dir = str(tmp_path / "traces")
    engine = make_engine(cfg(
        telemetry=tel(trace_dir=trace_dir, capture_on_anomaly=True),
        training_health={"enabled": True, "policy": "skip_batch",
                         "warmup_steps": 100,
                         "fault_injection": {"faults": [
                             {"kind": "nan_grads", "step": 2},
                             {"kind": "nan_grads", "step": 5}]}},
    ))
    for b in random_batches(8, BATCH, HIDDEN, seed=3):
        engine.train_batch(batch=stack1(b))
    assert engine.sentinel.anomalies == 2
    assert engine.telemetry.anomaly_captures == 2
    snapshots = glob.glob(os.path.join(trace_dir, "memory_anomaly_*"))
    assert len(snapshots) == 2
    with open(snapshots[0]) as f:
        snap = json.load(f)
    assert "devices" in snap and len(snap["devices"]) >= 1
    # each episode's armed window exported a loadable span trace
    span_files = glob.glob(os.path.join(trace_dir, "spans_anomaly_*"))
    assert len(span_files) == 2
    for path in span_files:
        with open(path) as f:
            assert json.load(f)["traceEvents"]


@pytest.mark.fault_injection
def test_anomaly_capture_coalesces_consecutive_steps(tmp_path, devices):
    trace_dir = str(tmp_path / "traces")
    engine = make_engine(cfg(
        telemetry=tel(trace_dir=trace_dir, capture_on_anomaly=True),
        training_health={"enabled": True, "policy": "skip_batch",
                         "warmup_steps": 100, "abort_after": 100,
                         "fault_injection": {"faults": [
                             {"kind": "nan_grads", "step": 2,
                              "times": 3}]}},
    ))
    for b in random_batches(7, BATCH, HIDDEN, seed=3):
        engine.train_batch(batch=stack1(b))
    assert engine.sentinel.anomalies == 3
    assert engine.telemetry.anomaly_captures == 1   # one episode


# ---------------------------------------------------------------------------
# zero-overhead path
# ---------------------------------------------------------------------------

def test_absent_block_is_null_telemetry(tmp_path, devices):
    engine = make_engine(cfg(
        tensorboard={"enabled": True, "output_path": str(tmp_path),
                     "job_name": "unit"}))
    assert engine.telemetry is tm.NULL_TELEMETRY
    assert engine.telemetry.enabled is False
    # the null span is one shared object — no per-call allocation
    assert engine.telemetry.span("a") is engine.telemetry.span("b")
    for b in random_batches(2, BATCH, HIDDEN, seed=3):
        engine.train_batch(batch=stack1(b))
    # no AOT compile, no flops harvest, no goodput/mfu scalars
    assert engine._step_flops == {}
    engine.monitor.flush()
    scalars = _read_scalars(os.path.join(str(tmp_path), "unit"))
    assert not any(t.startswith(("Train/Goodput", "Train/Memory"))
                   or t == "Train/Samples/mfu" for t in scalars)


def test_disabled_block_is_null_telemetry():
    engine = make_engine(cfg(telemetry={"enabled": False}))
    assert engine.telemetry is tm.NULL_TELEMETRY


# ---------------------------------------------------------------------------
# satellite: timers
# ---------------------------------------------------------------------------

def test_throughput_timer_no_inf_before_warmup():
    timer = ThroughputTimer(batch_size=4, start_step=2)
    assert timer.avg_samples_per_sec() == 0.0   # was float("-inf")
    logs = []
    timer.logging = logs.append
    timer.steps_per_output = 1
    for _ in range(2):                          # still inside warmup
        timer.start()
        timer.stop()
    assert timer.avg_samples_per_sec() == 0.0
    assert not any("-inf" in line or "inf" in line for line in logs)
    for _ in range(3):
        timer.start()
        time.sleep(0.001)
        timer.stop()
    assert timer.avg_samples_per_sec() > 0


def test_timers_use_monotonic_clock(monkeypatch):
    """A wall-clock jump (NTP slew) mid-span must not corrupt elapsed:
    the timers may not consult time.time() at all."""
    def boom():
        raise AssertionError("timer consulted the wall clock")

    monkeypatch.setattr(time, "time", boom)
    timer = SynchronizedWallClockTimer.Timer("t")
    timer.start()
    timer.stop()
    assert timer.elapsed(reset=True) >= 0
    tput = ThroughputTimer(batch_size=4, start_step=0)
    tput.start()
    tput.stop(report_speed=False)
    assert tput.total_elapsed_time >= 0


def test_wall_clock_breakdown_timers_reach_monitor(tmp_path, devices):
    """wall_clock_breakdown timer values land as Train/Timers/<name>_ms
    scalars keyed by the same sample counts as the loss series (they
    were log-only text before)."""
    engine = make_engine(cfg(
        wall_clock_breakdown=True,
        tensorboard={"enabled": True, "output_path": str(tmp_path),
                     "job_name": "unit"}))
    for b in random_batches(3, BATCH, HIDDEN, seed=3):
        engine.train_batch(batch=stack1(b))
    engine.monitor.flush()
    scalars = _read_scalars(os.path.join(str(tmp_path), "unit"))
    assert "Train/Timers/comms_ms" in scalars
    loss_samples = [s for s, _ in scalars["Train/Samples/train_loss"]]
    timer_samples = [s for s, _ in scalars["Train/Timers/comms_ms"]]
    assert timer_samples == loss_samples
    assert all(v >= 0 for _, v in scalars["Train/Timers/comms_ms"])


# ---------------------------------------------------------------------------
# satellite: monitor lifecycle
# ---------------------------------------------------------------------------

def test_monitor_record_after_close_warns_once(tmp_path, monkeypatch):
    from deeperspeed_tpu.runtime import monitor as monitor_mod
    warnings = []
    monkeypatch.setattr(monitor_mod.logger, "warning",
                        lambda msg, *a: warnings.append(msg))
    mon = TensorBoardMonitor(output_path=str(tmp_path), job_name="pc",
                             flush_interval=2)
    mon.record(8, {"Train/Samples/train_loss": 1.0})
    mon.close()
    for i in range(5):   # would previously crash at flush_interval
        mon.record(16 + i, {"Train/Samples/train_loss": 2.0})
    assert len([m for m in warnings if "after close" in m]) == 1
    assert mon._pending == []   # dropped, not queued forever
    scalars = _read_scalars(os.path.join(str(tmp_path), "pc"))
    assert scalars["Train/Samples/train_loss"] == [(8, 1.0)]
