"""The step timeline (`runtime/telemetry.py::StepTimeline`): one record a
step inside both engines, a slow step judged by one rule and named by
what held it (docs/observability.md, "Slow steps")."""

import gc
import json
import statistics
import time
import types
from collections import deque

import numpy as np
import pytest

import jax

import deeperspeed_tpu
from deeperspeed_tpu.inference import InferenceEngine
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.runtime import fault_injection as fi
from deeperspeed_tpu.runtime import telemetry as tm
from tests.simple_model import SimpleModel, random_batches

pytestmark = [pytest.mark.telemetry]

MS = 1e-3


# ---------------------------------------------------------------------------
# the rule, on synthetic durations
# ---------------------------------------------------------------------------

class FakeClock:
    """Stands in for the `time` module inside runtime/telemetry.py."""

    def __init__(self):
        self.now = 100.0
        self.cpu = 1.0

    def perf_counter(self):
        return self.now

    def thread_time(self):
        return self.cpu

    def time_ns(self):
        return int(self.now * 1e9)


@pytest.fixture
def clock(monkeypatch):
    fake = FakeClock()
    monkeypatch.setattr(tm, "time", fake)
    monkeypatch.setattr(tm.logger, "warning", lambda *a, **k: None)
    return fake


def run_step(timeline, clock, seconds, key="decode x4", outside=0.0,
             phases=(), cpu=0.0):
    """One synthetic step: `outside` seconds of the caller's, then a step
    of `seconds`, of which `phases` = ((name, seconds), ...) in spans."""
    clock.now += outside
    timeline.begin()
    timeline.enqueued(key)
    spent = 0.0
    for name, s in phases:
        with timeline.span(name):
            clock.now += s
        spent += s
    clock.now += seconds - spent
    clock.cpu += cpu
    return timeline.end()


def flat(timeline, clock, n, seconds=10 * MS, **kw):
    return [run_step(timeline, clock, seconds, **kw) for _ in range(n)]


@pytest.mark.parametrize("before, slow", [
    (tm.MIN_STEPS - 1, False),      # the 8th step of a key: no verdict yet
    (tm.MIN_STEPS, True),           # the 9th: judged
])
def test_no_verdict_before_eight_steps_of_a_key(clock, before, slow):
    timeline = tm.StepTimeline("serve")
    flat(timeline, clock, before)
    verdict = run_step(timeline, clock, 500 * MS)
    assert (verdict is not None) == slow
    assert timeline.ring[-1].verdict == ("slow" if slow else None)


@pytest.mark.parametrize("over_ms, slow", [(6.0, True), (4.0, False)])
def test_flat_key_is_slow_past_five_ms(clock, over_ms, slow):
    timeline = tm.StepTimeline("serve")
    flat(timeline, clock, 20)
    verdict = run_step(timeline, clock, (10 + over_ms) * MS)
    assert (verdict is not None) == slow
    if slow:
        assert verdict["excess"] == pytest.approx(over_ms * MS, rel=1e-6)
        assert verdict["typical_s"] == pytest.approx(10 * MS)


@pytest.mark.parametrize("over_ms, slow", [(12.0, False), (20.0, True)])
def test_noisy_key_needs_eight_deviations(clock, over_ms, slow):
    """Steps of 8 / 10 / 12 ms in turn: median 10, median absolute
    deviation 2, so a step is slow past 16 ms over."""
    timeline = tm.StepTimeline("serve")
    for i in range(30):
        run_step(timeline, clock, (8 + 2 * (i % 3)) * MS)
    verdict = run_step(timeline, clock, (10 + over_ms) * MS)
    assert (verdict is not None) == slow
    if slow:
        assert verdict["deviation_s"] == pytest.approx(2 * MS)


def test_a_compiled_step_is_classed_compile_never_slow(clock):
    timeline = tm.StepTimeline("serve")
    flat(timeline, clock, 20)
    timeline.begin()
    timeline.enqueued("decode x4")
    tm._on_lowered(tm.LOWERED_EVENT, 0.3)       # jax lowered a program
    clock.now += 2.0
    assert timeline.end() is None
    record = timeline.ring[-1]
    assert record.compiled and record.verdict == "compile"
    assert timeline.counters["compile_steps"] == 1
    assert timeline.counters["slow_steps"] == 0
    # and it is no part of the key's typical step
    assert run_step(timeline, clock, 16 * MS) is not None


def test_a_step_that_toggled_the_profiler_is_never_slow(clock, monkeypatch):
    """Starting or stopping a `jax.profiler` trace holds the loop for
    seconds (the benchmark's traced stretch, a capture window): the
    profiler's doing, not a stall, and no reason to arm another capture."""
    session = [False]
    monkeypatch.setattr(tm, "_profiler_on", lambda: session[0])
    timeline = tm.StepTimeline("train")
    flat(timeline, clock, 20)
    session[0] = True                       # start_trace, by the caller
    assert run_step(timeline, clock, 10 * MS, outside=1.5) is None
    assert timeline.ring[-1].verdict == "profiler"
    assert run_step(timeline, clock, 10 * MS) is None       # traced, steady
    assert timeline.ring[-1].verdict is None
    session[0] = False                      # stop_trace: seconds
    clock.now += 9.0
    timeline.begin()
    timeline.enqueued("decode x4")
    clock.now += 10 * MS
    assert timeline.end(starved=True) is None
    assert timeline.ring[-1].verdict == "profiler"
    assert timeline.counters["profiler_steps"] == 2
    assert timeline.counters["starved_steps"] == 0
    assert timeline.report()["starved_steps"] == 0
    assert timeline.counters["slow_steps"] == 0
    assert run_step(timeline, clock, 16 * MS) is not None   # typical: 10 ms


def test_the_profiler_session_is_read_from_jax(tmp_path):
    assert tm._profiler_on() is False
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tm._profiler_on() is True
    finally:
        jax.profiler.stop_trace()
    assert tm._profiler_on() is False


def test_slow_steps_stay_out_of_the_typical_window(clock):
    timeline = tm.StepTimeline("serve")
    flat(timeline, clock, 20)
    for _ in range(tm.RELEVEL_AFTER - 1):
        assert run_step(timeline, clock, 200 * MS) is not None
    assert run_step(timeline, clock, 10 * MS) is None
    verdict = run_step(timeline, clock, 17 * MS)
    assert verdict is not None and \
        verdict["typical_s"] == pytest.approx(10 * MS)


def test_eight_slow_verdicts_in_a_row_are_a_new_level(clock):
    timeline = tm.StepTimeline("serve")
    flat(timeline, clock, 20)
    verdicts = flat(timeline, clock, tm.RELEVEL_AFTER + tm.MIN_STEPS + 4,
                    seconds=30 * MS)
    assert all(v is not None for v in verdicts[:tm.RELEVEL_AFTER])
    # the key learns anew: no verdict for eight steps, then 30 ms is typical
    assert all(v is None for v in verdicts[tm.RELEVEL_AFTER:])
    assert run_step(timeline, clock, 37 * MS)["typical_s"] == \
        pytest.approx(30 * MS)


def test_keys_are_judged_apart(clock):
    timeline = tm.StepTimeline("serve")
    for _ in range(12):
        run_step(timeline, clock, 10 * MS, key="decode x4")
        run_step(timeline, clock, 200 * MS,
                 key="prefill 1x512 + decode x4")
    assert timeline.counters["slow_steps"] == 0
    assert run_step(timeline, clock, 200 * MS, key="decode x4") is not None
    assert run_step(timeline, clock, 204 * MS,
                    key="prefill 1x512 + decode x4") is None


@pytest.mark.parametrize("held_by, kw", [
    ("device_wait", {"phases": (("readback", 0.1 * MS),
                                ("device_wait", 59 * MS))}),
    ("complete", {"phases": (("device_wait", 9 * MS),
                             ("complete", 50 * MS))}),
    ("host", {"phases": (("device_wait", 9 * MS), ("complete", 50 * MS)),
              "cpu": 50.5 * MS}),
    ("outside", {"outside": 50 * MS}),
    ("other", {}),
])
def test_the_excess_is_split_over_what_held_it(clock, held_by, kw):
    timeline = tm.StepTimeline("serve")
    usual = {"phases": (("device_wait", 9 * MS), ("complete", 0.5 * MS)),
             "cpu": 0.5 * MS}
    flat(timeline, clock, 20, **usual)
    step = dict(usual, **kw)
    seconds = 60 * MS if "outside" not in kw else 10 * MS
    verdict = run_step(timeline, clock, seconds, **step)
    assert verdict["excess"] == pytest.approx(50 * MS, rel=1e-6)
    assert sum(verdict["held_by"].values()) == \
        pytest.approx(verdict["excess"], rel=1e-9)
    assert max(verdict["held_by"], key=verdict["held_by"].get) == held_by
    assert verdict["held_by"][held_by] == pytest.approx(50 * MS, rel=0.02)
    sums = timeline.counters
    assert sums["slow_step_excess_s"] == pytest.approx(50 * MS, rel=1e-6)
    for part in ("device_wait", "host", "outside"):
        expect = 50 * MS if part == held_by else 0.0
        assert sums[f"slow_excess_{part}_s"] == \
            pytest.approx(expect, rel=0.02, abs=1e-9)


def test_phases_are_self_seconds_and_tile_the_step(clock):
    timeline = tm.StepTimeline("serve")
    timeline.begin()
    with timeline.span("decode"):
        clock.now += 1 * MS
        with timeline.span("readback"):
            with timeline.span("device_wait"):
                clock.now += 7 * MS
            clock.now += 2 * MS
    clock.now += 0.5 * MS
    timeline.end()
    record = timeline.ring[-1]
    assert record.phases == pytest.approx(
        {"decode": 1 * MS, "readback": 2 * MS, "device_wait": 7 * MS,
         "other": 0.5 * MS})
    assert sum(record.phases.values()) + record.outside == \
        pytest.approx(record.wall)
    # the counters keep each span's whole seconds, children included
    assert timeline.counters["readback_s"] == pytest.approx(9 * MS)
    assert timeline.counters["decode_s"] == pytest.approx(10 * MS)


def test_an_idle_engines_gap_is_nobodys_stall(clock):
    timeline = tm.StepTimeline("serve")
    flat(timeline, clock, 20)
    clock.now += 5.0                    # no request for five seconds
    timeline.begin(busy=False)
    timeline.enqueued("decode x4")
    clock.now += 10 * MS
    assert timeline.end() is None
    assert timeline.ring[-1].outside == 0.0


def test_the_log_line_and_its_rate_limit(clock, monkeypatch):
    lines = []
    monkeypatch.setattr(tm.logger, "warning", lines.append)
    timeline = tm.StepTimeline("serve")
    usual = {"phases": (("device_wait", 9 * MS),), "cpu": 0.4 * MS}
    flat(timeline, clock, 20, **usual)
    run_step(timeline, clock, 3220.8 * MS, cpu=0.4 * MS,
             phases=(("device_wait", 3219.8 * MS),))
    assert lines == [
        "serve step 21 (decode x4) 3,220.8 ms, typical 10.0: 3,210.8 in "
        "device_wait, CPU 0.4 ms, no collection, no compile"]
    run_step(timeline, clock, 100 * MS, **usual)    # held back: too soon
    assert len(lines) == 1
    clock.now += tm.SLOW_LOG_INTERVAL_S
    run_step(timeline, clock, 100 * MS, **usual)
    assert len(lines) == 2 and \
        lines[1].endswith("(1 more slow steps since the last line)")


def test_step_report_is_process_wide_and_counts_the_newest_records(clock):
    train, serve = tm.StepTimeline("train"), tm.StepTimeline("serve")
    flat(train, clock, 20)
    run_step(train, clock, 100 * MS)
    flat(train, clock, 5)
    flat(serve, clock, 3)
    report = tm.step_report()
    assert len(report["clock"]) == 2
    assert train.report() in report["timelines"]
    assert serve.report() in report["timelines"]
    mine = train.report()
    assert (mine["steps"], mine["slow_steps"]) == (26, 1)
    assert mine["slow"][0]["serial"] == 21
    assert mine["slow_step_excess_s"] == pytest.approx(90 * MS)
    assert report["timelines"][0]["engine"] == "train"      # train first
    json.dumps(report)                                      # plain data
    # the newest five records hold no slow step
    last = train.report(last=5)
    assert (last["steps"], last["slow_steps"], last["slow"]) == (5, 0, [])
    assert train.report(last=6)["slow_steps"] == 1


def test_the_ring_is_bounded(clock):
    timeline = tm.StepTimeline("serve")
    flat(timeline, clock, tm.STEP_RING + 10, seconds=1 * MS)
    assert len(timeline.ring) == tm.STEP_RING
    assert timeline.ring[-1].serial == tm.STEP_RING + 10


def test_a_collection_inside_a_phase_lands_in_the_record():
    timeline = tm.StepTimeline("serve")
    gc.collect()
    gc.disable()        # the one collection is the one forced below
    try:
        junk = [[i] for i in range(200_000)]
        junk.append(junk)                       # a cycle worth collecting
        for _ in range(20):
            timeline.begin()
            timeline.enqueued("decode x4")
            with timeline.span("complete"):
                pass
            timeline.end()
        assert timeline.counters["gc_s"] == 0.0
        del junk
        timeline.begin()
        timeline.enqueued("decode x4")
        with timeline.span("complete"):
            gc.collect()
        slow = timeline.end()
    finally:
        gc.enable()
    record = timeline.ring[-1]
    assert record.gc_s > 0.0
    assert timeline.counters["gc_s"] == record.gc_s
    assert record.gc_s <= record.phases["complete"]
    if slow is not None:                        # a collection over 5 ms
        held = slow["held_by"]
        assert max(held, key=held.get) == "gc"
        assert held["gc"] == pytest.approx(record.gc_s, rel=0.05)


def test_the_timeline_alone_costs_under_ten_microseconds_a_step():
    timeline = tm.StepTimeline("serve")
    costs = []
    for _ in range(10_000):
        t0 = time.perf_counter()
        timeline.begin()
        timeline.enqueued("decode x32")
        timeline.end(rows=32)
        costs.append(time.perf_counter() - t0)
    assert statistics.median(costs) < 10e-6


# ---------------------------------------------------------------------------
# the serving engine
# ---------------------------------------------------------------------------

def tiny_server(**config):
    cfg = GPTNeoXConfig.tiny()
    model = GPTNeoX(config=cfg, use_pallas=False)
    inference = {
        "enabled": True, "page_size": 16, "num_pages": 64,
        "max_batch_size": 4, "token_budget": 256,
        "prefill_lengths": [16, 32], "prefill_batch_sizes": [1, 2],
        "decode_batch_sizes": [1, 2, 4]}
    inference.update(config.pop("inference", {}))
    config["inference"] = inference
    return InferenceEngine(
        model, config=config,
        params=model.init_params(jax.random.PRNGKey(1))), cfg


def prompts(cfg, n, length=5, seed=0):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(1, cfg.vocab_size, size=length))
            for _ in range(n)]


def serve_until_done(engine):
    while engine.scheduler.has_work:
        engine.step()


NEW_STATS = ("slow_steps", "slow_step_excess_s",
             "slow_excess_device_wait_s", "slow_excess_gc_s",
             "slow_excess_host_s", "slow_excess_outside_s",
             "device_wait_s", "gc_s", "compile_steps", "profiler_steps")


@pytest.fixture(scope="module")
def served():
    """A server without a `telemetry` block that has served a while."""
    engine, cfg = tiny_server()
    engine.generate(prompts(cfg, 4), max_new_tokens=24)
    return engine


@pytest.mark.parametrize("key", NEW_STATS)
def test_new_stats_are_numeric_and_there_without_a_block(served, key):
    fresh, _ = tiny_server()
    assert fresh.telemetry is tm.NULL_TELEMETRY
    assert fresh.stats[key] == 0
    assert isinstance(served.stats[key], (int, float))
    assert key in served.serve_stats()


def test_device_wait_is_a_child_of_readback(served):
    stats = served.stats
    assert 0.0 < stats["device_wait_s"] <= stats["readback_s"]
    decode = [r for r in served.timeline.ring if r.key == "decode x4"]
    assert decode and all("device_wait" in r.phases for r in decode)
    record = decode[-1]
    assert sum(record.phases.values()) + record.outside == \
        pytest.approx(record.wall)
    assert record.rows == 4 and record.cpu_s > 0.0


def test_a_first_seen_prefill_bucket_is_compile_not_slow():
    engine, cfg = tiny_server()
    engine.generate(prompts(cfg, 4), max_new_tokens=16)
    seen, slow = engine.stats["compile_steps"], engine.stats["slow_steps"]
    engine.submit(prompts(cfg, 1, length=20)[0], max_new_tokens=2)
    engine.step()
    record = engine.timeline.ring[-1]
    assert record.key.startswith("prefill 1x32")
    assert record.compiled and record.verdict == "compile"
    assert engine.stats["compile_steps"] == seen + 1
    assert engine.stats["slow_steps"] == slow


def test_decode_stall_is_one_slow_step_not_in_device_wait():
    """The injected stall is 0.2 s and the limits are one-sided or wide:
    on a loaded machine a sleep overruns and a neighbouring step runs
    tens of milliseconds late, which a stall of 0.05 s held to 10% took
    for a second stall or a wrong length (the driver's run of PR 40)."""
    stall = 0.2
    engine, cfg = tiny_server(inference={"fault_injection": {"faults": [
        {"kind": "decode_stall", "step": 30, "seconds": stall}]}})
    engine.generate(prompts(cfg, 4), max_new_tokens=3)      # warm
    anomalies = []
    engine.telemetry = types.SimpleNamespace(
        enabled=False, close=lambda: None,
        on_anomaly=lambda eng, kind, step=None: anomalies.append(
            (kind, step)))
    for p in prompts(cfg, 4):
        engine.submit(p, max_new_tokens=48)
    serve_until_done(engine)
    stalled = [s for s in engine.timeline.slow if s["excess"] > 0.75 * stall]
    assert len(stalled) == 1
    slow = stalled[0]
    assert slow["key"] == "decode x4" and not slow["compiled"]
    assert 0.95 * stall <= slow["excess"] < 1.5 * stall
    assert slow["held_by"].get("device_wait", 0.0) < 0.1 * stall
    # the injector sleeps inside the step and under no span
    assert 0.9 * stall <= slow["held_by"]["other"] <= slow["excess"]
    assert engine.stats["slow_step_excess_s"] >= slow["excess"]
    assert engine.stats["slow_steps"] == len(engine.timeline.slow)
    assert ("slow_step", slow["serial"]) in anomalies
    assert len(slow["clock"]) == 2


def test_the_callers_time_counts_only_while_there_is_work():
    engine, cfg = tiny_server()
    engine.generate(prompts(cfg, 4), max_new_tokens=3)      # warm
    for p in prompts(cfg, 4):
        engine.submit(p, max_new_tokens=40)
    for _ in range(20):
        engine.step()
    before = engine.stats["slow_excess_outside_s"]
    time.sleep(0.05)                    # the caller, with requests waiting
    engine.step()
    assert 0.04 <= engine.stats["slow_excess_outside_s"] - before < 0.1
    assert engine.timeline.ring[-1].outside >= 0.05
    serve_until_done(engine)
    time.sleep(0.05)                    # an idle server
    engine.submit(prompts(cfg, 1)[0], max_new_tokens=2)
    engine.step()
    assert engine.timeline.ring[-1].outside == 0.0


def test_span_serials_reach_the_tracer_and_the_chrome_export(tmp_path):
    engine, cfg = tiny_server(telemetry={
        "enabled": True, "goodput": False, "mfu": False, "spans": True})
    assert engine.telemetry.timeline is engine.timeline
    tracer = engine.telemetry.tracer
    tracer.start_capture()
    engine.generate(prompts(cfg, 2), max_new_tokens=4)
    events = tracer.stop_capture()
    serials = {r.serial for r in engine.timeline.ring}
    spans = [e for e in events if not e[0].startswith("request/")]
    requests = [e for e in events if e[0].startswith("request/")]
    assert spans and len(requests) == 2
    assert {"device_wait", "readback", "schedule"} <= {e[0] for e in spans}
    for event in spans:
        assert len(event) == 4                  # (name, t0, dur, depth)
        assert event.step in serials
    for event in requests:
        assert event.step in serials
        assert event.args["prefill_step"] in serials
        assert event.args["prefill_step"] <= event.step
    # a child span lies one level under its parent, in the same step
    wait = next(e for e in spans if e[0] == "device_wait")
    back = next(e for e in spans if e[0] == "readback"
                and e[1] <= wait[1] and wait[1] + wait[2] <= e[1] + e[2])
    assert wait[3] == back[3] + 1 and wait.step == back.step

    path = tm.SpanTracer.export_chrome_trace(
        events, str(tmp_path / "spans.json"), metadata={"host": 0})
    with open(path) as f:
        trace = json.load(f)
    for out in trace["traceEvents"]:
        assert out["args"]["step"] in serials
    named = [o for o in trace["traceEvents"]
             if o["name"].startswith("request/")]
    assert all("prefill_step" in o["args"] for o in named)
    clock = trace["otherData"]["clock"]
    assert clock["time_ns"] > 1e18 and clock["perf_counter"] > 0
    assert trace["otherData"]["host"] == 0


def test_a_bare_tracers_events_carry_no_step():
    tracer = tm.SpanTracer(mirror_annotations=False)
    tracer.start_capture()
    with tracer.span("outer"):
        pass
    tracer.record_event("request/7", 1.0, 2.0)
    events = tracer.stop_capture()
    assert [e.step for e in events] == [None, None]
    trace = tm.SpanTracer.chrome_trace(events)
    assert all("args" not in o for o in trace["traceEvents"])


# ---------------------------------------------------------------------------
# the training engine
# ---------------------------------------------------------------------------

HIDDEN = 512
BATCH = 64


def train_engine(hidden=HIDDEN, **overrides):
    model = SimpleModel(hidden_dim=hidden)
    config = {"train_batch_size": BATCH, "steps_per_print": 1000,
              "optimizer": {"type": "Adam", "params": {"lr": 0.01}}}
    config.update(overrides)
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=model.init_params(
            jax.random.PRNGKey(1)), config_params=config)
    return engine


def stacked(n, hidden=HIDDEN):
    return [jax.tree_util.tree_map(lambda x: x[None], b)
            for b in random_batches(n, BATCH, hidden, seed=3)]


def one_step_late(engine, batches, between=None):
    """The benchmark's loop: each loss fetched after the next step is
    dispatched. Returns the entry time of every call."""
    entries, pending = [], None
    for i, batch in enumerate(batches):
        if between is not None:
            between(i)
        entries.append(time.perf_counter())
        nxt = engine.train_batch(batch=batch)
        if pending is not None:
            float(pending)
        pending = nxt
    float(pending)
    return entries


@pytest.fixture(scope="module")
def trained(devices):
    """A train engine with no `telemetry` block after a one-step-late
    loop in which the caller slept once, past a whole step."""
    engine = train_engine()

    def between(i):
        if i == 14:
            time.sleep(0.5)
    entries = one_step_late(engine, stacked(24), between)
    return engine, entries


def test_a_train_record_runs_dispatch_to_dispatch(trained):
    engine, entries = trained
    assert engine.telemetry is tm.NULL_TELEMETRY
    records = list(engine.timeline.ring)
    assert [r.serial for r in records] == list(range(1, 24))
    assert records[0].compiled and records[0].verdict == "compile"
    assert {r.key for r in records} == {"gas 1"}
    assert all(r.rows == BATCH for r in records)
    for record, t0, t1 in zip(records, entries, entries[1:]):
        assert record.wall == pytest.approx(t1 - t0, abs=5e-3)
        assert record.outside + sum(record.phases.values()) == \
            pytest.approx(record.wall)
    assert engine.timeline.open          # the last call's, until the next


def test_starved_after_the_caller_sleeps_past_the_step(trained):
    engine, _ = trained
    records = list(engine.timeline.ring)
    assert records[13].starved           # closed by the call that slept
    assert records[13].outside >= 0.5
    slow = [s for s in engine.timeline.slow if s["serial"] == 14]
    assert slow and slow[0]["starved"]
    held = slow[0]["held_by"]
    assert max(held, key=held.get) == "outside"
    assert "device_wait" not in held
    assert tm.step_report()["timelines"][0]["engine"] == "train"


def test_not_starved_in_the_one_step_late_loop(trained):
    engine, _ = trained
    steady = [r for r in engine.timeline.ring
              if r.serial >= 3 and r.serial != 14]
    assert sum(r.starved for r in steady) == 0
    assert engine.timeline.report()["starved_steps"] == \
        sum(r.starved for r in engine.timeline.ring)


def test_throughput_timer_is_a_rate_of_training(trained):
    """`SamplesPerSec` under asynchronous dispatch: samples over wall
    time, not over the time it takes to enqueue a step."""
    engine, entries = trained
    timer = engine.tput_timer
    counted = len(entries) - 1 - timer.start_step     # whole intervals
    assert timer.global_step_count == len(entries) - 1
    by_hand = BATCH * counted / (entries[-1] - entries[timer.start_step])
    assert timer.avg_samples_per_sec() == pytest.approx(by_hand, rel=0.05)


def test_injected_train_stall_is_never_device_wait(monkeypatch, devices):
    """One-sided, as the serve side's stall test is: a sleep returns late
    under six workers (0.062 s for 0.05 s in a PR 46 run), never early."""
    stall = 0.05
    monkeypatch.setenv(fi.ENV_VAR, json.dumps({"faults": [
        {"kind": "stall", "step": 14, "seconds": stall}]}))
    engine = train_engine(hidden=16)
    for batch in stacked(20, hidden=16):
        float(engine.train_batch(batch=batch))
    stalled = [s for s in engine.timeline.slow
               if s["excess"] > 0.8 * stall]
    assert [s["serial"] for s in stalled] == [15]
    slow = stalled[0]
    assert slow["key"] == "gas 1 fault"
    assert 0.8 * stall <= slow["excess"] < 3 * stall
    assert "device_wait" not in slow["held_by"]
    # the injector sleeps inside train_batch, under no span
    held = slow["held_by"]
    assert max(held, key=held.get) == "other"
    assert 0.8 * stall <= held["other"] <= slow["excess"]


def test_with_a_block_the_train_spans_write_into_the_record(tmp_path,
                                                            devices):
    engine = train_engine(
        hidden=16,
        tensorboard={"enabled": True, "output_path": str(tmp_path),
                     "job_name": "unit"},
        telemetry={"enabled": True, "mfu": False})
    assert engine.telemetry.timeline is engine.timeline
    for batch in stacked(4, hidden=16):
        engine.train_batch(batch=batch)
    record = engine.timeline.ring[-1]
    assert {"h2d", "train_dispatch", "other"} <= set(record.phases)
    assert engine.timeline.counters["train_dispatch_s"] > 0.0
    # the goodput account still gets its spans, through the timeline
    assert engine.telemetry.goodput.total > 0.0
    engine.monitor.flush()
    from tests.test_telemetry import _read_scalars
    scalars = _read_scalars(str(tmp_path / "unit"))
    assert "Train/Goodput/slow_step_s" in scalars


# ---------------------------------------------------------------------------
# the set-up account (docs/observability.md, "Set-up"). These come last:
# they serve and train on the module's engines, whose records the tests
# above read as the fixtures left them
# ---------------------------------------------------------------------------

@pytest.fixture
def account(monkeypatch):
    """The process's compile account with nobody inside an engine call
    (a step or a constructor that died gave the account back), no timed
    event waiting for a longer one to hold it, and no entry kept yet; the
    running sums are the process's own."""
    assert tm._OWNER[0] is None
    tm._NESTED.clear()
    monkeypatch.setattr(tm, "_FIRST", [])
    monkeypatch.setattr(tm, "_EVENTS", deque(maxlen=tm.EVENTS_KEPT))
    monkeypatch.setattr(tm, "_DROPPED", [None])
    return tm


def fire(clock, kind, seconds=None, name=None):
    """jax reports an event that took `seconds` and ends now."""
    event = next(e for e, k in tm.COMPILE_EVENTS.items() if k == kind)
    if seconds is None:
        tm._on_lowered(event)
    else:
        clock.now += seconds
        tm._on_lowered(event, seconds, fun_name=name)


def compile_step(timeline, clock, key="prefill 1x4096 + decode x32",
                 cache="cache_miss", compile_s=38.20):
    """A step whose program jax traces, lowers and compiles: the events
    of a persistent-cache miss (or hit), in jax's order."""
    timeline.begin()
    timeline.enqueued(key)
    fire(clock, "trace", 0.41, "planned_prefill")
    fire(clock, "lower", 0.62, "jit(planned_prefill)")
    if cache == "cache_hit":
        fire(clock, "cache_hit")
    fire(clock, "compile", compile_s, "jit(planned_prefill)")
    if cache == "cache_miss":
        fire(clock, "cache_miss")
    clock.now += 0.1
    return timeline.end()


BUILD_PHASES = {
    "serve": {"weights", "pools", "other"},
    "train": {"config", "checkpoint_manager", "partition", "master",
              "params", "optimizer_state", "other"},
}


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_the_constructor_is_the_first_record_and_no_step(kind, served,
                                                         trained):
    timeline = (served if kind == "serve" else trained[0]).timeline
    build = timeline.built
    assert build is timeline.compiles[0]
    assert (build.serial, build.key, build.verdict) == (0, "build", "build")
    assert BUILD_PHASES[kind] <= set(build.phases)
    # the phases and `other` tile the constructor
    assert sum(build.phases.values()) == pytest.approx(build.wall)
    assert build.outside == 0.0 and build.excess == 0.0
    assert not build.starved
    # what it placed through jax is on its account (nothing, where this
    # process compiled the same placements for an earlier engine)
    assert build.compiled == (build.compile is not tm.NO_COMPILE)
    assert build.compile.trace_s + build.compile.lower_s \
        + build.compile.compile_s < build.wall
    # no step: not in the ring, no key's typical, no counter
    assert all(r.serial >= 1 for r in timeline.ring)
    assert "build" not in timeline._keys
    assert all(s["key"] != "build" for s in timeline.slow)
    assert not any(name.endswith("_s") and name[:-2] in build.phases
                   for name in timeline.counters)
    entry = timeline.setup()
    assert entry["engine"] == kind
    assert entry["build"]["wall_s"] == build.wall
    assert entry["build"]["phases"] == build.phases
    assert all(p["serial"] >= 1 for p in entry["programs"])


def test_a_steady_step_carries_an_empty_delta(served):
    record = [r for r in served.timeline.ring if r.key == "decode x4"][-1]
    assert not record.compiled and record.verdict is None
    assert record.compile is tm.NO_COMPILE
    assert record.compile.programs == 0 and record.compile.fun_names == ()


def test_a_first_seen_bucket_says_what_compiled(served, account, tmp_path):
    """The record of a step that compiled carries the program's name,
    its tracing and lowering seconds and whether the persistent cache
    had it (a fresh cache directory here: a miss, and nothing is read)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    before = tm.setup_report()
    mine = served.timeline.setup()
    try:
        served.submit(prompts(served.model.config, 1, length=20)[0],
                      max_new_tokens=2)
        served.step()
    finally:
        for name, value in was.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
    record = served.timeline.ring[-1]
    assert record.key.startswith("prefill 1x32")
    assert record.compiled and record.verdict == "compile"
    assert served.timeline.compiles[-1] is record
    delta = record.compile
    assert "planned_prefill" in delta.fun_names
    assert delta.programs >= 1
    assert delta.trace_s > 0.0 and delta.lower_s > 0.0
    assert delta.cache_hits + delta.cache_misses >= 1
    assert delta.compile_s > 0.0 and delta.cache_read_s == 0.0
    inside = record.wall - record.outside
    assert delta.trace_s + delta.lower_s + delta.compile_s < inside
    serve_until_done(served)
    # the report names the program, and its totals moved by what the
    # caller's and this engine's records did
    after, entry = tm.setup_report(), served.timeline.setup()
    program = next(p for p in entry["programs"]
                   if p["serial"] == record.serial)
    assert program["key"] == record.key
    assert program["first_call_s"] == pytest.approx(
        inside - delta.trace_s - delta.lower_s - delta.compile_s)
    new = entry["programs"][len(mine["programs"]):]
    for field in ("programs", "trace_s", "lower_s", "compile_s",
                  "cache_misses"):
        parts = sum(p[field] for p in new) \
            + after["caller"][field] - before["caller"][field]
        assert after["totals"][field] - before["totals"][field] == \
            pytest.approx(parts), field
    json.dumps(after)                                       # plain data


def test_what_the_caller_compiles_between_two_steps_is_the_callers(
        trained, account):
    engine, _ = trained
    (batch,) = stacked(1)
    float(engine.train_batch(batch=batch))
    before = tm.setup_report()["caller"]
    jax.jit(lambda x: x * 2.0 + 1.0)(np.ones(3, np.float32))    # the caller's
    float(engine.train_batch(batch=batch))      # closes the record between
    record = engine.timeline.ring[-1]
    assert record.outside > 0.0
    assert not record.compiled and record.compile is tm.NO_COMPILE
    assert record.verdict != "compile"
    after = tm.setup_report()["caller"]
    assert after["programs"] > before["programs"]
    assert after["compile_s"] > before["compile_s"]


def test_an_event_outside_every_engine_call_is_the_callers(clock, account,
                                                           monkeypatch):
    before = tm.setup_report()
    fire(clock, "trace", 0.125, "a_users_helper")
    fire(clock, "trace", 0.125, "a_users_function")
    fire(clock, "lower", 0.5, "jit(a_users_function)")
    fire(clock, "compile", 2.0, "jit(another)")
    after = tm.setup_report()
    assert after["caller"]["trace_s"] - before["caller"]["trace_s"] == \
        pytest.approx(0.25)
    assert after["caller"]["programs"] == before["caller"]["programs"] + 1
    assert after["totals"]["lower_s"] - before["totals"]["lower_s"] == \
        pytest.approx(0.5)
    # by name too, dearest first. A run of traces (jax reports a jitted
    # helper traced inside an outer trace first) is ONE entry, the last's
    assert after["caller"]["programs_by_name"] == [
        {"fun_name": "another", "seconds": 2.0},
        {"fun_name": "a_users_function", "seconds": pytest.approx(0.75)}]
    assert [e[1:] for e in tm._entries()] == [
        ("trace", "a_users_function", pytest.approx(0.25), None),
        ("lower", "a_users_function", 0.5, None),
        ("compile", "another", 2.0, None)]
    assert tm._entries()[-1][0] == clock.now


def test_a_step_owns_what_compiles_inside_it_until_it_leaves(clock,
                                                             account):
    """begin() to leave(): the record's. After leave(), while a train
    record stays open for the caller's time: the caller's."""
    timeline = tm.StepTimeline("train")
    before = tm.setup_report()["caller"]["programs"]
    timeline.begin()
    timeline.enqueued("gas 1")
    fire(clock, "lower", 0.3, "jit(train_step)")
    timeline.leave()
    fire(clock, "lower", 0.2, "jit(the_callers)")
    timeline.end()
    record = timeline.ring[-1]
    assert record.compile.programs == 1
    assert record.compile.fun_names == ("train_step",)
    assert record.compile.lower_s == pytest.approx(0.3)
    assert record.outside == pytest.approx(0.2)
    assert tm.setup_report()["caller"]["programs"] == before + 1
    assert tm._OWNER[0] is None


def test_seconds_are_self_seconds_and_a_hit_is_a_read(clock, account):
    """A helper traced inside an outer trace is not counted twice, and
    the backend's call that the cache answered is the read, whole."""
    timeline = tm.StepTimeline("serve")
    timeline.begin()
    timeline.enqueued("decode x4")
    clock.now += 0.2                        # the outer trace has begun
    fire(clock, "trace", 0.1, "_where")     # ... a helper inside it
    clock.now += 0.2
    tm._on_lowered(tm.TRACE_EVENT, 0.5, fun_name="planned_decode")
    fire(clock, "lower", 0.3, "jit(planned_decode)")
    fire(clock, "cache_hit")
    fire(clock, "compile", 0.05, "jit(planned_decode)")
    timeline.end()
    delta = timeline.ring[-1].compile
    assert delta.trace_s == pytest.approx(0.5)
    assert delta.lower_s == pytest.approx(0.3)
    assert (delta.cache_hits, delta.cache_misses) == (1, 0)
    assert delta.compile_s == 0.0
    assert delta.cache_read_s == pytest.approx(0.05)
    assert delta.fun_names == ("planned_decode",)
    # the two traces are one entry, the outer's
    assert [e[1:3] for e in tm._entries()] == [
        ("trace", "planned_decode"), ("lower", "planned_decode"),
        ("cache_hit", None), ("cache_read", "planned_decode")]


def test_until_cuts_the_report_at_a_stamp(clock, account):
    timeline = tm.StepTimeline("serve")
    compile_step(timeline, clock)                       # before the cut
    fire(clock, "lower", 0.5, "jit(weights)")           # the caller's, too
    cut = clock.now
    compile_step(timeline, clock, key="prefill 1x64")   # after it
    fire(clock, "lower", 0.7, "jit(reference)")
    # (both cut: the stamps of this process's real events lie far past
    # the fake clock's)
    early = tm.setup_report(until=cut)
    late = tm.setup_report(until=clock.now)
    mine = timeline.setup(until=cut)
    assert [p["key"] for p in mine["programs"]] == \
        ["prefill 1x4096 + decode x32"]
    assert len(timeline.setup()["programs"]) == 2
    assert late["caller"]["lower_s"] - early["caller"]["lower_s"] == \
        pytest.approx(0.7)
    assert late["totals"]["programs"] - early["totals"]["programs"] == 2
    assert late["totals"]["compile_s"] - early["totals"]["compile_s"] == \
        pytest.approx(38.20)
    assert "reference" not in {p["fun_name"] for p in
                               early["caller"]["programs_by_name"]}


def test_a_set_up_is_cut_exactly_whatever_compiles_after_it(clock, account,
                                                            monkeypatch):
    """The first entries of the process are kept as its newest are: a
    reference that compiles thousands of programs after the window opened
    takes nothing off the set-up's sums. A cut past the first entry that
    fell between the two says so."""
    kept = 4
    monkeypatch.setattr(tm, "EVENTS_KEPT", kept)
    monkeypatch.setattr(tm, "_EVENTS", deque(maxlen=kept))
    fire(clock, "lower", 0.5, "jit(weights)")
    fire(clock, "cache_miss")
    fire(clock, "compile", 2.0, "jit(weights)")
    cut = clock.now                         # the window opens
    stamps = []
    for n in range(2 * kept + 1):           # the reference, after it
        fire(clock, "lower", 0.25, f"jit(reference_{n})")
        stamps.append(clock.now)
    # reference_0 was the last of the first four; 1 to 4 fell between
    assert len(tm._entries()) == 2 * kept and tm._DROPPED[0] == stamps[1]
    early = tm.setup_report(until=cut)
    assert early["complete"]
    assert early["totals"] == pytest.approx({
        "programs": 1, "trace_s": 0.0, "lower_s": 0.5, "compile_s": 2.0,
        "cache_read_s": 0.0, "cache_hits": 0, "cache_misses": 1})
    assert early["caller"]["programs_by_name"] == [
        {"fun_name": "weights", "seconds": pytest.approx(2.5)}]
    late = tm.setup_report(until=clock.now)
    assert not late["complete"]
    assert late["totals"]["programs"] == 1 + 1 + kept    # too few, and said
    assert tm.setup_report()["complete"]    # no cut: the running sums


def test_compile_records_outlive_the_ring(clock, account):
    timeline = tm.StepTimeline("serve")
    with timeline.build():
        with timeline.span("weights"):
            fire(clock, "lower", 0.2, "jit(concatenate)")
    compile_step(timeline, clock, key="decode x4")
    flat(timeline, clock, tm.STEP_RING + 10, seconds=1 * MS)
    assert timeline.ring[0].serial > 1
    assert [(r.serial, r.verdict) for r in timeline.compiles] == \
        [(0, "build"), (1, "compile")]
    entry = timeline.setup()
    assert entry["build"]["phases"] == pytest.approx(
        {"weights": 0.2, "other": 0.0})
    assert entry["build"]["lower_s"] == pytest.approx(0.2)
    (program,) = entry["programs"]
    assert program["fun_names"] == ("planned_prefill",)
    assert program["first_call_s"] == pytest.approx(0.1)
    assert program["compile_s"] == pytest.approx(38.20)
    assert program["cache_misses"] == 1
    # and the build fed no counter of the steps
    assert "weights_s" not in timeline.counters
    assert timeline.counters["compile_steps"] == 1


@pytest.mark.parametrize("steady, logged", [
    (tm.COMPILE_LOG_AFTER - 1, False),      # still setting up: no news
    (tm.COMPILE_LOG_AFTER, True),
])
def test_the_late_compile_line_and_its_rate_limit(clock, account,
                                                  monkeypatch, steady,
                                                  logged):
    lines = []
    monkeypatch.setattr(tm.logger, "warning", lines.append)
    timeline = tm.StepTimeline("serve")
    flat(timeline, clock, steady, key="decode x32")
    compile_step(timeline, clock)
    if not logged:
        assert lines == []
        return
    assert lines == [
        f"serve step {steady + 1} (prefill 1x4096 + decode x32) compiled: "
        "planned_prefill traced 0.41 s, lowered 0.62 s, compiled 38.20 s "
        "(cache miss)"]
    compile_step(timeline, clock, key="prefill 1x64",
                 compile_s=0.5)                         # held back: too soon
    assert len(lines) == 1
    clock.now += tm.SLOW_LOG_INTERVAL_S
    compile_step(timeline, clock, key="prefill 1x128", cache="cache_hit")
    assert len(lines) == 2
    assert "compiled 0.00 s, read 38.20 s (cache hit)" in lines[1]
    assert lines[1].endswith("(1 more compile steps since the last line)")
    assert timeline.counters["slow_steps"] == 0


def test_a_nested_import_counts_once(clock, account, monkeypatch):
    monkeypatch.setattr(tm, "_IMPORTS", [])
    clock.now += 1.0
    outer = clock.now                   # the package's __init__ begins
    clock.now += 0.5
    inner = clock.now                   # ... and imports inference inside
    clock.now += 2.0
    tm.note_import(inner)
    clock.now += 0.5
    tm.note_import(outer)
    assert tm.setup_report()["import_s"] == pytest.approx(3.0)
    assert tm.setup_report(until=inner)["import_s"] == pytest.approx(0.5)
    clock.now += 4.0
    later = clock.now                   # a subpackage imported on its own
    clock.now += 0.25
    tm.note_import(later)
    assert tm.setup_report()["import_s"] == pytest.approx(3.25)


def test_a_constructor_that_raises_gives_the_account_back(account):
    from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError
    model = GPTNeoX(config=GPTNeoXConfig.tiny(), use_pallas=False)
    with pytest.raises(DeepSpeedConfigError):
        InferenceEngine(model, config={"inference": {"enabled": False}})
    assert tm._OWNER[0] is None


def test_a_step_that_dies_before_its_programs_gives_the_account_back(
        served, account, monkeypatch):
    def dies():
        raise RuntimeError("the injector's plan is broken")
    monkeypatch.setattr(served, "_plan_step_faults", dies)
    with pytest.raises(RuntimeError):
        served.step()
    assert tm._OWNER[0] is None and not served.timeline.open


def test_the_package_import_is_on_the_account():
    assert 0.0 < tm.setup_report()["import_s"] < 120.0
