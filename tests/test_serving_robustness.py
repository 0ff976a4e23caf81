"""Serving-under-failure tests: SLO-aware admission control, priority/
deadline scheduling, step-failure quarantine/retry/poison, the serving
hang watchdog, the drain-deadline typed failure, per-status counters
through both export backends, the shared KV retry wrapper, and the
fault-storm chaos soak.

Fast lane (tier-1): everything here — the chaos soak runs a tiny model
on small streams so the whole file stays well under the tier-1 budget.
Run the robustness subset alone with ``-m chaos``.
"""

import contextlib
import functools
import json
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.inference import (ContinuousBatchingScheduler,
                                       DeadlineExceeded, DrainAborted,
                                       InferenceEngine, PagedKVCache,
                                       Request, RequestFailed,
                                       RequestRejected)
from deeperspeed_tpu.inference.admission import (AdmissionController,
                                                 STATUS_SHED)
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.models.gpt_neox import forward as neox_forward
from deeperspeed_tpu.runtime.config import parse_inference_block
from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError
from deeperspeed_tpu.runtime.fault_injection import (InjectedServingFault,
                                                     validate_fault_spec)
from deeperspeed_tpu.utils.kv_retry import RetryingKVTransport
from tests.model.references import teacher_forced

pytestmark = [pytest.mark.serving, pytest.mark.chaos]


@pytest.fixture
def ds_logs(caplog):
    """The DeeperSpeedTPU logger has propagate=False; attach caplog's
    handler directly so log-content assertions work."""
    from deeperspeed_tpu.utils.logging import logger as ds_logger
    ds_logger.addHandler(caplog.handler)
    try:
        with caplog.at_level("INFO", logger=ds_logger.name):
            yield caplog
    finally:
        ds_logger.removeHandler(caplog.handler)


def _admission_params(**kw):
    p = {"max_queue_depth": 8, "shed_page_pool_util": 0.9,
         "shed_ttft_ema_ms": None, "ttft_ema_beta": 0.9,
         "retry_after_cap_s": 60.0}
    p.update(kw)
    return p


def _sched(pages=32, budget=128, max_batch=4,
           prefill_lengths=(16, 32), prefill_batches=(1, 2),
           decode_batches=(1, 2, 4), max_seq_len=64):
    cache = PagedKVCache(num_layers=1, num_pages=pages, num_heads=2,
                         page_size=16, head_dim=16, dtype=jnp.float32)
    return cache, ContinuousBatchingScheduler(
        cache, max_seq_len=max_seq_len, token_budget=budget,
        max_batch_size=max_batch, prefill_lengths=list(prefill_lengths),
        prefill_batch_sizes=list(prefill_batches),
        decode_batch_sizes=list(decode_batches))


def _engine_config(**kw):
    block = {"enabled": True, "page_size": 16, "num_pages": 64,
             "max_batch_size": 4, "token_budget": 256,
             "prefill_lengths": [16, 32, 64],
             "prefill_batch_sizes": [1, 2],
             "decode_batch_sizes": [1, 2, 4]}
    block.update(kw)
    return {"inference": block}


@functools.lru_cache(maxsize=None)
def _tiny_model():
    """The module's one tiny NeoX and its params; an engine a test, since
    most tests here break theirs on purpose."""
    model = GPTNeoX(config=GPTNeoXConfig.tiny(), use_pallas=False)
    return model, model.init_params(jax.random.PRNGKey(1))


def _tiny_engine(monitor=None, **kw):
    model, params = _tiny_model()
    eng = InferenceEngine(model, config=_engine_config(**kw),
                          params=params, monitor=monitor)
    return eng, model.config, params




# ---------------------------------------------------------------------------
# config validation (checkpoint-block strictness)
# ---------------------------------------------------------------------------

class TestRobustnessConfig:
    def test_defaults(self):
        p = parse_inference_block({"inference": {"enabled": True}})
        assert p["admission"] is None            # no block = no shedding
        assert p["default_priority"] == "interactive"
        assert p["hang_timeout_s"] == 0.0
        assert p["fault_injection"] is None
        # the retry/poison policy is always on
        assert p["retry"] == {"max_attempts": 3, "backoff_base_ms": 50.0,
                              "backoff_cap_ms": 2000.0, "jitter": 0.25}

    def test_admission_block_parses(self):
        p = parse_inference_block({"inference": {
            "enabled": True,
            "admission": {"max_queue_depth": 4,
                          "shed_page_pool_util": 0.5,
                          "shed_ttft_ema_ms": 250,
                          "ttft_ema_beta": 0.8,
                          "retry_after_cap_s": 10}}})
        assert p["admission"] == {
            "max_queue_depth": 4, "shed_page_pool_util": 0.5,
            "shed_ttft_ema_ms": 250.0, "ttft_ema_beta": 0.8,
            "retry_after_cap_s": 10.0}

    def test_admission_disabled_is_none(self):
        p = parse_inference_block({"inference": {
            "enabled": True, "admission": {"enabled": False,
                                           "max_queue_depth": 4}}})
        assert p["admission"] is None

    @pytest.mark.parametrize("block,match", [
        ({"default_priority": "interactiv"}, "interactive.*batch"),
        ({"hang_timeout_s": -1}, "hang_timeout"),
        ({"admission": {"max_queue_dpeth": 4}}, "Unknown"),
        ({"admission": {"max_queue_depth": 0}}, ">= 1"),
        ({"admission": {"shed_page_pool_util": 1.5}}, r"\(0, 1\]"),
        ({"admission": {"shed_ttft_ema_ms": 0}}, "shed_ttft_ema_ms"),
        ({"admission": {"ttft_ema_beta": 1.0}}, r"\(0, 1\)"),
        ({"admission": {"retry_after_cap_s": 0}}, "retry_after_cap_s"),
        ({"admission": {"enabled": "yes"}}, "boolean"),
        ({"admission": 7}, "must be an object"),
        ({"retry": {"max_attempt": 3}}, "Unknown"),
        ({"retry": {"max_attempts": 0}}, ">= 1"),
        ({"retry": {"backoff_base_ms": 0}}, "backoff_base_ms"),
        ({"retry": {"backoff_base_ms": 100, "backoff_cap_ms": 10}},
         "must be >="),
        ({"retry": {"jitter": 1}}, r"\[0, 1\)"),
        ({"fault_injection": {"faults": [{"kind": "chaos_monkey",
                                          "step": 0}]}}, "kind"),
        ({"fault_injection": {"faults": [{"kind": "page_pool_pressure",
                                          "step": 0, "factor": 2.0}]}},
         "fraction"),
    ])
    def test_rejects(self, block, match):
        conf = {"enabled": True}
        conf.update(block)
        with pytest.raises(DeepSpeedConfigError, match=match):
            parse_inference_block({"inference": conf})

    def test_serving_fault_kinds_validate(self):
        faults = validate_fault_spec({"faults": [
            {"kind": "prefill_error", "step": 1},
            {"kind": "decode_error", "step": 2, "times": 3},
            {"kind": "decode_stall", "step": 3, "seconds": 0.5},
            {"kind": "page_pool_pressure", "step": 4, "factor": 0.5},
        ]})
        assert [f["kind"] for f in faults] == [
            "prefill_error", "decode_error", "decode_stall",
            "page_pool_pressure"]
        # page_pool_pressure defaults its factor to a pool FRACTION,
        # not the loss-spike multiplier
        (f,) = validate_fault_spec({"faults": [
            {"kind": "page_pool_pressure", "step": 0}]})
        assert f["factor"] == 0.9

    def test_submit_priority_typo_lists_choices(self):
        eng, _, _ = _tiny_engine()
        with pytest.raises(ValueError, match="interactive.*batch"):
            eng.submit([1, 2, 3], max_new_tokens=2, priority="batchy")
        with pytest.raises(ValueError, match="deadline_ms"):
            eng.submit([1, 2, 3], max_new_tokens=2, deadline_ms=-5)


# ---------------------------------------------------------------------------
# admission controller (unit)
# ---------------------------------------------------------------------------

class TestAdmissionController:
    def test_queue_full_sheds_every_class(self):
        ctl = AdmissionController(_admission_params(max_queue_depth=2))
        for priority in ("interactive", "batch"):
            req = Request(prompt=[1], max_new_tokens=1,
                          priority=priority)
            with pytest.raises(RequestRejected) as ei:
                ctl.admit(req, queue_depth=2, page_pool_util=0.0)
            assert ei.value.reason == "queue_full"
            assert ei.value.retry_after_s > 0
            assert req.status == STATUS_SHED
            assert req.error is ei.value
        assert ctl.shed_counts["queue_full"] == 2

    def test_pool_pressure_sheds_batch_not_interactive(self):
        ctl = AdmissionController(
            _admission_params(shed_page_pool_util=0.8))
        batch = Request(prompt=[1], max_new_tokens=1, priority="batch")
        with pytest.raises(RequestRejected) as ei:
            ctl.admit(batch, queue_depth=0, page_pool_util=0.85)
        assert ei.value.reason == "overload"
        inter = Request(prompt=[1], max_new_tokens=1,
                        priority="interactive")
        assert ctl.admit(inter, queue_depth=0,
                         page_pool_util=0.85) is None

    def test_ttft_ema_sheds_batch(self):
        ctl = AdmissionController(
            _admission_params(shed_ttft_ema_ms=100.0))
        ctl.observe_ttft(500.0)
        batch = Request(prompt=[1], max_new_tokens=1, priority="batch")
        with pytest.raises(RequestRejected, match="TTFT EMA"):
            ctl.admit(batch, queue_depth=1, page_pool_util=0.0)
        inter = Request(prompt=[1], max_new_tokens=1,
                        priority="interactive")
        assert ctl.admit(inter, queue_depth=1, page_pool_util=0.0) is None

    def test_request_slo_unattainable_sheds_any_class(self):
        ctl = AdmissionController(_admission_params())
        ctl.observe_ttft(400.0)
        req = Request(prompt=[1], max_new_tokens=1,
                      priority="interactive", ttft_slo_ms=200.0)
        with pytest.raises(RequestRejected) as ei:
            ctl.admit(req, queue_depth=1, page_pool_util=0.0)
        assert ei.value.reason == "slo_unattainable"
        # a realistic SLO admits
        ok = Request(prompt=[1], max_new_tokens=1,
                     priority="interactive", ttft_slo_ms=800.0)
        assert ctl.admit(ok, queue_depth=1, page_pool_util=0.0) is None

    def test_stale_ttft_ema_never_sheds_an_idle_server(self):
        """The TTFT EMA only refreshes on admitted requests' first
        tokens: with an EMPTY queue a stale high EMA from a past burst
        must not shed SLO traffic (nothing admitted = the EMA could
        never recover — the server would reject 100% forever while
        idle)."""
        ctl = AdmissionController(
            _admission_params(shed_ttft_ema_ms=100.0))
        ctl.observe_ttft(900.0)                  # the past burst
        slo = Request(prompt=[1], max_new_tokens=1,
                      priority="interactive", ttft_slo_ms=200.0)
        assert ctl.admit(slo, queue_depth=0, page_pool_util=0.0) is None
        batch = Request(prompt=[1], max_new_tokens=1, priority="batch")
        assert ctl.admit(batch, queue_depth=0,
                         page_pool_util=0.0) is None

    def test_retry_after_tracks_drain_rate(self):
        clock = iter(float(t) for t in range(100))
        ctl = AdmissionController(_admission_params(),
                                  clock=lambda: next(clock))
        assert ctl.retry_after_s(10) == 1.0        # pre-warmup default
        ctl.note_finished(1)                       # t=0 (anchor)
        ctl.note_finished(2)                       # t=1: 2 req/s
        assert ctl.drain_rate == pytest.approx(2.0)
        # backlog of 9 + self at 2/s -> 5s
        assert ctl.retry_after_s(9) == pytest.approx(5.0)
        assert ctl.retry_after_s(10**6) == 60.0    # capped

    def test_engine_shed_path_counts_and_types(self):
        eng, cfg, _ = _tiny_engine(
            admission={"max_queue_depth": 2})
        rng = np.random.default_rng(0)
        p = list(rng.integers(1, cfg.vocab_size, size=5))
        eng.submit(p, max_new_tokens=2)            # queued (depth 0)
        eng.submit(p, max_new_tokens=2)            # queued (depth 1)
        with pytest.raises(RequestRejected) as ei:
            eng.submit(p, max_new_tokens=2)
        assert ei.value.retry_after_s > 0
        assert eng.stats["requests_shed"] == 1
        # the queued work still completes
        eng.run()
        assert eng.stats["requests_ok"] == 2


# ---------------------------------------------------------------------------
# priority/deadline-aware scheduling
# ---------------------------------------------------------------------------

class TestPriorityEviction:
    def _grow_until_eviction(self, s):
        """Decode the head request until the pool forces an eviction."""
        for _ in range(200):
            plan = s.schedule()
            if plan.evicted:
                return plan
            for r in plan.decodes:
                s.complete_decode(r, 1)
        raise AssertionError("no eviction occurred")

    def test_batch_evicted_before_younger_interactive(self):
        # 4 usable pages; two 30-token prompts (2 pages each) fill the
        # pool. The OLDER request is batch-class: pre-robustness
        # youngest-first would evict the interactive one.
        _, s = _sched(pages=5, max_seq_len=64, prefill_lengths=(32,),
                      max_batch=2, decode_batches=(1, 2))
        batch = Request(prompt=list(range(1, 31)), max_new_tokens=20,
                        priority="batch")
        inter = Request(prompt=list(range(1, 31)), max_new_tokens=20,
                        priority="interactive")
        s.add_request(batch)
        plan = s.schedule()
        s.complete_prefill(plan.prefills[0], 1)
        s.add_request(inter)
        plan = s.schedule()
        s.complete_prefill(plan.prefills[0], 1)
        plan = self._grow_until_eviction(s)
        assert plan.evicted == [batch]
        assert inter in s.running

    def test_latest_deadline_evicted_within_class(self):
        _, s = _sched(pages=5, max_seq_len=64, prefill_lengths=(32,),
                      max_batch=2, decode_batches=(1, 2))
        urgent = Request(prompt=list(range(1, 31)), max_new_tokens=20,
                         deadline_ms=500.0)
        slack = Request(prompt=list(range(1, 31)), max_new_tokens=20)
        for req in (urgent, slack):
            s.add_request(req, now=0.0)
            plan = s.schedule(now=0.0)
            s.complete_prefill(plan.prefills[0], 1)
        # both interactive: the one with NO deadline (infinite slack)
        # is the victim even though it is younger
        for _ in range(200):
            plan = s.schedule(now=0.0)
            if plan.evicted:
                break
            for r in plan.decodes:
                s.complete_decode(r, 1)
        assert plan.evicted == [slack]

    def test_homogeneous_stream_keeps_youngest_first(self):
        # no priorities/deadlines: the pre-robustness policy survives
        _, s = _sched(pages=5, max_seq_len=64, prefill_lengths=(32,),
                      max_batch=2, decode_batches=(1, 2))
        a = Request(prompt=list(range(1, 31)), max_new_tokens=20)
        b = Request(prompt=list(range(1, 31)), max_new_tokens=20)
        for req in (a, b):
            s.add_request(req)
            plan = s.schedule()
            s.complete_prefill(plan.prefills[0], 1)
        plan = self._grow_until_eviction(s)
        assert plan.evicted == [b]              # youngest


class TestDeadlineScheduling:
    def test_waiting_request_expires(self):
        _, s = _sched()
        req = Request(prompt=list(range(1, 8)), max_new_tokens=4,
                      deadline_ms=100.0)
        s.add_request(req, now=0.0)
        assert req.deadline_at == pytest.approx(0.1)
        plan = s.schedule(now=0.2)               # past the deadline
        assert plan.prefills == []
        assert req.status == "deadline_exceeded"
        assert isinstance(req.error, DeadlineExceeded)
        assert s.pop_finished() == [req]

    def test_running_request_expires_and_frees_pages(self):
        cache, s = _sched()
        req = Request(prompt=list(range(1, 8)), max_new_tokens=50,
                      deadline_ms=100.0)
        s.add_request(req, now=0.0)
        plan = s.schedule(now=0.0)
        s.complete_prefill(req, 1)
        free_before_expiry = cache.num_free
        plan = s.schedule(now=0.5)
        assert plan.decodes == []                # no further cadence
        assert req.status == "deadline_exceeded"
        assert req.pages == []
        assert cache.num_free > free_before_expiry
        assert s.status_counts["deadline_exceeded"] == 1

    def test_engine_deadline_to_terminal_status(self):
        eng, cfg, _ = _tiny_engine()
        rng = np.random.default_rng(1)
        p = list(rng.integers(1, cfg.vocab_size, size=5))
        ok_id = eng.submit(p, max_new_tokens=2)
        dead_id = eng.submit(p, max_new_tokens=64, deadline_ms=1.0)
        time.sleep(0.01)
        eng.run()
        done = {r.request_id: r for r in eng.scheduler.pop_finished()}
        assert done[ok_id].status == "ok"
        assert done[dead_id].status == "deadline_exceeded"
        assert eng.stats["requests_deadline_exceeded"] == 1
        assert eng.cache.num_free == eng.cache.num_pages - 1

    def test_terminal_status_single_assignment(self):
        _, s = _sched()
        req = Request(prompt=list(range(1, 8)), max_new_tokens=1)
        s.add_request(req)
        s.schedule()
        s.complete_prefill(req, 1)               # finishes: status ok
        assert req.status == "ok"
        with pytest.raises(RuntimeError, match="already reached"):
            s._finish(req, "failed")


# ---------------------------------------------------------------------------
# step-failure quarantine -> retry -> poison
# ---------------------------------------------------------------------------

class TestQuarantineRetry:
    @pytest.mark.parametrize("step,unread", [(1, 0), (3, 1)], ids=[
        "first_decode", "behind_a_decode_in_flight"])
    def test_transient_decode_error_retries_to_exact_tokens(self, step,
                                                            unread):
        """At step 1 the failing decode is the stream's first; at step 3
        the previous decode is still unread when the fault fires: its
        token is the request's own and lands before the quarantine."""
        eng, cfg, params = _tiny_engine(
            fault_injection={"faults": [
                {"kind": "decode_error", "step": step, "times": 1}]},
            retry={"max_attempts": 3, "backoff_base_ms": 1,
                   "backoff_cap_ms": 2, "jitter": 0.0})
        rng = np.random.default_rng(2)
        p = list(rng.integers(1, cfg.vocab_size, size=9))
        rid = eng.submit(p, max_new_tokens=6)
        for _ in range(step):
            eng.step()
        (req,) = eng.scheduler.running
        assert req.pending == unread and len(eng._inflight) == unread
        seen = len(req.generated)
        eng.step()                               # the fault fires here
        assert eng.stats["quarantines"] == 1 and not eng._inflight
        assert len(req.generated) == seen + unread and req.pending == 0
        eng.run()
        (done,) = eng.scheduler.pop_finished()
        assert done.request_id == rid
        assert done.generated == teacher_forced(
            cfg, params, neox_forward, p, 6)
        assert eng.stats["retries"] == 1
        assert eng.stats["requests_failed"] == 0
        assert eng.stats["lookahead_discarded"] == 0
        assert eng.cache.num_free == eng.cache.num_pages - 1

    @pytest.mark.parametrize("step", [0, 3], ids=[
        "idle_engine", "behind_a_decode_in_flight"])
    def test_transient_prefill_error_retries(self, step):
        """At step 3 the failing prefill is the second request's, while
        the first one's decode is in flight: that one is settled, not
        lost, and both streams stay exact."""
        eng, cfg, params = _tiny_engine(
            fault_injection={"faults": [
                {"kind": "prefill_error", "step": step, "times": 1}]},
            retry={"max_attempts": 3, "backoff_base_ms": 1,
                   "backoff_cap_ms": 2, "jitter": 0.0})
        rng = np.random.default_rng(3)
        prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
                   for n in (5, 11)]
        ids = [eng.submit(prompts[0], max_new_tokens=8)]
        for _ in range(step):
            eng.step()
        assert len(eng._inflight) == (1 if step else 0)
        ids.append(eng.submit(prompts[1], max_new_tokens=4))
        eng.step()                               # the fault fires here
        assert eng.stats["quarantines"] == 1
        # the first request's decode went on in the same step
        assert [r.request_id for r in eng.scheduler.running] == \
            ([0] if step else [])
        assert len(eng._inflight) == (1 if step else 0)
        eng.run()
        done = {r.request_id: r for r in eng.scheduler.pop_finished()}
        for rid, p, n in zip(ids, prompts, (8, 4)):
            assert done[rid].generated == teacher_forced(
                cfg, params, neox_forward, p, n)
        assert eng.stats["requests_failed"] == 0
        assert eng.cache.num_free == eng.cache.num_pages - 1

    def test_persistent_failure_poisons_typed(self):
        # `times` counts engine-step serials, not prefill attempts —
        # idle steps while the backoff window runs down consume it too,
        # so a persistent fault needs a step budget far past the
        # retry horizon
        eng, cfg, _ = _tiny_engine(
            fault_injection={"faults": [
                {"kind": "prefill_error", "step": 0, "times": 10**6}]},
            retry={"max_attempts": 2, "backoff_base_ms": 1,
                   "backoff_cap_ms": 2, "jitter": 0.0})
        rng = np.random.default_rng(4)
        rid = eng.submit(list(rng.integers(1, cfg.vocab_size, size=5)),
                         max_new_tokens=4)
        # drive until the backoff windows elapse and the poison
        # verdict lands — the server never dies along the way
        t0 = time.time()
        while eng.scheduler.has_work and time.time() - t0 < 30:
            eng.step()
        (req,) = eng.scheduler.pop_finished()
        assert req.request_id == rid
        assert req.status == "failed"
        assert isinstance(req.error, RequestFailed)
        assert isinstance(req.error.last_error, InjectedServingFault)
        assert req.error.attempts == 2
        # the stored exception must not pin the failing step's frames
        # (plan/batch arrays/engine) for the Request's lifetime
        assert req.error.last_error.__traceback__ is None
        assert eng.stats["requests_failed"] == 1
        assert eng.cache.num_free == eng.cache.num_pages - 1

    def test_backoff_gates_readmission(self):
        eng, cfg, _ = _tiny_engine(
            fault_injection={"faults": [
                {"kind": "prefill_error", "step": 0, "times": 1}]},
            retry={"max_attempts": 3, "backoff_base_ms": 60000,
                   "backoff_cap_ms": 60000, "jitter": 0.0})
        rng = np.random.default_rng(5)
        eng.submit(list(rng.integers(1, cfg.vocab_size, size=5)),
                   max_new_tokens=2)
        eng.step()                               # fails -> quarantined
        assert len(eng.scheduler.quarantined) == 1
        req = eng.scheduler.quarantined[0]
        assert req.retry_at > time.perf_counter() + 30
        eng.step()                               # backoff not elapsed
        assert eng.scheduler.quarantined == [req]
        assert req.state != "running"
        # collapse the backoff window: the retry then runs
        req.retry_at = 0.0
        eng.run(max_steps=20)
        assert req.status == "ok"

    def test_innocent_cobatched_failures_reset_on_success(self):
        eng, cfg, params = _tiny_engine(
            fault_injection={"faults": [
                {"kind": "decode_error", "step": 4, "times": 1}]},
            retry={"max_attempts": 2, "backoff_base_ms": 1,
                   "backoff_cap_ms": 2, "jitter": 0.0})
        rng = np.random.default_rng(6)
        prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
                   for n in (5, 12)]
        outs = eng.generate(prompts, max_new_tokens=8)
        # both requests rode the failed batch (failures=1 each with
        # max_attempts=2) yet completed exactly — the counter reset on
        # their next successful step kept them off the poison edge
        for p, o in zip(prompts, outs):
            assert o == teacher_forced(cfg, params, neox_forward, p, 8)
        assert eng.stats["requests_failed"] == 0

    @pytest.mark.parametrize("in_flight", [0, 1], ids=[
        "settled", "a_decode_in_flight"])
    def test_mid_execution_cache_loss_recovers(self, in_flight):
        """A compiled call that dies MID-EXECUTION consumes the donated
        KV pools: the quarantine path must rebuild them zeroed, evict
        every running sequence, and leave each request in exactly one
        scheduler collection — then everything still completes with the
        exact greedy continuation (re-prefill from full context). With a
        decode in flight, its tokens are read before the pools go."""
        eng, cfg, params = _tiny_engine(
            retry={"max_attempts": 3, "backoff_base_ms": 1,
                   "backoff_cap_ms": 2, "jitter": 0.0})
        rng = np.random.default_rng(15)
        prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
                   for n in (5, 12)]
        for p in prompts:
            eng.submit(p, max_new_tokens=6)
        while len(eng.scheduler.running) < 2 or \
                len(eng._inflight) < in_flight:
            eng.step()
        running = list(eng.scheduler.running)
        assert len(eng._inflight) == in_flight
        eng.cache.k.delete()                     # simulate the death
        eng.cache.v.delete()
        eng._quarantine_batch([running[0]], RuntimeError("device OOM"),
                              "decode")
        assert not eng.cache.k.is_deleted()      # pools rebuilt
        assert not eng._inflight and not any(r.pending for r in running)
        for r in running:
            places = sum([r in eng.scheduler.running,
                          r in eng.scheduler.quarantined,
                          r in list(eng.scheduler.waiting)])
            assert places == 1                   # never double-queued
        t0 = time.time()
        while eng.scheduler.has_work and time.time() - t0 < 30:
            eng.step()
        done = {r.request_id: r for r in eng.scheduler.pop_finished()}
        outs = [list(done[i].generated) for i in sorted(done)]
        for p, o in zip(prompts, outs):
            assert o == teacher_forced(cfg, params, neox_forward, p, 6)
        assert eng.cache.num_free == eng.cache.num_pages - 1

    def test_mid_execution_prefill_death_skips_stale_decode(self):
        """When a prefill dies mid-execution and cache-loss recovery
        evicts the running set, the SAME step's planned decode batch
        must be skipped — its rows now point at trash pages, and a
        decode would append a garbage token (possibly finishing a
        request 'ok' on it)."""
        eng, cfg, params = _tiny_engine(
            retry={"max_attempts": 3, "backoff_base_ms": 1,
                   "backoff_cap_ms": 2, "jitter": 0.0})
        rng = np.random.default_rng(16)
        p1 = list(rng.integers(1, cfg.vocab_size, size=5))
        p2 = list(rng.integers(1, cfg.vocab_size, size=12))
        eng.submit(p1, max_new_tokens=6)
        eng.step()                               # p1 running, 1 token
        (r1,) = list(eng.scheduler.running)
        tokens_before = list(r1.generated)
        eng.submit(p2, max_new_tokens=6)

        real = eng._dispatch_prefill

        def dying_prefill(plan):
            eng.cache.k.delete()                 # donated pools consumed
            eng.cache.v.delete()
            raise RuntimeError("mid-execution death")

        eng._dispatch_prefill = dying_prefill
        summary = eng.step()     # prefill dies -> recovery evicts r1
        eng._dispatch_prefill = real
        assert summary["decoded"] == 0           # stale decode skipped
        assert list(r1.generated) == tokens_before   # no garbage token
        assert not eng.cache.k.is_deleted()
        t0 = time.time()
        while eng.scheduler.has_work and time.time() - t0 < 30:
            eng.step()
        done = {r.request_id: r for r in eng.scheduler.pop_finished()}
        for p, rid in ((p1, 0), (p2, 1)):
            assert done[rid].status == "ok"
            assert list(done[rid].generated) == \
                teacher_forced(cfg, params, neox_forward, p, 6)
        assert eng.cache.num_free == eng.cache.num_pages - 1

    def test_page_pool_pressure_forces_evictions(self):
        eng, cfg, params = _tiny_engine(
            num_pages=9,                     # 8 usable pages
            max_seq_len=64, prefill_lengths=[32],
            max_batch_size=2, decode_batch_sizes=[1, 2],
            fault_injection={"faults": [
                {"kind": "page_pool_pressure", "step": 3, "times": 2,
                 "factor": 0.9}]})
        rng = np.random.default_rng(7)
        prompts = [list(rng.integers(1, cfg.vocab_size, size=30))
                   for _ in range(2)]
        outs = eng.generate(prompts, max_new_tokens=6)
        assert eng.stats["evictions"] >= 1
        for p, o in zip(prompts, outs):
            assert o == teacher_forced(cfg, params, neox_forward, p, 6)
        # seized pages all returned
        assert eng.cache.num_free == eng.cache.num_pages - 1


# ---------------------------------------------------------------------------
# hang watchdog around the serving step
# ---------------------------------------------------------------------------

class TestServingWatchdog:
    def test_decode_stall_fires_watchdog_and_requests_drain(self):
        eng, cfg, _ = _tiny_engine(
            hang_timeout_s=0.05,
            fault_injection={"faults": [
                {"kind": "decode_stall", "step": 6, "seconds": 0.4}]})
        assert eng.watchdog is not None
        rng = np.random.default_rng(8)
        p = list(rng.integers(1, cfg.vocab_size, size=5))
        eng.generate([p], max_new_tokens=3)      # warm the programs
        eng.submit(p, max_new_tokens=4)
        while eng.scheduler.has_work:
            eng.step()
        assert eng.watchdog_fires >= 1
        assert "thread" in eng.last_stack_dump
        assert eng._drain_requested              # emergency flush armed

    def test_compile_is_not_a_hang(self):
        # 0.2 s: under a cold program's compile (a second or so), and far
        # over the read-back of the warm decode left in flight, for which
        # the watchdog IS armed (at 1 ms that read-back lost the race on a
        # loaded machine, which is not what this test is about)
        eng, cfg, _ = _tiny_engine(hang_timeout_s=0.2)
        rng = np.random.default_rng(9)
        # every program cold: the watchdog must never arm on the
        # first (compiling) call of a bucket
        eng.generate([list(rng.integers(1, cfg.vocab_size, size=5))],
                     max_new_tokens=2)
        assert eng.watchdog_fires == 0


# ---------------------------------------------------------------------------
# drain deadline: typed terminal failure instead of silent abandonment
# ---------------------------------------------------------------------------

class _RecMonitor:
    def __init__(self):
        self.records = []
        self.closed = False

    def record(self, sample, scalars):
        self.records.append((sample, dict(scalars)))

    def observe_histogram(self, tag, value, edges=None):
        pass

    def flush(self):
        pass

    def close(self):
        self.closed = True

    def scalars(self):
        out = {}
        for _, sc in self.records:
            out.update(sc)
        return out


@pytest.mark.elastic
class TestDrainDeadlineTyped:
    @pytest.mark.parametrize("steps", [1, 3], ids=[
        "after_the_prefill", "a_decode_in_flight"])
    def test_inflight_failed_typed_and_flushed(self, steps):
        mon = _RecMonitor()
        eng, cfg, _ = _tiny_engine(monitor=mon)
        rng = np.random.default_rng(10)
        rid = eng.submit(list(rng.integers(1, cfg.vocab_size, size=6)),
                         max_new_tokens=64)
        for _ in range(steps):
            eng.step()
        assert len(eng._inflight) == (steps > 1)
        summary = eng.drain(deadline_s=0.0)
        assert summary["deadline_hit"] is True
        assert summary["inflight_abandoned"] == 1
        (req,) = eng.scheduler.pop_finished()
        assert req.request_id == rid
        # what was in flight was read before the request was failed
        assert not eng._inflight and req.pending == 0
        assert len(req.generated) == steps
        assert req.status == "failed"
        assert isinstance(req.error, DrainAborted)
        assert "drain" in str(req.error)
        # flushed to metrics BEFORE exit: the monitor saw the terminal
        # counter and was closed
        assert mon.scalars()["Serve/requests_failed"] == 1.0
        assert mon.closed
        assert eng.cache.num_free == eng.cache.num_pages - 1

    def test_quarantined_requests_also_failed_on_drain(self):
        eng, cfg, _ = _tiny_engine(
            fault_injection={"faults": [
                {"kind": "prefill_error", "step": 0, "times": 1}]},
            retry={"max_attempts": 3, "backoff_base_ms": 60000,
                   "backoff_cap_ms": 60000, "jitter": 0.0})
        rng = np.random.default_rng(11)
        eng.submit(list(rng.integers(1, cfg.vocab_size, size=5)),
                   max_new_tokens=2)
        eng.step()                   # quarantined with a long backoff
        summary = eng.drain(deadline_s=0.0)
        assert summary["inflight_abandoned"] == 1
        (req,) = eng.scheduler.pop_finished()
        assert isinstance(req.error, DrainAborted)


# ---------------------------------------------------------------------------
# a decode program in flight (the serve loop's one-step lookahead)
# ---------------------------------------------------------------------------

class TestProgramInFlight:
    """What happens to a request between the dispatch of its token and
    the read-back, in the cases the tests above do not already cover
    with a program in flight."""

    def _midstream(self, **kw):
        eng, cfg, params = _tiny_engine(**kw)
        rng = np.random.default_rng(21)
        prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
                   for n in (5, 12)]
        for p in prompts:
            eng.submit(p, max_new_tokens=8)
        for _ in range(4):
            eng.step()
        reqs = list(eng.scheduler.running)
        assert len(reqs) == 2 and len(eng._inflight) == 1
        assert all(r.pending == 1 for r in reqs)
        return eng, cfg, params, prompts, reqs

    def _finish(self, eng):
        t0 = time.time()
        while eng.scheduler.has_work and time.time() - t0 < 30:
            eng.step()
        assert not eng._inflight
        return {r.request_id: r for r in eng.scheduler.pop_finished()}

    def test_evicting_a_row_drops_its_pending_token(self):
        eng, cfg, params, prompts, reqs = self._midstream()
        seen = [list(r.generated) for r in reqs]
        victim = eng.scheduler._evict_victim(time.perf_counter())
        assert victim is reqs[1] and victim.pending == 0
        eng.step()       # reads the decode back: the victim's row is dropped
        assert eng.stats["lookahead_discarded"] == 1
        # the victim re-prefilled in that step: one token, sampled anew
        # at the dropped one's position
        assert len(victim.generated) == len(seen[1]) + 1
        assert victim.generated[:-1] == seen[1]
        done = self._finish(eng)
        for rid, p in enumerate(prompts):
            assert done[rid].generated == teacher_forced(
                cfg, params, neox_forward, p, 8)
        assert eng.stats["decode_tokens"] + eng.stats["prefill_requests"] \
            == 16
        assert eng.cache.num_free == eng.cache.num_pages - 1

    def test_deadline_expiry_drops_the_pending_token(self):
        eng, cfg, params, prompts, reqs = self._midstream()
        seen = list(reqs[0].generated)
        reqs[0].deadline_ms, reqs[0].deadline_at = 1.0, 0.0   # long past
        eng.step()
        assert reqs[0].status == "deadline_exceeded"
        assert reqs[0].generated == seen and reqs[0].pending == 0
        assert eng.stats["lookahead_discarded"] == 1
        done = self._finish(eng)
        assert done[1].generated == teacher_forced(
            cfg, params, neox_forward, prompts[1], 8)
        assert eng.cache.num_free == eng.cache.num_pages - 1

    def test_drain_finishes_what_is_in_flight(self):
        eng, cfg, params, prompts, reqs = self._midstream()
        summary = eng.drain(deadline_s=30.0)
        assert summary["deadline_hit"] is False
        assert summary["inflight_abandoned"] == 0
        assert not eng._inflight
        done = {r.request_id: r for r in eng.scheduler.pop_finished()}
        for rid, p in enumerate(prompts):
            assert done[rid].status == "ok"
            assert done[rid].generated == teacher_forced(
                cfg, params, neox_forward, p, 8)

    def test_hot_swap_between_steps_settles_first(self):
        eng, cfg, params, prompts, reqs = self._midstream()
        seen = [len(r.generated) for r in reqs]
        out = eng.hot_swap_weights(params)
        assert out["compile_delta"] == 0
        assert not eng._inflight
        assert [len(r.generated) for r in reqs] == [n + 1 for n in seen]
        assert all(r.pending == 0 for r in reqs)
        done = self._finish(eng)
        for rid, p in enumerate(prompts):
            assert done[rid].generated == teacher_forced(
                cfg, params, neox_forward, p, 8)
        assert eng.compile_count() == len(eng._compiled)


    _RETRY = {"max_attempts": 3, "backoff_base_ms": 1,
              "backoff_cap_ms": 2, "jitter": 0.0}

    @staticmethod
    def _fail_readbacks(eng, n):
        """The next `n` read-backs raise, as a device error does: at the
        host's read of the program's tokens, not at its dispatch."""
        real, left = eng._readback, [n]

        def flaky(arr):
            if left[0]:
                left[0] -= 1
                raise RuntimeError("device error at the read-back")
            return real(arr)
        eng._readback = flaky

    @pytest.mark.parametrize("fails", [1, 2], ids=[
        "its_own_read_only", "the_successor_fails_too"])
    def test_readback_error_quarantines_its_batch_once(self, fails):
        """Decode n's error surfaces when n+1 is already enqueued behind
        it. n+1 consumed n's tokens: whether its own read fails too (a
        real device error) or succeeds, nothing of it is recorded for
        n's requests, and they are quarantined once."""
        eng, cfg, params, prompts, reqs = self._midstream(retry=self._RETRY)
        seen = [list(r.generated) for r in reqs]
        self._fail_readbacks(eng, fails)
        eng.step()          # enqueues n+1, then reads n: the error is here
        assert not eng._inflight
        assert eng.stats["quarantines"] == 1 and eng.stats["retries"] == 2
        assert sorted(map(id, eng.scheduler.quarantined)) == \
            sorted(map(id, reqs))
        for r, before in zip(reqs, seen):
            assert (r.failures, r.evictions, r.pending) == (1, 1, 0)
            assert r.generated == before
        # n+1's rows: read and dropped, or lost with their program
        assert eng.stats["lookahead_discarded"] == (2 if fails == 1 else 0)
        done = self._finish(eng)
        for rid, p in enumerate(prompts):
            assert done[rid].status == "ok"
            assert done[rid].generated == teacher_forced(
                cfg, params, neox_forward, p, 8)
        assert eng.stats["quarantines"] == 1
        assert eng.stats["requests_failed"] == 0
        assert eng.cache.num_free == eng.cache.num_pages - 1

    def test_prefill_readback_error_retries(self):
        eng, cfg, params = _tiny_engine(retry=self._RETRY)
        p = list(np.random.default_rng(22).integers(1, cfg.vocab_size, 9))
        eng.submit(p, max_new_tokens=4)
        self._fail_readbacks(eng, 1)
        eng.step()
        (req,) = eng.scheduler.quarantined
        assert (req.failures, req.pending, req.generated) == (1, 0, [])
        assert eng.stats["quarantines"] == 1 and not eng._inflight
        done = self._finish(eng)
        assert done[0].generated == teacher_forced(
            cfg, params, neox_forward, p, 4)
        assert eng.cache.num_free == eng.cache.num_pages - 1

    def test_dispatch_error_over_a_failing_readback_is_one_fault(self):
        """A decode that fails at dispatch settles what is in flight
        first; when that read fails too, its requests are quarantined
        there and not a second time for the dispatch."""
        eng, cfg, params, prompts, reqs = self._midstream(
            retry=self._RETRY, fault_injection={"faults": [
                {"kind": "decode_error", "step": 4, "times": 1}]})
        self._fail_readbacks(eng, 1)
        eng.step()
        assert eng.stats["quarantines"] == 1 and eng.stats["retries"] == 2
        assert len(eng.scheduler.quarantined) == 2
        assert all((r.failures, r.evictions) == (1, 1) for r in reqs)
        done = self._finish(eng)
        for rid, p in enumerate(prompts):
            assert done[rid].generated == teacher_forced(
                cfg, params, neox_forward, p, 8)
        assert eng.cache.num_free == eng.cache.num_pages - 1

    def test_failure_path_counts_each_phase_once(self):
        """The read-backs a failure settles are timed under phases of
        their own, not inside the dispatch phase that failed."""
        eng, cfg, params, prompts, reqs = self._midstream(
            retry=self._RETRY, fault_injection={"faults": [
                {"kind": "decode_error", "step": 4, "times": 1}]})
        opened = []
        real = eng._phase

        @contextlib.contextmanager
        def spy(name):
            opened.append(name)
            try:
                with real(name):
                    yield
            finally:
                opened.append("/" + name)
        eng._phase = spy
        eng.step()
        assert opened == ["schedule", "/schedule",
                          "decode", "/decode",           # the dispatch
                          "decode", "readback",
                          "device_wait", "/device_wait", "/readback",
                          "complete", "/complete", "/decode"]


# ---------------------------------------------------------------------------
# per-status counters through the Prometheus + JSONL backends
# ---------------------------------------------------------------------------

@pytest.mark.fleet
class TestStatusCounterExport:
    def test_both_backends_serve_request_status_families(self, tmp_path):
        import urllib.request

        from deeperspeed_tpu.runtime.monitor import TensorBoardMonitor
        mon = TensorBoardMonitor(
            output_path=str(tmp_path), job_name="chaos",
            flush_interval=100,
            export={"prometheus_port": 0, "jsonl": True})
        try:
            eng, cfg, _ = _tiny_engine(
                monitor=mon, admission={"max_queue_depth": 2})
            rng = np.random.default_rng(12)
            p = list(rng.integers(1, cfg.vocab_size, size=5))
            eng.submit(p, max_new_tokens=2)
            eng.submit(p, max_new_tokens=2)
            with pytest.raises(RequestRejected):
                eng.submit(p, max_new_tokens=2)          # shed
            eng.run()
            eng.serve_stats()
            mon.flush()
            port = mon.prometheus.port
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics",
                timeout=5).read().decode()
            assert "ds_serve_requests_ok 2" in body
            assert "ds_serve_requests_shed 1" in body
            assert "ds_serve_requests_deadline_exceeded 0" in body
            assert "ds_serve_requests_failed 0" in body
            jsonl = (tmp_path / "chaos" / "events.jsonl").read_text()
            keys = set()
            for line in jsonl.splitlines():
                ev = json.loads(line)
                keys |= set(ev.get("scalars", {}))
            assert {"Serve/requests_ok", "Serve/requests_shed",
                    "Serve/requests_deadline_exceeded",
                    "Serve/requests_failed"} <= keys
        finally:
            mon.close()


# ---------------------------------------------------------------------------
# shared coordination-KV retry wrapper (heartbeat + fleet)
# ---------------------------------------------------------------------------

class _FlakyTransport:
    def __init__(self, fail_times):
        self.fail_times = fail_times
        self.calls = 0
        self.store = {}

    def _maybe_fail(self):
        self.calls += 1
        if self.fail_times:
            self.fail_times -= 1
            raise ConnectionError("coordination service unavailable")

    def publish(self, peer, payload):
        self._maybe_fail()
        self.store[str(peer)] = dict(payload)

    def read_all(self):
        self._maybe_fail()
        return {k: dict(v) for k, v in self.store.items()}


@pytest.mark.fleet
class TestKVRetryWrapper:
    def test_transient_blips_absorbed(self):
        inner = _FlakyTransport(fail_times=2)
        kv = RetryingKVTransport(inner, attempts=3, backoff_base_s=0.0,
                                 backoff_cap_s=0.0)
        kv.publish("0", {"serial": 1})
        assert inner.store == {"0": {"serial": 1}}
        assert kv.retry_count == 2
        assert not kv.degraded

    def test_backoff_is_capped_exponential_with_jitter(self):
        kv = RetryingKVTransport(_FlakyTransport(0), attempts=5,
                                 backoff_base_s=0.1, backoff_cap_s=0.25,
                                 jitter=0.0)
        assert [kv._backoff_s(a) for a in (1, 2, 3, 4)] == \
            [0.1, 0.2, 0.25, 0.25]
        jittered = RetryingKVTransport(
            _FlakyTransport(0), backoff_base_s=0.1, jitter=0.5,
            rng=type("R", (), {"random": staticmethod(lambda: 1.0)})())
        assert jittered._backoff_s(1) == pytest.approx(0.15)

    def test_persistent_failure_degrades_once_to_local(self, ds_logs):
        inner = _FlakyTransport(fail_times=10**6)
        kv = RetryingKVTransport(inner, attempts=2, backoff_base_s=0.0,
                                 backoff_cap_s=0.0,
                                 degrade_to_local=True, name="fleet test")
        kv.publish("0", {"serial": 1})
        kv.publish("0", {"serial": 2})
        degrade_warnings = [r for r in ds_logs.records
                            if "degrading to a local" in r.message]
        assert len(degrade_warnings) == 1                # warned ONCE
        assert kv.degraded
        # local continuity: the store still works this-host-only
        assert kv.read_all() == {"0": {"serial": 2}}
        assert inner.calls == 2                 # no further remote calls

    def test_no_degrade_reraises_for_heartbeat_escalation(self):
        kv = RetryingKVTransport(_FlakyTransport(fail_times=10**6),
                                 attempts=2, backoff_base_s=0.0,
                                 backoff_cap_s=0.0,
                                 degrade_to_local=False)
        with pytest.raises(ConnectionError):
            kv.publish("0", {"serial": 1})
        assert not kv.degraded
        assert kv.error_count == 2

    def test_fleet_aggregator_rides_degraded_wrapper(self):
        from deeperspeed_tpu.runtime.fleet import FleetAggregator
        kv = RetryingKVTransport(_FlakyTransport(fail_times=10**6),
                                 attempts=1, backoff_base_s=0.0,
                                 backoff_cap_s=0.0, degrade_to_local=True)
        agg = FleetAggregator(
            {"enabled": True, "window_steps": 2,
             "skew_interval_steps": 0},
            process_index=0, process_count=1,
            summary_transport=kv, trace_transport=kv)
        scalars = {}
        for _ in range(2):
            scalars = agg.on_step_end(0.01)
        # the window still closed with this host's own summary — the
        # degraded wrapper kept publish/read working locally
        assert scalars["Train/Fleet/hosts"] == 1.0
        assert agg._transport_errors == 0

    def test_heartbeat_monitor_escalates_through_wrapper(self):
        from deeperspeed_tpu.elasticity.heartbeat import (COORDINATOR,
                                                          PeerHealthMonitor)
        kv = RetryingKVTransport(_FlakyTransport(fail_times=10**6),
                                 attempts=2, backoff_base_s=0.0,
                                 backoff_cap_s=0.0,
                                 degrade_to_local=False)
        mon = PeerHealthMonitor("0", peers=["1"], interval_s=1.0,
                                warn_after_s=2.0, fail_after_s=5.0,
                                transport=kv, clock=lambda: 0.0)
        mon.poll_once(now=0.0)               # outage clock starts
        mon.poll_once(now=6.0)               # > fail_after_s
        assert COORDINATOR in mon.failed     # escalation still fires


# ---------------------------------------------------------------------------
# the chaos soak: fault storm + overload burst, invariants pinned
# ---------------------------------------------------------------------------

class TestChaosSoak:
    def test_fault_storm_invariants(self):
        """Injected decode errors + stalls + page-pool pressure + an
        overload burst against a bounded admission queue. Invariants:
        the server never exits, every submitted request reaches exactly
        one terminal status, the page free list is exact afterwards
        (zero leaked pages), and the compile count is frozen after
        warmup."""
        cfg = GPTNeoXConfig.tiny()
        model = GPTNeoX(config=cfg, use_pallas=False)
        params = model.init_params(jax.random.PRNGKey(13))
        conf = _engine_config(
            num_pages=17,                        # 16 usable pages
            max_seq_len=64, prefill_lengths=[16, 32, 64],
            prefill_batch_sizes=[2], decode_batch_sizes=[4],
            admission={"max_queue_depth": 3},
            retry={"max_attempts": 3, "backoff_base_ms": 1,
                   "backoff_cap_ms": 5, "jitter": 0.5},
            fault_injection={"faults": [
                {"kind": "decode_error", "step": 24, "times": 2},
                {"kind": "prefill_error", "step": 31, "times": 1},
                {"kind": "decode_stall", "step": 36, "seconds": 0.02},
                {"kind": "page_pool_pressure", "step": 40, "times": 3,
                 "factor": 0.9},
                {"kind": "decode_error", "step": 48, "times": 1},
            ]})
        eng = InferenceEngine(model, config=conf, params=params)
        rng = np.random.default_rng(14)

        # warm every program the storm can dispatch: all three prefill
        # length buckets (batch bucket is always 2) + the single decode
        # bucket — 3 prompts so the warmup itself stays under the
        # bounded admission queue
        eng.generate([list(rng.integers(1, cfg.vocab_size, size=n))
                      for n in (10, 30, 40)], max_new_tokens=3)
        warm = eng.compile_count()
        base = {k: eng.stats[k] for k in
                ("requests_ok", "requests_deadline_exceeded",
                 "requests_failed")}       # warmup traffic excluded

        # the storm: open-loop arrivals (bursty: 3 per arrival step,
        # against max_queue_depth 3), mixed priorities, a few requests
        # with tight deadlines
        accepted, shed = {}, []
        statuses = {}
        arrival = 0
        for step in range(250):
            if step < 60 and step % 2 == 0:
                for _ in range(3):
                    n = int(rng.integers(3, 30))
                    prompt = list(rng.integers(1, cfg.vocab_size, size=n))
                    kw = {"priority": ("batch" if arrival % 3 == 0
                                       else "interactive")}
                    if arrival % 7 == 0:
                        kw["deadline_ms"] = 1.0          # will expire
                    arrival += 1
                    try:
                        rid = eng.submit(prompt, max_new_tokens=6, **kw)
                        accepted[rid] = prompt
                    except RequestRejected as e:
                        assert e.retry_after_s > 0
                        shed.append(e)
            if eng.scheduler.has_work:
                eng.step()                        # must never raise
            for r in eng.scheduler.pop_finished():
                assert r.request_id not in statuses   # exactly once
                statuses[r.request_id] = r.status
            if not eng.scheduler.has_work and arrival > 0 and step >= 60:
                break

        # arrivals are over: drive the remaining work (incl. requests
        # whose retry backoff is still running down) to completion
        t0 = time.time()
        while eng.scheduler.has_work and time.time() - t0 < 60:
            eng.step()
            for r in eng.scheduler.pop_finished():
                assert r.request_id not in statuses   # exactly once
                statuses[r.request_id] = r.status

        assert not eng.scheduler.has_work
        # every submitted request reached exactly one terminal status
        assert len(statuses) == len(accepted)
        assert len(shed) + len(accepted) == arrival
        assert set(statuses.values()) <= {"ok", "deadline_exceeded",
                                          "failed"}
        counts = {st: sum(1 for v in statuses.values() if v == st)
                  for st in set(statuses.values())}
        assert counts.get("ok", 0) > 0            # the storm didn't win
        assert eng.stats["requests_shed"] == len(shed)
        assert sum(eng.stats[k] - base[k] for k in base) == len(accepted)
        # the storm actually exercised the machinery
        assert eng.stats["quarantines"] >= 2
        # zero leaked pages: the free list is EXACT (every allocatable
        # id present exactly once)
        assert eng.cache.num_free == eng.cache.num_pages - 1
        assert sorted(eng.cache._free) == \
            list(range(1, eng.cache.num_pages))
        # zero post-warmup recompiles
        assert eng.compile_count() == warm


# ---------------------------------------------------------------------------
# eviction x deadline-expiry x quarantine interleavings (PR 16 audit):
# a quarantined request holds NO pages (quarantine_request releases them
# up front) and `_evict_victim` only ever scans `running` — so the
# eviction machinery cannot double-free a quarantined request's pages or
# pick a parked request as victim. Pinned here against refactors of
# either routine, plus each pairwise interleaving of the three
# preemption paths and the triple at engine level.
# ---------------------------------------------------------------------------

class TestPreemptionInterleavings:
    def _running_pair(self):
        cache, s = _sched(pages=32)
        a = Request(prompt=list(range(1, 20)), max_new_tokens=30)
        b = Request(prompt=list(range(1, 18)), max_new_tokens=30)
        s.add_request(a, now=0.0)
        s.add_request(b, now=0.0)
        s.schedule(now=0.0)
        s.complete_prefill(a, 5)
        s.complete_prefill(b, 5)
        return cache, s, a, b

    def test_quarantined_request_holds_no_pages_and_is_never_victim(self):
        cache, s, a, b = self._running_pair()
        free_before = cache.num_free
        held = len(a.pages)
        s.quarantine_request(a, retry_at=10**9, now=1.0)
        # pages released AT quarantine time, not at readmission
        assert a.pages == [] and a.cached == 0
        assert cache.num_free == free_before + held
        # the victim scan cannot reach the parked request
        assert s._evict_victim(now=1.0) is b
        assert a in s.quarantined
        assert s._evict_victim(now=1.0) is None     # running empty
        # free-list exact: quarantine + both evictions leaked nothing
        assert cache.num_free == cache.num_pages - 1
        assert sorted(cache._free) == list(range(1, cache.num_pages))

    def test_eviction_then_deadline_expiry_while_waiting(self):
        cache, s, a, b = self._running_pair()
        a.deadline_at = 5.0
        victim = s._evict_victim(now=1.0)           # both evictable
        assert victim in (a, b)
        if victim is not a:
            s._evict_victim(now=1.0)                # force a out too
        assert a.pages == [] and a.evictions == 1
        # the deadline lapses while a sits in the requeue: it must
        # terminate from `waiting` without another prefill or page grab
        expired = s.expire_deadlines(now=6.0)
        assert a in expired
        assert a.status == "deadline_exceeded"
        assert a not in list(s.waiting)
        assert isinstance(a.error, DeadlineExceeded)

    def test_quarantine_then_deadline_expiry_during_backoff(self):
        cache, s, a, b = self._running_pair()
        a.deadline_at = 5.0
        s.quarantine_request(a, retry_at=10**9, now=1.0)
        # expiry must reach INTO the quarantine (a parked request's
        # clock keeps running) and pull it out of that collection
        expired = s.expire_deadlines(now=6.0)
        assert a in expired
        assert a.status == "deadline_exceeded"
        assert s.quarantined == []
        # b is untouched and still schedulable
        plan = s.schedule(now=7.0)
        assert plan.decodes == [b]
        assert cache.num_free == \
            cache.num_pages - 1 - len(b.pages)

    def test_eviction_of_readmitted_quarantine_survivor(self):
        cache, s, a, b = self._running_pair()
        s.quarantine_request(a, retry_at=0.0, now=1.0)
        # backoff elapsed: readmission puts it at the queue FRONT and
        # re-prefills the full context (prompt + generated so far)
        plan = s.schedule(now=2.0)
        assert a in plan.prefills
        assert a.evictions == 1
        s.complete_prefill(a, 6)
        # now evict the survivor again: the counters accumulate and the
        # pages cycle cleanly through a second preemption
        victim = s._evict_victim(now=3.0)
        assert victim in (a, b)
        assert victim.evictions >= 1
        assert victim.pages == []
        total_held = sum(len(r.pages) for r in s.running)
        assert cache.num_free == cache.num_pages - 1 - total_held

    def test_triple_interleaving_engine_level(self):
        """All three preemption paths in ONE stream: page-pool pressure
        evicts, an injected decode fault quarantines, a tight deadline
        expires — every request still reaches exactly one terminal
        status and the free list is exact."""
        eng, cfg, params = _tiny_engine(
            num_pages=9, max_seq_len=64, prefill_lengths=[32],
            max_batch_size=2, decode_batch_sizes=[1, 2],
            retry={"max_attempts": 3, "backoff_base_ms": 1,
                   "backoff_cap_ms": 2, "jitter": 0.0},
            fault_injection={"faults": [
                {"kind": "decode_error", "step": 3, "times": 1},
                {"kind": "page_pool_pressure", "step": 5, "times": 2,
                 "factor": 0.9}]})
        rng = np.random.default_rng(21)
        prompts = [list(rng.integers(1, cfg.vocab_size, size=30))
                   for _ in range(2)]
        ids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        doomed = eng.submit(
            list(rng.integers(1, cfg.vocab_size, size=30)),
            max_new_tokens=34, deadline_ms=1.0)
        t0 = time.time()
        while eng.scheduler.has_work and time.time() - t0 < 30:
            eng.step()
        done = {r.request_id: r for r in eng.scheduler.pop_finished()}
        assert done[doomed].status == "deadline_exceeded"
        for p, rid in zip(prompts, ids):
            assert done[rid].status == "ok"
            assert list(done[rid].generated) == \
                teacher_forced(cfg, params, neox_forward, p, 6)
        assert eng.stats["quarantines"] >= 1
        assert eng.cache.num_free == eng.cache.num_pages - 1
        assert sorted(eng.cache._free) == \
            list(range(1, eng.cache.num_pages))
