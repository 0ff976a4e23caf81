"""Disaggregated prefill/decode serving (docs/inference.md
"Disaggregated serving"): the `inference.disaggregation` config block,
the cross-pool KV-page handoff wire (bit-exact bf16/int8 round-trips,
refcount/free-list exactness on both pools, TTFT counted once per
request), the two-pool token-identity + zero-recompile pins, and the
SLO-aware front-end `ServeRouter` (weighted least-load routing, typed
all-shed, graceful scale-down)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.elasticity.heartbeat import InMemoryTransport
from deeperspeed_tpu.inference import (InferenceEngine, PagedKVCache,
                                       RequestRejected, ServeRouter)
from deeperspeed_tpu.inference.handoff import (HandoffChannel,
                                               HandoffRejected,
                                               check_geometry,
                                               decode_pages, encode_pages,
                                               write_pages)
from deeperspeed_tpu.inference.kv_cache import QuantizedPages
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.runtime import constants as c
from deeperspeed_tpu.runtime.config import parse_inference_block
from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError

pytestmark = [pytest.mark.disagg, pytest.mark.serving]


def _config(role=None, router=None, **kw):
    block = {"enabled": True, "page_size": 16, "num_pages": 64,
             "max_batch_size": 4, "token_budget": 256,
             "prefill_lengths": [16, 32, 64],
             "prefill_batch_sizes": [1, 2],
             "decode_batch_sizes": [1, 2, 4]}
    if role is not None:
        block["disaggregation"] = {"role": role,
                                   "pool_id": f"{role[:3]}0"}
    if router is not None:
        block["router"] = router
    block.update(kw)
    return {"inference": block}


@pytest.fixture(scope="module")
def tiny():
    cfg = GPTNeoXConfig.tiny()
    model = GPTNeoX(config=cfg, use_pallas=False)
    params = model.init_params(jax.random.PRNGKey(1))
    return cfg, model, params




def _no_leaks(cache):
    """The free list and the refcounted allocations partition the
    allocatable pool exactly — no page leaked, none double-tracked."""
    free = set(cache._free)
    held = set(cache._refcount)
    assert not free & held
    assert free | held == set(range(1, cache.num_pages))


def _drive_split(pre, dec, ids, max_steps=300):
    done = {}
    for _ in range(max_steps):
        pre.step()
        dec.step()
        for r in pre.scheduler.pop_finished() + \
                dec.scheduler.pop_finished():
            done[r.request_id] = r
        if (len(done) == len(ids) and not pre._pending_handoff and
                not pre._handoff_outbox):
            break
    return done


# ---------------------------------------------------------------------------
# config strictness
# ---------------------------------------------------------------------------

class TestDisaggConfig:
    def test_defaults_unified(self):
        p = parse_inference_block(_config())
        assert p["disaggregation"] == {
            "role": "unified", "pool_id": "unified-0",
            "handoff_timeout_s": 30.0}
        assert p["router"] is None

    def test_role_and_pool_id_parse(self):
        p = parse_inference_block(_config("prefill"))
        assert p["disaggregation"]["role"] == "prefill"
        assert p["disaggregation"]["pool_id"] == "pre0"

    @pytest.mark.parametrize("block,msg", [
        ({"role": "prefil"}, "must be one of"),
        ({"role": "prefill", "pool_id": "a:b"}, "without"),
        ({"role": "prefill", "pool_id": "a/b"}, "without"),
        ({"role": "prefill", "pool_id": ""}, "non-empty"),
        ({"role": "decode", "handoff_timeout_s": 0}, "number > 0"),
        ({"role": "decode", "handoff_timeout_s": True}, "number > 0"),
        ({"rol": "decode"}, "Unknown"),
    ])
    def test_disagg_block_rejects(self, block, msg):
        cfg = _config()
        cfg["inference"]["disaggregation"] = block
        with pytest.raises(DeepSpeedConfigError, match=msg):
            parse_inference_block(cfg)

    @pytest.mark.parametrize("block,msg", [
        ({"queue_depth_weight": -1}, "number >= 0"),
        ({"pool_util_weight": True}, "number >= 0"),
        ({"scale_up_util": 0}, "in"),
        ({"scale_up_util": 1.5}, "in"),
        ({"ttft_wight": 0.1}, "Unknown"),
    ])
    def test_router_block_rejects(self, block, msg):
        with pytest.raises(DeepSpeedConfigError, match=msg):
            parse_inference_block(_config(router=block))

    def test_router_block_parses(self):
        p = parse_inference_block(_config(router={
            "queue_depth_weight": 2, "scale_up_util": 0.5}))
        assert p["router"]["queue_depth_weight"] == 2.0
        assert p["router"]["scale_up_util"] == 0.5
        assert p["router"]["pool_util_weight"] == 32.0

    def test_speculative_disagg_rejected(self):
        cfg = _config("prefill")
        cfg["inference"]["speculative"] = {"enabled": True,
                                           "num_draft_tokens": 2}
        with pytest.raises(DeepSpeedConfigError, match="speculative"):
            parse_inference_block(cfg)

    def test_role_needs_transport(self, tiny):
        cfg, model, params = tiny
        with pytest.raises(DeepSpeedConfigError, match="transport"):
            InferenceEngine(model, config=_config("prefill"),
                            params=params)

    def test_decode_role_refuses_submit(self, tiny):
        cfg, model, params = tiny
        eng = InferenceEngine(model, config=_config("decode"),
                              params=params,
                              handoff_transport=InMemoryTransport())
        with pytest.raises(RuntimeError, match="decode-role"):
            eng.submit([1, 2, 3], 4)


# ---------------------------------------------------------------------------
# KV-page wire format
# ---------------------------------------------------------------------------

def _filled_cache(dtype, seed=0):
    cache = PagedKVCache(num_layers=2, num_pages=8, num_heads=2,
                         page_size=4, head_dim=8, dtype=dtype)
    rng = np.random.default_rng(seed)
    shape = (2, 8, 2, 4, 8)
    if isinstance(cache.k, QuantizedPages):
        for pool in (cache.k, cache.v):
            data = rng.integers(-127, 128, size=shape, dtype=np.int8)
            scale = rng.random((2, 8, 2, 4), np.float32) + 0.5
        cache.k = QuantizedPages(jnp.asarray(data),
                                 jnp.asarray(scale, jnp.bfloat16))
        data2 = rng.integers(-127, 128, size=shape, dtype=np.int8)
        scale2 = rng.random((2, 8, 2, 4), np.float32) + 0.5
        cache.v = QuantizedPages(jnp.asarray(data2),
                                 jnp.asarray(scale2, jnp.bfloat16))
    else:
        cache.k = jnp.asarray(rng.standard_normal(shape), dtype)
        cache.v = jnp.asarray(rng.standard_normal(shape), dtype)
    return cache


class TestWireFormat:
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    def test_round_trip_bit_exact(self, dtype):
        src = _filled_cache(dtype)
        payload = encode_pages(src, [2, 5, 3])
        k, v, k_scale, v_scale = decode_pages(payload)
        assert k_scale is None and v_scale is None
        idx = np.asarray([2, 5, 3])
        np.testing.assert_array_equal(
            k.view(np.uint8), np.asarray(src.k[:, idx]).view(np.uint8))
        np.testing.assert_array_equal(
            v.view(np.uint8), np.asarray(src.v[:, idx]).view(np.uint8))
        # install into a second pool and compare the landed rows
        dst = PagedKVCache(num_layers=2, num_pages=8, num_heads=2,
                           page_size=4, head_dim=8, dtype=dtype)
        write_pages(dst, [6, 1, 4], payload)
        np.testing.assert_array_equal(
            np.asarray(dst.k[:, [6, 1, 4]]).view(np.uint8),
            np.asarray(src.k[:, idx]).view(np.uint8))

    def test_int8_scales_travel_bit_exact(self):
        src = _filled_cache(jnp.int8)
        payload = encode_pages(src, [1, 7])
        k, v, k_scale, v_scale = decode_pages(payload)
        idx = np.asarray([1, 7])
        np.testing.assert_array_equal(
            k, np.asarray(src.k.data[:, idx]))
        np.testing.assert_array_equal(
            k_scale.view(np.uint8),
            np.asarray(src.k.scale[:, idx]).view(np.uint8))
        np.testing.assert_array_equal(
            v_scale.view(np.uint8),
            np.asarray(src.v.scale[:, idx]).view(np.uint8))
        dst = PagedKVCache(num_layers=2, num_pages=8, num_heads=2,
                           page_size=4, head_dim=8, dtype=jnp.int8)
        write_pages(dst, [3, 2], payload)
        np.testing.assert_array_equal(
            np.asarray(dst.k.data[:, [3, 2]]),
            np.asarray(src.k.data[:, idx]))
        np.testing.assert_array_equal(
            np.asarray(dst.v.scale[:, [3, 2]]).view(np.uint8),
            np.asarray(src.v.scale[:, idx]).view(np.uint8))

    def test_trash_page_never_ships(self):
        src = _filled_cache(jnp.float32)
        with pytest.raises(ValueError, match="trash page"):
            encode_pages(src, [0, 2])
        with pytest.raises(ValueError, match="trash page"):
            encode_pages(src, [2, 99])

    def test_geometry_and_precision_rejected_typed(self):
        src = _filled_cache(jnp.float32)
        payload = encode_pages(src, [2])
        other = PagedKVCache(num_layers=2, num_pages=8, num_heads=2,
                             page_size=8, head_dim=8, dtype=jnp.float32)
        with pytest.raises(HandoffRejected) as e:
            check_geometry(other, payload)
        assert e.value.reason == "geometry"
        bf16 = PagedKVCache(num_layers=2, num_pages=8, num_heads=2,
                            page_size=4, head_dim=8, dtype=jnp.bfloat16)
        with pytest.raises(HandoffRejected) as e:
            check_geometry(bf16, payload)
        assert e.value.reason == "geometry"
        with pytest.raises(HandoffRejected) as e:
            write_pages(bf16, [2], payload)
        assert e.value.reason == "geometry"

    def test_channel_offer_ack_lifecycle(self):
        t = InMemoryTransport()
        pre = HandoffChannel(t, "p0")
        dec = HandoffChannel(t, "d0")
        dec.announce("decode", load=1.0)
        pre.announce("prefill", load=0.0)
        assert pre.choose_decode_pool() == "d0"
        key = pre.offer("d0", "7", {"n": 1, "blob": "x"})
        offers = dec.poll_offers()
        assert [k for k, _ in offers] == [key]
        # ack overwrites the slot: the page bytes are tombstoned
        dec.ack(key, ok=True)
        assert dec.poll_offers() == []
        acks = pre.poll_acks()
        assert len(acks) == 1 and acks[0][1] == "7"
        assert "blob" not in acks[0][2]
        pre.retire(key)
        assert pre.poll_acks() == []

    def test_withdrawn_offer_skipped(self):
        t = InMemoryTransport()
        pre = HandoffChannel(t, "p0")
        dec = HandoffChannel(t, "d0")
        key = pre.offer("d0", "1", {"n": 1})
        pre.withdraw(key)
        assert dec.poll_offers() == []
        assert pre.poll_acks() == []


# ---------------------------------------------------------------------------
# two-pool split: token identity, accounting exactness, recompiles
# ---------------------------------------------------------------------------

class TestTwoPoolSplit:
    def test_greedy_token_identity_and_no_leaks(self, tiny):
        cfg, model, params = tiny
        uni = InferenceEngine(model, config=_config(), params=params)
        rng = np.random.default_rng(0)
        prompts = [list(map(int, rng.integers(1, cfg.vocab_size, size=n)))
                   for n in (5, 11, 17, 30)]
        base = uni.generate(prompts, max_new_tokens=6)

        t = InMemoryTransport()
        pre = InferenceEngine(model, config=_config("prefill"),
                              params=params, handoff_transport=t)
        dec = InferenceEngine(model, config=_config("decode"),
                              params=params, handoff_transport=t)
        ids = [pre.submit(p, 6) for p in prompts]
        done = _drive_split(pre, dec, ids)
        assert [list(done[i].generated) for i in ids] == base
        assert [done[i].status for i in ids] == ["ok"] * 4
        assert pre.stats["handoff_acked"] == 4
        assert dec.stats["handoff_installed"] == 4
        _no_leaks(pre.cache)
        _no_leaks(dec.cache)
        assert pre.cache.num_free == pre.cache.num_pages - 1
        assert dec.cache.num_free == dec.cache.num_pages - 1

    def test_token_identity_int8_pools(self, tiny):
        """Int8 handoff: the pages AND their per-page scales travel, so
        the split decodes token-identically to an int8 unified engine."""
        cfg, model, params = tiny
        uni = InferenceEngine(model, config=_config(
            kv_cache_dtype="int8"), params=params)
        rng = np.random.default_rng(5)
        prompts = [list(map(int, rng.integers(1, cfg.vocab_size, size=n)))
                   for n in (7, 19)]
        base = uni.generate(prompts, max_new_tokens=5)

        t = InMemoryTransport()
        pre = InferenceEngine(model, config=_config(
            "prefill", kv_cache_dtype="int8"), params=params,
            handoff_transport=t)
        dec = InferenceEngine(model, config=_config(
            "decode", kv_cache_dtype="int8"), params=params,
            handoff_transport=t)
        ids = [pre.submit(p, 5) for p in prompts]
        done = _drive_split(pre, dec, ids)
        assert [list(done[i].generated) for i in ids] == base
        _no_leaks(pre.cache)
        _no_leaks(dec.cache)

    def test_ttft_counted_once_across_boundary(self, tiny):
        cfg, model, params = tiny
        t = InMemoryTransport()
        pre = InferenceEngine(model, config=_config("prefill"),
                              params=params, handoff_transport=t)
        dec = InferenceEngine(model, config=_config("decode"),
                              params=params, handoff_transport=t)
        ids = [pre.submit([1 + i, 2, 3, 4, 5], 4) for i in range(3)]
        done = _drive_split(pre, dec, ids)
        assert len(done) == 3
        # TTFT observed exactly once per request, on the PREFILL pool
        assert pre.request_metrics.ttft.count == 3
        assert dec.request_metrics.ttft.count == 0
        # the handoff round-trip latency landed on the prefill pool
        assert pre.request_metrics.handoff.count == 3
        assert "handoff_p50_ms" in pre.serve_stats()

    def test_zero_recompiles_after_warmup(self, tiny):
        cfg, model, params = tiny
        t = InMemoryTransport()
        pre = InferenceEngine(model, config=_config("prefill"),
                              params=params, handoff_transport=t)
        dec = InferenceEngine(model, config=_config("decode"),
                              params=params, handoff_transport=t)
        rng = np.random.default_rng(2)

        def burst(seed_lo):
            prompts = [list(map(int, rng.integers(1, cfg.vocab_size,
                                                  size=n)))
                       for n in (6, 12, 6, 12)]
            ids = [pre.submit(p, 4) for p in prompts]
            done = _drive_split(pre, dec, ids)
            assert len(done) == 4

        # two warmup bursts: the first runs before the decode pool has
        # announced (offers wait in the outbox, then install together),
        # the second with announcements live (staggered installs), so
        # between them every decode batch bucket the stream uses warms
        burst(0)
        burst(1)
        warm_pre, warm_dec = pre.compile_count(), dec.compile_count()
        burst(2)
        assert pre.compile_count() == warm_pre
        assert dec.compile_count() == warm_dec

    def test_decode_pool_rejection_returns_pages(self, tiny):
        """An offer the decode pool cannot hold bounces with a typed
        reason; the prefill pool requeues the request with eviction
        semantics and leaks nothing."""
        cfg, model, params = tiny
        t = InMemoryTransport()
        pre = InferenceEngine(model, config=_config("prefill"),
                              params=params, handoff_transport=t)
        # decode pool with a DIFFERENT page geometry: every offer
        # bounces with the typed ``geometry`` reason
        dec = InferenceEngine(model, config=_config(
            "decode", page_size=8, prefill_lengths=[16, 32, 64]),
            params=params, handoff_transport=t)
        rng = np.random.default_rng(3)
        prompt = list(map(int, rng.integers(1, cfg.vocab_size, size=33)))
        rid = pre.submit(prompt, 4)
        for _ in range(4):
            pre.step()
            dec.step()
        assert dec.stats["handoff_refused"] >= 1
        assert pre.stats["handoff_rejected"] >= 1
        # the request went back to the prefill pool, eviction-style
        req = next(r for r in list(pre.scheduler.waiting) +
                   list(pre.scheduler.running) + pre._handoff_outbox +
                   [r for r, _ in pre._pending_handoff.values()]
                   if r.request_id == rid)
        assert req.evictions >= 1
        _no_leaks(pre.cache)
        _no_leaks(dec.cache)
        assert dec.cache.num_free == dec.cache.num_pages - 1

    def test_offer_timeout_requeues(self, tiny):
        """A dead decode pool (announced, never stepping) times the
        offer out: withdrawn, requeued, zero leaks."""
        cfg, model, params = tiny
        t = InMemoryTransport()
        pre = InferenceEngine(model, config=_config("prefill"),
                              params=params, handoff_transport=t)
        pre.handoff_timeout_s = 0.0     # expire immediately
        # a decode pool that announced once and died
        ghost = HandoffChannel(t, "dead0")
        ghost.announce("decode", load=0.0)
        pre.submit([1, 2, 3, 4, 5], 4)
        pre.step()                       # prefill + offer
        assert pre.stats["handoff_sent"] == 1
        pre.step()                       # timeout sweep: withdraw+requeue
        assert pre.stats["handoff_expired"] >= 1
        _no_leaks(pre.cache)
        # the same step re-prefills and RE-OFFERS to the only announced
        # pool (same slot key, overwriting the withdraw tombstone): the
        # offer a late decode read now sees is the FRESH one, carrying
        # the eviction the withdrawal forced — never the stale pages
        assert pre.stats["handoff_sent"] == 2
        dec_ch = HandoffChannel(t, "dead0")
        offers = dec_ch.poll_offers()
        assert len(offers) == 1
        assert offers[0][1]["request"]["evictions"] >= 1

    def test_prefill_storm_decode_isolation(self, tiny):
        """The perf contract, functionally: a storm of fresh prompts on
        the prefill pool neither recompiles nor stalls the decode
        pool's cadence — its running sequences keep producing a token
        per step."""
        cfg, model, params = tiny
        t = InMemoryTransport()
        pre = InferenceEngine(model, config=_config("prefill"),
                              params=params, handoff_transport=t)
        # decode batch capped at the seeded pair: storm installs bounce
        # with the typed ``busy`` reason instead of warming new decode
        # buckets, so the compile-count pin measures steady state
        dec = InferenceEngine(model, config=_config(
            "decode", max_batch_size=2, decode_batch_sizes=[1, 2]),
            params=params, handoff_transport=t)
        rng = np.random.default_rng(4)
        # seed the decode pool with two long-running sequences
        seeds = [pre.submit(list(map(int, rng.integers(
            1, cfg.vocab_size, size=8))), 40) for _ in range(2)]
        for _ in range(6):
            pre.step()
            dec.step()
        assert len(dec.scheduler.running) == 2
        warm = dec.compile_count()
        # storm: a fresh prompt every decode step
        tokens_before = dec.stats["decode_tokens"]
        for _ in range(10):
            pre.submit(list(map(int, rng.integers(
                1, cfg.vocab_size, size=30))), 2)
            pre.step()
            dec.step()
        produced = dec.stats["decode_tokens"] - tokens_before
        # cadence held: >= 2 running seqs × ~10 steps of tokens (minus
        # install-step scheduling slack), zero new decode-pool programs
        assert produced >= 16
        assert dec.compile_count() == warm

    def test_eviction_deadline_soak_exact_accounting(self, tiny):
        """Soak with page pressure (decode-pool evictions) and expiring
        deadlines crossing the handoff: every request reaches exactly
        one terminal status and both free lists come back exact."""
        cfg, model, params = tiny
        t = InMemoryTransport()
        pre = InferenceEngine(model, config=_config("prefill"),
                              params=params, handoff_transport=t)
        # small decode pool: concurrent long sequences force evictions
        dec = InferenceEngine(model, config=_config(
            "decode", num_pages=7, max_seq_len=64, prefill_lengths=[32],
            max_batch_size=2, decode_batch_sizes=[1, 2]),
            params=params, handoff_transport=t)
        rng = np.random.default_rng(6)
        ids = []
        for i in range(5):
            prompt = list(map(int, rng.integers(1, cfg.vocab_size,
                                                size=14 + i)))
            # one immediate expiry, one that crosses the handoff alive
            deadline = {1: 1, 3: 60}.get(i)
            ids.append(pre.submit(prompt, 12, deadline_ms=deadline))
        done = _drive_split(pre, dec, ids, max_steps=600)
        assert len(done) == len(ids)
        statuses = {done[i].status for i in ids}
        assert statuses <= {"ok", "deadline_exceeded"}
        assert "deadline_exceeded" in statuses   # some did expire
        _no_leaks(pre.cache)
        _no_leaks(dec.cache)
        assert pre.cache.num_free == pre.cache.num_pages - 1
        assert dec.cache.num_free == dec.cache.num_pages - 1


# ---------------------------------------------------------------------------
# Prometheus pool labels
# ---------------------------------------------------------------------------

class TestPoolLabels:
    def test_serve_families_carry_role_and_host(self, tiny, tmp_path):
        from deeperspeed_tpu.runtime.monitor import TensorBoardMonitor
        cfg, model, params = tiny
        mon = TensorBoardMonitor(
            output_path=str(tmp_path), job_name="disagg",
            flush_interval=100, export={"prometheus_port": 0})
        t = InMemoryTransport()
        pre = InferenceEngine(model, config=_config("prefill"),
                              params=params, handoff_transport=t,
                              monitor=mon, owns_monitor=False)
        dec = InferenceEngine(model, config=_config("decode"),
                              params=params, handoff_transport=t)
        ids = [pre.submit([3, 1, 4, 1, 5], 3)]
        _drive_split(pre, dec, ids)
        pre.serve_stats()
        mon.flush()
        text = mon.prometheus.render()
        assert 'ds_serve_queue_depth{host="pre0",role="prefill"}' in text
        assert 'ds_serve_handoff_acked{host="pre0",role="prefill"}' in text
        # histogram families carry the labels merged with `le`
        assert 'ds_serve_ttft_ms_bucket{le="+Inf",host="pre0",' \
               'role="prefill"}' in text
        mon.close()


# ---------------------------------------------------------------------------
# front-end router
# ---------------------------------------------------------------------------

def _admission(**kw):
    block = {"max_queue_depth": 2, "shed_page_pool_util": 0.95,
             "shed_ttft_ema_ms": 1e9}
    block.update(kw)
    return block


class TestServeRouter:
    def test_routes_to_least_loaded(self, tiny):
        cfg, model, params = tiny
        a = InferenceEngine(model, config=_config(), params=params)
        b = InferenceEngine(model, config=_config(), params=params)
        router = ServeRouter({"a": a, "b": b})
        # load pool a: queued work raises its score
        a.submit([1, 2, 3], 4)
        a.submit([4, 5, 6], 4)
        name, rid = router.submit([7, 8, 9], 4)
        assert name == "b"
        assert router.stats["routed"] == 1
        assert router.routed_by_pool == {"a": 0, "b": 1}
        assert router.load_score("a") > router.load_score("b")

    def test_router_weights_picked_up_from_engine_config(self, tiny):
        """No explicit config= → the router reads the first pool's own
        validated ``inference.router`` block (the parse→consumer wire,
        not a dead knob)."""
        cfg, model, params = tiny
        eng = InferenceEngine(
            model, config=_config(router={"ttft_weight": 7.5}),
            params=params)
        router = ServeRouter({"a": eng})
        assert router.ttft_weight == 7.5
        # an explicit config= still wins
        router = ServeRouter({"a": eng}, config={"ttft_weight": 1.25})
        assert router.ttft_weight == 1.25
        # no block anywhere → the documented defaults
        bare = InferenceEngine(model, config=_config(), params=params)
        assert ServeRouter({"a": bare}).ttft_weight == \
            c.INFERENCE_ROUTER_TTFT_WEIGHT_DEFAULT

    def test_decode_pools_never_route(self, tiny):
        cfg, model, params = tiny
        t = InMemoryTransport()
        pre = InferenceEngine(model, config=_config("prefill"),
                              params=params, handoff_transport=t)
        dec = InferenceEngine(model, config=_config("decode"),
                              params=params, handoff_transport=t)
        router = ServeRouter({"pre": pre, "dec": dec})
        assert router.routable_pools() == ["pre"]
        name, _ = router.submit([1, 2, 3], 2)
        assert name == "pre"

    def test_all_shed_reraises_min_retry_after(self, tiny):
        cfg, model, params = tiny
        a = InferenceEngine(model, config=_config(
            admission=_admission()), params=params)
        b = InferenceEngine(model, config=_config(
            admission=_admission()), params=params)
        router = ServeRouter({"a": a, "b": b})
        # fill both admission queues to the brim
        for eng in (a, b):
            eng.submit([1, 2, 3], 2)
            eng.submit([4, 5, 6], 2)
        with pytest.raises(RequestRejected) as e:
            router.submit([7, 8, 9], 2)
        assert e.value.retry_after_s > 0
        assert e.value.reason == "queue_full"
        assert router.stats["shed"] == 1
        # the hint is the SOONEST across pools
        hints = []
        for eng in (a, b):
            with pytest.raises(RequestRejected) as pe:
                eng.submit([7, 8, 9], 2)
            hints.append(pe.value.retry_after_s)
        assert e.value.retry_after_s <= min(hints) + 1e-9

    def test_drain_removes_pool_from_rotation(self, tiny):
        cfg, model, params = tiny
        a = InferenceEngine(model, config=_config(), params=params)
        b = InferenceEngine(model, config=_config(), params=params)
        router = ServeRouter({"a": a, "b": b})
        summary = router.drain("a")
        assert summary["inflight_abandoned"] == 0
        assert router.routable_pools() == ["b"]
        for _ in range(3):
            name, _ = router.submit([1, 2, 3], 2)
            assert name == "b"
        assert a.scheduler.draining

    def test_serve_stats_gauges(self, tiny, tmp_path):
        from deeperspeed_tpu.runtime.monitor import TensorBoardMonitor
        cfg, model, params = tiny
        mon = TensorBoardMonitor(
            output_path=str(tmp_path), job_name="router",
            flush_interval=100, export={"prometheus_port": 0})
        t = InMemoryTransport()
        pre = InferenceEngine(model, config=_config("prefill"),
                              params=params, handoff_transport=t)
        dec = InferenceEngine(model, config=_config("decode"),
                              params=params, handoff_transport=t)
        router = ServeRouter({"pre": pre, "dec": dec}, monitor=mon)
        _, rid = router.submit([2, 7, 1, 8], 3)
        done = _drive_split(pre, dec, [rid])
        assert len(done) == 1
        stats = router.serve_stats()
        assert stats["routed"] == 1 and stats["shed"] == 0
        assert set(stats["pool_loads"]) == {"pre", "dec"}
        assert stats["advise_scale_up"] == 0.0
        assert stats["handoff_p50_ms"] is not None
        mon.flush()
        text = mon.prometheus.render()
        assert "ds_serve_router_routed 1.0" in text
        assert "ds_serve_router_load_pre" in text
        assert "ds_serve_router_advise_scale_up 0.0" in text
        mon.close()

    def test_router_step_convenience(self, tiny):
        cfg, model, params = tiny
        t = InMemoryTransport()
        pre = InferenceEngine(model, config=_config("prefill"),
                              params=params, handoff_transport=t)
        dec = InferenceEngine(model, config=_config("decode"),
                              params=params, handoff_transport=t)
        router = ServeRouter({"pre": pre, "dec": dec})
        _, rid = router.submit([5, 4, 3, 2, 1], 3)
        for _ in range(100):
            if not router.has_work:
                break
            router.step()
        done = {r.request_id: r for r in router.pop_finished()}
        assert done[rid].status == "ok"
