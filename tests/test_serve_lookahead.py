"""The serve loop's one-step lookahead (docs/inference.md): `step()`
enqueues its prefill and its decode before it reads the previous decode
back, the decode taking its continuing rows' tokens from the device.

The order of host work changes and nothing else, so the bar is the plain
loop's: every served token is what the model's own forward (no cache, one
pass over prompt + served tokens) gives at that position — argmax when
greedy, and when sampling the draw of the program's own key
(`fold_in(PRNGKey(seed), n)` for the n-th dispatched program) at the
row's place in the batch. Pythia-shaped and OLMoE-shaped tiny models.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.inference import InferenceEngine
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from tests.model.references import model_rows

pytestmark = pytest.mark.serving

SEED, TEMPERATURE = 7, 0.8


def _configs():
    tiny = GPTNeoXConfig.tiny()
    return {
        "pythia": tiny,
        # OLMoE's block at the tiny widths: RMSNorm, no bias, QK-norm,
        # SiLU-gated experts, 2 of 8 a token, nothing dropped
        "olmoe": dataclasses.replace(
            tiny, rotary_pct=1.0, use_parallel_residual=False,
            norm="rmsnorm", use_bias=False, qk_norm=True,
            hidden_act="silu", ffn_gated=True, ffn_width=32,
            moe_num_experts=8, moe_top_k=2, moe_dropless=True),
    }


@pytest.fixture(scope="module", params=["pythia", "olmoe"])
def served(request):
    model = GPTNeoX(_configs()[request.param], use_pallas=False)
    return model, model.init_params(jax.random.PRNGKey(1))


def _engine(model, params, draft=None, transport=None, **over):
    block = {"enabled": True, "page_size": 16, "num_pages": 64,
             "max_batch_size": 4, "token_budget": 256, "seed": SEED,
             "prefill_lengths": [16, 32, 64], "prefill_batch_sizes": [1, 2],
             "decode_batch_sizes": [1, 2, 4]}
    block.update(over)
    kw = {"draft_model": draft[0], "draft_params": draft[1]} if draft else {}
    return InferenceEngine(model, config={"inference": block},
                           params=params, handoff_transport=transport, **kw)


def _prompts(model, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, model.config.vocab_size, size=n).tolist()
            for n in lengths]


def _record_dispatches(engine):
    """Every program `engine` dispatches from now on, as (phase, rng
    counter, batch bucket, [(request, index of the token this row
    samples)])."""
    log = []

    def wrap(phase):
        inner = getattr(engine, "_dispatch_" + phase)

        def dispatch(plan):
            inner(plan)
            rec = next(r for r in reversed(engine._inflight)
                       if r.phase == phase)
            # `pending` counts this dispatch too (and, for a continuing
            # decode row, the token still unread before it)
            log.append((phase, engine._steps,
                        getattr(plan, phase + "_batch"),
                        [(r, len(r.generated) + r.pending - 1)
                         for r in rec.reqs]))
            return rec
        setattr(engine, "_dispatch_" + phase, dispatch)

    wrap("prefill")
    wrap("decode")
    return log


def _drive(engine, check=None):
    done = {}
    while engine.scheduler.has_work:
        summary = engine.step()
        if check is not None:
            check(summary)
        done.update({r.request_id: r
                     for r in engine.scheduler.pop_finished()})
    return done


def _assert_reference_tokens(model, params, log, temperature):
    """Every delivered token of every logged program row against the
    model's plain forward. Returns how many tokens were checked."""
    logits = {}

    def row_logits(req, g):
        if id(req) not in logits:
            logits[id(req)] = model_rows(
                model, params, list(req.prompt) + list(req.generated))
        return logits[id(req)][len(req.prompt) + g - 1]

    checked = 0
    for _, counter, bucket, rows in log:
        key = jax.random.fold_in(jax.random.PRNGKey(SEED), counter)
        lg = np.zeros((bucket, model.config.vocab_size), np.float32)
        live = [(i, r, g) for i, (r, g) in enumerate(rows)
                if g < len(r.generated)]       # else dropped at read-back
        for i, r, g in live:
            lg[i] = row_logits(r, g)
        if temperature > 0:
            want = np.asarray(jax.random.categorical(
                key, jnp.asarray(lg) / temperature, axis=-1))
        else:
            want = lg.argmax(-1)
        for i, r, g in live:
            assert r.generated[g] == int(want[i]), (r.request_id, g)
            checked += 1
    return checked


@pytest.mark.parametrize("temperature", [0.0, TEMPERATURE],
                         ids=["greedy", "sampled"])
def test_streams_equal_the_reference_loop(served, temperature):
    """Five requests through four slots (so prefills land between decode
    steps), ends by `max_new_tokens` from 1 to 12, and in further runs two
    ends by EOS in the middle of a batch."""
    model, params = served
    prompts = _prompts(model, (5, 17, 30, 9, 40))
    budgets = (12, 1, 8, 10, 6)

    def run(eos):
        engine = _engine(model, params, temperature=temperature)
        log = _record_dispatches(engine)
        ids = [engine.submit(p, max_new_tokens=n, eos_token_id=e)
               for p, n, e in zip(prompts, budgets, eos)]
        done = _drive(engine)
        return engine, log, [done[i] for i in ids]

    engine, log, reqs = run([None] * 5)
    assert [len(r.generated) for r in reqs] == list(budgets)
    total = sum(budgets)
    assert _assert_reference_tokens(model, params, log, temperature) == total
    assert engine.stats["decode_tokens"] == total - len(reqs)
    assert engine.stats["lookahead_discarded"] == 0   # every end by count
    assert engine.stats["lookahead_steps"] >= max(budgets) - 2
    assert engine.cache.num_free == engine.cache.num_pages - 1
    assert not engine._inflight

    # an EOS the host learns one step late: requests 0 and 3 end on the
    # first token of their stream (past the prefill's) not seen before.
    # One EOS is added a run: a request's end changes what is batched
    # with what, and with it what the others sample afterwards
    eos, cut = [None] * 5, {}
    for k in (0, 3):
        stream = list(reqs[k].generated)
        j = next(j for j in range(1, budgets[k] - 1)
                 if stream[j] not in stream[:j])
        eos[k], cut[k] = stream[j], stream[:j + 1]
        engine, log, reqs = run(eos)
    for k, r in enumerate(reqs):
        assert r.status == "ok"
        # the same stream, cut at its EOS: the token computed past it
        # is not there
        assert r.generated == cut.get(k, r.generated)
        assert len(r.generated) == (len(cut[k]) if k in cut else budgets[k])
    total = sum(len(r.generated) for r in reqs)
    assert _assert_reference_tokens(model, params, log, temperature) == total
    assert engine.stats["decode_tokens"] == total - len(reqs)
    assert engine.stats["lookahead_discarded"] == len(cut)
    assert engine.cache.num_free == engine.cache.num_pages - 1
    assert not engine._inflight


def test_a_step_shows_exactly_what_was_read_back(served):
    """After every `step()`: the tokens in `generated` are the prefills
    completed plus the decode tokens counted, every one a prefix of the
    final stream; at most the newest decode is in flight, and a request
    has at most one token pending. `while has_work: step()` ends with
    nothing in flight."""
    model, params = served
    engine = _engine(model, params)
    prompts = _prompts(model, (5, 17, 30, 9, 40), seed=3)
    reqs = []
    for p, n in zip(prompts, (9, 2, 7, 12, 5)):
        engine.submit(p, max_new_tokens=n)
        reqs.append(engine.scheduler.waiting[-1])
    seen, history = [0], []

    def check(summary):
        visible = sum(len(r.generated) for r in reqs)
        assert visible == engine.stats["prefill_requests"] + \
            engine.stats["decode_tokens"]
        assert visible - seen[0] == summary["prefilled"] + summary["decoded"]
        seen[0] = visible
        assert len(engine._inflight) <= 1
        assert all(rec.phase == "decode" for rec in engine._inflight)
        in_flight = {id(r) for rec in engine._inflight for r in rec.reqs}
        for r in reqs:
            assert r.pending == (1 if id(r) in in_flight and
                                 r.state == "running" else 0)
        history.append([list(r.generated) for r in reqs])

    _drive(engine, check)
    assert not engine._inflight
    assert [len(r.generated) for r in reqs] == [9, 2, 7, 12, 5]
    for snapshot in history:
        for r, part in zip(reqs, snapshot):
            assert part == r.generated[:len(part)]
    # steps whose decode was enqueued behind an unread one: all but each
    # batch's first
    assert 0 < engine.stats["lookahead_steps"] < engine.stats["steps"]


def test_tokens_cross_decode_buckets_on_the_device(served):
    """A row that continues from a decode of another batch bucket (and
    another row of it) still finds its token: the in-flight tokens have
    one shape for every bucket."""
    model, params = served
    engine = _engine(model, params, prefill_batch_sizes=[1])
    log = _record_dispatches(engine)
    prompts = _prompts(model, (6, 11, 20, 33), seed=5)
    ids = [engine.submit(p, max_new_tokens=n)
           for p, n in zip(prompts, (3, 14, 6, 10))]
    done = _drive(engine)
    assert {bucket for phase, _, bucket, _ in log
            if phase == "decode"} == {1, 2, 4}
    assert _assert_reference_tokens(model, params, log, 0.0) == 33
    assert [len(done[i].generated) for i in ids] == [3, 14, 6, 10]


def test_speculation_and_a_prefill_pool_stay_synchronous(served):
    """`spec_k > 0` accepts drafts on the host between its two programs,
    and a prefill pool hands its first tokens off: neither leaves a
    program in flight, and `lookahead_steps` stays 0."""
    from deeperspeed_tpu.elasticity.heartbeat import InMemoryTransport
    model, params = served
    prompts = _prompts(model, (5, 17, 30), seed=9)

    draft = GPTNeoX(dataclasses.replace(GPTNeoXConfig.tiny(), num_layers=1),
                    use_pallas=False)
    spec = _engine(model, params,
                   draft=(draft, draft.init_params(jax.random.PRNGKey(2))),
                   speculative={"enabled": True, "num_draft_tokens": 2})
    plain = _engine(model, params)
    ids = [spec.submit(p, max_new_tokens=7) for p in prompts]
    done = _drive(spec, lambda summary: spec._inflight and pytest.fail(
        "a speculative step left a program in flight"))
    assert [done[i].generated for i in ids] == \
        plain.generate(prompts, max_new_tokens=7)
    assert spec.stats["spec_steps"] > 0
    assert spec.stats["lookahead_steps"] == 0
    assert plain.stats["lookahead_steps"] > 0

    pool = _engine(model, params, transport=InMemoryTransport(),
                   disaggregation={"role": "prefill", "pool_id": "p0"})
    for p in prompts:
        pool.submit(p, max_new_tokens=7)
    for _ in range(4):
        pool.step()
        assert not pool._inflight
    assert pool.stats["prefill_requests"] == 3
    assert len(pool._handoff_outbox) == 3      # no decode pool announced
    assert all(len(r.generated) == 1 for r in pool._handoff_outbox)
    assert pool.stats["lookahead_steps"] == 0
    assert pool.stats["decode_tokens"] == 0
