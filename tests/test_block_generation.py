"""Generation by diffusion over blocks (SDAR-MoE: a block of 4 positions
a step under the block-causal mask, unmasking by confidence, a block's
commit riding the first pass of its successor) on the normal path against
the plain reference
(`benchmarks/reference/sdar_moe.py`), at a small size on the CPU: hidden
64, 4 query heads over 2 KV heads of 16, 8 experts of width 32 (2 a
token), 2 layers, block 4, page 8.

Both sides compute in float32, so the tolerances are those of float32
rounding in another order of summation (a paged cache against a full
forward, a scan against a loop, sorted experts against a loop over them).
"""

import dataclasses
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.families import sdar_moe as family
from benchmarks.reference import sdar_moe as reference
from deeperspeed_tpu.inference import InferenceEngine
from deeperspeed_tpu.inference.kv_cache import PagedKVCache
from deeperspeed_tpu.inference.scheduler import (ContinuousBatchingScheduler,
                                                 Request)
from deeperspeed_tpu.models import gpt_neox as neox
from deeperspeed_tpu.models.gpt_neox import GPTNeoXConfig, LayerSpec
from deeperspeed_tpu.ops.pallas import decode_attention as da
from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError
from tests.model.references import jitted

# the package re-exports the function under the module's name
fa = importlib.import_module("deeperspeed_tpu.ops.pallas.flash_attention")

VOCAB, PAGE, LAYERS, BLOCK, MASK = 256, 8, 2, 4, 255
# float32 rounding through 2 layers on logits of size ~1 (measured 2e-7 on
# the forward, 1e-6 through the cache and the sorted experts); a causal
# mask where the block-causal one belongs, or a provisional row left in
# the pool, moves logits and rows by 1e-2 and more (asserted below)
ATOL = 1e-4


def conf(**over):
    return {
        "family": "sdar_moe", "model_type": "sdar_moe",
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
        "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 192,
        "max_position_embeddings": 128, "mlp_only_layers": [],
        "moe_intermediate_size": 32, "norm_topk_prob": True,
        "num_attention_heads": 4, "num_experts": 8,
        "num_experts_per_tok": 2, "num_hidden_layers": LAYERS,
        "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": VOCAB, "mask_token_id": MASK, **over}


def perturbed(params, seed=1):
    """Norm scales away from their init of 1 (a misplaced or dropped one
    shows) and an output head large enough for confidences to spread."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 100))

    def move(path, p):
        name = jax.tree_util.keystr(path)
        if "scale" in name or "_norm" in name:
            return p + 0.1 * jax.random.normal(next(keys), p.shape)
        if "embed_out" in name:
            return p * 40.0
        return p
    return jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def setup():
    c = conf()
    model = family.build_model(c, "float32", {"use_pallas": False})
    params = perturbed(model.init_params(jax.random.PRNGKey(0)))
    return c, model, params


@pytest.fixture
def one_program_a_pass(monkeypatch):
    """`reference.generate` calls the module's `forward` once a pass, over
    the prefix up to the pass's block: the same forward under `jax.jit`,
    one program a length, where bare every operation is its own."""
    plain = reference.forward
    monkeypatch.setattr(
        reference, "forward", lambda conf, params, tokens, block:
        jitted(plain, conf, block=block)(params, tokens))


def gen_for(c):
    return family.generation(c)


def engine_for(model, params, **over):
    inference = {"enabled": True, "page_size": PAGE, "num_pages": 40,
                 "max_seq_len": 64, "max_batch_size": 4,
                 "token_budget": 64, "prefill_lengths": [8, 16, 32],
                 "prefill_batch_sizes": [1, 2],
                 "decode_batch_sizes": [4], **over}
    engine = InferenceEngine(model, config={"inference": inference},
                             params=params)
    engine.block_trace = []
    return engine


def serve(engine, prompts, new):
    """The requests through the engine's normal loop -> {id: Request},
    with the pages each held when it was last seen running."""
    pages = {}
    with jax.default_matmul_precision("highest"):
        ids = [engine.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, new)]
        while engine.scheduler.has_work:
            engine.step()
            for r in engine.scheduler.running:
                pages[r.request_id] = list(r.pages)
    done = {r.request_id: r for r in engine.scheduler.pop_finished()}
    assert all(done[i].status == "ok" for i in ids)
    return [done[i] for i in ids], pages


def replay(c, params, request, trace):
    """The reference's statistics of every recorded denoising pass of
    `request` (`reference.replay_stats`), and the passes."""
    passes = [p for p in trace
              if p["request"] == request.request_id and not p["committed"]]
    seq = list(request.prompt) + list(request.generated)
    tokens = np.zeros(64, np.int32)
    tokens[:len(seq)] = seq
    # under one jit, as the cell's driver calls it (`n` traced)
    stats = jitted(reference.replay_stats, c, block=BLOCK, head_rows=8)(
        params, jnp.asarray(tokens), len(seq),
        jnp.asarray([p["start"] for p in passes]),
        jnp.asarray([p["tokens_in"] for p in passes]),
        jnp.asarray([p["tokens"] for p in passes]))
    return {k: np.asarray(v) for k, v in stats.items()}, passes


# ---------------------------------------------------------------------------
# the model's forward against the reference
# ---------------------------------------------------------------------------

def test_forward_logits_equal_the_references(setup):
    """The model file's own forward takes the block-causal mask when
    `generation_block > 0`, and the norm on each head of q and k."""
    c, model, params = setup
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 23), 0, VOCAB)
    with jax.default_matmul_precision("highest"):
        got = jitted(model.apply)(params, tokens)
        for row in range(2):
            want = jitted(reference.forward, c, block=BLOCK)(
                params, tokens[row])
            np.testing.assert_allclose(got[row], want, atol=ATOL, rtol=0)


@pytest.mark.parametrize("what", ["causal mask", "no head norm"])
def test_each_fact_of_the_block_moves_the_logits(setup, what):
    """What the tolerance must tell apart: the causal mask in place of the
    block-causal one, and the head norm's scales dropped, each move the
    logits by far more than ATOL."""
    c, model, params = setup
    tokens = jax.random.randint(jax.random.PRNGKey(2), (23,), 0, VOCAB)
    with jax.default_matmul_precision("highest"):
        want = jitted(reference.forward, c, block=BLOCK)(params, tokens)
        if what == "causal mask":
            got = jitted(reference.forward, c, block=1)(params, tokens)
        else:
            stack = reference.stack(c, params)
            scale = {k: stack["attn"][k] for k in ("q_norm", "k_norm")}
            flat = dict(stack, attn=dict(stack["attn"], **{
                k: jnp.ones_like(v) for k, v in scale.items()}))
            got = jitted(reference.forward, c, block=BLOCK)(
                dict(params, stacks={"full4.experts": flat}), tokens)
    assert float(jnp.abs(got - want).max()) > 100 * ATOL


# ---------------------------------------------------------------------------
# serving: prefill, then block passes through the paged cache
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_block_passes_through_the_paged_cache_equal_the_full_forward(
        setup, kernel):
    """Prompts with P % 4 in {0, 1, 3} and P < 4 in one batch, 10 to 14
    new tokens each across page edges (8), lookahead on. Logits, not
    tokens: for every recorded denoising pass, from the block as the
    engine had it going in, every row the engine unmasked holds a token
    whose reference logit is within float32 rounding of the reference's
    best at that row, and the rows it unmasked are the rows the reference
    would have (its confidences, its threshold and floor). `pallas` runs
    the paged kernels' bodies (the 8-row group, the 4-row write) in
    interpret mode."""
    c, model, params = setup
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, MASK, size=n).tolist() for n in (8, 9, 11, 3)]
    new = [12, 10, 14, 11]
    engine = engine_for(model, params, kernel=kernel)
    done, _ = serve(engine, prompts, new)
    assert [len(r.generated) for r in done] == new
    gen = gen_for(c)
    for r in done:
        stats, passes = replay(c, params, r, engine.block_trace)
        assert passes
        for i, p in enumerate(passes):
            masked_in = np.asarray(p["masked_in"])
            rows = np.flatnonzero(masked_in & ~np.asarray(p["masked"]))
            short = stats["best"][i, rows] - stats["served"][i, rows]
            assert float(short.max()) <= ATOL
            assert sorted(reference.choose(
                stats["confidence"][i], masked_in, gen)) == rows.tolist()
    stats = engine.serve_stats()
    assert stats["decode_tokens"] == sum(new)
    assert stats["lookahead_steps"] > 0
    assert stats["prefill_requests"] == 3       # the prompt of 3 took none
    assert stats["prefill_tokens"] == 8 + 8 + 8
    assert engine.cache.num_free == engine.cache.num_pages - 1
    backend = da._LAST_BACKEND
    assert backend["decode"] == backend["kv_write"] == kernel


@pytest.mark.parametrize("threshold", [1.0, 0.5])
def test_unmasking_order_and_delivered_prefix_equal_generate(
        setup, one_program_a_pass, threshold):
    """The engine's passes, request by request, against the reference's
    `generate` (no cache, the whole prefix a pass): the same rows unmasked
    in the same passes with the same tokens, and the same delivered
    tokens. At 1.0 no confidence fires and every block takes the floor,
    one row a row-pass: a block of 4 masks takes 4 row-passes, and its
    commit rides the first of its successor's (no row-pass does nothing
    but commit). At 0.5 passes unmask several rows, the threshold
    completes blocks the host had not foreseen, and the served tokens and
    the delivered prefixes are still `generate`'s."""
    c, model, params = setup
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, MASK, size=n).tolist()
               for n in (8, 9, 11, 3, 17)]
    # the threshold is the model's (`GPTNeoXConfig.generation_threshold`)
    c = conf(confidence_threshold=threshold)
    model = family.build_model(c, "float32", {"use_pallas": False})
    engine = engine_for(model, params)
    done, _ = serve(engine, prompts, [10] * 5)
    gen = gen_for(c)
    several = 0
    for r in done:
        with jax.default_matmul_precision("highest"):
            want, passes = reference.generate(c, params, r.prompt, 10, gen)
        assert r.generated == want
        mine = [p for p in engine.block_trace
                if p["request"] == r.request_id and not p["committed"]]
        # the engine's last pass may be one the reference never needed:
        # dispatched before the read-back that ended the request
        assert len(mine) >= len(passes)
        for p, (lo, rows, tokens) in zip(mine, passes):
            got = np.flatnonzero(np.asarray(p["masked_in"]) &
                                 ~np.asarray(p["masked"]))
            assert p["start"] == lo and got.tolist() == sorted(rows)
            assert [p["tokens"][j] for j in rows] == tokens
            several += len(rows) > 1
    stats = engine.serve_stats()
    # every request's first row was unmasked once, no later than its first
    # TOKEN (the leftmost row's) was delivered
    assert stats["block_first_unmasks"] == len(done)
    assert all(r.submitted_at <= r.first_unmask_at <= r.first_token_at
               for r in done)
    assert stats["block_first_unmask_s"] == pytest.approx(
        sum(r.first_unmask_at - r.submitted_at for r in done))
    commits = [p for p in engine.block_trace if p["committed"]]
    assert stats["blocks_committed"] == len(commits) > len(done)
    assert stats["block_commit_passes"] == \
        stats["block_fused_commits"] + stats["block_commit_only_passes"]
    if threshold == 1.0:
        assert several == 0
        # every row-pass read unmasked one row: 4 row-passes a block of 4
        # masks (unread: dispatched before the read-back that ended a
        # request inside its last block)
        assert stats["block_tokens_final"] == \
            stats["block_passes"] - stats["lookahead_discarded"]
        assert stats["block_commit_only_passes"] == 0
        assert stats["block_fused_commits"] == stats["blocks_committed"]
        for r in done:
            mine = [p for p in engine.block_trace
                    if p["request"] == r.request_id]
            for i, p in enumerate(mine):
                if p["committed"]:
                    # the commit and its successor's first pass: one program
                    nxt = mine[i + 1]
                    assert nxt["program"] == p["program"]
                    assert nxt["start"] == p["start"] + BLOCK
                    assert all(nxt["masked_in"]) and not nxt["committed"]
                    assert [q["program"] for q in mine].count(
                        p["program"]) == 2
    else:
        assert several > 0
        assert stats["block_tokens_final"] > 1.3 * (
            stats["block_passes"] - stats["lookahead_discarded"])


def test_rows_of_one_batch_at_different_duties_equal_each_served_alone(setup):
    """One program serves rows at different duties: in a batch of 4 some
    row-passes denoise their block, some commit it and open its successor,
    and the fourth row is inactive (3 requests), a row's block lying one
    past the block the host last read. Every request's record of passes
    is the one it leaves when served alone."""
    c, model, params = setup
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, MASK, size=n).tolist() for n in (8, 9, 3)]
    engine = engine_for(model, params)
    together, _ = serve(engine, prompts, [12] * 3)
    by_program = {}
    for p in engine.block_trace:
        by_program.setdefault(p["program"], []).append(p)
    # a program in which one request committed (two records) and another
    # went on denoising (one)
    assert any(
        {sum(1 for p in ps if p["request"] == r) for r in
         {p["request"] for p in ps}} == {1, 2} for ps in by_program.values())
    assert any(len({sum(p["masked_in"]) for p in ps
                    if not p["committed"]}) > 1
               for ps in by_program.values())
    # a pass that did nothing but commit: a request's last block, which
    # the threshold completed before the read-back that ended the request
    stats = engine.serve_stats()
    assert stats["block_commit_only_passes"] <= stats["lookahead_discarded"]
    assert stats["block_fused_commits"] >= stats["blocks_committed"] > 3

    def record(engine, request):
        return [{k: v for k, v in p.items() if k not in ("request",
                                                         "program")}
                for p in engine.block_trace
                if p["request"] == request.request_id]

    for prompt, request in zip(prompts, together):
        other = engine_for(model, params)
        (alone,), _ = serve(other, [prompt], [12])
        assert alone.generated == request.generated
        assert record(other, alone) == record(engine, request)


def held_rows(engine, pages, n):
    """[L, n, 2 G d] of what the pools hold in `pages`: a token's
    [K | V] of every layer."""
    pages = np.asarray(pages, np.int32)

    def held(pool):                 # [L, P, G, page, d] -> [L, n, G * d]
        rows = jnp.moveaxis(pool[:, pages], 2, 3)
        return rows.reshape(pool.shape[0], -1, 32)[:, :n]

    return jnp.concatenate([held(engine.cache.k), held(engine.cache.v)], -1)


@pytest.mark.parametrize("kernel,page", [("xla", 8), ("pallas", 8),
                                         ("pallas", 16)])
def test_the_pool_after_a_commit_is_a_teacher_forced_prefills(setup, kernel,
                                                              page):
    """After a request's commits its pool rows are the finished
    sequence's K (after the rotary) and V of every layer: the reference's
    `cache_rows`, and what a block-causal prefill of the same tokens
    writes. The two slots of a pass are two runs of rows: from the block
    at 12 they straddle a page (12..15 | 16..19, at both page sizes) and
    a packed group (a float32 page of 16 has two of 8: 20..23 | 24..27
    lie in one page and two groups). A provisional row left in the pool
    (a denoising pass's, made from a block still holding mask tokens)
    differs by far more than the tolerance (asserted)."""
    c, model, params = setup
    prompt = np.random.default_rng(3).integers(1, MASK, size=11).tolist()
    over = {"kernel": kernel, "page_size": page, "prefill_lengths": [16, 32]}
    engine = engine_for(model, params, **over)
    (request,), pages = serve(engine, [prompt], [22])
    n = request.cached
    # every block but the last was committed, each in the pass that opened
    # the next
    assert n == (len(prompt) + 22 - 1) // BLOCK * BLOCK and n > 3 * PAGE
    stats = engine.serve_stats()
    assert stats["block_fused_commits"] == stats["blocks_committed"] == \
        (n - 8) // BLOCK
    tokens = (list(prompt) + list(request.generated))[:n]
    got = held_rows(engine, pages[request.request_id], n)
    with jax.default_matmul_precision("highest"):
        want = reference.cache_rows(c, params, jnp.asarray(tokens), BLOCK)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)

    other = engine_for(model, params, **over)
    with jax.default_matmul_precision("highest"):
        rid = other.submit(tokens, max_new_tokens=8)
        other.step()
    running = next(r for r in other.scheduler.running if r.request_id == rid)
    assert running.cached == n
    np.testing.assert_allclose(held_rows(other, running.pages, n), got,
                               atol=ATOL, rtol=0)

    # the last denoising pass's rows of a block, against its commit's
    masked = list(tokens)
    masked[n - 2] = MASK
    with jax.default_matmul_precision("highest"):
        provisional = reference.cache_rows(c, params, jnp.asarray(masked),
                                           BLOCK)
    assert float(jnp.abs(provisional[:, n - BLOCK:] -
                         want[:, n - BLOCK:]).max()) > 100 * ATOL


def test_a_causal_prefill_mask_fails_the_cache_rows(setup, monkeypatch):
    """The deliberate fault: the prompt read under the causal mask. The
    pool's rows of the prompt then miss the reference's by far more than
    the tolerance from the second layer on."""
    c, model, params = setup
    real = neox.causal_attention

    def causal(*args, block=0, **kw):
        return real(*args, **kw)

    monkeypatch.setattr(neox, "causal_attention", causal)
    prompt = np.random.default_rng(4).integers(1, MASK, size=16).tolist()
    engine = engine_for(model, params)
    with jax.default_matmul_precision("highest"):
        rid = engine.submit(prompt, max_new_tokens=4)
        engine.step()
        request = next(r for r in engine.scheduler.running
                       if r.request_id == rid)
        got = held_rows(engine, request.pages, 16)
        want = reference.cache_rows(c, params, jnp.asarray(prompt), BLOCK)
    assert float(jnp.abs(got[0] - want[0]).max()) <= ATOL     # layer 1 sees no mask
    assert float(jnp.abs(got[1] - want[1]).max()) > 100 * ATOL


def test_slot_a_seeing_slot_b_fails_the_cache_rows(setup, monkeypatch):
    """The deliberate fault: the committed block's rows (slot A) attend
    the positions of the block the same pass opens (slot B's mask tokens).
    From the second layer on the committed rows then miss the reference's
    by far more than the tolerance."""
    from deeperspeed_tpu.inference import engine as engine_module
    c, model, params = setup
    real = engine_module.paged_decode_attention

    def one_limit(q, k, v, table, lengths, first_lengths=None, **kw):
        return real(q, k, v, table, lengths, first_lengths=lengths, **kw)

    monkeypatch.setattr(engine_module, "paged_decode_attention", one_limit)
    prompt = np.random.default_rng(3).integers(1, MASK, size=11).tolist()
    engine = engine_for(model, params)
    (request,), pages = serve(engine, [prompt], [14])
    n = request.cached
    assert n == 24
    tokens = (list(prompt) + list(request.generated))[:n]
    got = held_rows(engine, pages[request.request_id], n)
    with jax.default_matmul_precision("highest"):
        want = reference.cache_rows(c, params, jnp.asarray(tokens), BLOCK)
    assert float(jnp.abs(got[0] - want[0]).max()) <= ATOL     # sees no mask
    assert float(jnp.abs(got[1, 8:] - want[1, 8:]).max()) > 100 * ATOL


def test_a_requests_first_and_last_block_are_slot_a_alone(setup):
    """The first generated block is the context's tail and masks, denoised
    where it lies; the last block is never committed and has no successor:
    no page is grown for one (the natural end 16 is a page's edge), a
    dead slot B writes to the trash page even where the request holds
    the page its rows would lie in, and no other page is touched."""
    c, model, params = setup
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, MASK, size=n).tolist() for n in (10, 8)]
    engine = engine_for(model, params)
    (short, edge), pages = serve(engine, prompts, [2, 8])
    first = [next(p for p in engine.block_trace if p["request"] == r.request_id)
             for r in (short, edge)]
    assert first[0]["start"] == 8 and not first[0]["committed"]
    assert first[0]["tokens_in"] == (*prompts[0][8:], MASK, MASK)
    assert first[0]["masked_in"] == (False, False, True, True)
    assert first[1]["start"] == 8 and all(first[1]["masked_in"])
    # 8 + 8 tokens: the block at 8 was committed in the pass that opened
    # the block at 12, which ends the request where the second page does
    assert edge.cached == 12 and len(pages[edge.request_id]) == 2
    assert [p["start"] for p in engine.block_trace
            if p["request"] == edge.request_id and p["committed"]] == [8]
    # 10 + 2 tokens end inside the block at 8: never committed, and the
    # rows 12..15 of its page, where a slot B would lie, were never written
    assert short.cached == 8 and len(pages[short.request_id]) == 2
    assert not any(p["committed"] for p in engine.block_trace
                   if p["request"] == short.request_id)
    held = held_rows(engine, pages[short.request_id], 16)
    assert float(jnp.abs(held[:, 8:12]).min()) > 0
    assert float(jnp.abs(held[:, 12:]).max()) == 0
    used = set(pages[short.request_id]) | set(pages[edge.request_id]) | {0}
    free = [p for p in range(engine.cache.num_pages) if p not in used]
    assert float(jnp.abs(engine.cache.k[:, free]).max()) == 0
    assert float(jnp.abs(engine.cache.k[:, 0]).max()) > 0     # the trash
    stats = engine.serve_stats()
    assert stats["block_commit_only_passes"] <= stats["lookahead_discarded"]
    assert stats["block_fused_commits"] == stats["blocks_committed"] == 1


# ---------------------------------------------------------------------------
# the kernels' new shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (8, 2)])
def test_flash_forward_block_causal_mask_equals_a_dense_mask(heads,
                                                             kv_heads):
    """The segmented forward kernel (interpret mode) under `mask_block`
    against a dense block-causal softmax, with pad rows (segment 0)
    behind the real ones; and with `mask_block` left out the call is the
    causal one it was, to the bit."""
    S, D, n = 256, 64, 200
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, S, heads, D), jnp.float32)
    k = jax.random.normal(ks[1], (1, S, kv_heads, D), jnp.float32)
    v = jax.random.normal(ks[2], (1, S, kv_heads, D), jnp.float32)
    seg = (jnp.arange(S) < n).astype(jnp.int32)[None]

    def dense(block):
        kk = jnp.repeat(k, heads // kv_heads, axis=2)
        vv = jnp.repeat(v, heads // kv_heads, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(D)
        pos = jnp.arange(S)
        seen = (pos[None, :] <= (pos[:, None] | (block - 1))) & \
            (seg[0][:, None] == seg[0][None, :])
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vv)

    got = fa.flash_attention_segmented(q, k, v, seg, True, mask_block=BLOCK)
    np.testing.assert_allclose(got[:, :n], dense(BLOCK)[:, :n], atol=2e-5,
                               rtol=0)
    assert float(jnp.abs(dense(BLOCK) - dense(1))[:, :n].max()) > 1e-2
    causal = fa.flash_attention_segmented(q, k, v, seg, True)
    np.testing.assert_allclose(causal[:, :n], dense(1)[:, :n], atol=2e-5,
                               rtol=0)
    np.testing.assert_array_equal(
        causal, fa.flash_attention_segmented(q, k, v, seg, True,
                                             mask_block=0))
    with pytest.raises(ValueError, match="power of two"):
        fa.flash_attention_segmented(q, k, v, seg, True, mask_block=3)


def test_the_causal_kernels_traced_program_is_unchanged():
    """`mask_block` 0 adds nothing to the segmented kernel's body: the
    same jaxpr with the parameter left out and with it 0 (the train cells
    and the autoregressive serve cells run this path)."""
    q = jnp.zeros((1, 256, 4, 64), jnp.float32)
    seg = jnp.ones((1, 256), jnp.int32)

    def text(**kw):
        return str(jax.make_jaxpr(lambda q: fa.flash_attention_segmented(
            q, q, q, seg, True, **kw))(q))

    assert text() == text(mask_block=0)
    assert text() != text(mask_block=BLOCK)
    assert " or " in text(mask_block=BLOCK) and " or " not in text()


@pytest.mark.parametrize("dtype,run", [(jnp.float32, 4), (jnp.bfloat16, 4),
                                       (jnp.bfloat16, 2)])
def test_a_run_of_rows_is_written_in_place_as_one_group(dtype, run):
    """`paged_kv_write` with [B, H, run, D] rows: the kernel (interpret
    mode) and the XLA scatter put a block's rows at `slot ..
    slot + run - 1` of each sequence's page and touch nothing else."""
    L, P, H, ps, D, B = 3, 6, 2, 16, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    pools = tuple(jax.random.normal(k, (L, P, H, ps, D)).astype(dtype)
                  for k in ks[:2])
    rows = tuple(jax.random.normal(k, (B, H, run, D)).astype(dtype)
                 for k in ks[2:])
    page_idx = jnp.asarray([1, 3, 4, 0], jnp.int32)
    slot = jnp.asarray([0, 8, 12, 4], jnp.int32)
    want = [np.array(p.astype(jnp.float32)) for p in pools]
    for w, r in zip(want, rows):
        for b in range(B):
            w[2, int(page_idx[b]), :, int(slot[b]):int(slot[b]) + run] = \
                np.asarray(r[b].astype(jnp.float32))
    for backend in ("pallas", "xla"):
        got = da.paged_kv_write(pools, rows, jnp.asarray(2, jnp.int32),
                                page_idx, slot, backend=backend)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(
                np.asarray(g.astype(jnp.float32)), w)
    with pytest.raises(ValueError, match="run of rows"):
        da.paged_kv_write(
            pools, tuple(jnp.tile(r, (1, 1, 2, 1))[:, :, :3] for r in rows),
            2, page_idx, slot, backend="xla")


@pytest.mark.parametrize("slots", [1, 2])
@pytest.mark.parametrize("G,rep,ps,width,ends", [
    # a span of 16 pages: a first slot that ends where a grid step does
    # (128), rows without a successor (12, 12) and inactive (0, 0)
    (2, 2, 8, 17, [(20, 24), (128, 132), (4, 8), (12, 12), (0, 0)]),
    # SDAR's own group: 4 KV heads, 2 x 4 rows x 8 query heads a KV head,
    # pages of 64, a grid step of several pages: rows that end mid-span,
    # and a second slot that opens a page (64 | 68)
    (4, 8, 64, 3, [(170, 174), (64, 68), (4, 8)]),
], ids=["2x2_page8", "sdar_4x8_page64"])
def test_a_blocks_rows_ride_as_one_group_of_the_grouped_kernel(
        G, rep, ps, width, ends, slots):
    """The paged kernel (interpret mode) and its XLA twin at a group of
    block x (query heads a KV head) rows a KV head, no mask inside the
    block, against a dense softmax over each sequence's first `length`
    positions; and at TWO SLOTS a group (`first_lengths`), the first
    half of the rows under their own, earlier end."""
    D, B, R = 32, len(ends), slots * BLOCK
    P = B * width + 1
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    k_pool = jax.random.normal(ks[0], (1, P, G, ps, D), jnp.float32)
    v_pool = jax.random.normal(ks[1], (1, P, G, ps, D), jnp.float32)
    q = jax.random.normal(ks[2], (B, R, G * rep, D), jnp.float32)
    table = 1 + jnp.arange(B * width, dtype=jnp.int32).reshape(B, width)
    ends = np.asarray(ends, np.int32)
    if slots == 1:
        ends = ends[:, 1:]
    table = jnp.where(jnp.arange(width) * ps < ends[:, -1:], table, 0)
    rows = jnp.swapaxes(q.reshape(B, R, G, rep, D), 1, 2).reshape(B, -1, D)
    kw = {"first_lengths": jnp.asarray(ends[:, 0])} if slots == 2 else {}
    for backend in ("pallas", "xla"):
        got = da.paged_decode_attention(
            rows, k_pool, v_pool, table, jnp.asarray(ends[:, -1]),
            backend=backend, layer=jnp.asarray(0, jnp.int32),
            block_pass=True, **kw)
        if backend == "pallas":
            # a KV head's group met its own slots, a span of pages
            assert da._LAST_BACKEND["decode_scores"] == "per_head"
            assert da._LAST_BACKEND["decode_pages_per_step"] == \
                min(width, 16)
        got = jnp.swapaxes(got.reshape(B, G, R, rep, D), 1, 2).reshape(
            B, R, G * rep, D)
        for b in range(B):
            for slot, n in enumerate(ends[b]):
                mine = got[b, slot * BLOCK:(slot + 1) * BLOCK]
                if n == 0:
                    assert float(jnp.abs(mine).max()) == 0
                    continue
                pages = table[b, :-(-n // ps)]
                k = jnp.moveaxis(k_pool[0, pages], 1, 0).reshape(
                    G, -1, D)[:, :n]
                v = jnp.moveaxis(v_pool[0, pages], 1, 0).reshape(
                    G, -1, D)[:, :n]
                for h in range(G * rep):
                    s = q[b, slot * BLOCK:(slot + 1) * BLOCK, h] @ \
                        k[h // rep].T / np.sqrt(D)
                    want = jax.nn.softmax(s, axis=-1) @ v[h // rep]
                    np.testing.assert_allclose(mine[:, h], want, atol=2e-5,
                                               rtol=0)


def test_the_grouped_kernels_traced_program_without_slots_is_unchanged():
    """`first_lengths` left out adds nothing to the paged kernel's call:
    the same jaxpr as with it None, and the scope's name apart the jaxpr
    of a token step's call (every other model's program); with it the
    body selects a row's end once more."""
    q = jnp.zeros((2, 16, 32), jnp.float32)
    pool = jnp.zeros((1, 5, 2, 8, 32), jnp.float32)
    table = jnp.zeros((2, 3), jnp.int32)
    lengths = jnp.full((2,), 12, jnp.int32)

    def text(**kw):
        return str(jax.make_jaxpr(lambda q: da.paged_decode_attention(
            q, pool, pool, table, lengths, backend="pallas",
            layer=jnp.asarray(0, jnp.int32), **kw))(q))

    plain = text(block_pass=True)
    assert plain == text(block_pass=True, first_lengths=None)
    slots = text(block_pass=True, first_lengths=lengths - BLOCK)
    assert slots.count("select_n") == plain.count("select_n") + 1
    assert text().replace("ds.paged_decode", "") == \
        plain.replace("ds.paged_decode_block", "")
    with pytest.raises(ValueError, match="first_lengths"):
        text(first_lengths=lengths)


# ---------------------------------------------------------------------------
# the scheduler counts in blocks
# ---------------------------------------------------------------------------

def block_scheduler(**over):
    cache = PagedKVCache(num_layers=1, num_pages=12, num_heads=1,
                         page_size=PAGE, head_dim=8, dtype=jnp.float32)
    kw = dict(max_seq_len=64, token_budget=24, max_batch_size=4,
              prefill_lengths=[8, 16], prefill_batch_sizes=[1],
              decode_batch_sizes=[4], block=BLOCK, mask_token_id=MASK)
    return ContinuousBatchingScheduler(cache, **dict(kw, **over))


def test_a_prefill_completes_with_no_token_and_a_short_prompt_takes_none():
    s = block_scheduler()
    long, short = Request(list(range(1, 11)), 6), Request([5, 6, 7], 6)
    s.add_request(long)
    s.add_request(short)
    plan = s.schedule()
    # 10 tokens: the first 8 are prefilled, 2 open the first block
    assert plan.prefills == [long] and plan.prefill_len == 8
    s.complete_prefill(long)
    assert long.cached == 8 and long.generated == []
    assert long.block_tokens == [9, 10, MASK, MASK]
    # the prompt of 3 is admitted without a prefill and holds one page
    plan = s.schedule()
    assert plan.decodes == [long] and plan.prefills == []
    assert short in s.running and len(short.pages) == 1
    assert short.block_tokens == [5, 6, 7, MASK]
    assert short.block_masked == [False, False, False, True]
    assert s.schedule().decodes == [long, short]


def test_a_pass_appends_the_contiguous_unmasked_prefix_and_commits_by_block():
    s = block_scheduler()
    r = Request(list(range(1, 11)), 6)
    s.add_request(r)
    s.schedule()
    s.complete_prefill(r)
    # row 3 unmasked behind the masked row 2: nothing is delivered yet
    assert s.complete_block(r, [9, 10, MASK, 44], [0, 0, 1, 0], False) == 0
    assert r.generated == []
    assert s.complete_block(r, [9, 10, 33, 44], [0, 0, 0, 0], False) == 2
    assert r.generated == [33, 44] and r.cached == 8
    # the slots of the next pass: the block at 8 and, committed in it, its
    # successor; with that pass in flight the next goes on at 12, where
    # the request ends: its last block has no successor
    assert s.block_ends(r) == (12, 16)
    r.owed.append(7)
    assert s.block_start(r) == 12 and s.block_ends(r) == (16, 16)
    r.owed.clear()
    # ONE read-back: the commit, and the successor's first denoising
    assert s.complete_block(r, [MASK, 2, MASK, MASK], [1, 0, 1, 1],
                            True) == 0
    assert r.cached == 12 and r.block_masked == [True, False, True, True]
    assert s.complete_block(r, [1, 2, 3, 4], [0, 0, 0, 0], False) == 4
    # tokens past max_new_tokens are dropped, and the request ends
    assert r.generated == [33, 44, 1, 2, 3, 4] and r.status == "ok"
    # a pass that did nothing but commit leaves the successor all masks
    r = Request(list(range(1, 9)), 8)
    r.cached, r.block_tokens, r.block_masked = 8, [5, 6, 7, 8], [False] * 4
    r.generated = [5, 6, 7, 8]
    assert s.complete_block(r, [MASK] * 4, [1, 1, 1, 1], True) == 0
    assert r.cached == 12 and r.block_masked == [True] * 4


def test_a_running_row_costs_a_block_and_pages_grow_to_the_second_slot():
    s = block_scheduler(token_budget=16)
    a, b = Request(list(range(1, 9)), 20), Request(list(range(1, 9)), 20)
    s.add_request(a)
    s.add_request(b)
    assert s.schedule().prefills == [a]
    s.complete_prefill(a)
    # a's row costs 4 of the budget of 16: b's bucket of 8 still fits
    assert s.schedule().prefills == [b]
    s.complete_prefill(b)
    c = Request(list(range(1, 9)), 20)
    s.add_request(c)
    # two rows cost 8, which leaves the bucket of 8 its room and no more
    assert s.schedule().prefills == [c]
    s.complete_prefill(c)
    d = Request(list(range(1, 9)), 20)
    s.add_request(d)
    assert s.schedule().prefills == []          # 16 - 3 * 4 < 8
    # the prefill bucket's page holds positions 0..7; the block at 8 needs
    # the second page before its pass is dispatched, and slot B (12..15)
    # lies in it too
    assert len(a.pages) == 2
    # the block at 12: slot B (16..19) needs the third page, whatever the
    # host has read of the block (the threshold may complete it unseen)
    a.cached, a.block_masked = 12, [True] * 4
    s.schedule()
    assert len(a.pages) == 3 and s.block_ends(a) == (16, 20)
    # never past the request's natural end (8 + 20 = 28): the block at 24
    # is its last, and the fourth page (24..31) its last page
    a.cached = 24
    s.schedule()
    assert len(a.pages) == 4 and s.block_ends(a) == (28, 28)
    # nor past the serving window
    wide = Request(list(range(1, 9)), 100)
    wide.cached, wide.block_masked = 60, [True] * 4
    assert s.block_ends(wide) == (64, 64)
    # the last row of the last block, with its pass in flight: no step
    e = Request(list(range(1, 9)), 4)
    e.cached, e.block_masked, e.owed = 8, [False, False, True, False], [3]
    assert e.last_token_pending(64)
    e.block_masked = [False, True, True, False]
    assert not e.last_token_pending(64)


# ---------------------------------------------------------------------------
# what is not computed is refused by name
# ---------------------------------------------------------------------------

def plan_config(**over):
    plan = (LayerSpec(attn="full", heads=4, ffn="experts"),) * 2
    kw = dict(vocab_size=VOCAB, hidden_size=64, num_layers=2, num_heads=4,
              num_kv_heads=2, attn_head_dim=16, max_seq_len=128,
              norm="rmsnorm", use_bias=False, use_parallel_residual=False,
              hidden_act="silu", ffn_gated=True, ffn_width=32,
              layer_plan=plan, moe_num_experts=8, moe_top_k=2,
              moe_dropless=True, moe_norm_topk_prob=True,
              moe_expert_width=32, qk_norm="head", generation_block=4,
              mask_token_id=MASK)
    return GPTNeoXConfig(**dict(kw, **over))


@pytest.mark.parametrize("fields,match", [
    ({"qk_norm": True}, "qk_norm=True"),
    ({"generation_block": 3}, "power of two"),
    ({"mask_token_id": VOCAB}, "no token of a vocabulary"),
    ({"generation_steps": 5}, "generation_steps=5"),
    ({"generation_threshold": 1.5}, "generation_threshold=1.5"),
    ({"loop_steps": 2}, "loop_steps=2"),
    ({"layer_plan": (LayerSpec(attn="window", heads=4, ffn="experts"),) * 2,
      "attn_window": 8}, "attn_window=8"),
])
def test_a_block_the_code_does_not_compute_raises_by_name(fields, match):
    with pytest.raises((NotImplementedError, ValueError), match=match):
        plan_config(**fields).check_block()


@pytest.mark.parametrize("fields,match", [
    ({"qk_norm": "head"}, "qk_norm='head'"),
    ({"generation_block": 4}, "generation_block=4"),
])
def test_block_facts_without_a_plan_are_refused(fields, match):
    with pytest.raises(NotImplementedError, match=match):
        GPTNeoXConfig.tiny(**fields).check_block()


def test_the_head_norms_leaves_are_two_vectors_a_layer(setup):
    c, model, params = setup
    attn = params["stacks"]["full4.experts"]["attn"]
    assert attn["q_norm"].shape == attn["k_norm"].shape == (LAYERS, 16)
    leaves = sum(p.size for p in jax.tree_util.tree_leaves(params))
    assert model.config.num_params() == leaves


@pytest.mark.parametrize("key,value", [("norm_topk_prob", False),
                                       ("mlp_only_layers", [0]),
                                       ("attention_bias", True)])
def test_the_family_refuses_a_config_it_does_not_compute(key, value):
    with pytest.raises(ValueError, match=key):
        family.build_model(conf(**{key: value}), "float32", {})


def test_the_homogeneous_generate_path_refuses_the_block(setup):
    _, model, params = setup
    with pytest.raises(NotImplementedError, match="generation_block=4"):
        model.generate(params, jnp.zeros((1, 4), jnp.int32), 4)


@pytest.mark.parametrize("over,match", [
    ({"temperature": 0.7}, "sampled request"),
    ({"kv_cache_dtype": "int8"}, "int8"),
    ({"prefix_cache": {"enabled": True}}, "prefix_cache"),
    # the steps, the threshold and the rule are the model's: no option
    ({"block_generation": {"confidence_threshold": 0.5}},
     "block_generation"),
])
def test_serving_what_is_not_built_raises_by_name(setup, over, match):
    _, model, params = setup
    with pytest.raises(DeepSpeedConfigError, match=match):
        engine_for(model, params, **over)


def test_training_is_still_refused(setup):
    _, model, params = setup
    batch = (jnp.zeros((1, 8), jnp.int32), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(DeepSpeedConfigError, match="generation by blocks"):
        model.loss_fn(params, batch)


def test_an_autoregressive_models_requests_are_untouched():
    """A model with no generation block schedules as it did: `block` 0
    leaves the budget at a token a row and a prefill with its first
    token."""
    s = block_scheduler(block=0, mask_token_id=0)
    r = Request(list(range(1, 11)), 3)
    s.add_request(r)
    plan = s.schedule()
    assert plan.prefill_len == 16
    s.complete_prefill(r, 42)
    assert r.cached == 10 and r.generated == [42]
    assert not r.block_masked and dataclasses.asdict(r)["block_tokens"] == []
