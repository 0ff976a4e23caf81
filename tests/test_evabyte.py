"""evabyte (EvaByte's architecture: a byte model whose every layer is EVA
attention, exact inside a window and one pooled key / value a chunk behind
it, with a unit-offset RMS norm and a head of eight predictions) on the
normal path against the plain reference
(`benchmarks/reference/evabyte.py`), at a small size on the CPU: hidden
64, 4 heads of 16, window 32, chunk 4, page 8, 2 layers.

Both sides compute in float32, so the tolerances are those of float32
rounding in another order of summation, each written where it is used
with what it would refuse.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.families import evabyte as family
from benchmarks.reference import evabyte as reference
from deeperspeed_tpu.inference import InferenceEngine
from deeperspeed_tpu.inference.kv_cache import PagedKVCache
from deeperspeed_tpu.inference.scheduler import (ContinuousBatchingScheduler,
                                                 Request)
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, LayerSpec
from deeperspeed_tpu.ops.pallas import eva as eva_ops
from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError
from tests.model.references import jitted, reference_rows

VOCAB, HEADS, WINDOW, CHUNK, PAGE = 320, 8, 32, 4, 8
# float32 rounding through two layers on logits of size ~1; a pooled row
# left out, pooled unscaled or visible a chunk early, a norm without its
# unit offset or a rotary by adjacent pairs moves them by 1e-2 and more
LOGITS_ATOL = 2e-5
# a cached or pooled row against the reference's, relative
ROW_RTOL = 1e-5


def conf(layers=2):
    return {"family": "evabyte", "model_type": "evabyte",
            "attention_bias": False, "attention_class": "eva",
            "chunk_size": CHUNK, "hidden_act": "silu", "hidden_size": 64,
            "intermediate_size": 96, "max_position_embeddings": 256,
            "norm_add_unit_offset": True, "num_attention_heads": 4,
            "num_chunks": None, "num_hidden_layers": layers,
            "num_key_value_heads": 4, "num_pred_heads": HEADS,
            "rms_norm_eps": 1e-5, "rope_scaling": None,
            "rope_theta": 100000, "tie_word_embeddings": False,
            "vocab_size": VOCAB, "window_size": WINDOW}


def perturbed(params, seed=5):
    """The norms' w (zero at init) and the pooling's phi and mu away from
    their init, so that a dropped offset or a misplaced mu shows."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 100))
    return jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(next(keys), p.shape)
        if p.ndim <= 3 and p.shape[-1] <= 64 and p.shape[0] != VOCAB
        else p, params)


@pytest.fixture(scope="module")
def setup():
    c = conf()
    model = family.build_model(c, "float32", {"use_pallas": False})
    params = perturbed(model.init_params(jax.random.PRNGKey(0)))
    return c, model, params


def engine_for(model, params, **over):
    inference = {"enabled": True, "page_size": PAGE, "num_pages": 64,
                 "max_seq_len": 160, "max_batch_size": 4,
                 "token_budget": 160, "prefill_lengths": [32, 64, 96],
                 "prefill_batch_sizes": [1], "decode_batch_sizes": [4],
                 **over}
    return InferenceEngine(model, config={"inference": inference},
                           params=params)


def serve(engine, prompts, new, watch=None):
    """Serve `prompts`, return the finished requests in order; `watch` is
    called after every step."""
    ids = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts, new)]
    with jax.default_matmul_precision("highest"):
        while engine.scheduler.has_work:
            engine.step()
            if watch:
                watch(engine)
    done = {r.request_id: r for r in engine.scheduler.pop_finished()}
    return [done[i] for i in ids]


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, size=n).tolist() for n in lengths]


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


# -- the model's forward ------------------------------------------------------

def test_forward_matches_reference_over_three_and_a_half_windows(setup):
    c, model, params = setup
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 112), 0, VOCAB)
    with jax.default_matmul_precision("highest"):
        got = jitted(model.apply)(params, tokens)
    want = jitted(reference.logits, c)(params, tokens)
    assert got.shape == (2, 112, HEADS * VOCAB)
    np.testing.assert_allclose(got, want, atol=LOGITS_ATOL)


@pytest.mark.parametrize("fault", ["no_offset", "no_mu", "unscaled_pool"])
def test_forward_refuses_a_fault(setup, fault):
    """What the tolerance is for: each fault moves the logits far beyond
    it."""
    c, model, params = setup
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 80), 0, VOCAB)
    want = jitted(reference.logits, c)(params, tokens)
    cfg = model.config
    if fault == "no_offset":
        cfg = dataclasses.replace(cfg, norm_unit_offset=False)
    stacks = params["stacks"]
    if fault != "no_offset":
        name, = stacks
        leaf = "eva_mu" if fault == "no_mu" else "eva_phi"
        factor = 0.0 if fault == "no_mu" else 4.0    # phi / s: no scale
        stacks = {name: dict(stacks[name], attn=dict(
            stacks[name]["attn"],
            **{leaf: stacks[name]["attn"][leaf] * factor}))}
    with jax.default_matmul_precision("highest"):
        got = GPTNeoX(cfg, use_pallas=False).apply(
            dict(params, stacks=stacks), tokens)
    assert float(jnp.abs(got - want).max()) > 100 * LOGITS_ATOL


def test_parameter_count_is_the_reference_s(setup):
    c, model, params = setup
    held = sum(p.size for p in jax.tree_util.tree_leaves(params))
    assert held == model.config.num_params() == reference.num_params(c)


# -- the pooling --------------------------------------------------------------

def pools_and_rows(seed, layers=2, pages=12):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = (layers, pages, 4, PAGE, 16)
    k_pool = jax.random.normal(keys[0], shape)
    v_pool = jax.random.normal(keys[1], shape)
    phi = jax.random.normal(keys[2], (4, 16))
    mu = jax.random.normal(keys[3], (4, 16))
    return (k_pool, v_pool), phi, mu


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("closing", [
    (False,) * 5, (True, False, False, True, False), (True,) * 5],
    ids=["none", "some", "all"])
def test_eva_summarize_matches_jnp(backend, closing):
    """A decode step's pooling on a batch of which only some rows close a
    chunk, against the arithmetic written out in `jnp`: the closing rows'
    pooled K and V land in their pending slot, every other row of the
    pools but the trash page is left as it was."""
    pools, phi, mu = pools_and_rows(0)
    layer, scale = 1, 0.25
    src_page = jnp.asarray([3, 4, 5, 6, 7])
    src_slot = jnp.asarray([3, 7, 3, 7, 3])     # each the last of a chunk
    closing = jnp.asarray(closing)
    dst_page = jnp.asarray([8, 9, 10, 11, 2])
    dst_slot = jnp.asarray([0, 5, 1, 7, 3])
    got = eva_ops.eva_summarize(pools, phi, mu, layer, src_page, src_slot,
                                closing, dst_page, dst_slot, CHUNK, scale,
                                backend=backend)
    for pool, new, add in zip(pools, got, (mu, 0.0)):
        want = np.array(pool)
        for b in range(5):
            if not bool(closing[b]):
                continue
            first = int(src_slot[b]) // CHUNK * CHUNK
            k = np.asarray(pools[0][layer, src_page[b], :,
                                    first:first + CHUNK])       # [H, C, D]
            rows = np.asarray(pool[layer, src_page[b], :,
                                   first:first + CHUNK])
            a = scale * np.einsum("hcd,hd->hc", k, np.asarray(phi))
            p = np.exp(a - a.max(-1, keepdims=True))
            p = p / p.sum(-1, keepdims=True)
            want[layer, dst_page[b], :, dst_slot[b]] = \
                np.einsum("hc,hcd->hd", p, rows) + np.asarray(add)
        new = np.array(new)
        new[:, 0], want[:, 0] = 0, 0                    # the trash page
        np.testing.assert_allclose(new, want, rtol=1e-5, atol=1e-6)


def test_eva_pool_accumulates_in_float32():
    """bfloat16 rows pooled to float32: the sum of 16 rows rounded once,
    not sixteen times."""
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    k = jax.random.normal(keys[0], (7, 16, 4, 16)).astype(jnp.bfloat16)
    v = jax.random.normal(keys[1], (7, 16, 4, 16)).astype(jnp.bfloat16)
    phi, mu = jax.random.normal(keys[2], (2, 4, 16))
    got = eva_ops.eva_pool(k, v, phi, mu, 0.25)
    want = eva_ops.eva_pool(k.astype(jnp.float32), v.astype(jnp.float32),
                            phi, mu, 0.25)
    assert got[0].dtype == jnp.float32
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)


# -- prefill, then decode through the pool ------------------------------------

# (prompt lengths, new tokens): a window's end inside the decode (20 + 30
# crosses 32), inside a longer prompt's prefill (75 holds two; 50 one and
# crosses 64 in decode), a prompt that IS whole windows (32, 64: the table
# rolls at the first decode), all in one batch under the lookahead step
SERVED = {"end_in_decode": ((20,), (30,)),
          "ends_in_prefill": ((75,), (20,)),
          "whole_windows": ((32, 64), (12, 40)),
          "batch": ((20, 50, 75, 33), (30, 40, 20, 60))}


@pytest.mark.parametrize("case", sorted(SERVED))
def test_served_logits_of_every_head_match_the_reference(setup, case):
    """Prefill, then decode through the pool, must agree with the
    reference's full forward over prompt + served bytes: the logits of all
    eight prediction heads at every served position, and so the bytes."""
    c, model, params = setup
    lengths, new = SERVED[case]
    engine = engine_for(model, params)
    engine.head_trace = []
    done = serve(engine, prompts_of(lengths), new)
    assert engine.stats["eva_windows_rolled"] > 0 or case == "ends_in_prefill"
    for r, n in zip(done, new):
        assert len(r.generated) == n and r.status == "ok"
        want = reference_rows(reference, c, params,
                              list(r.prompt) + list(r.generated), 512)
        rows = [t for t in engine.head_trace if t["request"] == r.request_id]
        assert len(rows) == n
        for t in rows:
            np.testing.assert_allclose(t["logits"], want[t["at"] - 1],
                                       atol=LOGITS_ATOL)
        at = len(r.prompt) - 1 + np.arange(n)
        assert (want[at, :VOCAB].argmax(-1) == np.asarray(r.generated)).all()
    # every page is given back
    assert engine.cache.num_free == engine.cache.num_pages - 1


def test_an_evicted_request_re_prefills_across_its_windows(setup):
    """A pool too small for three long requests: the youngest is evicted,
    re-prefills its whole context (window ends inside it) and still ends
    on the reference's bytes."""
    c, model, params = setup
    engine = engine_for(model, params, num_pages=22, max_batch_size=3,
                        decode_batch_sizes=[3])
    done = serve(engine, prompts_of((60, 62, 50), seed=3), (70, 70, 70))
    assert engine.stats["evictions"] > 0
    for r in done:
        row = jnp.asarray(list(r.prompt) + list(r.generated))[None]
        want = np.asarray(jitted(reference.logits_at, c)(
            params, row,
            (len(r.prompt) - 1 + np.arange(len(r.generated)))[None])[0])
        assert (want.argmax(-1) == np.asarray(r.generated)).all()
    assert engine.cache.num_free == engine.cache.num_pages - 1


# -- the pool's rows ----------------------------------------------------------

def held_rows(engine, pages, count):
    """[L, count, 2 H D] of the first `count` rows the pages `pages` hold:
    [K | V] a row."""
    def rows(pool):
        r = np.asarray(pool)[:, np.asarray(pages, np.int32)]   # [L,n,H,ps,D]
        return np.moveaxis(r, 2, 3).reshape(r.shape[0], -1,
                                            r.shape[2] * r.shape[4])
    return np.concatenate([rows(engine.cache.k), rows(engine.cache.v)],
                          axis=-1)[:, :count]


@pytest.mark.parametrize("prompt,new", [(75, 40), (20, 30)],
                         ids=["ends_in_prefill_and_decode", "end_in_decode"])
def test_pool_rows_match_the_reference(setup, prompt, new):
    """What the pool holds for a request served alone, after its prefill
    and after a window's end that fell in decode: the visible pooled rows
    (the table's prefix), the pending rows (outside it) and the window's
    exact rows, each population against the reference's."""
    c, model, params = setup
    engine = engine_for(model, params)
    sch = engine.scheduler
    seen = []

    def watch(engine):
        for r in engine.scheduler.running:
            fed = r.cached + r.pending
            if not seen or (fed // WINDOW > seen[-1][0] // WINDOW and
                            fed % WINDOW >= CHUNK):
                tokens = (list(r.prompt) + list(r.generated))[:fed]
                if len(tokens) < fed:
                    continue
                want = reference.states(c, params, jnp.asarray(tokens), fed)
                ended = fed // WINDOW
                per_win = WINDOW // CHUNK
                kept = sch.eva_pages_summary * ended
                visible = held_rows(engine, r.pages[:kept] or [0],
                                    per_win * ended)
                pending = held_rows(engine, r.eva_pending,
                                    (fed - ended * WINDOW) // CHUNK)
                exact = held_rows(engine, r.pages[kept:],
                                  fed - ended * WINDOW)
                got = {"visible": (visible, want["pooled"][
                           :, :per_win * ended]),
                       "pending": (pending, want["pooled"][
                           :, per_win * ended:fed // CHUNK]),
                       "exact": (exact, want["rows"][
                           :, :fed - ended * WINDOW])}
                for name, (g, w) in got.items():
                    assert g.shape == w.shape, name
                    if g.size:
                        assert relative(g, w) < ROW_RTOL, (name, fed)
                seen.append((fed, ended))

    serve(engine, prompts_of((prompt,), seed=7), (new,), watch)
    # read after the prefill, and again after each window's end in decode
    assert len(seen) >= 2 and seen[-1][1] > seen[0][1]


# -- the table's roll ---------------------------------------------------------

def scheduler_for(num_pages=40, **kw):
    cache = PagedKVCache(num_layers=1, num_pages=num_pages, num_heads=1,
                         page_size=PAGE, head_dim=8, dtype=jnp.float32)
    return ContinuousBatchingScheduler(
        cache, max_seq_len=160, token_budget=160, max_batch_size=4,
        prefill_lengths=[32, 64, 96], prefill_batch_sizes=[1],
        decode_batch_sizes=[4], eva_window=WINDOW, eva_chunk=CHUNK, **kw)


@pytest.mark.parametrize("pos,index,length", [
    (0, 0, 1), (31, 3, 32), (32, 1, 8 + 1), (33, 1, 8 + 2),
    (63, 1 + 3, 8 + 32), (64, 2, 16 + 1), (159, 4 + 3, 32 + 32)])
def test_table_index_and_length_arithmetic(pos, index, length):
    """Window 32, chunk 4, page 8: a window's 8 pooled rows are one page
    and its exact rows four."""
    sch = scheduler_for()
    assert (sch.eva_pages_summary, sch.eva_pages_window) == (1, 4)
    assert sch.eva_table_index(pos) == index
    assert sch.eva_length(pos) == length


def test_roll_gives_back_the_window_and_splices_the_pending_pages():
    sch = scheduler_for()
    req = Request(prompt=list(range(1, 21)), max_new_tokens=100)
    sch.add_request(req)
    plan = sch.schedule()
    assert plan.prefills == [req] and req.eva_windows == 0
    # positions 0 .. 20: three window pages, and one pending page
    assert len(req.pages) == 3 and len(req.eva_pending) == 1
    sch.complete_prefill(req, 7)
    free0 = sch.cache.num_free
    for _ in range(11):                         # positions 20 .. 30 written
        sch.schedule()
        sch.complete_decode(req, 7)
    assert req.cached == 31 and len(req.pages) == 4 and req.eva_windows == 0
    pending, window = list(req.eva_pending), list(req.pages)
    sch.schedule()                              # writes 31: the window's last
    sch.complete_decode(req, 7)
    assert req.pages == window and sch.eva_windows_rolled == 0
    sch.schedule()                              # writes 32: the table rolls
    assert req.eva_windows == 1 and sch.eva_windows_rolled == 1
    assert sch.eva_pages_released == 4
    # the pending page is the table's prefix, a fresh one took its place,
    # and one page of the new window follows
    assert req.pages[:1] == pending and len(req.pages) == 2
    assert req.eva_pending != pending and len(req.eva_pending) == 1
    assert not set(window) & set(req.pages + req.eva_pending) or \
        set(window) & set(req.pages[1:] + req.eva_pending) <= set(window)
    # 4 given back, 1 pending and 1 window page taken, since the prefill
    assert sch.cache.num_free == free0 - 1 + 4 - 2


def test_a_request_that_ends_mid_window_returns_every_page():
    sch = scheduler_for()
    req = Request(prompt=list(range(1, 51)), max_new_tokens=5)
    sch.add_request(req)
    sch.schedule()
    assert req.eva_windows == 1 and len(req.eva_pending) == 1
    sch.complete_prefill(req, 7)
    while req.status is None:
        sch.schedule()
        sch.complete_decode(req, 7)
    assert req.status == "ok" and not req.pages and not req.eva_pending
    assert sch.cache.num_free == sch.cache.num_pages - 1


def test_the_lookahead_step_reads_the_table_it_was_built_with(setup):
    """The engine dispatches a step with its request's table as it stood,
    and rolls the table for the next one while that step is in flight: the
    table of the step that writes a window's LAST row still names the
    window's pages, the next one's names the pooled rows' page in their
    place, and both steps' bytes are the reference's."""
    c, model, params = setup
    engine = engine_for(model, params)
    sent = []
    tables = engine._tables

    def recording(reqs, batch, width, prefill=None):
        out = tables(reqs, batch, width, prefill)
        if prefill is None:
            sent.append((reqs[0].cached + reqs[0].pending,
                         out["eva"][0].copy(), out["eva_pending"][0].copy(),
                         bool(reqs[0].pending)))
        return out

    engine._tables = recording
    done, = serve(engine, prompts_of((20,)), (30,))
    by_pos = {pos: (table, pending, ahead)
              for pos, table, pending, ahead in sent}
    last, first = by_pos[31], by_pos[32]
    assert first[2], "the rolling step was built with a decode in flight"
    assert np.count_nonzero(last[0]) == 4       # the window's four pages
    assert np.count_nonzero(first[0]) == 2      # pooled page | one new page
    assert first[0][0] == last[1][0]            # the pending page, spliced
    assert first[1][0] != last[1][0]            # and a fresh one pending
    row = jnp.asarray(list(done.prompt) + list(done.generated))[None]
    want = np.asarray(jitted(reference.logits_at, c)(
        params, row, (19 + np.arange(30))[None])[0])
    assert (want.argmax(-1) == np.asarray(done.generated)).all()


def test_counters_count_rows_by_population(setup):
    c, model, params = setup
    engine = engine_for(model, params)
    serve(engine, prompts_of((40,)), (30,))
    # decode steps write positions 40 .. 68: window 1 (40 .. 63) reads
    # 8 pooled rows a step, window 2 (64 .. 68) sixteen
    stats = engine.stats
    window = sum(p % WINDOW + 1 for p in range(40, 69))
    summary = sum(p // WINDOW * 8 for p in range(40, 69))
    assert stats["decode_kv_tokens_eva_window"] == window
    assert stats["decode_kv_tokens_eva_summary"] == summary
    assert stats["decode_kv_tokens"] == window + summary
    assert stats["decode_context_tokens_eva"] == sum(range(41, 70))
    assert stats["eva_chunks_pooled"] == sum(
        p % CHUNK == CHUNK - 1 for p in range(40, 69))
    assert stats["eva_windows_rolled"] == 1
    assert stats["eva_pages_released"] == 4
    assert stats["kv_bytes_per_row_eva"] == 2 * 2 * 4 * 16 * 4
    assert stats["kv_bytes_per_token_eva_summary"] == \
        stats["kv_bytes_per_row_eva"] / CHUNK
    assert "eva_roll_s" in stats


# -- what is not built is refused by name -------------------------------------

def model_with(**over):
    cfg = dataclasses.replace(family.model_config(conf(), "float32"), **over)
    return GPTNeoX(cfg, use_pallas=False)


@pytest.mark.parametrize("over,word", [
    (dict(eva_chunk=5), "a chunk that divides it"),
    (dict(eva_window=0, eva_chunk=0), "window and a chunk"),
    (dict(layer_plan=(LayerSpec(attn="eva", heads=4),
                      LayerSpec(attn="full", heads=4))),
     "beside layers of another attention kind"),
    (dict(num_kv_heads=2), "one query head a KV head"),
    (dict(loop_steps=2), "loop_steps=2"),
    (dict(generation_block=4), "generation_block=4"),
    (dict(attn_gate="per-head"), "attn_gate='per-head'"),
    (dict(norm="layernorm", use_bias=True), "norm_unit_offset"),
    (dict(tie_word_embeddings=True), "num_pred_heads"),
], ids=["chunk", "no_window", "mixed_plan", "grouped_heads", "loop",
        "block", "gate", "layernorm_offset", "tied_heads"])
def test_check_block_refuses_by_name(over, word):
    with pytest.raises((NotImplementedError, ValueError)) as e:
        model_with(**over)
    assert word in str(e.value)


@pytest.mark.parametrize("over,word", [
    ({"prefix_cache": {"enabled": True}}, "prefix_cache"),
    ({"speculative": {"enabled": True, "num_draft_tokens": 2}},
     "speculative"),
    ({"disaggregation": {"role": "prefill", "pool_id": "a"}}, "handoff"),
    ({"kv_cache_dtype": "int8"}, "int8 with a chunk-pooled"),
    ({"prefill_lengths": [16, 32]}, "whole windows"),
], ids=["prefix_sharing", "speculation", "handoff", "int8_pages", "buckets"])
def test_engine_refuses_by_name(setup, over, word):
    _, model, params = setup
    with pytest.raises((DeepSpeedConfigError, ValueError)) as e:
        engine_for(model, params, **over)
    assert word in str(e.value)


def test_engine_refuses_a_model_parallel_mesh(setup):
    _, model, params = setup
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                ("data", "model"))
    with pytest.raises(DeepSpeedConfigError, match="mp > 1"):
        InferenceEngine(model, params=params, mesh=mesh, config={
            "inference": {"enabled": True, "page_size": PAGE,
                          "num_pages": 64, "max_seq_len": 160}})


def test_training_is_refused(setup):
    _, model, params = setup
    tokens = jnp.zeros((1, 32), jnp.int32)
    with pytest.raises(DeepSpeedConfigError, match="chunk-pooled"):
        model.loss_fn(params, (tokens, tokens))


@pytest.mark.parametrize("kind", ["window", "eva"])
@pytest.mark.parametrize("what", ["prefix_cache", "spec_tokens"])
def test_scheduler_names_the_kind_it_refuses(kind, what):
    """A window kind and a chunk-pooled kind alike take neither a prefix
    cache nor speculation, and the refusal says which one it met."""
    from deeperspeed_tpu.inference.kv_cache import PrefixCache
    cache = PagedKVCache(num_layers=1, num_pages=40, num_heads=1,
                         page_size=PAGE, head_dim=8, dtype=jnp.float32)
    kinds = dict(eva_window=WINDOW, eva_chunk=CHUNK) if kind == "eva" else \
        dict(window=16, window_cache=PagedKVCache(
            num_layers=1, num_pages=40, num_heads=1, page_size=PAGE,
            head_dim=8, dtype=jnp.float32))
    extra = {"prefix_cache": PrefixCache(cache)} \
        if what == "prefix_cache" else {"spec_tokens": 2}
    with pytest.raises(ValueError, match=f"a {kind} cache kind takes "
                                         f"neither"):
        ContinuousBatchingScheduler(
            cache, max_seq_len=160, token_budget=160, max_batch_size=4,
            prefill_lengths=[32, 64], prefill_batch_sizes=[1],
            decode_batch_sizes=[4], **kinds, **extra)
