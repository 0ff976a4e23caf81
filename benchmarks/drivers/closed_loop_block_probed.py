"""Traffic kind `closed_loop_block_probed`: `closed_loop`'s traffic, window
and every measured number (through `closed_loop_probed`, which also notes
`traced_stats`), for a model that GENERATES A BLOCK of tokens at a time
(SDAR: a block of 4 positions a step under the block-causal mask,
unmasking by confidence, a commit pass a block).

`closed_loop.check_served` teacher-forces the served tokens through a
causal forward: position i's logits are those a token-at-a-time decode
computed. A block model's token is the argmax of a pass over a block that
still held mask tokens, so the check here REPLAYS THE PASSES the engine
recorded (`InferenceEngine.block_trace`, on from the engine's first step:
the block going into each row-pass and coming out). The float32 reference
(`reference.replay_stats`, one forward a request: the finished sequence,
and behind it every recorded pass's block as it went in) replays

- the TIMED WINDOW's own requests: the seeded sample of `check_requests`
  that `closed_loop.run` hands its check, every pass as the window's
  programs ran it, all slots live, prefills and lookahead between them;
- after the window, `check_requests` of `clients` fresh requests (fresh
  token ids) that run TOGETHER through the same engine, every slot live
  and each refilled as its request ends, as in the window, until the
  checked ones have ended: theirs are the pool rows that are read, when
  each ends and before its pages can go to another;

and holds

(a) `logit_margin`: the `SHORTFALL_QUANTILE` of the shortfalls of the
    tokens the engine unmasked (the reference's best logit at the row,
    from the same state, less its logit of the served token). Not the
    largest: four in five shortfalls are 0 and the largest of some 2,700
    a run has a long tail (0.029 to 0.104 over 11 sound seeds, where the
    nearest lower precision reads 0.2), so no limit on it has room on
    both sides; the cell's file gives the readings;
(b) `exact_match_floor`: the least share of the unmasked tokens that are
    the reference's argmax at a row the reference would also have
    unmasked (its own confidences under the same threshold and floor);
(c) `cache_row_error_limit`: when a checked request of the second set
    ends, the rows its pages hold for every committed block, every layer,
    against the reference's K (after the rotary) and V of the finished
    sequence: each row's relative error, its median over a layer's rows,
    the worst layer's median. This is what catches a provisional row left
    in the pool, a skipped commit, a causal mask where the block-causal
    one belongs.

(a) and (b) are held for each set apart (`probe_` names the second's
readings). It wraps two seams of `closed_loop_probed` on the copy loaded
beside this one (`check_served`, which becomes the replay of the window's
sample, and `probe_cache`, which becomes the second set) and the engine's
class for the length of a run (the record of passes on), and edits
neither that file nor `closed_loop.py`.
"""

import os

import numpy as np

from benchmarks import harness

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_probed = harness.load_module(_ROOT, "drivers", "closed_loop_probed")
# what readers ask a traffic kind's driver for
quantile_lengths = _probed.quantile_lengths

# the statistic (a) holds, and the others the record carries beside it
SHORTFALL_QUANTILE = 0.99
_QUANTILES = {"p90": 0.9, "p999": 0.999}
_engines = []           # the engine of the run in flight (`run`)


def _generation(conf):
    family = harness.load_module(_ROOT, "families", conf["family"])
    return family.generation(conf)


def replay(reference, conf, params, served, t_max, s_max):
    """The float32 reference over `served`, [(request, its recorded
    passes, the [K | V] rows [L, cached, 2 G d] its pages held when it
    ended, or None)] -> the readings of (a), (b), (c) (module docstring).
    `t_max` and `s_max`: the most tokens a request may hold and the most
    denoising passes it may take: one shape, so one program whatever the
    request."""
    import jax
    import jax.numpy as jnp

    gen = _generation(conf)
    block = gen["block"]

    @jax.jit
    def one(params, tokens, n, starts, states, unmasked, held, cached):
        stats = reference.replay_stats(conf, params, tokens, n, starts,
                                       states, unmasked, block)
        ref = stats.pop("cache")
        rows = jnp.arange(tokens.shape[0]) < cached
        got = held.astype(jnp.float32)
        err = jnp.linalg.norm(got - ref, axis=-1) / \
            jnp.maximum(jnp.linalg.norm(ref, axis=-1), 1e-30)
        med = jnp.nanmedian(jnp.where(rows[None], err, jnp.nan), axis=-1)
        return stats, med, jnp.isfinite(got).all()

    shortfalls, exact, medians, finite = [], 0, [], True
    n_passes = n_commits = 0
    # where no rows were read none is compared (`cached` 0)
    no_rows = jnp.zeros((conf["num_hidden_layers"], t_max, 2 *
                         conf["num_key_value_heads"] * conf["head_dim"]),
                        jnp.bfloat16)
    for request, passes, held in served:
        denoise = [p for p in passes if not p["committed"]]
        n_passes += len(passes)
        n_commits += len(passes) - len(denoise)
        seq = list(request.prompt) + list(request.generated)
        tokens = np.zeros(t_max, np.int32)
        tokens[:len(seq)] = seq
        starts = np.full(s_max, -1, np.int32)
        states = np.zeros((s_max, block), np.int32)
        unmasked = np.zeros((s_max, block), np.int32)
        for i, p in enumerate(denoise):
            starts[i] = p["start"]
            states[i], unmasked[i] = p["tokens_in"], p["tokens"]
        if held is None:
            held, cached = no_rows, 0
        else:
            held = jnp.pad(held, ((0, 0), (0, t_max - held.shape[1]),
                                  (0, 0)))
            cached = request.cached
        with jax.default_matmul_precision("highest"):
            stats, med, ok = one(params, jnp.asarray(tokens), len(seq),
                                 jnp.asarray(starts), jnp.asarray(states),
                                 jnp.asarray(unmasked), held, cached)
        stats = {k: np.asarray(v) for k, v in stats.items()}
        finite = finite and bool(ok) and \
            bool(np.isfinite(stats["best"][:len(denoise)]).all())
        if cached:
            medians.append([float(m) for m in med])
        for i, p in enumerate(denoise):
            masked_in = np.asarray(p["masked_in"])
            rows = np.flatnonzero(masked_in & ~np.asarray(p["masked"]))
            if not len(rows):
                continue
            shortfalls.extend(
                (stats["best"][i, rows] - stats["served"][i, rows]).tolist())
            would = reference.choose(stats["confidence"][i], masked_in, gen)
            exact += sum(1 for r in rows if r in would and
                         p["tokens"][r] == stats["argmax"][i, r])
    by_layer = [max(m[layer] for m in medians)
                for layer in range(len(medians[0]))] if medians else []
    total = len(shortfalls)
    short = np.asarray(shortfalls or [float("nan")])
    out = {
        # NaN where nothing was unmasked: it fails the limit
        "logit_shortfall": float(np.quantile(short, SHORTFALL_QUANTILE)),
        "max_logit_shortfall": float(short.max()),
        "exact_match_share": exact / max(total, 1),
        "checked_requests": len(served), "checked_tokens": total,
        "checked_passes": n_passes, "checked_commit_passes": n_commits,
        "reference_finite": finite,
        # NaN where no request committed a block: it fails the limit
        "cache_row_error": max(by_layer) if by_layer else float("nan"),
        "cache_row_error_by_layer": by_layer}
    out.update({f"logit_shortfall_{k}": float(np.quantile(short, q))
                for k, q in _QUANTILES.items()})
    return out


def replay_window(reference, conf, params, done, width, t_max, margin):
    """In `closed_loop.check_served`'s place: the window's sample `done`
    replayed from the passes the engine recorded for it."""
    trace = _engines[-1].block_trace
    ids = {r.request_id for r in done}
    passes = {rid: [] for rid in ids}
    for p in trace:
        if p["request"] in ids:
            passes[p["request"]].append(p)
    block = _generation(conf)["block"]
    out = replay(reference, conf, params,
                 [(r, passes[r.request_id], None) for r in done],
                 width, t_max + 2 * block)
    del out["cache_row_error"], out["cache_row_error_by_layer"]
    return dict(out, logit_margin=margin)


_probed._base.check_served = replay_window


def served_together(engine, source):
    """`source.clients` requests of the traffic through the engine
    together, each slot refilled as its request ends until the first
    `source.check_requests` drawn by the source's seed among them have
    ended -> [(request, its passes, the [K | V] rows [L, cached, 2 G d]
    its pages held for its committed blocks when it ended)] of those."""
    import jax.numpy as jnp

    first = len(engine.block_trace)
    ids = []

    def submit():
        prompt, n_out = source.next()
        ids.append(engine.submit(prompt, max_new_tokens=n_out))

    for _ in range(source.clients):
        submit()
    want = {ids[i] for i in source.rng.choice(
        source.clients, size=source.check_requests, replace=False)}
    pages, out = {}, []

    def held(request):
        idx = np.asarray(pages[request.request_id], np.int32)
        n = request.cached

        def rows(pool):         # [L, P, G, page, d] -> [L, n, G * d]
            got = jnp.moveaxis(pool[:, idx], 2, 3)
            return got.reshape(pool.shape[0], -1,
                               pool.shape[2] * pool.shape[4])[:, :n]

        return jnp.concatenate([rows(engine.cache.k), rows(engine.cache.v)],
                               axis=-1)

    while engine.scheduler.has_work:
        engine.step()
        for r in engine.scheduler.running:
            pages[r.request_id] = list(r.pages)
        # read a finished request's rows before the next step can hand
        # its pages to another (what is in flight for it writes at and
        # past `cached` only)
        for r in engine.scheduler.pop_finished():
            if r.request_id in want:
                out.append((r, held(r)))
            if len(out) < len(want):
                submit()
    trace = engine.block_trace[first:]
    return [(r, [p for p in trace if p["request"] == r.request_id], rows)
            for r, rows in out]


def probe_blocks(engine, reference, conf, params, source, width):
    """The second set (module docstring) -> the `check` entries."""
    block = _generation(conf)["block"]
    served = served_together(engine, source)
    # the most a row may hold, and the most denoising passes a request
    # may take (a row a pass at least): `replay_window`'s shapes
    out = replay(reference, conf, params, served, source.max_total,
                 source.output_max + 2 * block)
    rows = {k: out.pop(k) for k in ("cache_row_error",
                                    "cache_row_error_by_layer")}
    return dict(
        {f"probe_{k}": v for k, v in out.items()}, **rows,
        cache_rows_finite=out["reference_finite"],
        probed_tokens=sum(r.cached for r, _, _ in served),
        probed_prompt=sum(len(r.prompt) for r, _, _ in served))


_probed.probe_cache = probe_blocks


class _CheckSource(_probed.RequestSource):
    """The second set's source, with what `probe_blocks` reads of the
    traffic."""

    def __init__(self, traffic, vocab, seed):
        super().__init__(traffic, vocab, seed)
        self.clients = traffic["clients"]
        self.check_requests = traffic["check_requests"]
        self.output_max = traffic["output_len"]["max"]


RequestSource = _probed.RequestSource
_probed.RequestSource = _CheckSource


def run(spec, family, reference, **kw):
    import deeperspeed_tpu.inference as inference

    cell = spec["cell"]

    class Recorded(inference.InferenceEngine):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            self.block_trace = []
            _engines.append(self)

    seam, inference.InferenceEngine = inference.InferenceEngine, Recorded
    try:
        rec = _probed.run(spec, family, reference, **kw)
    finally:
        inference.InferenceEngine = seam
        _engines.clear()
    check = rec["check"]
    sets = ("", "probe_")       # the window's sample, the second set
    rec["checks"].update(
        served_tokens_within_margin=all(
            check[s + "logit_shortfall"] <= cell["logit_margin"]
            for s in sets),
        served_tokens_match_reference=all(
            check[s + "exact_match_share"] >= cell["exact_match_floor"]
            for s in sets),
        reference_finite=all(bool(check[s + "reference_finite"])
                             for s in sets),
        requests_checked=all(check[s + "checked_requests"] > 0 and
                             check[s + "checked_tokens"] > 0 for s in sets))
    rec["correct"] = all(rec["checks"].values())
    return rec
