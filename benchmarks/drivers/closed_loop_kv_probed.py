"""Traffic kind `closed_loop_kv_probed`: `closed_loop_probed` as it is
(the traffic, the window and every measured number of `closed_loop`, the
floor on exact matches, the limit on the pool's rows, `traced_stats`),
for a model whose pool is K and V pages and whose cache layers outnumber
its layers: a LOOPED model (Ouro: 4 passes x 48 layers = 192 cache layers
a token).

`closed_loop_probed.probe_cache` reads a latent pool (`cache.k` as
`[L, P, page, row]`) and holds every layer's rows and the reference's at
once; here that would be 2 GB of float32 beside a full chip. This probe
reads `cache.k` and `cache.v` (`[cache layers, P, heads, page, head
dim]`) a PASS at a time: the reference's `one_pass` gives pass t's rows
of every layer (`[L, n, K | V]`, K after the rotary) and the next pass's
input, and the pool's cache layers `(t - 1) L .. t L` are held to them.
So a cache index that mixes passes, or a pass that reads another's rows,
fails every layer of that pass. The statistic is `closed_loop_probed`'s:
each row's relative error, its median over a cache layer's rows, the
worst cache layer's median held to the cell's `cache_row_error_limit`.

Rounding grows through a looped stack, so the worst cache layer is always
one of the LAST pass, where a lower precision and bfloat16 lie nearest.
`run` therefore also holds EVERY pass's worst cache layer to a limit of
its own (the cell's `cache_row_error_limit_by_pass`, one entry a pass):
the first pass separates the precisions best, the last holds the whole
loop. All must hold for `correct`.

It wraps ONE seam of `closed_loop_probed` (its `probe_cache`, looked up
when its `run` calls it) on the copy of that module loaded beside this
one (the harness loads a driver anew for every run), and edits neither
that file nor `closed_loop.py`.
"""

import os

import numpy as np

from benchmarks import harness

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_probed = harness.load_module(_ROOT, "drivers", "closed_loop_probed")
# what readers ask a traffic kind's driver for
quantile_lengths = _probed.quantile_lengths
RequestSource = _probed.RequestSource


def probe_cache(engine, reference, conf, params, source, width):
    """One request through prefill and enough decode steps to cross a
    page, every pass's cached K and V rows against the reference's.
    Returns the `check` entries `closed_loop_probed.run` reads."""
    import jax
    import jax.numpy as jnp

    prompt, n_out = source.next()
    want = min(n_out - 1, int(_probed.PROBE_TOKENS_PAGES * engine.page_size))
    rid = engine.submit(prompt, max_new_tokens=n_out)
    request = None
    while engine.scheduler.has_work:
        engine.step()
        request = next((r for r in engine.scheduler.running
                        if r.request_id == rid), None)
        if request is not None and len(request.generated) >= want:
            break
    if request is None or len(request.generated) < want:
        raise harness.BenchmarkError("the probe request left the engine "
                                     "before its cache could be read")
    # generated token j is written when it is the input that yields
    # token j + 1: of k tokens read back, k - 1 are in the cache
    tokens = list(prompt) + list(request.generated)[:-1]
    n = len(tokens)
    row = np.zeros(width, np.int32)
    row[:n] = tokens
    pages = np.zeros(width // engine.page_size, np.int32)
    pages[:len(request.pages)] = request.pages
    L = conf["num_hidden_layers"]

    @jax.jit
    def one(params, x, k_pool, v_pool, pages, first):
        z, ref = reference.one_pass(conf, params, x)
        layers = first + jnp.arange(L)

        def held(pool):         # the request's pages of this pass's layers
            rows = pool[layers[:, None], pages[None, :]]
            return jnp.moveaxis(rows, 2, 3).reshape(L, width, -1)[:, :n]

        got = jnp.concatenate([held(k_pool), held(v_pool)],
                              axis=-1).astype(jnp.float32)
        err = jnp.linalg.norm(got - ref[:, :n], axis=-1) / \
            jnp.linalg.norm(ref[:, :n], axis=-1)
        return z, jnp.median(err, axis=-1), jnp.isfinite(got).all()

    x = jax.jit(reference.embed)(params, row)
    medians, finite = [], True
    with jax.default_matmul_precision("highest"):
        for t in range(conf["total_ut_steps"]):
            x, med, ok = one(params, x, engine.cache.k, engine.cache.v,
                             pages, t * L)
            medians += [float(m) for m in med]
            finite = finite and bool(ok)
    while engine.scheduler.has_work:            # let it end: pages freed
        engine.step()
    engine.scheduler.pop_finished()
    return {"cache_row_error": max(medians),
            "cache_row_error_by_layer": medians,
            "cache_rows_finite": finite,
            "probed_tokens": n, "probed_prompt": len(prompt)}


_probed.probe_cache = probe_cache


def run(spec, family, reference, **kw):
    rec = _probed.run(spec, family, reference, **kw)
    limits = spec["cell"]["cache_row_error_limit_by_pass"]
    by_layer = rec["check"]["cache_row_error_by_layer"]
    L = spec["config"]["num_hidden_layers"]
    if len(by_layer) != L * len(limits):
        raise harness.BenchmarkError(
            f"{len(limits)} limits a pass for {len(by_layer)} cache layers "
            f"of {L} layers a pass")
    # numpy's max, not Python's: a NaN anywhere in a pass is the pass's
    worst = [float(np.max(by_layer[t * L:(t + 1) * L]))
             for t in range(len(limits))]
    rec["check"].update(cache_row_error_by_pass=worst,
                        cache_row_error_limit_by_pass=list(limits))
    # a NaN compares false: a pass that is not finite fails
    rec["checks"]["cached_rows_within_limit_every_pass"] = all(
        w <= limit for w, limit in zip(worst, limits))
    rec["correct"] = all(rec["checks"].values())
    return rec
