"""Traffic kind `closed_loop_eva_probed`: `closed_loop_probed` as it is
(the traffic, the window and every measured number of `closed_loop`, the
floor on exact matches, the limit on the pool's rows, `traced_stats`), for
a model whose ONE page pool holds TWO populations of rows (EvaByte: the
exact K/V rows of a sequence's current window, and one pooled row for
every chunk of 16 positions of the windows before it) and whose head
makes eight predictions a position.

`closed_loop`'s own check reads HEAD 0 (`reference.logits_at`: the next
byte's logits, what greedy decoding reads). After the window one more
request of the cell's own traffic (fresh ids; the first of the schedule
whose prompt holds a window's end and whose decode crosses another) is
served ALONE, with `engine.head_trace` on, and what the pool holds for it
is read TWICE against `reference.states` over the tokens that were fed:

- right after its prefill: the prompt's whole windows are pooled rows in
  the table's prefix, its last window's whole chunks pooled rows in the
  PENDING pages, its last window's rows exact rows in the window's pages;
- `SETTLE_CHUNKS` chunks after a window's end that fell in DECODE: the
  table has rolled (the pending pages are its newest prefix entries, the
  ended window's pages are gone), the new window's rows and pending rows
  were written a row and a chunk at a time by the decode steps.

Each reading gives, a population (`visible`, `pending`, `exact`): each
live row's relative error, its median over a layer's rows, the worst
layer. `cache_row_error`, the worst of all of them, is held by
`closed_loop_probed.run` to the cell's `cache_row_error_limit`. Rounding
of the activations grows through the layers and is most of that number,
so it says little of the POOLING's own precision; that is held apart:
`pooling_error` is what the pending pages hold against the float32
pooling (`reference.pool`) of the exact rows THE POOL ITSELF holds for
the same chunks, the relative error of a layer's pending rows together,
the worst layer of both readings, held to `pooling_error_limit`. A pooled
row accumulated in bfloat16, or left out, fails it whatever the layers
before it did. `head_logit_error` is the worst absolute error of ANY of
the eight heads' logits at the probe request's served positions, program
against reference, held to `head_logit_error_limit`.

It wraps ONE seam of `closed_loop_probed` (its `probe_cache`, looked up
when its `run` calls it) on the copy of that module loaded beside this
one, and edits neither that file nor `closed_loop.py`.
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

# whole chunks of the new window the second reading waits for
SETTLE_CHUNKS = 4


def _fed(request):
    """The context tokens that went through the model: those read back
    and cached, and the one a decode in flight took."""
    return request.cached + request.pending


def _probe_request(source, window, chunk):
    """The first request of the schedule whose prompt holds a window's
    end and whose decode crosses another and goes `SETTLE_CHUNKS` chunks
    on."""
    for _ in range(len(source.prompts)):
        prompt, n_out = source.next()
        n = len(prompt)
        if n >= window and n + n_out - 1 >= \
                (n // window + 1) * window + SETTLE_CHUNKS * chunk:
            return prompt, n_out
    raise harness.BenchmarkError(
        "no request of the traffic crosses a window's end in decode")


def probe_pool(engine, reference, conf, params, source, width):
    """One request alone through prefill and decode to its end, the pool
    read after its prefill and after a window's end in decode, every
    head's logits read at every served position. Returns the `check`
    entries `closed_loop_probed.run` reads, and the pooling's and the
    heads' beside them."""
    import jax
    import jax.numpy as jnp

    ps, W, C = engine.page_size, conf["window_size"], conf["chunk_size"]
    H = conf["num_attention_heads"]
    D = conf["hidden_size"] // H
    per_win = W // C
    n_visible = per_win * (width // W) // ps        # the widest prefix
    stack = reference.stack_name(conf)
    f32 = jnp.float32

    @jax.jit
    def compare(params, row, n, ended, k_pool, v_pool, visible, pending,
                window):
        # `ended`: the windows the request's table has rolled past (the
        # step that writes a window's last row leaves it one short of
        # n // W until the next step is planned)
        want = reference.states(conf, params, row, ended * W)
        live = {"visible": per_win * ended,
                "pending": (n - ended * W) // C, "exact": n - ended * W}

        def held(pages):
            def rows(pool):
                r = pool[:, pages]                  # [L, pages, H, ps, D]
                return jnp.moveaxis(r, 2, 3).reshape(
                    r.shape[0], -1, H * D)
            return jnp.concatenate([rows(k_pool), rows(v_pool)],
                                   axis=-1).astype(f32)

        got = {"visible": held(visible), "pending": held(pending),
               "exact": held(window)}
        ref = {"visible": want["pooled"][:, :n_visible * ps],
               "pending": jax.lax.dynamic_slice_in_dim(
                   jnp.pad(want["pooled"], ((0, 0), (0, per_win), (0, 0))),
                   per_win * ended, per_win, axis=1),
               "exact": want["rows"]}
        out, finite = {}, True
        for name in got:
            g, r = got[name], ref[name]
            err = jnp.linalg.norm(g - r, axis=-1) / \
                jnp.linalg.norm(r, axis=-1)
            on = jnp.arange(g.shape[1]) < live[name]
            out[f"{name}_row_error_by_layer"] = jnp.where(
                live[name] > 0,
                jnp.nanmedian(jnp.where(on, err, jnp.nan), axis=-1), 0.0)
            finite = finite & jnp.isfinite(
                jnp.where(on[:, None], g, 0.0)).all()
        # the pending rows against the float32 pooling of the exact rows
        # the pool itself holds: the pooling's own arithmetic
        attn = params["stacks"][stack]["attn"]
        exact = got["exact"].reshape(-1, W, 2, H, D)

        def pooled(rows, phi, mu):
            k, v = reference.pool(rows[:, 0], rows[:, 1], phi, mu,
                                  D ** -0.5, C)
            return jnp.concatenate([k.reshape(per_win, -1),
                                    v.reshape(per_win, -1)], axis=-1)

        again = jax.vmap(pooled)(exact, attn["eva_phi"], attn["eva_mu"])
        on = (jnp.arange(per_win) < live["pending"])[None, :, None]
        diff = jnp.where(on, got["pending"] - again, 0.0)
        out["pooling_error_by_layer"] = jnp.where(
            live["pending"] > 0,
            jnp.linalg.norm(diff.reshape(diff.shape[0], -1), axis=-1) /
            jnp.linalg.norm(jnp.where(on, again, 0.0).reshape(
                diff.shape[0], -1), axis=-1), 0.0)
        out["finite"] = finite
        return out

    def read(request):
        n = _fed(request)
        context = (list(request.prompt) + list(request.generated))[:n]
        if len(context) != n:
            raise harness.BenchmarkError("a fed token was not read back")
        row = np.zeros(width, np.int32)
        row[:n] = context
        kept = engine.scheduler.eva_pages_summary * request.eva_windows
        tables = {"visible": (request.pages[:kept], n_visible),
                  "pending": (request.eva_pending, per_win // ps),
                  "window": (request.pages[kept:], W // ps)}
        padded = {}
        for name, (pages, size) in tables.items():
            padded[name] = np.zeros(size, np.int32)
            padded[name][:len(pages)] = pages
        with jax.default_matmul_precision("highest"):
            out = compare(params, row, np.int32(n),
                          np.int32(request.eva_windows), engine.cache.k,
                          engine.cache.v, padded["visible"],
                          padded["pending"], padded["window"])
        out = {k: np.asarray(v).tolist() for k, v in out.items()}
        return dict(out, fed=n, windows=request.eva_windows)

    prompt, n_out = _probe_request(source, W, C)
    engine.head_trace = []
    rid = engine.submit(prompt, max_new_tokens=n_out)
    readings, request = {}, None
    while engine.scheduler.has_work:
        engine.step()
        request = next((r for r in engine.scheduler.running
                        if r.request_id == rid), None)
        if request is None:
            break
        fed = _fed(request)
        if "after_prefill" not in readings and request.generated:
            readings["after_prefill"] = read(request)
        elif "after_roll" not in readings and \
                fed // W > len(prompt) // W and \
                fed % W >= SETTLE_CHUNKS * C:
            readings["after_roll"] = read(request)
    heads, engine.head_trace = engine.head_trace, None
    done = next((r for r in engine.scheduler.pop_finished()
                 if r.request_id == rid), None)
    if set(readings) != {"after_prefill", "after_roll"} or done is None:
        raise harness.BenchmarkError("the probe request left the engine "
                                     "before its pool could be read")

    # every head's logits at every served position, program against
    # reference: row i of the trace is the logits the token at index
    # `at` was sampled from, the reference's at position `at` - 1
    t_max = len(heads)
    row = np.zeros(width, np.int32)
    context = list(done.prompt) + list(done.generated)
    row[:len(context)] = context
    positions = np.asarray([t["at"] - 1 for t in heads], np.int32)
    want = np.asarray(jax.jit(
        lambda params, row, positions: reference.all_heads_at(
            conf, params, row[None], positions[None])[0])(
                params, row, positions))
    got = np.stack([t["logits"] for t in heads]).astype(np.float32)
    V = conf["vocab_size"]
    by_head = np.abs(got - want).reshape(t_max, -1, V).max(axis=(0, 2))

    def worst(key):
        # numpy's max, not Python's: a NaN anywhere is the worst
        return float(np.max([np.max(r[key]) for r in readings.values()]))

    rows = {name: worst(f"{name}_row_error_by_layer")
            for name in ("visible", "pending", "exact")}
    return {"cache_row_error": float(np.max(list(rows.values()))),
            "cache_row_error_by_population": rows,
            "cache_rows_finite": all(r["finite"]
                                     for r in readings.values()) and
            bool(np.isfinite(got).all()),
            "pooling_error": worst("pooling_error_by_layer"),
            "head_logit_error": float(by_head.max()),
            "head_logit_error_by_head": by_head.tolist(),
            "head_logit_scale": float(np.std(want)),
            "readings": readings, "probed_prompt": len(prompt),
            "probed_tokens": readings["after_roll"]["fed"],
            "probed_positions": t_max, "page_size": ps}


_probed.probe_cache = probe_pool


def run(spec, family, reference, **kw):
    rec = _probed.run(spec, family, reference, **kw)
    cell, check = spec["cell"], rec["check"]
    limits = ("pooling_error", "head_logit_error")
    check.update({f"{k}_limit": cell[f"{k}_limit"] for k in limits})
    # a NaN compares false: a row that is not finite fails
    rec["checks"]["pooled_rows_are_the_pool_s_own_rows_pooled"] = \
        check["pooling_error"] <= cell["pooling_error_limit"]
    rec["checks"]["every_head_within_limit"] = \
        check["head_logit_error"] <= cell["head_logit_error_limit"]
    rec["correct"] = all(rec["checks"].values())
    return rec
