"""Traffic kind `closed_loop_state_probed`: `closed_loop_probed` as it is
(the traffic, the window and every measured number of `closed_loop`, the
floor on exact matches, the limit on the pools' rows, `traced_stats`),
for a model whose cache is THREE kinds: K and V pages of ONE full layer,
K and V pages of window layers whose pages behind the window are given
back, and a slot of RECURRENT STATE a sequence (phi4flash: nine Mamba
layers' scan state and convolution rows).

After the window one more request of the cell's own traffic (fresh token
ids) is served alone to its end (the traffic's next request: hundreds
to thousands of recurrent steps), and what the pools hold for it is read
TWICE, against `reference.states` over the
tokens that were fed (`Request.cached + pending`: a decode in flight has
written its row and taken its step):

- right after its prefill, where a prefill that lets the bucket's padding
  move the state, or one that starts from what the slot held before,
  shows whole;
- before its last token, where every rounding of its decode steps
  through the state has been added up.

Each reading gives: every Mamba layer's scan state and convolution rows
as the relative error of the layer's whole state (a norm over channels
and state), and the full layer's and each window layer's `[K | V]` rows
as `closed_loop_probed` takes them (each live row's relative error, the
median over a cache layer's rows; a window layer's live rows are those
its pages still hold). `cache_row_error` is the worst K/V cache layer of
both readings, held by `closed_loop_probed.run` to the cell's
`cache_row_error_limit`; the worst layer's scan state and convolution
rows are held here to the cell's `state_error_limit` and
`conv_rows_error_limit`. Rounding of the activations grows through the
layers (a tenth of the last Mamba layer's error at the first), so the
worst layer says little of the STATE's own precision: the FIRST Mamba
layer's state before the last token, whose input is the embedding's
norm alone, is held to a limit of its own
(`state_error_first_layer_limit`), which is where a scan state kept in
bfloat16 shows (PERF.md section 6, PR 47).

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


def _fed(request):
    """The context tokens that went through the model: those read back
    and cached, and the one a decode in flight took."""
    return request.cached + request.pending


def read_pools(engine, params, request, width, compare):
    """One reading: the live `request`'s slot and pages against the
    reference over the tokens fed. Returns the reading's entries."""
    n = _fed(request)
    context = (list(request.prompt) + list(request.generated))[:n]
    if len(context) != n:
        raise harness.BenchmarkError("a fed token was not read back")
    ps = engine.page_size
    row = np.zeros(width, np.int32)
    row[:n] = context
    pages = {}
    for kind, held in (("full", request.pages),
                       ("window", request.window_pages)):
        pages[kind] = np.zeros(width // ps, np.int32)
        pages[kind][:len(held)] = held
    # a window layer's live rows: those of the pages it still holds
    first = next((i for i, p in enumerate(request.window_pages) if p), 0) * ps
    state = engine.state_cache
    out = compare(params, row, np.int32(n), np.int32(first),
                  state.conv[:, request.state_slot],
                  state.ssm[:, request.state_slot],
                  engine.cache.k, engine.cache.v, pages["full"],
                  engine.window_cache.k, engine.window_cache.v,
                  pages["window"])
    out = {k: np.asarray(v).tolist() for k, v in out.items()}
    return dict(out, fed=n, first_live_window_row=first)


def probe_state(engine, reference, conf, params, source, width):
    """One request alone through prefill and decode to its end, the pools
    read after its prefill and before its last token. Returns the `check`
    entries `closed_loop_probed.run` reads, and the state's beside them."""
    import jax
    import jax.numpy as jnp

    ps = engine.page_size

    @jax.jit
    def compare(params, row, n, first, conv, ssm, k_full, v_full,
                pages_full, k_win, v_win, pages_win):
        want = reference.states(conf, params, row, n)
        f32 = jnp.float32

        def whole(got, ref):
            layers = ref.shape[0]
            got = got.reshape(layers, -1).astype(f32)
            ref = ref.reshape(layers, -1)
            return jnp.linalg.norm(got - ref, axis=-1) / \
                jnp.linalg.norm(ref, axis=-1), jnp.isfinite(got).all()

        def rows(k_pool, v_pool, pages, ref, lo):
            def held(pool):
                r = pool[:, pages]                  # [L, pages, G, ps, D]
                return jnp.moveaxis(r, 2, 3).reshape(r.shape[0], width, -1)
            got = jnp.concatenate([held(k_pool), held(v_pool)],
                                  axis=-1).astype(f32)
            err = jnp.linalg.norm(got - ref, axis=-1) / \
                jnp.linalg.norm(ref, axis=-1)
            at = jnp.arange(width)
            live = (at >= lo) & (at < n)
            return jnp.nanmedian(jnp.where(live, err, jnp.nan), axis=-1), \
                jnp.isfinite(jnp.where(live[:, None], got, 0.0)).all()

        state_err, ok_s = whole(ssm, want["ssm"])
        conv_err, ok_c = whole(conv, want["conv"])
        full_err, ok_f = rows(k_full, v_full, pages_full, want["full"], 0)
        win_err, ok_w = rows(k_win, v_win, pages_win, want["window"], first)
        return {"state_error_by_layer": state_err,
                "conv_rows_error_by_layer": conv_err,
                "full_row_error_by_layer": full_err,
                "window_row_error_by_layer": win_err,
                "finite": ok_s & ok_c & ok_f & ok_w}

    prompt, n_out = source.next()
    rid = engine.submit(prompt, max_new_tokens=n_out)
    readings, request = {}, None
    while engine.scheduler.has_work:
        engine.step()
        request = next((r for r in engine.scheduler.running
                        if r.request_id == rid), None)
        if request is None:
            break
        got = len(request.generated)
        if "after_prefill" not in readings and got >= 1:
            readings["after_prefill"] = read_pools(
                engine, params, request, width, compare)
        if got >= n_out - 1:
            readings["at_end"] = read_pools(
                engine, params, request, width, compare)
            break
    if set(readings) != {"after_prefill", "at_end"}:
        raise harness.BenchmarkError("the probe request left the engine "
                                     "before its pools could be read")
    while engine.scheduler.has_work:            # let it end: all given back
        engine.step()
    engine.scheduler.pop_finished()

    def worst(key):
        # numpy's max, not Python's: a NaN anywhere is the worst
        return float(np.max([np.max(r[key]) for r in readings.values()]))

    kv = [e for r in readings.values()
          for e in r["full_row_error_by_layer"] +
          r["window_row_error_by_layer"]]
    return {"cache_row_error": float(np.max(kv)),
            "cache_row_error_by_layer": kv,
            "cache_rows_finite": all(r["finite"] for r in readings.values()),
            "state_error": worst("state_error_by_layer"),
            "state_error_first_layer":
                readings["at_end"]["state_error_by_layer"][0],
            "conv_rows_error": worst("conv_rows_error_by_layer"),
            "readings": readings, "probed_tokens": readings["at_end"]["fed"],
            "probed_prompt": len(prompt), "page_size": ps}


_probed.probe_cache = probe_state


def run(spec, family, reference, **kw):
    rec = _probed.run(spec, family, reference, **kw)
    cell, check = spec["cell"], rec["check"]
    limits = ("state_error", "conv_rows_error", "state_error_first_layer")
    check.update({f"{k}_limit": cell[f"{k}_limit"] for k in limits})
    # a NaN compares false: a state that is not finite fails
    rec["checks"]["recurrent_state_within_limit"] = all(
        check[k] <= cell[f"{k}_limit"] for k in limits)
    rec["correct"] = all(rec["checks"].values())
    return rec
