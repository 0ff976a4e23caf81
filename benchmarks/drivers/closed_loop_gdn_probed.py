"""Traffic kind `closed_loop_gdn_probed`: `closed_loop_state_probed` as it
is (the traffic, the window and every measured number of `closed_loop`,
the floor on exact matches, the limits on the pools' rows and on the slot
of recurrent state, `traced_stats`, the probe request served alone and
read after its prefill and before its last token), for a model whose
cache is TWO kinds: K and V pages of its full layers, and a slot of
recurrent state a sequence that holds, for every Gated DeltaNet layer, a
float32 MATRIX a value head and the convolution's last rows
(qwen3_next).

What differs is ONE seam: how a reading compares the live request's slot
and pages with `reference.states` (this model has no window pool, and its
reference names the recurrent state `state`). `read_pools` here takes the
place of `closed_loop_state_probed.read_pools`, which that driver's
`probe_state` looks up when it runs, on the copy of that module loaded
beside this one; its loop, its two readings, its worst-layer and
first-layer entries and its `run` (the three state limits) are used as
they are, and no driver file is edited. Each reading gives every gdn
layer's state and convolution rows as the relative error of the layer's
whole state, and each full layer's `[K | V]` rows as `closed_loop_probed`
takes them (each live row's relative error, the median over the layer's
rows).
"""

import os

import numpy as np

from benchmarks import harness

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_state = harness.load_module(_ROOT, "drivers", "closed_loop_state_probed")
# what readers ask a traffic kind's driver for
quantile_lengths = _state.quantile_lengths
RequestSource = _state.RequestSource
run = _state.run


def _comparison(reference, conf, width):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def compare(params, row, n, conv, state, k_full, v_full, pages):
        want = reference.states(conf, params, row, n)
        f32 = jnp.float32

        def whole(got, ref):
            layers = ref.shape[0]
            got = got.reshape(layers, -1).astype(f32)
            ref = ref.reshape(layers, -1)
            return jnp.linalg.norm(got - ref, axis=-1) / \
                jnp.linalg.norm(ref, axis=-1), jnp.isfinite(got).all()

        def held(pool):
            r = pool[:, pages]                      # [L, pages, G, ps, D]
            return jnp.moveaxis(r, 2, 3).reshape(r.shape[0], width, -1)

        got = jnp.concatenate([held(k_full), held(v_full)],
                              axis=-1).astype(f32)
        err = jnp.linalg.norm(got - want["full"], axis=-1) / \
            jnp.linalg.norm(want["full"], axis=-1)
        live = jnp.arange(width) < n
        state_err, ok_s = whole(state, want["state"])
        conv_err, ok_c = whole(conv, want["conv"])
        return {"state_error_by_layer": state_err,
                "conv_rows_error_by_layer": conv_err,
                "full_row_error_by_layer": jnp.nanmedian(
                    jnp.where(live, err, jnp.nan), axis=-1),
                "finite": ok_s & ok_c & jnp.isfinite(
                    jnp.where(live[:, None], got, 0.0)).all()}

    return compare


def probe_gdn(engine, reference, conf, params, source, width):
    """`closed_loop_state_probed.probe_state` with this model's reading."""
    compare = _comparison(reference, conf, width)

    def read_pools(engine, params, request, width, _windowed):
        n = _state._fed(request)
        context = (list(request.prompt) + list(request.generated))[:n]
        if len(context) != n:
            raise harness.BenchmarkError("a fed token was not read back")
        row = np.zeros(width, np.int32)
        row[:n] = context
        pages = np.zeros(width // engine.page_size, np.int32)
        pages[:len(request.pages)] = request.pages
        slot = request.state_slot
        out = compare(params, row, np.int32(n),
                      engine.state_cache.conv[:, slot],
                      engine.state_cache.ssm[:, slot],
                      engine.cache.k, engine.cache.v, pages)
        out = {k: np.asarray(v).tolist() for k, v in out.items()}
        return dict(out, window_row_error_by_layer=[], fed=n)

    _state.read_pools = read_pools
    return _state.probe_state(engine, reference, conf, params, source, width)


_state._probed.probe_cache = probe_gdn
