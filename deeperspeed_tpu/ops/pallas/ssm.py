"""The selective scan of a Mamba-1 layer: a prompt's walk over time
(`ssm_scan`) and a decode step's one-token update of the recurrent state
in place (`ssm_step`).

    h_t = exp(dt_t[None, :] * A) * h_{t-1} + (dt_t * x_t)[None, :] * B_t[:, None]
    s_t = sum_n h_t[n, :] * C_t[n] + D * x_t

with `h` [N, d] (N the state size, d the inner channels), `A` [N, d] (the
layer's `-exp(A_log)`, transposed), `dt_t`, `x_t` [d] (the step after its
softplus; the convolution's output), `B_t`, `C_t` [N]. Everything here is
float32. A row whose step is 0 moves nothing: `exp(0) = 1` and the input
`dt * x` is 0, which is how a prefill bucket's padding and a decode
batch's inactive rows leave the state as it was.

**The layout.** The channels ride as whole vector registers: `d` as
`[sub, lanes]` = `[8, d / 8]` where `d` is a multiple of 1,024
(`state_tile`; a tiny test model's as `[1, d]`), so a state row `h[n]`
of one lane tile is ONE [8, 128] register and a time step is elementwise
work on registers. `B_t[n]` and `C_t[n]` are scalars: they come from
scalar memory and are splat, where a `[N, 1]` column would need a
lane-broadcast of a vector made by a transpose. The recurrent-state pools
(`inference.kv_cache.StateCache`) are stored in this shape, `[layers,
slots, N, sub, lanes]` and the convolution's rows `[layers, slots, K - 1,
sub, lanes]`, so no call re-lays them out.

`ssm_scan` is a kernel over grid (rows, lane tiles, time chunks): the
time axis is the carried one, the state of a lane tile ([N, 8, 128]: 16
registers) lives in the output block that stays resident across it, and
each step reads a chunk's `dt`, `x` and the chunk's B and C scalars.
XLA's associative scan would pass log T times over the `[T, d, N]`
products (335 MB a layer at 1,024 rows of 5,120 channels).

`ssm_step` is a kernel over grid (rows,): a row's whole state
([N, 8, d / 8], 327 KB at 16 x 5,120) comes in from its slot, is
updated and goes back where it came from (the output aliases the pool),
and the row's new convolution rows go into its slot of their pool the
same way, so a decode step moves each live state once each way and never
a pool.

Off a TPU both default to XLA forms with the same semantics (a
`lax.scan` over time; a gather, the update and a scatter) and the kernels
run in the interpreter where a test asks for them by name.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import scopes
from ...compat import CompilerParams
from .decode_attention import _layer_operand
from .flash_attention import LANES, _interpret, note_xla_on_tpu

# backend of the last `ssm_scan` / `ssm_step` traced (`dispatch_report`)
_LAST_BACKEND = {}

# time steps a grid step of the scan walks: a chunk's dt, x and s blocks
# are chunk x 4 KB each at a [8, 128] lane tile (256: 1 MiB a block)
SCAN_CHUNK = 256


def state_tile(d_inner):
    """(sub, lanes) the channels ride as: whole [8, 128] registers where
    `d_inner` is a multiple of 1,024, else one row."""
    return (8, d_inner // 8) if d_inner % (8 * LANES) == 0 else (1, d_inner)


def _auto_backend(op, d_inner):
    if not _interpret() and d_inner % (8 * LANES) == 0:
        return "pallas"
    note_xla_on_tpu(op, f"{d_inner} inner channels: the kernel takes a "
                        f"multiple of {8 * LANES}")
    return "xla"


def _time_step(h, a, d_skip, dt, x, b, c):
    """One step on one lane tile: `h`, `a` lists of N [sub, lanes] tiles,
    `dt`, `x`, `d_skip` such tiles, `b`, `c` lists of N scalars. Returns
    (the new state rows, s)."""
    u = dt * x
    s = d_skip * x
    out = []
    for n, (h_n, a_n) in enumerate(zip(h, a)):
        h_n = jnp.exp(dt * a_n) * h_n + b[n] * u
        s = s + c[n] * h_n
        out.append(h_n)
    return out, s


# ---------------------------------------------------------------------------
# prefill: the walk over time
# ---------------------------------------------------------------------------

def _scan_kernel(bt_ref, ct_ref, dt_ref, x_ref, a_ref, d_ref, s_ref, h_ref,
                 *, chunk, n_state):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = [a_ref[n] for n in range(n_state)]
    d_skip = d_ref[...]

    def body(t, h):
        b = [bt_ref[n, t] for n in range(n_state)]
        c = [ct_ref[n, t] for n in range(n_state)]
        h, s = _time_step(h, a, d_skip, dt_ref[t], x_ref[t], b, c)
        s_ref[t] = s
        return h

    h = jax.lax.fori_loop(0, chunk, body,
                          [h_ref[n] for n in range(n_state)])
    for n in range(n_state):
        h_ref[n] = h[n]


def ssm_scan_pallas(dt, x, Bm, Cm, A, D):
    B, S, d = dt.shape
    N = A.shape[0]
    sub, lanes = state_tile(d)
    tile = min(lanes, LANES)
    chunk = min(SCAN_CHUNK, S)
    if S % chunk:
        raise ValueError(f"ssm_scan: {S} rows are no whole chunks of "
                         f"{chunk}")

    def rows(t):
        return t.reshape(B, S, sub, lanes)

    seq = pl.BlockSpec((None, chunk, sub, tile),
                       lambda b, c, j: (b, j, 0, c))
    coef = pl.BlockSpec((None, N, chunk), lambda b, c, j: (b, 0, j),
                        memory_space=pltpu.SMEM)
    with scopes.scope("ds.ssm_scan"):
        s, h = pl.pallas_call(
            functools.partial(_scan_kernel, chunk=chunk, n_state=N),
            out_shape=[jax.ShapeDtypeStruct((B, S, sub, lanes), jnp.float32),
                       jax.ShapeDtypeStruct((B, N, sub, lanes),
                                            jnp.float32)],
            grid=(B, lanes // tile, S // chunk),
            in_specs=[coef, coef, seq, seq,
                      pl.BlockSpec((N, sub, tile), lambda b, c, j: (0, 0, c)),
                      pl.BlockSpec((sub, tile), lambda b, c, j: (0, c))],
            out_specs=[seq,
                       pl.BlockSpec((None, N, sub, tile),
                                    lambda b, c, j: (b, 0, 0, c))],
            compiler_params=CompilerParams(dimension_semantics=(
                "parallel", "parallel", "arbitrary")),
            interpret=_interpret(), name="ds.ssm_scan",
        )(jnp.swapaxes(Bm, 1, 2), jnp.swapaxes(Cm, 1, 2), rows(dt), rows(x),
          A.reshape(N, sub, lanes), D.reshape(sub, lanes))
    return s.reshape(B, S, d), h


def ssm_scan_xla(dt, x, Bm, Cm, A, D):
    B, S, d = dt.shape
    N = A.shape[0]

    def step(h, t):
        dt_t, x_t, b_t, c_t = t
        h = jnp.exp(dt_t[:, None, :] * A) * h + \
            (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.sum(h * c_t[:, :, None], axis=1) + D * x_t

    h, s = jax.lax.scan(step, jnp.zeros((B, N, d), jnp.float32),
                        tuple(jnp.swapaxes(t, 0, 1)
                              for t in (dt, x, Bm, Cm)))
    return jnp.swapaxes(s, 0, 1), h.reshape(B, N, *state_tile(d))


def ssm_scan(dt, x, Bm, Cm, A, D, backend=None):
    """The scan of `S` steps from a zero state. `dt`, `x` [B, S, d], `Bm`,
    `Cm` [B, S, N], `A` [N, d], `D` [d], all float32; a padding row has
    `dt` = 0. Returns (s [B, S, d], the state after the last step
    [B, N, sub, lanes] as `state_tile` lays the channels out).

    backend: None = the kernel on a TPU where the channels are whole
    registers, XLA otherwise; "pallas" / "xla" force one."""
    if backend is None:
        backend = _auto_backend("ssm_scan", dt.shape[-1])
    _LAST_BACKEND["scan"] = backend
    args = [t.astype(jnp.float32) for t in (dt, x, Bm, Cm, A, D)]
    if backend == "xla":
        return ssm_scan_xla(*args)
    if backend != "pallas":
        raise ValueError(f"unknown ssm_scan backend {backend!r}")
    return ssm_scan_pallas(*args)


# ---------------------------------------------------------------------------
# decode: one token, the state in place
# ---------------------------------------------------------------------------

def _step_kernel(slot_ref, lyr_ref, bt_ref, ct_ref, dt_ref, x_ref, a_ref,
                 d_ref, tail_ref, h_ref, conv_ref, s_ref, out_ref,
                 conv_out_ref, *, n_state, tile):
    del slot_ref, lyr_ref, conv_ref         # the index maps read them
    conv_out_ref[...] = tail_ref[...]
    row = pl.program_id(0)
    b = [bt_ref[n, row] for n in range(n_state)]
    c = [ct_ref[n, row] for n in range(n_state)]
    for lo in range(0, dt_ref.shape[-1], tile):
        at = (slice(None), pl.ds(lo, tile))
        h, s = _time_step(
            [h_ref[(n, *at)] for n in range(n_state)],
            [a_ref[(n, *at)] for n in range(n_state)],
            d_ref[at], dt_ref[at], x_ref[at], b, c)
        s_ref[at] = s
        for n in range(n_state):
            out_ref[(n, *at)] = h[n]


def ssm_step_pallas(pools, tail, slots, layer, dt, x, Bm, Cm, A, D):
    conv, state = pools
    B, d = dt.shape
    N, sub, lanes = state.shape[2:]
    taps = conv.shape[2]
    tile = min(lanes, LANES)

    def rows(t):
        return t.reshape(B, sub, lanes)

    row = pl.BlockSpec((None, sub, lanes), lambda b, sl, lyr: (b, 0, 0))
    def pool(*tail):
        return pl.BlockSpec((None, None, *tail),
                            lambda b, sl, lyr: (lyr[0], sl[b], 0, 0, 0))

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    with scopes.scope("ds.ssm_step"):
        s, state, conv = pl.pallas_call(
            functools.partial(_step_kernel, n_state=N, tile=tile),
            out_shape=[jax.ShapeDtypeStruct((B, sub, lanes), jnp.float32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype),
                       jax.ShapeDtypeStruct(conv.shape, conv.dtype)],
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B,),
                in_specs=[smem, smem, row, row,
                          pl.BlockSpec((N, sub, lanes),
                                       lambda b, sl, lyr: (0, 0, 0)),
                          pl.BlockSpec((sub, lanes),
                                       lambda b, sl, lyr: (0, 0)),
                          pl.BlockSpec((None, taps, sub, lanes),
                                       lambda b, sl, lyr: (b, 0, 0, 0)),
                          pool(N, sub, lanes), pool(taps, sub, lanes)],
                out_specs=[row, pool(N, sub, lanes),
                           pool(taps, sub, lanes)],
            ),
            # the alias indices count the two scalar-prefetch operands
            input_output_aliases={9: 1, 10: 2},
            compiler_params=CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=_interpret(), name="ds.ssm_step",
        )(slots.astype(jnp.int32), _layer_operand(layer), Bm.T, Cm.T,
          rows(dt), rows(x), A.reshape(N, sub, lanes), D.reshape(sub, lanes),
          tail.reshape(B, taps, sub, lanes).astype(conv.dtype), state, conv)
    return s.reshape(B, d), (conv, state)


def ssm_step_xla(pools, tail, slots, layer, dt, x, Bm, Cm, A, D):
    conv, state = pools
    B, d = dt.shape
    N = A.shape[0]
    h = state[layer, slots].reshape(B, N, d)
    h = jnp.exp(dt[:, None, :] * A) * h + \
        (dt * x)[:, None, :] * Bm[:, :, None]
    s = jnp.sum(h * Cm[:, :, None], axis=1) + D * x
    return s, (
        conv.at[layer, slots].set(
            tail.reshape(B, *conv.shape[2:]).astype(conv.dtype)),
        state.at[layer, slots].set(
            h.reshape(B, *state.shape[2:]).astype(state.dtype)))


def ssm_step(pools, tail, slots, layer, dt, x, Bm, Cm, A, D, backend=None):
    """One token a row. `pools` are the stacked (convolution rows
    [layers, slots, K - 1, sub, lanes], scan state [layers, slots, N, sub,
    lanes] float32): row b's state `[layer, slots[b]]` takes one step and
    goes back, and its new convolution rows `tail[b]` [K - 1, d] take its
    slot's place, both in place under jit with the pools donated. `dt`,
    `x` [B, d], `Bm`, `Cm` [B, N], `A` [N, d], `D` [d], float32; an
    inactive row has `dt` = 0, brings the rows its slot held and names
    the trash slot 0. Returns (s [B, d], the pools). Live rows hold
    distinct slots, so no two write one state.

    backend: as `ssm_scan`."""
    if backend is None:
        backend = _auto_backend("ssm_step", dt.shape[-1])
    _LAST_BACKEND["step"] = backend
    args = [t.astype(jnp.float32) for t in (dt, x, Bm, Cm, A, D)]
    if backend == "xla":
        return ssm_step_xla(pools, tail, slots, layer, *args)
    if backend != "pallas":
        raise ValueError(f"unknown ssm_step backend {backend!r}")
    return ssm_step_pallas(pools, tail, slots, layer, *args)
