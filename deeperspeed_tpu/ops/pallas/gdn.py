"""The gated delta rule of a Gated DeltaNet layer (arXiv:2412.06464): a
prompt's walk in chunks (`gdn_chunk`) and a decode step's one-token
update of the recurrent state in place (`gdn_step`).

A value head keeps a MATRIX state `S` [d_k, d_v], float32, zero at the
start. With `q_t`, `k_t` [d_k] (l2-normalised; q also scaled), `v_t`
[d_v], the decay `g_t <= 0` and the step `beta_t` in (0, 1):

    S'  = exp(g_t) S_{t-1}
    d_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t d_t^T
    o_t = S_t^T q_t

A row with `g` = 0 and `beta` = 0 moves nothing, exactly (`S` times 1,
plus 0): how a prefill bucket's padding and a decode batch's inactive
rows leave a state as it was. A key head serves `n_v / n_k` consecutive
value heads; everything here is float32 and every matmul runs at the
MXU's highest precision, since the state is carried over thousands of
steps.

**`gdn_chunk`** runs the recurrence a chunk of `CHUNK` = 64 rows at a
time, as matmuls. Inside a chunk, with `G_i` the running sum of `g` from
the chunk's first row and `S0` the state at its start, the steps `d_i`
solve a unit lower-triangular system (the WY form):

    A[i, j] = beta_i (k_i . k_j) exp(G_i - G_j)          (j < i)
    (I + A) D = beta V - (beta exp(G) K) S0
    o_i = exp(G_i) S0^T q_i + sum_{j <= i} exp(G_i - G_j) (q_i . k_j) d_j
    S_C = exp(G_C) S0 + sum_j exp(G_C - G_j) k_j d_j^T

`(I + A)^-1` is formed by products, never a row at a time: `A`'s blocks
of 16 on the diagonal are nilpotent of index 16, so `(I + D)^-1 = (I -
D)(I + D^2)(I + D^4)(I + D^8)`; with `M = (I + D)^-1 (A - D)`, nilpotent
of index 4 over the blocks, `(I + A)^-1 = (I - M)(I + M^2) (I + D)^-1`.
The alternating series over the whole 64 rows would cancel
catastrophically where neighbouring keys agree (a run of one token: the
terms reach 1e9 where the inverse is of order 1); over 16 rows they stay
under 50. The value heads of ONE key head share q and k, so their chunks
are solved together, rows stacked (2 x 64 = 128, the matrix unit's own
size) and `A` block-diagonal over the heads: 19 matmuls a pair of heads
where 36 of half the rows would run. The kernel is a grid over (rows,
groups of value heads, chunks): the chunk axis is the carried one and a
group's states live in the output block that stays resident across it.

**`gdn_step`** is a kernel over (rows, groups of value heads): a group's
states come in from the row's slot of the pool `[layers, slots, n_v, d_k,
d_v]`, take the step on the vector unit (a key is a column, broadcast
along the lanes; a sublane reduction gives `S^T k`) and go back where they
came from (the output aliases the pool), and the row's new convolution
rows go into its slot of their pool the same way, so a decode step moves
each live state once each way and never a pool.

There is no XLA form of either: off a TPU the kernels run in the
interpreter. What they are checked against is the recurrence above,
written out in `benchmarks/reference/qwen3_next.py` and in the tests.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import scopes
from ...compat import CompilerParams
from .decode_attention import _layer_operand
from .flash_attention import _interpret

# rows a grid step of `gdn_chunk` solves at once, and the diagonal blocks
# its inverse is built from
CHUNK = 64
SOLVE_BLOCK = 16
# value heads a grid step takes: of the chunk walk (each unrolls some 25
# matmuls) and of the decode step (8 states: 512 KiB a block each way)
CHUNK_HEADS = 4
STEP_HEADS = 8

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


# every matmul of the chunk walk: float32 operands at the matrix unit's
# highest precision (the module's note)
PRECISION = jax.lax.Precision.HIGHEST


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, precision=PRECISION,
                               preferred_element_type=jnp.float32)


def _heads_step(n_v, n_k, most):
    """Value heads a grid step takes: at most `most`, whole key heads."""
    rep = n_v // n_k
    step = min(most, n_v)
    if n_v % n_k or step % rep or n_v % step:
        raise ValueError(f"gdn: {n_v} value heads over {n_k} key heads in "
                         f"steps of {step}")
    return step, rep


# ---------------------------------------------------------------------------
# prefill: the walk over chunks
# ---------------------------------------------------------------------------

def _unit_lower_inverse(a, eye, row, col):
    """(I + a)^-1 of `a` [R, R], strictly lower triangular and
    block-diagonal over chunks of `CHUNK` rows, by products (the module's
    note): `eye` the identity, `row`, `col` the index grids."""
    diag = jnp.where(row // SOLVE_BLOCK == col // SOLVE_BLOCK, a, 0.0)
    t = eye - diag
    power = diag
    for _ in range(SOLVE_BLOCK.bit_length() - 2):       # D^2, D^4, D^8
        power = _dot(power, power)
        t = _dot(t, eye + power)
    m = _dot(t, a - diag)
    return _dot(_dot(eye - m, eye + _dot(m, m)), t)


def _chunk_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, s_ref, *,
                  heads, rep, d_k, d_v):
    """One chunk of `C` rows for `heads` value heads. The `rep` value
    heads of a key head share q and k, so they are solved TOGETHER: their
    rows stacked to `R = rep x C` (128 at 2 x 64: the matrix unit's own
    size), `A` and its inverse block-diagonal over the heads, one matmul
    where `rep` of half the rows would run."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    C = q_ref.shape[0]
    R = rep * C
    row = jax.lax.broadcasted_iota(jnp.int32, (R, R), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (R, R), 1)
    eye = (row == col).astype(jnp.float32)
    own = row // C == col // C                  # a head's own rows

    def stacked(ref, first):
        """Columns `first .. first + rep` of `ref` [C, heads], one under
        the other: [R, 1]."""
        return jnp.concatenate([ref[:, first + i:first + i + 1]
                                for i in range(rep)], axis=0)

    for kh in range(heads // rep):
        first = kh * rep
        q = q_ref[:, kh * d_k:(kh + 1) * d_k]
        k = k_ref[:, kh * d_k:(kh + 1) * d_k]
        q2, k2 = (jnp.concatenate([t] * rep, axis=0) for t in (q, k))
        v2 = jnp.concatenate(
            [v_ref[:, h * d_v:(h + 1) * d_v]
             for h in range(first, first + rep)], axis=0)
        G = stacked(g_ref, first)                   # [R, 1] running sums
        beta = stacked(beta_ref, first)
        # G as a row: the column times the identity, summed over rows
        G_row = jnp.sum(G * eye, axis=0, keepdims=True)
        # exp(G_i - G_j) for j <= i of the same head, else 0
        decay = jnp.where(own & (row >= col),
                          jnp.exp(jnp.minimum(G - G_row, 0.0)), 0.0)
        a = jnp.where(row > col, beta * _dot(k2, k2, _NT) * decay, 0.0)
        t = _unit_lower_inverse(a, eye, row, col)
        eG = jnp.exp(G)
        uw = _dot(t, jnp.concatenate([beta * v2, beta * eG * k2], axis=1))
        u, w = uw[:, :d_v], uw[:, d_v:]
        deltas, from_state, kept = [], [], []
        for i in range(rep):
            mine = slice(i * C, (i + 1) * C)
            s0 = s_ref[first + i]
            # [W; q] S0 in one product
            ws = _dot(jnp.concatenate([w[mine], q], axis=0), s0)
            deltas.append(u[mine] - ws[:C])
            from_state.append(ws[C:])
            kept.append(s0)
        delta = jnp.concatenate(deltas, axis=0)
        o = eG * jnp.concatenate(from_state, axis=0) + \
            _dot(_dot(q2, k2, _NT) * decay, delta)
        for i in range(rep):
            h = first + i
            mine = slice(i * C, (i + 1) * C)
            o_ref[:, h * d_v:(h + 1) * d_v] = o[mine]
            # the chunk's whole decay as a ROW (a [1, 1] has no broadcast
            # over both sublanes and lanes)
            G_h = G[mine]
            G_last = jnp.broadcast_to(G_h, (C, d_v))[C - 1:C]
            s_ref[h] = jnp.exp(G_last) * kept[i] + \
                _dot(jnp.exp(G_last[:, :1] - G_h) * k, deltas[i], _TN)


def gdn_chunk(q, k, v, g, beta):
    """The delta rule over `S` rows from a zero state. `q`, `k`
    [B, S, n_k, d_k] (normalised; q scaled), `v` [B, S, n_v, d_v], `g`,
    `beta` [B, S, n_v], float32; a padding row has `g` = `beta` = 0, and
    rows that fill the last chunk are added as such. Returns (o
    [B, S, n_v, d_v], the state after the last row [B, n_v, d_k, d_v])."""
    B, rows, n_k, d_k = q.shape
    n_v, d_v = v.shape[2:]
    # whole chunks; a row shorter than one is ONE chunk of whole blocks
    C = CHUNK if rows >= CHUNK else -(-rows // SOLVE_BLOCK) * SOLVE_BLOCK
    S = -(-rows // C) * C
    if S != rows:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, S - rows)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    heads, rep = _heads_step(n_v, n_k, CHUNK_HEADS)
    f32 = jnp.float32
    # the running sum of g inside each chunk; g and beta a group of heads
    # at a time: [B, groups, S, heads]
    G = jnp.cumsum(g.astype(f32).reshape(B, S // C, C, n_v), axis=2)

    def grouped(t):
        return jnp.moveaxis(t.reshape(B, S, n_v // heads, heads), 2, 1)

    def flat(t):
        return t.astype(f32).reshape(B, S, -1)

    def seq(width):
        return pl.BlockSpec((None, C, width), lambda b, j, c: (b, c, j))

    coef = pl.BlockSpec((None, None, C, heads), lambda b, j, c: (b, j, c, 0))
    with scopes.scope("ds.gdn_chunk"):
        o, state = pl.pallas_call(
            functools.partial(_chunk_kernel, heads=heads, rep=rep, d_k=d_k,
                              d_v=d_v),
            out_shape=[jax.ShapeDtypeStruct((B, S, n_v * d_v), f32),
                       jax.ShapeDtypeStruct((B, n_v, d_k, d_v), f32)],
            grid=(B, n_v // heads, S // C),
            in_specs=[seq(heads // rep * d_k), seq(heads // rep * d_k),
                      seq(heads * d_v), coef, coef],
            out_specs=[seq(heads * d_v),
                       pl.BlockSpec((None, heads, d_k, d_v),
                                    lambda b, j, c: (b, j, 0, 0))],
            compiler_params=CompilerParams(dimension_semantics=(
                "parallel", "parallel", "arbitrary")),
            interpret=_interpret(), name="ds.gdn_chunk",
        )(flat(q), flat(k), flat(v), grouped(G.reshape(B, S, n_v)),
          grouped(beta.astype(f32)))
    return o.reshape(B, S, n_v, d_v)[:, :rows], state


# ---------------------------------------------------------------------------
# decode: one token, the state in place
# ---------------------------------------------------------------------------

def _step_kernel(slot_ref, lyr_ref, eg_ref, beta_ref, q_ref, k_ref, v_ref,
                 tail_ref, s_ref, conv_ref, o_ref, s_out_ref, conv_out_ref,
                 *, heads, rep):
    del slot_ref, lyr_ref, conv_ref         # the index maps read them
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _rows():                    # the slot's block stays over the groups
        conv_out_ref[...] = tail_ref[...]

    for h in range(heads):
        head = j * heads + h
        k = k_ref[:, h // rep:h // rep + 1]             # [d_k, 1]
        q = q_ref[:, h // rep:h // rep + 1]
        s = s_ref[h] * eg_ref[b, head]                  # [d_k, d_v]
        delta = beta_ref[b, head] * (
            v_ref[h:h + 1, :] - jnp.sum(k * s, axis=0, keepdims=True))
        s = s + k * delta
        s_out_ref[h] = s
        o_ref[h:h + 1, :] = jnp.sum(q * s, axis=0, keepdims=True)


def gdn_step(pools, tail, slots, layer, q, k, v, g, beta):
    """One token a row. `pools` are the stacked (convolution rows [layers,
    slots, K - 1, sub, lanes], matrix states [layers, slots, n_v, d_k,
    d_v] float32): row b's states `[layer, slots[b]]` take one step and go
    back, and its new convolution rows `tail[b]` [K - 1, channels] take
    its slot's place, both in place under jit with the pools donated. `q`,
    `k` [B, n_k, d_k], `v` [B, n_v, d_v], `g`, `beta` [B, n_v], float32;
    an inactive row has `g` = `beta` = 0, brings the rows its slot held
    and names the trash slot 0. Returns (o [B, n_v, d_v], the pools). Live
    rows hold distinct slots, so no two write one state."""
    conv, pool = pools
    B, n_k, d_k = q.shape
    n_v, d_v = v.shape[1:]
    taps, sub, lanes = conv.shape[2:]
    heads, rep = _heads_step(n_v, n_k, STEP_HEADS)
    f32 = jnp.float32

    def columns(t):
        """A step's key heads as columns: [B, groups, d_k, heads / rep]."""
        return jnp.swapaxes(t.astype(f32).reshape(
            B, n_k * rep // heads, heads // rep, d_k), 2, 3)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    cols = pl.BlockSpec((None, None, d_k, heads // rep),
                        lambda b, j, sl, lyr: (b, j, 0, 0))
    rows = pl.BlockSpec((None, heads, d_v), lambda b, j, sl, lyr: (b, j, 0))
    states = pl.BlockSpec((None, None, heads, d_k, d_v),
                          lambda b, j, sl, lyr: (lyr[0], sl[b], j, 0, 0))
    held = pl.BlockSpec((None, None, taps, sub, lanes),
                        lambda b, j, sl, lyr: (lyr[0], sl[b], 0, 0, 0))
    with scopes.scope("ds.gdn_step"):
        o, pool, conv = pl.pallas_call(
            functools.partial(_step_kernel, heads=heads, rep=rep),
            out_shape=[jax.ShapeDtypeStruct((B, n_v, d_v), f32),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                       jax.ShapeDtypeStruct(conv.shape, conv.dtype)],
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B, n_v // heads),
                in_specs=[smem, smem, cols, cols, rows,
                          pl.BlockSpec((None, taps, sub, lanes),
                                       lambda b, j, sl, lyr: (b, 0, 0, 0)),
                          states, held],
                out_specs=[rows, states, held],
            ),
            # the alias indices count the two scalar-prefetch operands
            input_output_aliases={8: 1, 9: 2},
            compiler_params=CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=_interpret(), name="ds.gdn_step",
        )(slots.astype(jnp.int32), _layer_operand(layer),
          jnp.exp(g.astype(f32)), beta.astype(f32), columns(q), columns(k),
          v.astype(f32),
          tail.reshape(B, taps, sub, lanes).astype(conv.dtype), pool, conv)
    return o, (conv, pool)
