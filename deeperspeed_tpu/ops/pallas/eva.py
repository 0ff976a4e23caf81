"""EVA attention's pooling (arXiv:2302.04542, as a byte model uses it): ONE
key and ONE value stand for a chunk of `C` consecutive positions once the
window that holds the chunk has ended.

    a_i = s * (k_i . phi_h)            the chunk's C rows, k after rotary
    p   = softmax_i(a)                 over those C rows, float32
    K~  = sum_i p_i k_i + mu_h         V~ = sum_i p_i v_i

per head h, with the attention's own scale s. Everything is accumulated
in float32 and rounded once, to the type the rows are kept in.

`eva_pool` is the arithmetic on rows in hand (a prefill pools every whole
chunk of a prompt at once; a memory-bound pass XLA fuses). `eva_summarize`
is a decode step's: for every batch row whose token CLOSES a chunk it
reads the chunk's rows from the page they were written to, pools them and
writes the pooled row into another page of the same stacked pools, in
place: a kernel that aliases the pools as `paged_kv_write` does, leaves
them in HBM and moves the four tiles of a closing row itself, and nothing
for any other row (a chunk closes one step in `C`). Off a TPU the same
arithmetic runs as XLA's gather and scatter, where the other rows write
the trash page 0.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import scopes
from .decode_attention import (CompilerParams, _auto_backend, _interpret,
                               _layer_operand, _write_group,
                               paged_kv_write_xla)


def eva_pool(k, v, phi, mu, sm_scale):
    """k, v [..., C, H, D] (a chunk's rows) -> (K~, V~) [..., H, D] in
    float32; `phi`, `mu` [H, D]."""
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    a = sm_scale * jnp.einsum("...chd,hd->...ch", k32,
                              phi.astype(jnp.float32),
                              precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.softmax(a, axis=-2)[..., None]
    return (jnp.sum(p * k32, axis=-3) + mu.astype(jnp.float32),
            jnp.sum(p * v32, axis=-3))


def _summarize_kernel(lyr_ref, src_page_ref, src_slot_ref, dst_page_ref,
                      dst_slot_ref, closing_ref, phi_ref, mu_ref, k_in, v_in,
                      k_pool, v_pool, kc, vc, kd, vd, sem, *, sm_scale,
                      chunk, group):
    """One batch row, and nothing at all unless its token closes a chunk.
    The pools stay in HBM (the outputs ARE the inputs, aliased) and the
    kernel moves four tiles itself: the [H, group, D] sublane groups that
    hold the chunk's rows (K and V, of the window's page) and the pooled
    row's slot (K and V, of the pending page) come in, the chunk's `chunk`
    rows ending at the token's slot are pooled in float32, the pooled rows
    are selected into their slot and the two destination tiles go back.
    Every reduction keeps its dims, so the rows stay on the sublanes and
    D on the lanes."""
    del k_in, v_in
    b = pl.program_id(0)

    @pl.when(closing_ref[b] > 0)
    def _():
        lyr = lyr_ref[0]
        src = pl.multiple_of(src_slot_ref[b] // group * group, group)
        dst = pl.multiple_of(dst_slot_ref[b] // group * group, group)

        def tile(pool, page, first):
            return pool.at[lyr, page, :, pl.ds(first, group), :]

        src_page, dst_page = src_page_ref[b], dst_page_ref[b]
        loads = [pltpu.make_async_copy(tile(k_pool, src_page, src), kc,
                                       sem.at[0]),
                 pltpu.make_async_copy(tile(v_pool, src_page, src), vc,
                                       sem.at[1]),
                 pltpu.make_async_copy(tile(k_pool, dst_page, dst), kd,
                                       sem.at[2]),
                 pltpu.make_async_copy(tile(v_pool, dst_page, dst), vd,
                                       sem.at[3])]
        for copy in loads:
            copy.start()
        for copy in loads:
            copy.wait()
        k32, v32 = kc[...].astype(jnp.float32), vc[...].astype(jnp.float32)
        rows = jax.lax.broadcasted_iota(jnp.int32, (*k32.shape[:2], 1), 1)
        last = src_slot_ref[b] % group
        chosen = (rows > last - chunk) & (rows <= last)
        phi = phi_ref[...].astype(jnp.float32)[:, None, :]
        a = jnp.sum(k32 * phi, axis=-1, keepdims=True) * sm_scale
        a = jnp.where(chosen, a, -1e30)                     # [H, group, 1]
        p = jnp.where(chosen, jnp.exp(a - jnp.max(a, axis=1, keepdims=True)),
                      0.0)
        p = p / jnp.sum(p, axis=1, keepdims=True)
        pooled_k = jnp.sum(p * k32, axis=1, keepdims=True) + \
            mu_ref[...].astype(jnp.float32)[:, None, :]     # [H, 1, D]
        pooled_v = jnp.sum(p * v32, axis=1, keepdims=True)
        slots = jax.lax.broadcasted_iota(jnp.int32, kd.shape, 1)
        here = slots == dst_slot_ref[b] % group
        kd[...] = jnp.where(here, pooled_k.astype(kd.dtype), kd[...])
        vd[...] = jnp.where(here, pooled_v.astype(vd.dtype), vd[...])
        stores = [pltpu.make_async_copy(kd, tile(k_pool, dst_page, dst),
                                        sem.at[0]),
                  pltpu.make_async_copy(vd, tile(v_pool, dst_page, dst),
                                        sem.at[1])]
        for copy in stores:
            copy.start()
        for copy in stores:
            copy.wait()


def _summarize_pallas(pools, phi, mu, layer, src_page, src_slot, closing,
                      dst_page, dst_slot, chunk, sm_scale):
    H, page_size, D = pools[0].shape[2:]
    g = _write_group(page_size, pools[0].dtype)
    B = src_page.shape[0]
    whole = pl.BlockSpec(memory_space=pl.ANY)
    heads = pl.BlockSpec((H, D), lambda b, *_: (0, 0))
    tile = pltpu.VMEM((H, g, D), pools[0].dtype)
    call = pl.pallas_call(
        functools.partial(_summarize_kernel, sm_scale=float(sm_scale),
                          chunk=int(chunk), group=g),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(B,),
            in_specs=[heads, heads, whole, whole],
            out_specs=[whole, whole],
            scratch_shapes=[tile, tile, tile, tile,
                            pltpu.SemaphoreType.DMA((4,))]),
        # the alias index counts the six scalar-prefetch operands
        input_output_aliases={8: 0, 9: 1},
        compiler_params=CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=_interpret(), name="ds.eva_summarize",
    )
    ints = [jnp.asarray(x, jnp.int32) for x in
            (src_page, src_slot, dst_page, dst_slot, closing)]
    return tuple(call(_layer_operand(layer), *ints, phi, mu, *pools))


@scopes.scoped("ds.eva_summarize")
def eva_summarize(pools, phi, mu, layer, src_page, src_slot, closing,
                  dst_page, dst_slot, chunk, sm_scale, backend=None):
    """A decode step's pooling, in place on layer `layer` of the stacked
    (K, V) `pools` [L, P, H, page_size, D].

    Batch row b wrote its token's row to slot `src_slot[b]` of page
    `src_page[b]`. Where `closing[b]` (the token is the last of its chunk:
    the chunk's `chunk` rows are the slots ending at `src_slot[b]`, which
    lie in that one page because `chunk` divides the page size) the rows
    are read back, pooled (`eva_pool`) and written to slot `dst_slot[b]` of
    page `dst_page[b]`; any other row moves nothing (the XLA form: it
    writes the trash page 0, whatever it read). `phi`, `mu` [H, D]: the
    layer's. Returns the pools."""
    pools = tuple(pools)
    k_pool, v_pool = pools
    H, page_size, D = k_pool.shape[2:]
    group = _write_group(page_size, k_pool.dtype)
    if page_size % chunk or (group % chunk and backend != "xla"):
        raise ValueError(
            f"a chunk of {chunk} rows has to divide the page's {page_size} "
            f"slots and the {group} slots of a row's sublane group")
    layer = jnp.asarray(layer, jnp.int32).reshape(())
    if backend is None:
        backend = _auto_backend("paged_kv_write", D, page_size, False)
    if backend == "pallas":
        return _summarize_pallas(pools, phi, mu, layer, src_page, src_slot,
                                 closing, dst_page, dst_slot, chunk,
                                 sm_scale)
    if backend != "xla":
        raise ValueError(f"unknown eva summarize backend {backend!r}")
    first = (src_slot - src_slot % chunk).astype(jnp.int32)
    zero = jnp.zeros((), jnp.int32)

    def rows_of(pool):
        return jax.vmap(lambda page, slot: jax.lax.dynamic_slice(
            pool, (layer, page, zero, slot, zero),
            (1, 1, H, chunk, D))[0, 0])(src_page.astype(jnp.int32), first)

    kc, vc = rows_of(k_pool), rows_of(v_pool)           # [B, H, C, D]
    pooled = eva_pool(jnp.swapaxes(kc, 1, 2), jnp.swapaxes(vc, 1, 2), phi,
                      mu, sm_scale)
    return paged_kv_write_xla(pools, pooled, layer,
                              jnp.where(closing, dst_page, 0), dst_slot)
