"""Grouped expert matmul kernel tests (interpret mode — the fast lane's
CPU stand-in for the Mosaic lowering; see tests/test_flash_attention.py
for the same strategy). Parity oracle is the XLA segment-einsum
fallback, itself checked against a per-group loop."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeperspeed_tpu.ops.autotune import (GMM_BLOCK_CANDIDATES,
                                          gmm_vmem_bytes,
                                          grouped_matmul_blocks)
from deeperspeed_tpu.ops.pallas.grouped_matmul import (
    _fit_cols, _fit_rows, grouped_matmul, grouped_matmul_supported,
    grouped_matmul_xla, ragged_block_m, ragged_buffer_rows, ragged_matmul,
    ragged_tile_maps)


def _case(G=4, span=8, K=16, N=12, W=None, sizes=(8, 0, 5, 3), seed=0,
          dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    W = W or G
    x = jnp.asarray(rng.normal(size=(G * span, K)), dtype)
    w = jnp.asarray(rng.normal(size=(W, K, N)), dtype)
    return x, w, jnp.asarray(sizes, jnp.int32)


def _loop_reference(x, w, sizes, span, lut=None):
    """Independent oracle: per-span python loop."""
    G = x.shape[0] // span
    lut = list(range(w.shape[0])) if lut is None else list(lut)
    outs = []
    for g in range(G):
        xg = np.asarray(x[g * span:(g + 1) * span], np.float32)
        yg = xg @ np.asarray(w[lut[g]], np.float32)
        yg[int(sizes[g]):] = 0.0
        outs.append(yg)
    return np.concatenate(outs, axis=0)


# --- forward --------------------------------------------------------------

def test_xla_fallback_matches_loop_reference():
    x, w, sizes = _case()
    ref = _loop_reference(x, w, sizes, span=8)
    got = grouped_matmul_xla(x, w, sizes, span=8)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-5, atol=1e-5)


def test_kernel_matches_fallback_ragged_sizes():
    """Ragged group sizes including an EMPTY expert (size 0) and a FULL
    span (size == span)."""
    x, w, sizes = _case(sizes=(8, 0, 5, 3))
    ref = grouped_matmul_xla(x, w, sizes, span=8)
    got = grouped_matmul(x, w, sizes, span=8, backend="pallas",
                         block_m=4, block_n=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_kernel_masks_tail_rows_to_exact_zero():
    x, w, sizes = _case(sizes=(2, 0, 8, 1))
    got = np.asarray(grouped_matmul(x, w, sizes, span=8, backend="pallas",
                                    block_m=4, block_n=4))
    for g, s in enumerate([2, 0, 8, 1]):
        assert np.all(got[g * 8 + s:(g + 1) * 8] == 0.0), f"group {g}"
        if s:
            assert np.abs(got[g * 8:g * 8 + s]).max() > 0


def test_kernel_lut_many_spans_per_weight():
    """The expert-parallel layout: several contiguous spans share one
    weight row (ep·g source spans per local expert)."""
    x, w, sizes = _case(W=2, sizes=(8, 3, 0, 6))
    lut = (0, 0, 1, 1)
    ref = _loop_reference(x, w, sizes, span=8, lut=lut)
    got = grouped_matmul(x, w, sizes, span=8, lut=lut, backend="pallas",
                         block_m=4, block_n=4)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-5, atol=1e-5)
    xla = grouped_matmul_xla(x, w, sizes, span=8, lut=lut)
    np.testing.assert_allclose(np.asarray(xla), ref, rtol=1e-5, atol=1e-5)


def test_kernel_under_jit_and_bf16():
    x, w, sizes = _case(dtype=jnp.bfloat16)
    f = jax.jit(lambda x, w: grouped_matmul(
        x, w, sizes, span=8, backend="pallas", block_m=4, block_n=4))
    got = f(x, w)
    assert got.dtype == jnp.bfloat16
    ref = grouped_matmul_xla(x, w, sizes, span=8)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


# --- backward -------------------------------------------------------------

@pytest.mark.parametrize("sizes", [(8, 0, 5, 3), (8, 8, 8, 8),
                                   (0, 0, 0, 0)])
def test_kernel_grads_match_fallback(sizes):
    x, w, sz = _case(sizes=sizes)

    def loss(fn):
        return lambda x, w: jnp.sum(jnp.sin(fn(x, w)))

    pall = loss(lambda x, w: grouped_matmul(
        x, w, sz, span=8, backend="pallas", block_m=4, block_n=4))
    xla = loss(lambda x, w: grouped_matmul_xla(x, w, sz, span=8))
    gp = jax.grad(pall, argnums=(0, 1))(x, w)
    gx = jax.grad(xla, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gp[0]), np.asarray(gx[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gp[1]), np.asarray(gx[1]),
                               rtol=1e-5, atol=1e-5)


def test_kernel_grads_with_lut():
    x, w, sz = _case(W=2, sizes=(8, 3, 0, 6))
    lut = (0, 0, 1, 1)
    gp = jax.grad(lambda x, w: jnp.sum(jnp.cos(grouped_matmul(
        x, w, sz, span=8, lut=lut, backend="pallas", block_m=4,
        block_n=4))), argnums=(0, 1))(x, w)
    gx = jax.grad(lambda x, w: jnp.sum(jnp.cos(grouped_matmul_xla(
        x, w, sz, span=8, lut=lut))), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gp[0]), np.asarray(gx[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gp[1]), np.asarray(gx[1]),
                               rtol=1e-5, atol=1e-5)


def test_tail_rows_get_zero_dx():
    """Cotangents flowing into masked tail rows must not leak into dx."""
    x, w, sz = _case(sizes=(3, 0, 8, 1))
    dx = jax.grad(lambda x: jnp.sum(grouped_matmul(
        x, w, sz, span=8, backend="pallas", block_m=4, block_n=4)))(x)
    dx = np.asarray(dx)
    for g, s in enumerate([3, 0, 8, 1]):
        assert np.all(dx[g * 8 + s:(g + 1) * 8] == 0.0)


def test_gap_lut_gives_the_unvisited_weight_a_zero_gradient():
    """The rule was "the lut covers every weight" (the dw kernel wrote
    only the blocks it visited). Now a weight no span points at is
    legal, forward and backward: its gradient is selected to zero."""
    x, w, sz = _case(W=3)
    lut = (0, 0, 2, 2)
    y = grouped_matmul(x, w, sz, span=8, lut=lut, backend="pallas")
    np.testing.assert_allclose(np.asarray(y),
                               _loop_reference(x, w, sz, 8, lut),
                               rtol=1e-5, atol=1e-5)
    dw = jax.grad(lambda w: grouped_matmul(
        x, w, sz, span=8, lut=lut, backend="pallas").sum())(w)
    dw_xla = jax.grad(lambda w: grouped_matmul(
        x, w, sz, span=8, lut=lut, backend="xla").sum())(w)
    assert not np.asarray(dw[1]).any()
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_xla),
                               rtol=1e-5, atol=1e-5)


# --- the ragged layout (groups of any length in one buffer) ---------------

# per-expert row counts: empty groups (first, middle, last), a group of
# one row, groups that are not a multiple of the row tile, whole tiles
RAGGED_COUNTS = {
    "empty_one_and_odd": (0, 1, 13, 0, 8, 21, 0),
    "all_in_one_expert": (0, 0, 37, 0),
    "one_row_each": (1, 1, 1, 1, 1),
    "whole_tiles": (8, 16, 0, 8),
    "nothing_routed": (0, 0, 0),
}


def _ragged_case(counts, K=16, N=24, block_m=8, seed=0,
                 dtype=jnp.float32):
    """Rows sorted by expert, each group at its tile-aligned start, in a
    buffer sized for ANY split of as many rows (so tiles are left over)."""
    rng = np.random.default_rng(seed)
    E, total = len(counts), int(sum(counts))
    R = ragged_buffer_rows(max(total, 1), E, block_m)
    te, tr, starts = ragged_tile_maps(jnp.asarray(counts, jnp.int32),
                                      block_m, R // block_m)
    x = np.zeros((R, K), np.float32)
    row_expert = np.full((R,), -1)
    for e, (c, s0) in enumerate(zip(counts, np.asarray(starts))):
        x[s0:s0 + c] = rng.normal(size=(c, K))
        row_expert[s0:s0 + c] = e
    w = rng.normal(size=(E, K, N)).astype(np.float32)
    return jnp.asarray(x, dtype), jnp.asarray(w, dtype), te, tr, row_expert


def _ragged_loop(x, w, row_expert):
    """Independent oracle: one plain matmul a row's expert."""
    out = np.zeros((x.shape[0], w.shape[2]), np.float32)
    for e in range(w.shape[0]):
        rows = row_expert == e
        out[rows] = np.asarray(x, np.float32)[rows] @ \
            np.asarray(w[e], np.float32)
    return out


@pytest.mark.parametrize("backend", ["pallas", "xla"])
@pytest.mark.parametrize("counts", RAGGED_COUNTS.values(),
                         ids=RAGGED_COUNTS.keys())
def test_ragged_forward_matches_plain_matmuls(counts, backend):
    x, w, te, tr, row_expert = _ragged_case(counts)
    y = ragged_matmul(x, w, te, tr, 8, backend=backend)
    # float32 both sides, K = 16: rounding only
    np.testing.assert_allclose(np.asarray(y), _ragged_loop(x, w, row_expert),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(y)[row_expert < 0].any()   # padding: exact zero


@pytest.mark.parametrize("backend", ["pallas", "xla"])
@pytest.mark.parametrize("counts", RAGGED_COUNTS.values(),
                         ids=RAGGED_COUNTS.keys())
def test_ragged_gradients_match_plain_matmuls(counts, backend):
    """dx and dw against the gradient of a loop of plain matmuls; an
    expert without a row gets a zero dw, not an unvisited block."""
    x, w, te, tr, row_expert = _ragged_case(counts, seed=1)
    cot = jnp.asarray(np.random.default_rng(2).normal(
        size=(x.shape[0], w.shape[2])), jnp.float32)

    def ours(x, w):
        return jnp.sum(ragged_matmul(x, w, te, tr, 8, backend=backend) * cot)

    def plain(x, w):
        live = jnp.asarray(row_expert >= 0)[:, None]
        per_row = w[jnp.asarray(np.maximum(row_expert, 0))]    # [R, K, N]
        y = jnp.einsum("rk,rkn->rn", x, per_row)
        return jnp.sum(jnp.where(live, y, 0.0) * cot)

    dx, dw = jax.grad(ours, argnums=(0, 1))(x, w)
    dx_ref, dw_ref = jax.grad(plain, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref),
                               rtol=1e-4, atol=1e-4)
    for e, c in enumerate(counts):
        if c == 0:
            assert not np.asarray(dw[e]).any()
    assert not np.asarray(dx)[row_expert < 0].any()


def test_ragged_kernel_under_jit_in_bf16_with_traced_maps():
    """The maps are traced: one compiled program serves every split."""
    block_m, E = 16, 6
    rng = np.random.default_rng(3)
    R = ragged_buffer_rows(64, E, block_m)

    @jax.jit
    def run(x, w, counts):
        te, tr, _ = ragged_tile_maps(counts, block_m, R // block_m)
        return ragged_matmul(x, w, te, tr, block_m, backend="pallas")

    for counts in ((64, 0, 0, 0, 0, 0), (3, 17, 0, 20, 1, 23)):
        x, w, _, _, row_expert = _ragged_case(counts, K=32, N=16,
                                              block_m=block_m, seed=4)
        assert x.shape[0] == R
        y = run(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                jnp.asarray(counts, jnp.int32))
        ref = _ragged_loop(np.asarray(x.astype(jnp.bfloat16), np.float32),
                           np.asarray(w.astype(jnp.bfloat16), np.float32),
                           row_expert)
        # bf16 result of a K = 32 sum of O(1) terms: 2^-8 relative
        np.testing.assert_allclose(np.asarray(y, np.float32), ref,
                                   rtol=2e-2, atol=5e-2)
    assert run._cache_size() == 1
    del rng


def test_ragged_geometry_and_refusals():
    # the tile follows the mean group: 256 rows over 64 experts are 4 a
    # group (the bf16 tile's 16 rows), 8192 over 64 are 128 (the MXU's)
    assert ragged_block_m(256, 64) == 16
    assert ragged_block_m(2048, 64) == 32
    assert ragged_block_m(8192, 64) == 128
    assert ragged_block_m(12288, 64) == 128
    # at most one tile of padding a group, whatever the split
    for rows, E, bm in ((256, 64, 16), (8192, 64, 128), (5, 3, 8)):
        R = ragged_buffer_rows(rows, E, bm)
        assert R % bm == 0 and rows + E * (bm - 1) <= R < \
            rows + E * (bm - 1) + bm
    x, w, te, tr, _ = _ragged_case((3, 5))
    with pytest.raises(ValueError, match="block_m"):
        ragged_matmul(x, w, te, tr, 7)
    with pytest.raises(ValueError, match="one entry a row tile"):
        ragged_matmul(x, w, te[:-1], tr[:-1], 8)
    with pytest.raises(ValueError, match="contraction"):
        ragged_matmul(x, w[:, :4], te, tr, 8)
    with pytest.raises(ValueError, match="backend"):
        ragged_matmul(x, w, te, tr, 8, backend="cuda")


# --- validation / geometry ------------------------------------------------

def test_invalid_args_raise():
    x, w, sz = _case()
    with pytest.raises(ValueError, match="span"):
        grouped_matmul(x, w, sz, span=7)
    with pytest.raises(ValueError, match="lut"):
        grouped_matmul(x, w, sz, span=8, lut=(1, 0, 2, 3))  # decreasing
    with pytest.raises(ValueError, match="lut"):
        grouped_matmul(x, w, sz, span=8, lut=(0, 1))        # wrong length
    with pytest.raises(ValueError, match="lut"):
        grouped_matmul(x, w, sz, span=8, lut=(0, 1, 2, 4))  # no weight 4
    with pytest.raises(ValueError, match="group_sizes"):
        grouped_matmul(x, w, sz[:2], span=8)
    with pytest.raises(ValueError, match="contraction"):
        grouped_matmul(x, w[:, :4], sz, span=8)
    with pytest.raises(ValueError, match="backend"):
        grouped_matmul(x, w, sz, span=8, backend="cuda")


def test_fit_helpers():
    assert _fit_rows(256, 512) == 256
    assert _fit_rows(256, 320) == 160
    assert _fit_rows(256, 8) == 8
    assert _fit_cols(512, 768) == 384
    assert _fit_cols(256, 768) == 256
    assert _fit_cols(512, 3072) == 512
    # no 128-aligned divisor → whole dim (interpret-mode shapes)
    assert _fit_cols(256, 12) == 12


def test_supported_gate():
    # interpret mode (CPU test run) always supports; the TPU constraints
    # are still checkable through the helper's math
    assert grouped_matmul_supported(768, 3072, 256)


def test_autotune_static_screen():
    """The pick is deterministic, VMEM-screened, and fattest-first."""
    bm, bn = grouped_matmul_blocks(768, 3072, jnp.bfloat16)
    assert (bm, bn) in GMM_BLOCK_CANDIDATES
    assert gmm_vmem_bytes(bm, bn, 768, 2) <= (10 << 20)
    # a huge contraction dim must push the pick off the fattest blocks;
    # when NOTHING fits the model, the helper degrades to the narrowest
    # candidate rather than refusing
    bm2, bn2 = grouped_matmul_blocks(16384, 3072, jnp.float32)
    assert (bm2, bn2) == GMM_BLOCK_CANDIDATES[-1]
