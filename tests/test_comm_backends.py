"""Compressed-comm backend API parity tests (reference:
`tests/onebit/test_nccl_backend.py`, `deepspeed/runtime/comm/nccl.py:47`,
`runtime/compression/cupy.py`)."""

import numpy as np
import pytest

from deeperspeed_tpu.runtime.comm import NcclBackend, MpiBackend
from deeperspeed_tpu.runtime.compression import CupyBackend


def test_cupy_backend_pack_roundtrip():
    be = CupyBackend()
    x = np.random.default_rng(0).normal(size=100).astype(np.float32)
    chunks = be.compress_by_chunk(x, 4)
    assert len(chunks) == 4
    signs = be.decompress(chunks, x.size)
    np.testing.assert_array_equal(signs, np.where(x >= 0, 1.0, -1.0))


@pytest.mark.parametrize("backend_cls", [NcclBackend, MpiBackend])
def test_compressed_allreduce_error_feedback(backend_cls):
    """Accumulated error compensation keeps the compressed allreduce
    unbiased: averaging the compressed results over many steps of the
    same input converges to the true mean (the 1-bit Adam premise)."""
    rng = np.random.default_rng(1)
    world = 4
    n = 256
    xs = [rng.normal(size=n).astype(np.float32) for _ in range(world)]
    true_mean = sum(xs) / world

    be = backend_cls()
    worker_err = [np.zeros(n, np.float32) for _ in range(world)]
    server_err = np.zeros(n, np.float32)
    acc = np.zeros(n, np.float64)
    steps = 50
    for _ in range(steps):
        outs, worker_err, server_err = be.compressed_allreduce(
            xs, worker_err, server_err)
        acc += np.asarray(outs[0], np.float64)

    # Exact error-feedback invariant: sum_t out_t = T·mean − (w̄err_T +
    # serr_T); the residual errors are all that separates the applied
    # cumulative update from the true one. server_err comes back as
    # per-rank server CHUNKS (the reference's rank-local phase-2 buffers).
    werr_mean = sum(np.asarray(e, np.float64) for e in worker_err) / world
    serr_flat = np.concatenate([np.asarray(e, np.float64)
                                for e in server_err])
    recovered = (acc + werr_mean + serr_flat) / steps
    np.testing.assert_allclose(recovered, true_mean, atol=1e-4)

    # and the residuals stay bounded (error feedback self-stabilizes:
    # the quantization scale grows with the compensated buffer, so the
    # error plateaus at a few × the input norm instead of diverging)
    assert np.linalg.norm(werr_mean) < 10 * np.linalg.norm(xs[0])


def test_compressed_allreduce_ragged_length():
    """Buffer length not divisible by world: zero-padded internally, no
    element silently dropped."""
    rng = np.random.default_rng(3)
    world, n = 3, 10
    xs = [rng.normal(size=n).astype(np.float32) for _ in range(world)]
    be = NcclBackend()
    worker_err = [np.zeros(n, np.float32) for _ in range(world)]
    server_err = np.zeros(n, np.float32)
    outs, werr, serr = be.compressed_allreduce(xs, worker_err, server_err)
    assert all(np.asarray(o).shape == (n,) for o in outs)
    assert all(np.asarray(e).shape == (n,) for e in werr)
    # feeding the returned server chunks back works
    outs2, werr2, serr2 = be.compressed_allreduce(xs, werr, serr)
    assert np.asarray(outs2[0]).shape == (n,)


def test_compressed_allreduce_single_buffer():
    be = NcclBackend()
    x = np.ones(32, np.float32)
    out, werr, serr = be.compressed_allreduce(
        x, np.zeros(32, np.float32), np.zeros(32, np.float32))
    # all-positive constant input is exactly representable: sign=+1,
    # scale=1 → lossless, zero residual error
    np.testing.assert_allclose(np.asarray(out), x, atol=1e-6)
    np.testing.assert_allclose(np.asarray(werr), 0.0, atol=1e-6)


def test_op_builder_surface():
    from deeperspeed_tpu.ops.op_builder import (ALL_OPS, UtilsBuilder,
                                                CPUAdamBuilder,
                                                AsyncIOBuilder)
    assert set(ALL_OPS) == {"fused_adam", "fused_lamb", "cpu_adam",
                            "transformer", "stochastic_transformer",
                            "sparse_attn", "async_io", "utils"}
    util = UtilsBuilder().load()
    ts = [np.ones((2, 3), np.float32), np.arange(4, dtype=np.float32)]
    flat = util.flatten(ts)
    assert flat.shape == (10,)
    back = util.unflatten(flat, ts)
    assert back[0].shape == (2, 3) and back[1].shape == (4,)
    np.testing.assert_allclose(np.asarray(back[1]), ts[1])
    # native builders report sources the way the reference does
    assert CPUAdamBuilder().sources() == ["csrc/adam/cpu_adam.cpp"]
    assert AsyncIOBuilder().sources() == ["csrc/aio/aio_engine.cpp"]
