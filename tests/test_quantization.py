"""Low-precision hot-path tests (docs/quantization.md).

Covers: int8 weight-only quant matmul (Pallas-interpret vs XLA parity,
per-channel scale semantics, 3-D dispatch, autotune screen);
delayed-scaling fp8/int8 fake-quant matmuls (amax-history mechanics,
bootstrap, fp8 saturation, STE gradients, the grouped-operand variant);
int8 paged KV pools (quantize/dequant roundtrip, decode-attention kernel
vs fallback vs dense oracle, capacity accounting ≥1.9×); serving
integration (int8 weights + int8 KV end-to-end, backend token parity,
dtypes report, hot-swap restore); the error-feedback compressed
reduce-scatter (shard_map vs host oracle, EF-gather cotangent smuggling)
and the packed-vs-dense two-phase transports over ragged tails (the
satellite closing the packed transport's coverage gap); the
"quantization" config block + kv_cache_dtype validation; and engine
loss-curve parity + bit-exact checkpoint resume for the fp8 FFN and
compressed-gradient training paths.
"""

import copy

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import deeperspeed_tpu
from deeperspeed_tpu.compat import shard_map
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.ops.pallas import quant_matmul as qm
from deeperspeed_tpu.ops import dispatch_report
from deeperspeed_tpu.ops.pallas import decode_attention
from deeperspeed_tpu.ops.pallas.decode_attention import (
    paged_decode_attention, paged_decode_attention_xla, step_geometry)
from deeperspeed_tpu.inference.kv_cache import (PagedKVCache,
                                                QuantizedPages,
                                                quantize_kv)
from deeperspeed_tpu.runtime.comm.compressed import (
    compressed_allreduce_two_phase, compressed_allreduce_two_phase_host,
    compressed_reduce_scatter, compressed_reduce_scatter_host, wire_pad)
from deeperspeed_tpu.runtime.config import (DeepSpeedConfig,
                                            parse_inference_block,
                                            parse_quantization_block)
from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError

pytestmark = pytest.mark.quant

WORLD = 8


def data_mesh():
    return Mesh(np.array(jax.devices()[:WORLD]), ("data",))


# ---------------------------------------------------------------------------
# int8 weight-only matmul
# ---------------------------------------------------------------------------

class TestQuantMatmul:
    def _wx(self, m=16, k=64, n=128, seed=0):
        rng = np.random.default_rng(seed)
        w = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
        x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
        return w, x

    def test_per_channel_scale_roundtrip(self):
        w, _ = self._wx()
        qw = qm.quantize_weight(w)
        assert qw.qval.dtype == jnp.int8 and qw.scale.shape == (128,)
        # symmetric per-output-channel: dequant error bounded by scale/2
        err = jnp.abs(qw.dequant() - w)
        assert float(jnp.max(err / qw.scale[None, :])) <= 0.5 + 1e-6

    def test_zero_column_scale_one(self):
        w = jnp.zeros((32, 128))
        qw = qm.quantize_weight(w)
        np.testing.assert_array_equal(np.asarray(qw.scale), 1.0)
        np.testing.assert_array_equal(np.asarray(qw.qval), 0)

    def test_xla_matches_dequant_reference(self):
        w, x = self._wx()
        qw = qm.quantize_weight(w)
        got = qm.quant_matmul(x, qw, backend="xla")
        ref = x @ qw.dequant(jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-4, rtol=1e-4)

    def test_pallas_interpret_matches_xla(self):
        w, x = self._wx()
        qw = qm.quantize_weight(w)
        a = qm.quant_matmul(x, qw, backend="pallas")
        b = qm.quant_matmul(x, qw, backend="xla")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)

    def test_3d_input_dispatch(self):
        w, _ = self._wx()
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(2, 8, 64)).astype(np.float32))
        y = qm.quant_matmul(x, qm.quantize_weight(w), backend="xla")
        assert y.shape == (2, 8, 128)

    def test_shape_mismatch_raises(self):
        w, x = self._wx()
        with pytest.raises(ValueError, match="contraction"):
            qm.quant_matmul(x[:, :32], qm.quantize_weight(w))

    def test_pytree_stacking(self):
        w, _ = self._wx()
        qw = qm.quantize_weight(w)
        st = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), qw, qw)
        assert isinstance(st, qm.QuantizedWeight)
        assert st.qval.shape == (2, 64, 128)
        assert st.scale.shape == (2, 128)

    def test_dispatch_report_records_backend(self):
        from deeperspeed_tpu.ops import dispatch_report
        w, x = self._wx()
        qm.quant_matmul(x, qm.quantize_weight(w), backend="xla")
        assert dispatch_report()["quant_matmul"]["quant_matmul"] == "xla"

    def test_autotune_screen_static_pick(self):
        from deeperspeed_tpu.ops.autotune import (QMM_BLOCK_CANDIDATES,
                                                  qmm_vmem_bytes,
                                                  quant_matmul_blocks)
        pick = quant_matmul_blocks(jnp.bfloat16)
        assert pick in QMM_BLOCK_CANDIDATES
        assert qmm_vmem_bytes(*pick, itemsize=2) <= 10 << 20


# ---------------------------------------------------------------------------
# delayed scaling (training fake-quant)
# ---------------------------------------------------------------------------

class TestDelayedScaling:
    def test_history_roll(self):
        h = jnp.zeros((4,))
        h = qm.amax_history_update(h, 3.0)
        h = qm.amax_history_update(h, 5.0)
        np.testing.assert_allclose(np.asarray(h), [5.0, 3.0, 0.0, 0.0])

    def test_bootstrap_uses_current_amax(self):
        s = qm.scale_from_history(jnp.zeros((8,)), jnp.asarray(2.54),
                                  qm.INT8_QMAX)
        np.testing.assert_allclose(float(s), 2.54 / 127.0, rtol=1e-6)

    def test_delayed_uses_history_max(self):
        hist = jnp.asarray([1.0, 7.0, 2.0])
        s = qm.scale_from_history(hist, jnp.asarray(100.0), qm.FP8_QMAX)
        np.testing.assert_allclose(float(s), 7.0 / qm.FP8_QMAX, rtol=1e-6)

    @pytest.mark.parametrize("recipe,tol", [("int8", 0.05), ("fp8", 0.1)])
    def test_value_error_bounded(self, recipe, tol):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(32, 64)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(64, 128)).astype(np.float32))
        y, hx, hw = qm.scaled_matmul(x, w, jnp.zeros((4,)),
                                     jnp.zeros((4,)), recipe)
        ref = x @ w
        rel = float(jnp.max(jnp.abs(y - ref)) / jnp.max(jnp.abs(ref)))
        assert rel < tol
        assert float(hx[0]) > 0 and float(hw[0]) > 0

    def test_fp8_saturates_instead_of_nan(self):
        # a stale (too-small) delayed scale must clamp, never NaN: the
        # engine hit exactly this on the first amax-growth step
        x = jnp.full((8, 8), 100.0)
        w = jnp.eye(8)
        hist = jnp.asarray([1e-3])     # scale way below this step's amax
        y, _, _ = qm.scaled_matmul(x, w, hist, hist, "fp8")
        assert bool(jnp.all(jnp.isfinite(y)))

    def test_ste_gradient_flows(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(16, 32)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(32, 64)).astype(np.float32))
        h = jnp.zeros((4,))

        g = jax.grad(lambda x: jnp.sum(
            qm.scaled_matmul(x, w, h, h, "int8")[0]))(x)
        # STE: cotangent flows through the quantize as identity, so the
        # x-grad is (ones @ wq^T) with wq the fake-quantized weight
        wq_rowsum = jnp.sum(jax.grad(lambda w: jnp.sum(
            qm.scaled_matmul(x, w, h, h, "int8")[0] * 0 + 1) * 0)(w))
        assert g.shape == x.shape and bool(jnp.all(jnp.isfinite(g)))
        rel = float(jnp.max(jnp.abs(g - jnp.sum(w, axis=1)))
                    / jnp.max(jnp.abs(jnp.sum(w, axis=1))))
        assert rel < 0.05           # quantized-weight transpose ≈ w^T
        del wq_rowsum

    def test_grouped_scaled_operands(self):
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(4, 32, 16)).astype(np.float32))
        xq, wq, hx, hw = qm.grouped_scaled_operands(
            x, w, jnp.zeros((4,)), jnp.zeros((4,)), "int8")
        assert xq.shape == x.shape and wq.shape == w.shape
        relx = float(jnp.max(jnp.abs(xq - x)) / jnp.max(jnp.abs(x)))
        assert relx < 0.02
        assert float(hx[0]) == pytest.approx(float(jnp.max(jnp.abs(x))))

    def test_unknown_recipe_raises(self):
        with pytest.raises(ValueError, match="recipe"):
            qm.recipe_qmax("int4")


# ---------------------------------------------------------------------------
# int8 KV pages + decode attention
# ---------------------------------------------------------------------------

class TestInt8KV:
    def test_quantize_kv_roundtrip(self):
        rng = np.random.default_rng(0)
        v = jnp.asarray(rng.normal(size=(5, 4, 8, 64)).astype(np.float32))
        q, s = quantize_kv(v)
        assert q.dtype == jnp.int8 and s.shape == (5, 4, 8)
        back = q.astype(jnp.float32) * s[..., None]
        rel = float(jnp.max(jnp.abs(back - v)) / jnp.max(jnp.abs(v)))
        assert rel < 0.01

    def test_pool_layout_and_capacity(self):
        bf = PagedKVCache(num_layers=2, num_pages=8, num_heads=4,
                          page_size=8, head_dim=64, dtype=jnp.bfloat16)
        q8 = PagedKVCache(num_layers=2, num_pages=8, num_heads=4,
                          page_size=8, head_dim=64, dtype=jnp.int8)
        assert isinstance(q8.k, QuantizedPages)
        assert q8.k.data.dtype == jnp.int8
        assert q8.k.scale.shape == (2, 8, 4, 8)
        # the acceptance ratio: ≥1.9× resident tokens at fixed bytes
        assert bf.bytes_per_token() / q8.bytes_per_token() >= 1.9

    def test_reset_pools_keeps_quantization(self):
        q8 = PagedKVCache(num_layers=1, num_pages=4, num_heads=2,
                          page_size=8, head_dim=64, dtype=jnp.int8)
        q8.reset_pools()
        assert isinstance(q8.k, QuantizedPages)
        assert float(jnp.max(jnp.abs(q8.k.data))) == 0.0

    def _decode_setup(self, seed=0):
        rng = np.random.default_rng(seed)
        B, H, D, ps, Pn, NP = 3, 4, 64, 8, 16, 4
        q = jnp.asarray(rng.normal(size=(B, H, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(Pn, H, ps, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(Pn, H, ps, D)).astype(np.float32))
        pt = jnp.asarray(rng.integers(1, Pn, size=(B, NP)).astype(np.int32))
        lengths = jnp.asarray([0, 13, 32], np.int32)
        return q, k, v, pt, lengths

    def test_int8_decode_fallback_vs_dense(self):
        q, k, v, pt, lengths = self._decode_setup()
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        ks = ks.astype(jnp.bfloat16)
        vs = vs.astype(jnp.bfloat16)
        ref = paged_decode_attention_xla(q, k, v, pt, lengths,
                                         1 / np.sqrt(64))
        got = paged_decode_attention(q, kq, vq, pt, lengths,
                                     backend="xla", k_scales=ks,
                                     v_scales=vs)
        rel = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
        assert rel < 0.05          # documented dequant tolerance
        assert bool(jnp.all(got[0] == 0))   # inactive row exact zero

    def test_int8_decode_kernel_vs_fallback(self):
        q, k, v, pt, lengths = self._decode_setup(1)
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        ks = ks.astype(jnp.bfloat16)
        vs = vs.astype(jnp.bfloat16)
        a = paged_decode_attention(q, kq, vq, pt, lengths,
                                   backend="pallas", k_scales=ks,
                                   v_scales=vs)
        b = paged_decode_attention(q, kq, vq, pt, lengths,
                                   backend="xla", k_scales=ks,
                                   v_scales=vs)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5)

    @pytest.mark.parametrize("stacked", [False, True],
                             ids=["one_layer", "layer_of_stack"])
    @pytest.mark.parametrize("H,D", [(4, 128), (12, 64), (16, 128),
                                     (64, 128)],
                             ids=["h4", "h12_d64", "h16", "h64_split"])
    def test_int8_kernel_over_heads_and_ragged_rows(self, H, D, stacked):
        """The int8 kernel against its fallback and the unquantized
        attention, a page of all heads a step (64 heads: of 32, through
        `step_geometry`), over an inactive row, one token, an exact page
        edge, a row ending mid-table beside two that fill it."""
        rng = np.random.default_rng(H)
        ps, NP, Pn = 32, 4, 32
        lengths = jnp.asarray([0, 1, 64, 70, 128, 128], np.int32)
        B = lengths.shape[0]
        assert step_geometry(H, ps, D, jnp.int8)[0] == min(H, 32)
        q = jnp.asarray(rng.normal(size=(B, H, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(Pn, H, ps, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(Pn, H, ps, D)).astype(np.float32))
        pt = jnp.asarray(rng.permutation(np.arange(1, Pn))[:B * NP]
                         .reshape(B, NP).astype(np.int32))
        ref = paged_decode_attention_xla(q, k, v, pt, lengths,
                                         1 / np.sqrt(D))
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        pools = [kq, vq, ks.astype(jnp.bfloat16), vs.astype(jnp.bfloat16)]
        kw = {}
        if stacked:
            # the layer under test last, behind a layer of other content
            pools = [jnp.stack([t[::-1], t]) for t in pools]
            kw = {"layer": 1}
        kq, vq, ks, vs = pools
        a, b = (paged_decode_attention(q, kq, vq, pt, lengths,
                                       backend=backend, k_scales=ks,
                                       v_scales=vs, **kw)
                for backend in ("pallas", "xla"))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
        rel = float(jnp.max(jnp.abs(a - ref)) / jnp.max(jnp.abs(ref)))
        assert rel < 0.05          # documented dequant tolerance
        assert bool(jnp.all(a[0] == 0))     # inactive row exact zero

    @pytest.mark.parametrize("stacked", [False, True],
                             ids=["one_layer", "layer_of_stack"])
    @pytest.mark.parametrize("G,r,pages", [(4, 1, 1), (4, 1, 2), (4, 1, 4),
                                           (4, 6, 2), (2, 32, 4)],
                             ids=["mha_p1", "mha_p2", "mha_p4", "group6_p2",
                                  "group32_p4"])
    def test_int8_kernel_over_spans_and_groups(self, monkeypatch, G, r,
                                               pages, stacked):
        """Int8 pages through a grid step of several pages (the scale
        tiles ride the same maps, a page an operand, and join along their
        slots) and under grouped KV heads, where they keep the collapsed
        score form: rows that end in every page of a span, an inactive
        row, one token, a full table."""
        rng = np.random.default_rng(G * r + pages)
        ps, NP, D = 32, 8, 64
        lengths = jnp.asarray([0, 1, 32, 33, 70, 100, 128, 129, 200, 256],
                              np.int32)
        B, H, Pn = lengths.shape[0], G * r, 81
        q = jnp.asarray(rng.normal(size=(B, H, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(Pn, G, ps, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(Pn, G, ps, D)).astype(np.float32))
        pt = jnp.asarray(rng.permutation(np.arange(1, Pn))[:B * NP]
                         .reshape(B, NP).astype(np.int32))
        ref = paged_decode_attention_xla(q, k, v, pt, lengths,
                                         1 / np.sqrt(D))
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        pools = [kq, vq, ks.astype(jnp.bfloat16), vs.astype(jnp.bfloat16)]
        kw = {}
        if stacked:
            pools = [jnp.stack([t[::-1], t]) for t in pools]
            kw = {"layer": 1}
        kq, vq, ks, vs = pools
        monkeypatch.setattr(decode_attention, "_STEP_MIN_BYTES",
                            pages * 2 * G * ps * D)
        a = paged_decode_attention(q, kq, vq, pt, lengths, backend="pallas",
                                   k_scales=ks, v_scales=vs, **kw)
        report = dispatch_report()["decode_attention"]
        assert (report["decode_pages_per_step"],
                report["decode_scores"]) == (pages, "collapsed")
        b = paged_decode_attention(q, kq, vq, pt, lengths, backend="xla",
                                   k_scales=ks, v_scales=vs, **kw)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
        rel = float(jnp.max(jnp.abs(a - ref)) / jnp.max(jnp.abs(ref)))
        assert rel < 0.05          # documented dequant tolerance
        assert bool(jnp.all(a[0] == 0))     # inactive row exact zero

    def test_scale_shape_validated(self):
        q, k, v, pt, lengths = self._decode_setup()
        kq, ks = quantize_kv(k)
        with pytest.raises(ValueError, match="scales"):
            paged_decode_attention(q, kq, kq, pt, lengths,
                                   k_scales=ks[:, :1], v_scales=ks)


# ---------------------------------------------------------------------------
# serving integration (int8 weights + int8 KV)
# ---------------------------------------------------------------------------

def _serve_cfg(**inf_extra):
    inf = {"enabled": True, "page_size": 8, "num_pages": 64,
           "max_seq_len": 64, "max_batch_size": 2, "token_budget": 64}
    inf.update(inf_extra)
    return {"inference": inf}


def _drain(engine, rids, max_steps=60):
    outs = {}
    for _ in range(max_steps):
        engine.step()
        for r in engine.scheduler.pop_finished():
            outs[r.request_id] = list(r.generated)
        if len(outs) == len(rids):
            break
    return [outs[r] for r in rids]


class TestServingQuant:
    @pytest.fixture(scope="class")
    def model_params(self):
        cfg = GPTNeoXConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=128)
        model = GPTNeoX(config=cfg)
        return model, model.init_params(jax.random.PRNGKey(0))

    def test_int8_weights_end_to_end(self, model_params):
        from deeperspeed_tpu.inference.engine import InferenceEngine
        model, params = model_params
        conf = _serve_cfg()
        conf["quantization"] = {"weights": "int8"}
        eng = InferenceEngine(model, config=conf, params=params)
        assert eng.dtypes["weight"] == "int8"
        # the block stack rests int8; embed/head stay compute dtype
        (stack,) = eng.params_stacked.values()
        assert isinstance(stack["attn"]["qkv_w"], qm.QuantizedWeight)
        assert isinstance(stack["mlp"]["in_w"], qm.QuantizedWeight)
        assert "blocks" not in eng.params
        assert eng.params["embed"]["wte"].dtype != jnp.int8
        rid = eng.submit([3, 5, 7, 9], max_new_tokens=6)
        (toks,) = _drain(eng, [rid])
        assert len(toks) == 6

    def test_int8_weight_decode_deterministic(self, model_params):
        """Exactness claim: the weight-only int8 path is deterministic —
        two engines over the same quantized weights decode
        token-identically (greedy)."""
        from deeperspeed_tpu.inference.engine import InferenceEngine
        model, params = model_params
        conf = _serve_cfg()
        conf["quantization"] = {"weights": "int8"}
        outs = []
        for _ in range(2):
            eng = InferenceEngine(model, config=copy.deepcopy(conf),
                                  params=params)
            rid = eng.submit([2, 4, 6], max_new_tokens=8)
            outs.append(_drain(eng, [rid])[0])
        assert outs[0] == outs[1]

    def test_int8_kv_backend_parity(self, model_params):
        """Greedy decode is token-identical between the Pallas
        (interpret) int8 decode kernel and the XLA fallback — the
        exactness pin for the int8-KV path (vs bf16 KV only a
        documented tolerance holds)."""
        from deeperspeed_tpu.inference.engine import InferenceEngine
        model, params = model_params
        outs = []
        for kernel in ("pallas", "xla"):
            # page_size 32: a FORCED pallas kernel with int8 pools
            # requires the int8 sublane tile even off-TPU (parse-time
            # rule, keeps configs portable to real hardware)
            conf = _serve_cfg(kv_cache_dtype="int8", kernel=kernel,
                              page_size=32)
            eng = InferenceEngine(model, config=conf, params=params)
            assert eng.kv_quant and eng.dtypes["kv_cache"] == "int8"
            rid = eng.submit([1, 2, 3, 4, 5], max_new_tokens=6)
            outs.append(_drain(eng, [rid])[0])
        assert outs[0] == outs[1]

    def test_int8_kv_tracks_bf16_decode(self, model_params):
        from deeperspeed_tpu.inference.engine import InferenceEngine
        model, params = model_params
        outs = []
        for kvd in (None, "int8"):
            conf = _serve_cfg(**({"kv_cache_dtype": kvd} if kvd else {}))
            eng = InferenceEngine(model, config=conf, params=params)
            rid = eng.submit([7, 8, 9, 10], max_new_tokens=8)
            outs.append(_drain(eng, [rid])[0])
        # tolerance policy: int8 KV is NOT claimed token-identical to
        # bf16, but on a short window of an untrained tiny model the
        # argmax should survive the <1% dequant error
        agree = sum(a == b for a, b in zip(*outs))
        assert agree >= len(outs[0]) - 1

    def test_weight_quant_rejects_model_parallel(self, model_params):
        from deeperspeed_tpu.inference.engine import InferenceEngine
        from deeperspeed_tpu.parallel.mesh import MODEL_AXIS
        model, params = model_params
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        mesh = Mesh(np.array(jax.devices()[:2]), (MODEL_AXIS,))
        conf = _serve_cfg()
        conf["quantization"] = {"weights": "int8"}
        with pytest.raises(DeepSpeedConfigError, match="model-parallel"):
            InferenceEngine(model, config=conf, params=params, mesh=mesh)

    def test_prepare_inference_params_requires_blocks(self):
        from deeperspeed_tpu.module_inject.replace_module import \
            prepare_inference_params
        with pytest.raises(ValueError, match="blocks"):
            prepare_inference_params({"w": jnp.ones((4, 4))},
                                     jnp.bfloat16, weight_quant="int8")
        with pytest.raises(ValueError, match="int8"):
            prepare_inference_params({"blocks": []}, jnp.bfloat16,
                                     weight_quant="int4")


# ---------------------------------------------------------------------------
# compressed collectives: reduce-scatter + the two-phase transports
# ---------------------------------------------------------------------------

class TestCompressedComm:
    def test_reduce_scatter_matches_host_oracle(self):
        rng = np.random.default_rng(0)
        S = 24
        xs = [rng.normal(size=(WORLD, S)).astype(np.float32)
              for _ in range(WORLD)]
        errs = [rng.normal(size=(WORLD, S)).astype(np.float32) * 0.1
                for _ in range(WORLD)]
        mesh = data_mesh()

        def body(x, e):
            out, new_e = compressed_reduce_scatter(x[0], e[0], "data",
                                                   WORLD)
            return out[None], new_e[None]

        f = jax.jit(shard_map(body, mesh=mesh,
                              in_specs=(P("data"), P("data")),
                              out_specs=(P("data"), P("data")),
                              check_vma=False))
        out, new_e = f(jnp.asarray(np.stack(xs)),
                       jnp.asarray(np.stack(errs)))
        ref_outs, ref_errs = compressed_reduce_scatter_host(xs, errs)
        for r in range(WORLD):
            np.testing.assert_allclose(np.asarray(out[r]),
                                       np.asarray(ref_outs[r]),
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(np.asarray(new_e[r]),
                                       np.asarray(ref_errs[r]),
                                       rtol=1e-5, atol=1e-5)

    def test_reduce_scatter_world_one(self):
        x = jnp.asarray(np.random.default_rng(1).normal(
            size=(1, 16)).astype(np.float32))
        out, err = compressed_reduce_scatter(x, jnp.zeros_like(x), None, 1)
        assert out.shape == (16,)
        np.testing.assert_allclose(np.asarray(x[0] - err[0]),
                                   np.asarray(out), rtol=1e-6)

    def test_error_feedback_unbiased_over_steps(self):
        """sum_t out_t = sum_t x_t − err_T: the EF invariant that makes
        1-bit compression converge."""
        rng = np.random.default_rng(2)
        S, steps = 8, 40
        xs = [rng.normal(size=(WORLD, S)).astype(np.float32)
              for _ in range(WORLD)]
        errs = [np.zeros((WORLD, S), np.float32) for _ in range(WORLD)]
        acc = [np.zeros(S, np.float64) for _ in range(WORLD)]
        for _ in range(steps):
            outs, errs = compressed_reduce_scatter_host(
                [jnp.asarray(x) for x in xs], errs)
            for r in range(WORLD):
                acc[r] += np.asarray(outs[r], np.float64)
        for r in range(WORLD):
            true = steps * sum(x[r] for x in xs)
            resid = sum(np.asarray(e[r], np.float64) for e in errs)
            np.testing.assert_allclose(acc[r] + resid, true, atol=1e-3)

    @pytest.mark.parametrize("n_valid", [None, 50, 17])
    def test_packed_vs_dense_two_phase_ragged(self, n_valid):
        """Satellite: fast-lane parity of the PACKED two-phase transport
        (all_to_all sign bytes + gathered scales, inside shard_map on
        the 8-device mesh) against the host oracle, covering ragged
        last-chunk shapes (n_valid < n) — the packed transport
        previously had no fast-lane coverage at all."""
        n = wire_pad(n_valid or 64, WORLD)
        rng = np.random.default_rng(3)
        xs = np.stack([rng.normal(size=n).astype(np.float32)
                       for _ in range(WORLD)])
        if n_valid is not None:
            xs[:, n_valid:] = 0.0
        werr = np.stack([rng.normal(size=n).astype(np.float32) * 0.1
                         for _ in range(WORLD)])
        if n_valid is not None:
            werr[:, n_valid:] = 0.0
        serr = np.stack([rng.normal(size=n // WORLD).astype(np.float32)
                         * 0.1 for _ in range(WORLD)])
        mesh = data_mesh()

        def body(x, we, se):
            out, nwe, nse = compressed_allreduce_two_phase(
                x[0], we[0], se[0], "data", WORLD, n_valid=n_valid)
            return out[None], nwe[None], nse[None]

        f = jax.jit(shard_map(body, mesh=mesh,
                              in_specs=(P("data"), P("data"), P("data")),
                              out_specs=(P("data"), P("data"), P("data")),
                              check_vma=False))
        out, nwe, nse = f(jnp.asarray(xs), jnp.asarray(werr),
                          jnp.asarray(serr))
        # server errors are per-rank CHUNKS in the packed transport;
        # the host oracle returns the same chunking
        r_out, r_we, r_se = compressed_allreduce_two_phase_host(
            [jnp.asarray(x) for x in xs],
            [jnp.asarray(e) for e in werr],
            [jnp.asarray(e) for e in serr], n_valid=n_valid)
        for r in range(WORLD):
            np.testing.assert_allclose(np.asarray(out[r]),
                                       np.asarray(r_out[r]),
                                       rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(np.asarray(nwe[r]),
                                       np.asarray(r_we[r]),
                                       rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(np.asarray(nse[r]),
                                       np.asarray(r_se[r]),
                                       rtol=1e-4, atol=1e-5)
        if n_valid is not None:
            # pad lanes pinned to exactly zero everywhere
            assert float(jnp.max(jnp.abs(out[:, n_valid:]))) == 0.0
            assert float(jnp.max(jnp.abs(nwe[:, n_valid:]))) == 0.0


# ---------------------------------------------------------------------------
# EF gather (cotangent smuggling) unit
# ---------------------------------------------------------------------------

class TestEfGather:
    def test_pad_lanes_stay_zero(self):
        """Review-fix pin: a ragged flat-padded leaf's pad lanes carry
        exact-zero cotangents, and the compressed transport must keep
        them zero — sign(0) = +scale would pollute grad norms and the
        flat-padded Adam tails (the hazard the two-phase transport
        already documents)."""
        from deeperspeed_tpu.parallel.schedule import (LayerPlan,
                                                       make_ef_gather,
                                                       plan_valid_mask)
        from deeperspeed_tpu.runtime.zero.partition_parameters import \
            FlatPad
        mesh = data_mesh()
        numel = 50                      # pads to 56 over 8 ranks
        padded = -(-numel // WORLD) * WORLD
        pad = FlatPad((numel,), numel, padded)
        template = {"w": jnp.zeros((numel,))}
        plan = LayerPlan(template, {"w": P("data")}, {"w": pad},
                         "data", WORLD, 1 << 20)
        mask = plan_valid_mask(plan)
        assert mask.shape == (WORLD, plan.shard_size)
        assert int(mask.sum()) == numel
        gather_ef = make_ef_gather(plan)
        S = plan.shard_size
        rng = np.random.default_rng(0)
        rows = jnp.asarray(rng.normal(size=(WORLD, S)).astype(np.float32))
        # real cotangents: zero on pad lanes (rebuild slices them away)
        cots = jnp.asarray(
            rng.normal(size=(WORLD, WORLD, S)).astype(np.float32))
        cots = cots * jnp.asarray(mask)[None]
        werr = jnp.zeros((WORLD, WORLD, S), jnp.float32)

        def body(row, werr, cot):
            def f(row, werr):
                return jnp.sum(gather_ef(row, werr[0]) * cot[0])
            row_bar, new_err = jax.grad(f, argnums=(0, 1))(row[0], werr)
            return row_bar[None], new_err

        f = jax.jit(shard_map(body, mesh=mesh,
                              in_specs=(P("data"), P("data"), P("data")),
                              out_specs=(P("data"), P("data")),
                              check_vma=False))
        row_bar, new_err = f(rows, werr, cots)
        dead = 1.0 - np.asarray(mask)
        # pad lanes of the compressed grad AND the error buffer: zero
        assert float(np.abs(np.asarray(new_err) * dead[None]).max()) == 0
        # row_bar lane (r_self, j) comes from chunk r_self of every
        # rank's cotangent: its pad lanes are mask row r_self's zeros
        for r in range(WORLD):
            assert float(np.abs(np.asarray(row_bar[r]) *
                                dead[r]).max()) == 0
        # real lanes carry signal
        assert float(np.abs(np.asarray(row_bar)).max()) > 0

    def test_cotangent_is_new_error(self):
        from deeperspeed_tpu.parallel.schedule import (LayerPlan,
                                                       make_ef_gather)
        from deeperspeed_tpu.runtime.zero.partition_parameters import \
            FlatPad
        mesh = data_mesh()
        numel = 48
        pad = FlatPad((numel,), numel, numel)
        template = {"w": jnp.zeros((numel,))}
        specs = {"w": P("data")}
        pads = {"w": pad}
        plan = LayerPlan(template, specs, pads, "data", WORLD, 1 << 20)
        gather_ef = make_ef_gather(plan)
        S = plan.shard_size
        rng = np.random.default_rng(0)
        rows = jnp.asarray(rng.normal(size=(WORLD, S)).astype(np.float32))
        cots = jnp.asarray(
            rng.normal(size=(WORLD, WORLD, S)).astype(np.float32))
        werr = jnp.zeros((WORLD, WORLD, S), jnp.float32)

        def body(row, werr, cot):
            def f(row, werr):
                g = gather_ef(row, werr[0])
                return jnp.sum(g * cot[0])
            row_bar, new_err = jax.grad(f, argnums=(0, 1))(row[0], werr)
            return row_bar[None], new_err

        f = jax.jit(shard_map(body, mesh=mesh,
                              in_specs=(P("data"), P("data"), P("data")),
                              out_specs=(P("data"), P("data")),
                              check_vma=False))
        row_bar, new_err = f(rows, werr, cots)
        ref_outs, ref_errs = compressed_reduce_scatter_host(
            [cots[r] for r in range(WORLD)],
            [jnp.zeros((WORLD, S)) for _ in range(WORLD)])
        for r in range(WORLD):
            np.testing.assert_allclose(np.asarray(row_bar[r]),
                                       np.asarray(ref_outs[r]),
                                       rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(np.asarray(new_err[r]),
                                       np.asarray(ref_errs[r]),
                                       rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

class TestQuantConfig:
    def test_absent_or_disabled(self):
        assert parse_quantization_block({}) is False
        assert parse_quantization_block(
            {"quantization": {"enabled": False,
                              "weights": "int8"}}) is False

    def test_defaults(self):
        p = parse_quantization_block({"quantization": {}})
        assert p == {"weights": None, "ffn": None,
                     "gradient_compression": False,
                     "gradient_compression_packed": False}

    def test_full_block(self):
        p = parse_quantization_block({"quantization": {
            "weights": "int8",
            "ffn": {"recipe": "fp8", "amax_history_len": 8,
                    "margin": 1.5},
            "gradient_compression": {"enabled": True}}})
        assert p["weights"] == "int8"
        assert p["ffn"] == {"recipe": "fp8", "amax_history_len": 8,
                            "margin": 1.5}
        assert p["gradient_compression"] is True

    @pytest.mark.parametrize("block,match", [
        ({"wieghts": "int8"}, "Unknown"),
        ({"weights": "int4"}, "weights"),
        ({"ffn": {"recipe": "int4"}}, "recipe"),
        ({"ffn": {}}, "recipe"),
        ({"ffn": {"recipe": "int8", "histroy": 2}}, "Unknown"),
        ({"ffn": {"recipe": "int8", "amax_history_len": 0}}, ">= 1"),
        ({"ffn": {"recipe": "int8", "margin": 0}}, "margin"),
        ({"gradient_compression": {"enalbed": True}}, "Unknown"),
        ({"gradient_compression": {"enabled": "yes"}}, "boolean"),
        ({"enabled": "yes"}, "boolean"),
    ])
    def test_rejects(self, block, match):
        with pytest.raises(DeepSpeedConfigError, match=match):
            parse_quantization_block({"quantization": block})

    def test_kv_dtype_choices_listed(self):
        with pytest.raises(DeepSpeedConfigError, match="int8"):
            parse_inference_block({"inference": {
                "enabled": True, "kv_cache_dtype": "int7"}})
        p = parse_inference_block({"inference": {
            "enabled": True, "kv_cache_dtype": "int8"}})
        assert p["kv_cache_dtype"] == "int8"

    def test_int8_forced_pallas_needs_aligned_pages(self):
        with pytest.raises(DeepSpeedConfigError, match="32"):
            parse_inference_block({"inference": {
                "enabled": True, "kv_cache_dtype": "int8",
                "kernel": "pallas", "page_size": 8}})
        # auto kernel degrades to the XLA fallback instead (documented)
        p = parse_inference_block({"inference": {
            "enabled": True, "kv_cache_dtype": "int8", "page_size": 8}})
        assert p["kv_cache_dtype"] == "int8"

    def test_resolve_kv_cache_dtype(self):
        from deeperspeed_tpu.runtime.precision import \
            resolve_kv_cache_dtype
        assert resolve_kv_cache_dtype("int8") == jnp.int8
        assert resolve_kv_cache_dtype("bf16") == jnp.bfloat16
        with pytest.raises(DeepSpeedConfigError, match="int8"):
            resolve_kv_cache_dtype("int2")

    def test_rides_deepspeed_config(self):
        cfg = DeepSpeedConfig(
            {"train_batch_size": 8,
             "quantization": {"ffn": {"recipe": "int8"}}},
            world_size=8)
        assert cfg.quantization_config["ffn"]["recipe"] == "int8"

    def test_ops_matrix_has_quant_rows(self):
        from deeperspeed_tpu.ops.compat import ALL_OPS
        assert "quant_matmul" in ALL_OPS and "int8_kv_decode" in ALL_OPS
        assert ALL_OPS["quant_matmul"]()
        assert ALL_OPS["int8_kv_decode"]()


# ---------------------------------------------------------------------------
# engine integration: loss parity + bit-exact resume
# ---------------------------------------------------------------------------

SEQ = 32
BATCH = 16


def _train(config_overrides, steps=8, seed=0, return_engine=False,
           model_kw=None):
    cfg = GPTNeoXConfig(vocab_size=128, hidden_size=32, num_layers=4,
                        num_heads=4, max_seq_len=64)
    model = GPTNeoX(cfg, use_pallas=False, **(model_kw or {}))
    params = model.init_params(jax.random.PRNGKey(seed))
    config = {
        "train_batch_size": BATCH,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "steps_per_print": 10_000,
    }
    config.update(copy.deepcopy(config_overrides))
    if "moe" in config:
        # expert weights only exist after apply_ds_config reshapes the
        # model — let the engine init params from the configured model
        params = None
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=params, config_params=config)
    gas = config.get("gradient_accumulation_steps", 1)
    rng = np.random.default_rng(1)
    losses = []
    for _ in range(steps):
        toks = rng.integers(0, cfg.vocab_size,
                            (gas, BATCH // gas, SEQ), np.int32)
        losses.append(float(engine.train_batch(batch=(toks, toks))))
    if return_engine:
        return np.asarray(losses), engine
    return np.asarray(losses)


def _ez3(extra=None):
    conf = {"zero_optimization": {
        "stage": 3, "stage3_param_persistence_threshold": 0,
        "schedule": {"mode": "explicit", "group_layers": 2}}}
    conf.update(extra or {})
    return conf


class TestEngineQuant:
    def test_ffn_quant_loss_parity(self):
        """The fp8 FFN loss curve matches full precision within noise —
        the scaled-down pin of the 125m acceptance gate (the bench row
        carries the full-size measurement)."""
        base = _train({})
        for recipe in ("fp8", "int8"):
            q = _train({"quantization": {"ffn": {"recipe": recipe}}})
            assert q[0] == pytest.approx(base[0], abs=5e-3)
            np.testing.assert_allclose(q, base, atol=2e-2)
            assert np.isfinite(q).all()

    def test_ffn_quant_amax_advances_and_persists(self, tmp_path):
        conf = {"quantization": {"ffn": {"recipe": "int8",
                                         "amax_history_len": 4}}}
        losses, eng = _train(conf, steps=3, return_engine=True)
        amax = np.asarray(eng.state.quant.amax)
        assert amax.shape == (4, 4, 4)
        assert amax.max() > 0
        eng.save_checkpoint(str(tmp_path), tag="q1")

        # resumed engine continues BIT-EXACTLY (amax history restored)
        _, fresh = _train(conf, steps=0, return_engine=True, seed=7)
        fresh.load_checkpoint(str(tmp_path), tag="q1")
        np.testing.assert_array_equal(
            np.asarray(fresh.state.quant.amax), amax)
        rng = np.random.default_rng(9)
        toks = rng.integers(0, 128, (1, BATCH, SEQ), np.int32)
        l_resumed = float(fresh.train_batch(batch=(toks, toks)))
        l_cont = float(eng.train_batch(batch=(toks, toks)))
        assert l_resumed == pytest.approx(l_cont, abs=0)

    def test_compressed_grads_loss_parity(self):
        base = _train(_ez3())
        comp = _train(_ez3({"quantization": {
            "gradient_compression": {"enabled": True}}}))
        assert comp[0] == pytest.approx(base[0], abs=5e-3)
        np.testing.assert_allclose(comp, base, atol=3e-2)
        assert np.isfinite(comp).all()

    def test_compressed_grads_ef_state_and_resume(self, tmp_path):
        conf = _ez3({"quantization": {
            "gradient_compression": {"enabled": True}}})
        losses, eng = _train(conf, steps=3, return_engine=True)
        ef = np.asarray(eng.state.quant.ef)
        assert ef.ndim == 4 and ef.shape[0] == WORLD
        assert np.abs(ef).max() > 0
        eng.save_checkpoint(str(tmp_path), tag="c1")

        _, fresh = _train(conf, steps=0, return_engine=True, seed=7)
        fresh.load_checkpoint(str(tmp_path), tag="c1")
        np.testing.assert_array_equal(np.asarray(fresh.state.quant.ef),
                                      ef)
        rng = np.random.default_rng(9)
        toks = rng.integers(0, 128, (1, BATCH, SEQ), np.int32)
        l_resumed = float(fresh.train_batch(batch=(toks, toks)))
        l_cont = float(eng.train_batch(batch=(toks, toks)))
        assert l_resumed == pytest.approx(l_cont, abs=0)

    def test_gas_threads_quant_state(self):
        q = _train({"train_batch_size": BATCH,
                    "gradient_accumulation_steps": 2,
                    "quantization": {"ffn": {"recipe": "int8"}}},
                   steps=3)
        assert np.isfinite(q).all()

    def test_ffn_quant_rejects_explicit_schedule(self):
        with pytest.raises(DeepSpeedConfigError, match="explicit"):
            _train(_ez3({"quantization": {"ffn": {"recipe": "int8"}}}),
                   steps=0)

    def test_grad_compression_requires_explicit(self):
        with pytest.raises(DeepSpeedConfigError, match="explicit"):
            _train({"quantization": {
                "gradient_compression": {"enabled": True}}}, steps=0)

    def test_manual_forward_rejected(self):
        _, eng = _train({"quantization": {"ffn": {"recipe": "int8"}}},
                        steps=0, return_engine=True)
        with pytest.raises(RuntimeError, match="quantization"):
            eng.forward((np.zeros((BATCH, SEQ), np.int32),
                         np.zeros((BATCH, SEQ), np.int32)))

    def test_moe_einsum_rejected_with_ffn_quant(self):
        with pytest.raises((DeepSpeedConfigError, ValueError),
                           match="sort"):
            _train({"moe": {"num_experts": 4},
                    "quantization": {"ffn": {"recipe": "int8"}}},
                   steps=0)

    def test_moe_sort_ffn_quant_trains(self):
        q = _train({"moe": {"num_experts": 4, "dispatch": "sort"},
                    "quantization": {"ffn": {"recipe": "int8"}}},
                   steps=3)
        assert np.isfinite(q).all()

    @pytest.mark.fault_injection
    def test_skipped_step_reverts_quant_state(self):
        """Review-fix pin: a quarantined/overflowed step must NOT carry
        its quant state forward — the skip exists to discard an
        anomalous step, and a poisoned amax history (or EF buffer)
        would NaN every later step's scales."""
        conf = {"quantization": {"ffn": {"recipe": "int8"}},
                "training_health": {
                    "enabled": True, "policy": "skip_batch",
                    "fault_injection": {"faults": [
                        {"kind": "nan_grads", "step": 2}]}}}
        losses, eng = _train(conf, steps=2, return_engine=True)
        before = np.asarray(eng.state.quant.amax)
        rng = np.random.default_rng(5)
        toks = rng.integers(0, 128, (1, BATCH, SEQ), np.int32)
        eng.train_batch(batch=(toks, toks))      # the faulted step
        assert int(eng.sentinel.quarantined) == 1
        np.testing.assert_array_equal(
            np.asarray(eng.state.quant.amax), before)
        # next clean step advances again
        eng.train_batch(batch=(toks, toks))
        assert not np.array_equal(np.asarray(eng.state.quant.amax),
                                  before)
