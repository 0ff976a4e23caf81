"""Serving subsystem tests: paged decode-attention kernel, paged KV
cache, continuous-batching scheduler, and the InferenceEngine.

Fast lane (tier-1): kernel parity against the XLA fallback and a dense
oracle, allocator/scheduler unit coverage, config validation, greedy
paged decode pinned token-identical to full-context teacher-forced
argmax (the acceptance bar), the zero-recompile-after-warmup assertion,
params-only checkpoint loads, and the base engine's
`inference_batch` / `eval_batch(return_logits=True)`.

The synthetic-stream soak rides the `serving` marker + `slow` so tier-1
stays fast; run with ``-m serving``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deeperspeed_tpu
from deeperspeed_tpu.inference import (ContinuousBatchingScheduler,
                                       InferenceEngine, PagedKVCache,
                                       Request, pages_for_tokens)
from deeperspeed_tpu.models.gpt2 import GPT2, GPT2Config
from deeperspeed_tpu.models.gpt2 import forward as gpt2_forward
from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
from deeperspeed_tpu.models.gpt_neox import forward as neox_forward
from deeperspeed_tpu.ops import dispatch_report
from deeperspeed_tpu.ops.pallas import decode_attention
from deeperspeed_tpu.ops.pallas.decode_attention import (
    decode_steps, paged_decode_attention, paged_decode_attention_xla,
    span_table, step_geometry)
from deeperspeed_tpu.runtime.config import parse_inference_block
from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError
from tests.model.references import teacher_forced

pytestmark = pytest.mark.serving


# ---------------------------------------------------------------------------
# decode-attention kernel
# ---------------------------------------------------------------------------

def _rand_paged(rng, B, H, D, ps, NP, P):
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, H, ps, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, H, ps, D)), jnp.float32)
    pages = rng.permutation(np.arange(1, P))[:B * NP].reshape(B, NP)
    return q, kp, vp, jnp.asarray(pages, jnp.int32), pages


def _dense_oracle(q, kp, vp, pages, lens, B, H, D, NP, window=None):
    """Softmax attention over each row's first `lens[b]` cached tokens
    (its last `window` of them), query head h over KV head h // (H / G)."""
    r = H // np.asarray(kp).shape[1]
    out = []
    for b in range(B):
        L = int(lens[b])
        if L == 0:
            out.append(np.zeros((H, D), np.float32))
            continue
        lo = max(L - window, 0) if window else 0
        ks, vs = (np.repeat(np.concatenate(
            [np.asarray(pool)[pages[b, i]] for i in range(NP)],
            axis=1)[:, lo:L], r, axis=0) for pool in (kp, vp))
        s = np.einsum("hd,hsd->hs", np.asarray(q)[b],
                      ks) / np.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out.append(np.einsum("hs,hsd->hd", p, vs))
    return np.stack(out)


class TestDecodeAttentionKernel:
    def test_kernel_matches_xla_and_dense(self):
        rng = np.random.default_rng(0)
        B, H, D, ps, NP, P = 3, 4, 64, 16, 4, 16
        q, kp, vp, pt, pages = _rand_paged(rng, B, H, D, ps, NP, P)
        # ragged lengths: partial page, inactive row, exact page edge
        lens = jnp.asarray([37, 0, 32], jnp.int32)
        o_xla = paged_decode_attention(q, kp, vp, pt, lens, backend="xla")
        o_pl = paged_decode_attention(q, kp, vp, pt, lens,
                                      backend="pallas")
        np.testing.assert_allclose(np.asarray(o_xla), np.asarray(o_pl),
                                   atol=2e-6)
        ref = _dense_oracle(q, kp, vp, pages, lens, B, H, D, NP)
        np.testing.assert_allclose(ref, np.asarray(o_pl), atol=2e-6)

    def test_inactive_row_is_exact_zero(self):
        rng = np.random.default_rng(1)
        q, kp, vp, pt, _ = _rand_paged(rng, 2, 2, 64, 8, 2, 8)
        lens = jnp.asarray([0, 9], jnp.int32)
        for backend in ("xla", "pallas"):
            out = np.asarray(paged_decode_attention(q, kp, vp, pt, lens,
                                                    backend=backend))
            assert (out[0] == 0.0).all()
            assert np.isfinite(out[1]).all()

    def test_single_token_sequence(self):
        rng = np.random.default_rng(2)
        q, kp, vp, pt, pages = _rand_paged(rng, 1, 2, 64, 8, 3, 8)
        lens = jnp.asarray([1], jnp.int32)
        out = np.asarray(paged_decode_attention(q, kp, vp, pt, lens,
                                                backend="pallas"))
        # attention over one key == that key's value row
        np.testing.assert_allclose(
            out[0], np.asarray(vp)[pages[0, 0], :, 0, :], atol=1e-6)

    def test_bf16_cache(self):
        rng = np.random.default_rng(3)
        B, H, D, ps, NP, P = 2, 2, 64, 16, 2, 8
        q, kp, vp, pt, pages = _rand_paged(rng, B, H, D, ps, NP, P)
        q16, k16, v16 = (t.astype(jnp.bfloat16) for t in (q, kp, vp))
        lens = jnp.asarray([20, 7], jnp.int32)
        o_pl = paged_decode_attention(q16, k16, v16, pt, lens,
                                      backend="pallas")
        assert o_pl.dtype == jnp.bfloat16
        ref = _dense_oracle(q, kp, vp, pages, lens, B, H, D, NP)
        np.testing.assert_allclose(ref, np.asarray(o_pl, np.float32),
                                   atol=3e-2)

    # page 16, table 4 wide: an inactive row, one token, an exact page
    # edge, a row that ends mid-table beside two that fill the table
    RAGGED = [0, 1, 32, 37, 64, 64]

    @pytest.mark.parametrize("stacked", [False, True],
                             ids=["one_layer", "layer_of_stack"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["fp32", "bf16"])
    @pytest.mark.parametrize("D", [64, 128])
    @pytest.mark.parametrize("H", [4, 12, 16])
    def test_kernel_over_heads_dims_and_ragged_rows(self, H, D, dtype,
                                                    stacked):
        rng = np.random.default_rng(H * D)
        B, ps, NP, P = len(self.RAGGED), 16, 4, 32
        q, kp, vp, pt, pages = _rand_paged(rng, B, H, D, ps, NP, P)
        lens = jnp.asarray(self.RAGGED, jnp.int32)
        # dead entries hold the trash page, as the scheduler pads them
        live = np.arange(NP)[None, :] * ps < np.asarray(lens)[:, None]
        pt = jnp.where(live, pt, 0)
        ref = _dense_oracle(q, kp, vp, pages, lens, B, H, D, NP)
        qc, kc, vc = (t.astype(dtype) for t in (q, kp, vp))
        if stacked:
            # the layer under test between two layers of other content
            kc, vc = (jnp.stack([t[::-1], t, t * 0]) for t in (kc, vc))

        def call(backend):
            if not stacked:
                return paged_decode_attention(qc, kc, vc, pt, lens,
                                              backend=backend)
            return jax.jit(lambda layer: paged_decode_attention(
                qc, kc, vc, pt, lens, backend=backend, layer=layer))(
                    jnp.int32(1))

        o_pl, o_xla = call("pallas"), call("xla")
        assert o_pl.dtype == dtype and o_pl.shape == (B, H, D)
        exact = dtype == jnp.float32
        np.testing.assert_allclose(
            np.asarray(o_pl, np.float32), np.asarray(o_xla, np.float32),
            atol=5e-6 if exact else 2e-2)
        np.testing.assert_allclose(ref, np.asarray(o_pl, np.float32),
                                   atol=5e-6 if exact else 3e-2)
        assert (np.asarray(o_pl, np.float32)[0] == 0.0).all()

    @pytest.mark.parametrize("H,ps,D,dtype,want", [
        (16, 64, 128, jnp.bfloat16, 16),    # both serve cells: all heads
        (4, 64, 128, jnp.bfloat16, 4),      # a model-parallel shard of 4
        (12, 16, 64, jnp.bfloat16, 12),
        (16, 64, 256, jnp.bfloat16, 16),
        (16, 64, 128, jnp.int8, 16),
        (32, 64, 256, jnp.float32, 8),      # too wide: the heads split
        (64, 64, 128, jnp.int8, 32),        # int8: whole or 16s
        (24, 64, 128, jnp.int8, 24),        # no multiple of 16 divides it
        # (heads, pages) a step, at (query rows a KV head, table width):
        # SDAR's block pass, Laguna's two layer kinds, Pythia, Ouro: a
        # step of 1 MiB of K and V
        (4, 64, 128, jnp.bfloat16, (4, 8, 32, 48)),
        (8, 64, 128, jnp.bfloat16, (8, 4, 6, 136)),
        (8, 64, 128, jnp.bfloat16, (8, 4, 9, 136)),
        (16, 64, 128, jnp.bfloat16, (16, 2, 1, 32)),
        (16, 64, 128, jnp.bfloat16, (16, 2, 1, 5)),
        # a page that is a step's bytes already: one
        (16, 64, 256, jnp.bfloat16, (16, 1, 1, 32)),
        # a table narrower than the span the bytes ask for
        (4, 64, 128, jnp.bfloat16, (4, 3, 32, 3)),
        # pages of 16 slots: no more than the most a step takes
        (12, 16, 64, jnp.bfloat16, (12, 16, 1, 128)),
        # int8 pages: half the bytes a slot, as many pages as fit the
        # step's share of VMEM beside their widened copies
        (16, 64, 128, jnp.int8, (16, 2, 1, 32)),
        (24, 64, 128, jnp.int8, (24, 1, 1, 32)),
    ])
    def test_heads_per_step(self, H, ps, D, dtype, want):
        if isinstance(want, tuple):
            *want, group, table = want
            got = step_geometry(H, ps, D, dtype, group=group,
                                table_width=table)
            assert got == tuple(want)
            return
        hb, pages = step_geometry(H, ps, D, dtype)
        assert hb == want and H % hb == 0 and pages >= 1

    @pytest.mark.parametrize("stacked", [False, True],
                             ids=["one_layer", "layer_of_stack"])
    def test_split_heads_match_xla(self, stacked):
        """A shape whose page of all heads does not fit a step: the grid
        gets a head-group dimension, through `step_geometry` alone."""
        rng = np.random.default_rng(9)
        B, H, D, ps, NP, P = 3, 32, 256, 64, 3, 10
        assert step_geometry(H, ps, D, jnp.float32)[0] < H
        q, kp, vp, pt, _ = _rand_paged(rng, B, H, D, ps, NP, P)
        lens = jnp.asarray([64 * 3, 0, 70], jnp.int32)
        kw = {}
        if stacked:
            kp, vp, kw = kp[None], vp[None], {"layer": 0}
        o_pl = paged_decode_attention(q, kp, vp, pt, lens,
                                      backend="pallas", **kw)
        o_xla = paged_decode_attention(q, kp, vp, pt, lens, backend="xla",
                                       **kw)
        np.testing.assert_allclose(np.asarray(o_xla), np.asarray(o_pl),
                                   atol=1e-5)
        assert (np.asarray(o_pl)[1] == 0.0).all()

    # page 16, table 8 wide: an inactive row, one token, a page edge,
    # rows that end in every page of a span of 2 or 4 (mid-span, at a
    # span's edge: 32, 64, 96), and a full table
    SPANS = [0, 1, 16, 17, 33, 49, 64, 65, 81, 97, 113, 128]

    @pytest.mark.parametrize("stacked", [False, True],
                             ids=["one_layer", "layer_of_stack"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["fp32", "bf16"])
    @pytest.mark.parametrize("G,r,pages,window", [
        (4, 32, 4, None),       # SDAR's block pass: 4 rows x 8 query heads
        (8, 6, 2, None),        # Laguna's full layers
        (8, 9, 2, 40),          # Laguna's window layers
        (8, 9, 4, 24),          # a window inside one span
        (4, 1, 1, None),        # one row a head: a page a step
        (4, 1, 2, None),        # one row a head: a span of two
        (4, 1, 2, 40),
    ], ids=["sdar_4x32_p4", "laguna_8x6_p2", "laguna_8x9_p2_window",
            "group_8x9_p4_window", "mha_p1", "mha_p2", "mha_p2_window"])
    def test_kernel_over_groups_spans_and_ragged_rows(
            self, monkeypatch, G, r, pages, window, dtype, stacked):
        """Each form of the grid step (a KV head's query group against its
        own slots / all rows against all slots; one page / a span of
        pages a step, chosen by bytes: the threshold is set so that the
        shape asks for `pages`) against the XLA form and the dense
        oracle, over rows that end in every slot of a span."""
        rng = np.random.default_rng(G * r + pages)
        B, ps, NP, D = len(self.SPANS), 16, 8, 64
        H, P = G * r, len(self.SPANS) * 8 + 1
        q, _, _, pt, pages_np = _rand_paged(rng, B, H, D, ps, NP, P)
        kp, vp = (jnp.asarray(rng.normal(size=(P, G, ps, D)), jnp.float32)
                  for _ in range(2))
        lens = jnp.asarray(self.SPANS, jnp.int32)
        # dead entries hold the trash page, as the scheduler pads them,
        # and so do the pages a window has left behind
        at = np.arange(NP)[None, :] * ps
        live = at < np.asarray(lens)[:, None]
        if window:
            live &= at + ps > np.asarray(lens)[:, None] - window
        pt = jnp.where(live, pt, 0)
        ref = _dense_oracle(q, kp, vp, pages_np, lens, B, H, D, NP, window)
        qc, kc, vc = (t.astype(dtype) for t in (q, kp, vp))
        if stacked:
            kc, vc = (jnp.stack([t[::-1], t, t * 0]) for t in (kc, vc))
        monkeypatch.setattr(
            decode_attention, "_STEP_MIN_BYTES",
            pages * 2 * G * ps * D * jnp.dtype(dtype).itemsize)

        def call(backend):
            layer = {"layer": jnp.int32(1)} if stacked else {}
            return paged_decode_attention(qc, kc, vc, pt, lens,
                                          backend=backend, window=window,
                                          **layer)

        o_pl = call("pallas")
        report = dispatch_report()["decode_attention"]
        assert report["decode_pages_per_step"] == pages
        assert report["decode_heads_per_step"] == G
        assert report["decode_scores"] == \
            ("per_head" if r > 1 else "collapsed")
        o_xla = call("xla")
        assert "decode_pages_per_step" not in \
            dispatch_report()["decode_attention"]
        assert o_pl.dtype == dtype and o_pl.shape == (B, H, D)
        exact = dtype == jnp.float32
        np.testing.assert_allclose(
            np.asarray(o_pl, np.float32), np.asarray(o_xla, np.float32),
            atol=5e-6 if exact else 2e-2)
        np.testing.assert_allclose(ref, np.asarray(o_pl, np.float32),
                                   atol=5e-6 if exact else 3e-2)
        assert (np.asarray(o_pl, np.float32)[0] == 0.0).all()

    @pytest.mark.parametrize("shape,want", [
        # (B, H, KV heads, table width, block pass, window): SDAR's block
        # pass, Laguna's full and window layers, Pythia's decode step
        ((32, 128, 4, 48, True, None), (4, 8, "per_head")),
        ((32, 48, 8, 136, False, None), (8, 4, "per_head")),
        ((32, 72, 8, 136, False, 512), (8, 4, "per_head")),
        ((32, 16, 16, 32, False, None), (16, 2, "collapsed")),
    ], ids=["sdar_block", "laguna_full", "laguna_window", "pythia"])
    def test_dispatch_report_names_the_step(self, shape, want):
        """`dispatch_report()["decode_attention"]` says how the grid step
        engaged at the last call traced, at the serve cells' own shapes
        (bf16 pages of 64 x 128; traced, not run)."""
        B, H, G, NP, block, window = shape
        S = jax.ShapeDtypeStruct
        pool = S((2, 9, G, 64, 128), jnp.bfloat16)
        jax.eval_shape(
            lambda q, k, v, pt, ln, layer: paged_decode_attention(
                q, k, v, pt, ln, backend="pallas", layer=layer,
                window=window, block_pass=block),
            S((B, H, 128), jnp.bfloat16), pool, pool, S((B, NP), jnp.int32),
            S((B,), jnp.int32), S((), jnp.int32))
        report = dispatch_report()["decode_attention"]
        assert (report["decode_heads_per_step"],
                report["decode_pages_per_step"],
                report["decode_scores"]) == want

    def test_decode_steps_lists_live_pages_only(self):
        lens = jnp.asarray([0, 1, 64, 65, 130, 256], jnp.int32)
        n, row, start = decode_steps(lens, 64, 4)
        # an inactive row keeps one step; nobody pays for the table's
        # width: 12 steps where the (B, pages) grid had 24
        assert int(n) == 1 + 1 + 1 + 2 + 3 + 4
        assert row.shape == (6 * 4,)
        assert np.asarray(row)[:12].tolist() == \
            [0, 1, 2, 3, 3, 4, 4, 4, 5, 5, 5, 5]
        assert np.asarray(row).max() == 5          # the unread tail too
        assert np.asarray(start).tolist() == [0, 1, 2, 3, 5, 8]
        full, _, _ = decode_steps(jnp.full((6,), 256, jnp.int32), 64, 4)
        assert int(full) == 6 * 4
        # a step a SPAN of 2 pages: a row's last step may be half live
        n, row, start = decode_steps(lens, 64, 4, pages=2)
        assert int(n) == 1 + 1 + 1 + 1 + 2 + 2
        assert row.shape == (6 * 2,)
        assert np.asarray(row)[:8].tolist() == [0, 1, 2, 3, 4, 4, 5, 5]
        assert np.asarray(start).tolist() == [0, 1, 2, 3, 4, 6]
        # a span wider than the table: a step a row, one entry a row
        n, row, _ = decode_steps(lens, 64, 4, pages=8)
        assert int(n) == 6 and np.asarray(row).tolist() == list(range(6))
        # under a window the spans count from the row's first live page:
        # 130 tokens, window 64: pages 1 and 2 (one step of 2, two of 1);
        # 256 tokens: page 3 alone
        for pages, want in ((1, [1, 1, 1, 2, 2, 1]), (2, [1] * 6)):
            n, _, start = decode_steps(lens, 64, 4, 64, pages)
            assert int(n) == sum(want)
            assert np.asarray(start).tolist() == \
                np.cumsum([0] + want[:-1]).tolist()

    def test_span_table_resolves_the_slots_past_a_row(self):
        """What a step's operands fetch: a live slot its page; a slot past
        the row's length the page its operand held a step ago (`pages`
        entries back: no second fetch) where the row has such a step,
        else the trash page; an inactive row the trash page alone."""
        table = jnp.asarray([[11, 12, 13, 14, 15, 16],
                             [21, 22, 0, 0, 0, 0],
                             [31, 32, 33, 34, 35, 36],
                             [7, 7, 7, 7, 7, 7]], jnp.int32)
        lens = jnp.asarray([5 * 8, 10, 6 * 8, 0], jnp.int32)
        got = np.asarray(span_table(table, lens, 8, 4))
        assert got.shape == (4, 6 + 3)
        # 5 live pages: the second step's slots 1-3 repeat pages 2-4
        assert got[0].tolist() == [11, 12, 13, 14, 15, 12, 13, 14, 15]
        # one step, two live: the rest of it is the trash page (entries
        # no step reads are don't-care)
        assert got[1, :4].tolist() == [21, 22, 0, 0]
        assert got[2, :8].tolist() == [31, 32, 33, 34, 35, 36, 33, 34]
        assert not got[3, :4].any()
        # a window of 16: 40 tokens start at page 3 (pages 3 and 4, one
        # step of 2), 41 tokens at page 3 too (pages 3, 4 and 5: the
        # second step's dead slot repeats page 4); a held page is never
        # one the window has left behind
        got = np.asarray(span_table(table, jnp.asarray([40, 10, 41, 0]), 8,
                                    2, window=16))
        assert got[0, 3:5].tolist() == [14, 15]
        assert got[2, 3:7].tolist() == [34, 35, 36, 35]
        assert got[1, :2].tolist() == [21, 22]

    def test_shape_validation(self):
        rng = np.random.default_rng(4)
        q, kp, vp, pt, _ = _rand_paged(rng, 2, 2, 64, 8, 2, 8)
        lens = jnp.asarray([1, 1], jnp.int32)
        with pytest.raises(ValueError, match="v_pages"):
            paged_decode_attention(q, kp, vp[:4], pt, lens)
        with pytest.raises(ValueError, match="heads"):
            paged_decode_attention(q[:, :1], kp, vp, pt, lens)
        with pytest.raises(ValueError, match="lengths"):
            paged_decode_attention(q, kp, vp, pt, lens[:1])
        with pytest.raises(ValueError, match="backend"):
            paged_decode_attention(q, kp, vp, pt, lens, backend="cuda")


# ---------------------------------------------------------------------------
# paged KV cache allocator
# ---------------------------------------------------------------------------

class TestPagedKVCache:
    def _cache(self, pages=8):
        return PagedKVCache(num_layers=2, num_pages=pages, num_heads=2,
                            page_size=8, head_dim=16, dtype=jnp.float32)

    def test_shapes_and_reserved_trash_page(self):
        c = self._cache()
        assert c.k.shape == (2, 8, 2, 8, 16)
        assert c.num_free == 7            # page 0 reserved
        got = c.allocate(7)
        assert 0 not in got and sorted(got) == list(range(1, 8))

    def test_allocate_free_roundtrip(self):
        c = self._cache()
        a = c.allocate(3)
        b = c.allocate(2)
        assert len(set(a) | set(b)) == 5
        assert c.allocate(3) is None      # only 2 left: all-or-nothing
        assert c.allocate(0) == []
        c.free(b)
        assert c.num_free == 4

    def test_free_validation(self):
        c = self._cache()
        with pytest.raises(ValueError, match="double free"):
            c.free([3])
        pages = c.allocate(1)
        c.free(pages)
        with pytest.raises(ValueError, match="not an allocatable"):
            c.free([0])

    def test_min_pool_size(self):
        with pytest.raises(ValueError, match="num_pages"):
            self._cache(pages=1)

    def test_pages_for_tokens(self):
        assert pages_for_tokens(1, 8) == 1
        assert pages_for_tokens(8, 8) == 1
        assert pages_for_tokens(9, 8) == 2


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def _sched(pages=32, budget=128, max_batch=4,
           prefill_lengths=(16, 32), prefill_batches=(1, 2),
           decode_batches=(1, 2, 4), max_seq_len=64):
    cache = PagedKVCache(num_layers=1, num_pages=pages, num_heads=2,
                         page_size=16, head_dim=16, dtype=jnp.float32)
    return cache, ContinuousBatchingScheduler(
        cache, max_seq_len=max_seq_len, token_budget=budget,
        max_batch_size=max_batch, prefill_lengths=list(prefill_lengths),
        prefill_batch_sizes=list(prefill_batches),
        decode_batch_sizes=list(decode_batches))


class TestScheduler:
    def test_fifo_admission_and_buckets(self):
        _, s = _sched()
        for n in (7, 13, 20):
            s.add_request(Request(prompt=list(range(1, n + 1)),
                                  max_new_tokens=4))
        plan = s.schedule()
        # 7 and 13 share the 16 bucket; 20 (bucket 32) waits — one
        # length bucket per prefill call
        assert len(plan.prefills) == 2
        assert plan.prefill_len == 16 and plan.prefill_batch == 2
        assert [len(r.pages) for r in plan.prefills] == [1, 1]
        assert not plan.decodes
        for r in plan.prefills:
            s.complete_prefill(r, 1)
        plan2 = s.schedule()
        assert len(plan2.prefills) == 1 and plan2.prefill_len == 32
        assert len(plan2.decodes) == 2 and plan2.decode_batch == 2

    def test_token_budget_caps_admission(self):
        _, s = _sched(budget=40)
        for _ in range(3):
            s.add_request(Request(prompt=list(range(1, 30)),
                                  max_new_tokens=2))
        plan = s.schedule()          # each prefill costs its 32 bucket
        assert len(plan.prefills) == 1
        assert len(s.waiting) == 2

    def test_page_pool_caps_admission(self):
        # 3 usable pages; each 32-bucket prompt needs 2
        _, s = _sched(pages=4)
        for _ in range(2):
            s.add_request(Request(prompt=list(range(1, 30)),
                                  max_new_tokens=2))
        plan = s.schedule()
        assert len(plan.prefills) == 1 and len(s.waiting) == 1

    def test_eviction_frees_youngest(self):
        cache, s = _sched(pages=5, max_seq_len=64)   # 4 usable pages
        a = Request(prompt=list(range(1, 31)), max_new_tokens=20)
        b = Request(prompt=list(range(1, 31)), max_new_tokens=4)
        s.add_request(a)
        s.add_request(b)
        plan = s.schedule()
        assert len(plan.prefills) == 2               # 2 pages each
        for r in plan.prefills:
            s.complete_prefill(r, 5)
        # fill a's bucket (positions 30, 31): no page growth yet
        for _ in range(2):
            plan = s.schedule()
            assert not plan.evicted
            for r in plan.decodes:
                s.complete_decode(r, 5)
        # position 32 now needs page 3 for BOTH; pool is empty → the
        # youngest (b) is evicted and its pages hand a the growth room
        plan = s.schedule()
        assert plan.evicted == [b]
        assert b.state == "waiting" and b.pages == [] and b.cached == 0
        assert len(b.context) == len(b.prompt) + 3   # keeps its tokens
        assert a in plan.decodes and b not in plan.decodes

    def test_completion_frees_pages(self):
        cache, s = _sched()
        r = Request(prompt=[1, 2, 3], max_new_tokens=1)
        s.add_request(r)
        plan = s.schedule()
        assert cache.num_free == 31 - len(plan.prefills[0].pages)
        s.complete_prefill(r, 7)     # max_new_tokens reached
        assert r.state == "finished" and r.generated == [7]
        assert cache.num_free == 31

    def test_prompt_validation(self):
        _, s = _sched()
        with pytest.raises(ValueError, match="empty"):
            s.add_request(Request(prompt=[], max_new_tokens=1))
        with pytest.raises(ValueError, match="max_new_tokens"):
            s.add_request(Request(prompt=[1, 2], max_new_tokens=0))
        with pytest.raises(ValueError, match="largest prefill"):
            s.add_request(Request(prompt=list(range(40)),
                                  max_new_tokens=1))
        with pytest.raises(ValueError, match="max_seq_len"):
            s.add_request(Request(prompt=list(range(1, 30)),
                                  max_new_tokens=60))

    def test_prefill_length_page_alignment(self):
        cache = PagedKVCache(num_layers=1, num_pages=8, num_heads=2,
                             page_size=16, head_dim=16)
        with pytest.raises(ValueError, match="multiple"):
            ContinuousBatchingScheduler(
                cache, max_seq_len=64, token_budget=64, max_batch_size=2,
                prefill_lengths=[24], prefill_batch_sizes=[1],
                decode_batch_sizes=[1, 2])
        with pytest.raises(ValueError, match="multiple"):
            ContinuousBatchingScheduler(
                cache, max_seq_len=60, token_budget=64, max_batch_size=2,
                prefill_lengths=[16], prefill_batch_sizes=[1],
                decode_batch_sizes=[1, 2])

    def test_token_budget_must_cover_largest_bucket(self):
        # budget 16 < bucket 32: such a prompt could never admit — the
        # queue would livelock with run() spinning on empty plans
        with pytest.raises(ValueError, match="livelock"):
            _sched(budget=16)

    def test_evicted_regrowth_exempt_from_budget(self):
        # user ladder tops at 32 and budget 48 < the extended 64
        # bucket: an evicted request regrowing past the ladder must
        # bypass the budget for the step's first prefill, or the queue
        # wedges behind it forever
        cache, s = _sched(pages=5, budget=48, max_seq_len=64)
        a = Request(prompt=list(range(1, 29)), max_new_tokens=20)
        b = Request(prompt=list(range(1, 31)), max_new_tokens=20)
        s.add_request(a)
        s.add_request(b)
        plan = s.schedule()
        assert plan.prefills == [a]      # budget admits ONE 32-bucket
        s.complete_prefill(a, 5)
        plan = s.schedule()
        assert plan.prefills == [b] and a in plan.decodes
        s.complete_prefill(b, 5)
        for r in plan.decodes:
            s.complete_decode(r, 5)
        evicted = []
        for _ in range(8):               # decode until b self-evicts
            plan = s.schedule()
            evicted += plan.evicted
            for r in plan.decodes:
                s.complete_decode(r, 5)
            if evicted:
                break
        assert evicted == [b]
        assert len(b.context) == 33      # bucket 64 > budget 48
        a.max_new_tokens = len(a.generated) + 1    # finish a next step
        plan = s.schedule()
        for r in plan.decodes:
            s.complete_decode(r, 5)
        assert a.state == "finished"     # pages freed
        plan = s.schedule()
        assert plan.prefills == [b] and plan.prefill_len == 64


# ---------------------------------------------------------------------------
# config block
# ---------------------------------------------------------------------------

class TestInferenceConfig:
    def test_absent_or_disabled(self):
        assert parse_inference_block({}) is False
        assert parse_inference_block(
            {"inference": {"enabled": False}}) is False

    def test_minimal_defaults(self):
        p = parse_inference_block({"inference": {"enabled": True}})
        assert p["page_size"] == 128 and p["temperature"] == 0.0
        assert p["kernel"] == "auto" and p["prefill_lengths"] is None

    @pytest.mark.parametrize("block,match", [
        ({"enabled": True, "page_szie": 128}, "Unknown"),
        ({"enabled": "yes"}, "boolean"),
        ({"enabled": True, "page_size": 12}, "multiple of 8"),
        ({"enabled": True, "num_pages": 1}, ">= 2"),
        ({"enabled": True, "token_budget": 0}, ">= 1"),
        ({"enabled": True, "prefill_lengths": []}, "non-empty"),
        ({"enabled": True, "prefill_lengths": [256, 128]}, "increasing"),
        ({"enabled": True, "prefill_lengths": [100]}, "multiples"),
        ({"enabled": True, "max_batch_size": 8,
          "decode_batch_sizes": [1, 4]}, "tops out"),
        ({"enabled": True, "temperature": -1}, "temperature"),
        ({"enabled": True, "kernel": "cuda"}, "kernel"),
        ({"enabled": True, "kv_cache_dtype": "int7"}, "precision"),
    ])
    def test_rejects(self, block, match):
        with pytest.raises(DeepSpeedConfigError, match=match):
            parse_inference_block({"inference": block})

    def test_rides_deepspeed_config(self):
        from deeperspeed_tpu.runtime.config import DeepSpeedConfig
        cfg = DeepSpeedConfig(
            {"train_batch_size": 8,
             "inference": {"enabled": True, "page_size": 64}},
            world_size=8)
        assert cfg.inference_enabled
        assert cfg.inference_params["page_size"] == 64


# ---------------------------------------------------------------------------
# engine: greedy paged decode == teacher-forced argmax
# ---------------------------------------------------------------------------

def _engine_config(**kw):
    block = {"enabled": True, "page_size": 16, "num_pages": 64,
             "max_batch_size": 4, "token_budget": 256,
             "prefill_lengths": [16, 32, 64],
             "prefill_batch_sizes": [1, 2],
             "decode_batch_sizes": [1, 2, 4]}
    block.update(kw)
    return {"inference": block}




@pytest.fixture(scope="module")
def tiny_neox():
    """(config, model, params) of the tiny NeoX on the XLA path."""
    cfg = GPTNeoXConfig.tiny()
    model = GPTNeoX(config=cfg, use_pallas=False)
    return cfg, model, model.init_params(jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def _plain_engine(tiny_neox):
    _, model, params = tiny_neox
    return InferenceEngine(model, config=_engine_config(), params=params)


@pytest.fixture
def plain_engine(_plain_engine):
    """The module's one engine over `_engine_config()`, for a test that
    only generates and compares: its programs compile once a module. It
    comes back with nothing queued and every page in the pool."""
    yield _plain_engine
    assert not _plain_engine.scheduler.has_work
    assert _plain_engine.cache.num_free == _plain_engine.cache.num_pages - 1


class TestGreedyDecodeParity:
    def test_gpt_neox_token_identical(self, tiny_neox, plain_engine):
        cfg, _, params = tiny_neox
        eng = plain_engine
        rng = np.random.default_rng(0)
        prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
                   for n in (5, 11, 17, 30)]
        outs = eng.generate(prompts, max_new_tokens=6)
        for p, o in zip(prompts, outs):
            assert o == teacher_forced(cfg, params, neox_forward, p, 6)
        # every page returned to the pool
        assert eng.cache.num_free == eng.cache.num_pages - 1

    def test_gpt2_token_identical(self):
        cfg = GPT2Config.tiny()                     # max_seq_len 64
        model = GPT2(config=cfg, use_pallas=False)
        params = model.init_params(jax.random.PRNGKey(2))
        eng = InferenceEngine(model, config=_engine_config(
            prefill_lengths=[16, 32], num_pages=32), params=params)
        rng = np.random.default_rng(1)
        prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
                   for n in (4, 9, 21)]
        outs = eng.generate(prompts, max_new_tokens=5)
        for p, o in zip(prompts, outs):
            assert o == teacher_forced(cfg, params, gpt2_forward, p, 5)

    def test_pallas_kernel_path_token_identical(self, tiny_neox):
        """Force the interpreted Pallas kernel end-to-end on CPU: the
        acceptance pin runs through the real kernel, not the fallback."""
        cfg, model, params = tiny_neox
        eng = InferenceEngine(model, config=_engine_config(
            kernel="pallas", prefill_lengths=[16], num_pages=16),
            params=params)
        rng = np.random.default_rng(2)
        prompt = list(rng.integers(1, cfg.vocab_size, size=9))
        (out,) = eng.generate([prompt], max_new_tokens=4)
        assert out == teacher_forced(cfg, params, neox_forward, prompt, 4)
        from deeperspeed_tpu.ops.pallas.decode_attention import \
            _LAST_BACKEND
        assert _LAST_BACKEND["decode"] == "pallas"

    def test_eviction_preserves_greedy_tokens(self, tiny_neox):
        """A request evicted mid-flight re-prefills its full context and
        must still emit the exact greedy continuation."""
        cfg, model, params = tiny_neox
        # 4 usable pages of 16 = 64 tokens; two 30-token prompts force
        # an eviction when the older request outgrows its bucket
        eng = InferenceEngine(model, config=_engine_config(
            num_pages=5, max_seq_len=64, prefill_lengths=[32],
            max_batch_size=2, decode_batch_sizes=[1, 2]), params=params)
        rng = np.random.default_rng(3)
        pa = list(rng.integers(1, cfg.vocab_size, size=30))
        pb = list(rng.integers(1, cfg.vocab_size, size=30))
        outs = eng.generate([pa, pb], max_new_tokens=6)
        assert eng.stats["evictions"] >= 1
        assert outs[0] == teacher_forced(cfg, params, neox_forward, pa, 6)
        assert outs[1] == teacher_forced(cfg, params, neox_forward, pb, 6)

    def test_temperature_sampling_deterministic(self, tiny_neox):
        cfg, model, params = tiny_neox
        outs = []
        for _ in range(2):
            eng = InferenceEngine(
                model, config=_engine_config(temperature=0.8, seed=11),
                params=params)
            outs.append(eng.generate([[5, 6, 7]], max_new_tokens=6)[0])
        assert outs[0] == outs[1]

    def test_generate_drains_finished(self, plain_engine):
        """Long-lived serving must not accumulate completed requests:
        generate() consumes pop_finished(), so repeated batches leave
        the scheduler's finished list empty."""
        eng = plain_engine
        for _ in range(3):
            eng.generate([[1, 2, 3], [4, 5]], max_new_tokens=2)
        assert eng.scheduler.finished == []

    def test_eos_stops_early_and_frees_pages(self, tiny_neox, plain_engine):
        cfg, _, params = tiny_neox
        eng = plain_engine
        prompt = [3, 4, 5]
        ref = teacher_forced(cfg, params, neox_forward, prompt, 8)
        eos = ref[2]
        (out,) = eng.generate([prompt], max_new_tokens=8,
                              eos_token_id=eos)
        assert out == ref[:3]         # stops AT the eos token
        assert eng.cache.num_free == eng.cache.num_pages - 1


class TestNoRecompiles:
    def test_mixed_stream_zero_recompiles_after_warmup(self, tiny_neox):
        """The acceptance pin: a mixed prefill/decode stream holds the
        compile count constant once the bucket ladder has warmed up."""
        cfg, model, params = tiny_neox
        eng = InferenceEngine(model, config=_engine_config(),
                              params=params)
        rng = np.random.default_rng(4)

        def stream(seed):
            r = np.random.default_rng(seed)
            return [list(r.integers(1, cfg.vocab_size, size=n))
                    for n in (5, 12, 20, 9, 31, 7)]

        eng.generate(stream(0), max_new_tokens=5)    # warmup: all buckets
        warm = eng.compile_count()
        assert warm > 0
        eng.generate(stream(1), max_new_tokens=5)    # same bucket coverage
        assert eng.compile_count() == warm

    def test_compile_count_tracks_new_buckets(self, tiny_neox):
        cfg, model, params = tiny_neox
        eng = InferenceEngine(model, config=_engine_config(),
                              params=params)
        eng.generate([[1, 2, 3]], max_new_tokens=2)
        first = eng.compile_count()
        # a longer prompt warms a NEW prefill length bucket
        eng.generate([list(range(1, 25))], max_new_tokens=2)
        assert eng.compile_count() > first


# ---------------------------------------------------------------------------
# engine validation / wiring
# ---------------------------------------------------------------------------

class TestEngineValidation:
    def _model(self, **kw):
        cfg = GPTNeoXConfig.tiny(**kw)
        return GPTNeoX(config=cfg, use_pallas=False)

    def test_requires_inference_block(self):
        with pytest.raises(DeepSpeedConfigError, match="inference"):
            InferenceEngine(self._model(), config={})
        with pytest.raises(DeepSpeedConfigError, match="config"):
            InferenceEngine(self._model())

    def test_rejects_moe_and_sparse(self):
        with pytest.raises(DeepSpeedConfigError, match="MoE"):
            InferenceEngine(self._model(moe_num_experts=4),
                            config=_engine_config())
        with pytest.raises(DeepSpeedConfigError, match="dense"):
            InferenceEngine(self._model(attention_engine="sparse"),
                            config=_engine_config())

    def test_rejects_overlong_window_and_tiny_pool(self):
        with pytest.raises(DeepSpeedConfigError, match="max_seq_len"):
            InferenceEngine(self._model(),
                            config=_engine_config(max_seq_len=4096))
        with pytest.raises(DeepSpeedConfigError, match="num_pages"):
            InferenceEngine(self._model(),
                            config=_engine_config(num_pages=2))

    def test_rejects_prefill_bucket_beyond_window(self):
        # a bucket past the window is a config error, not a silent drop
        with pytest.raises(DeepSpeedConfigError, match="serving window"):
            InferenceEngine(self._model(), config=_engine_config(
                prefill_lengths=[16, 2048]))

    def test_rejects_misaligned_window(self):
        # a misaligned window would leave a re-prefill-less tail: an
        # evicted request there would crash the serving loop — init-
        # time config error instead (parse strictness discipline)
        with pytest.raises(DeepSpeedConfigError, match="multiple"):
            InferenceEngine(self._model(), config=_engine_config(
                max_seq_len=100, prefill_lengths=[16, 32]))

    def test_prefill_token_accounting_excludes_sampled_token(self):
        model = self._model()
        eng = InferenceEngine(model, config=_engine_config(),
                              params=model.init_params(
                                  jax.random.PRNGKey(1)))
        eng.generate([[1, 2, 3, 4, 5]], max_new_tokens=3)
        s = eng.serve_stats()
        assert s["prefill_tokens"] == 5      # not 6: first sampled
        assert s["decode_tokens"] == 2       # token is decode-side

    def test_compute_dtype_inferred_from_weights(self):
        """Round-tripped params (fp32 1-D leaves, bf16 weights — what
        `prepare_inference_params` produces) must infer bf16, not the
        first leaf's fp32."""
        from deeperspeed_tpu.module_inject.replace_module import \
            prepare_inference_params
        model = self._model()
        params = prepare_inference_params(
            model.init_params(jax.random.PRNGKey(0)), jnp.bfloat16)
        eng = InferenceEngine(model, config=_engine_config(),
                              params=params)
        assert eng.compute_dtype == jnp.bfloat16
        assert eng.cache.k.dtype == jnp.bfloat16

    def test_kv_cache_dtype_override(self):
        """kv_cache_dtype sets the CACHE pools only — the weights keep
        their own (serving compute) dtype."""
        model = self._model()
        eng = InferenceEngine(model,
                              config=_engine_config(
                                  kv_cache_dtype="bfloat16"),
                              params=model.init_params(
                                  jax.random.PRNGKey(0)))
        assert eng.cache.k.dtype == jnp.bfloat16
        assert eng.params["embed"]["wte"].dtype == jnp.float32
        assert eng.compute_dtype == jnp.float32
        # 1-D leaves stay fp32 (layernorm quality)
        assert eng.params["final_ln"]["scale"].dtype == jnp.float32
        # decode runs through the reduced-precision pools
        (out,) = eng.generate([[1, 2, 3]], max_new_tokens=2)
        assert len(out) == 2


# ---------------------------------------------------------------------------
# tensor-parallel serving (heads sharded over the model axis)
# ---------------------------------------------------------------------------

class TestTensorParallelServing:
    def test_tp_decode_matches_single_device(self, devices):
        from deeperspeed_tpu.parallel.mesh import build_mesh
        from deeperspeed_tpu.parallel.topology import ProcessTopology
        cfg = GPTNeoXConfig.tiny()               # 4 heads
        model = GPTNeoX(config=cfg, use_pallas=False)
        params = model.init_params(jax.random.PRNGKey(9))
        mesh = build_mesh(ProcessTopology(axes=["data", "model"],
                                          dims=[4, 2]), devices)
        rng = np.random.default_rng(5)
        prompts = [list(rng.integers(1, cfg.vocab_size, size=n))
                   for n in (6, 14)]
        ref_eng = InferenceEngine(model, config=_engine_config(),
                                  params=params)
        ref = ref_eng.generate(prompts, max_new_tokens=5)
        tp_eng = InferenceEngine(model, config=_engine_config(),
                                 params=params, mesh=mesh)
        assert tp_eng.mp == 2
        out = tp_eng.generate(prompts, max_new_tokens=5)
        assert out == ref
        # the cache really is head-sharded over the model axis
        spec = tp_eng.cache.k.sharding.spec
        assert spec[2] == "model"


# ---------------------------------------------------------------------------
# params-only checkpoint load + base-engine API parity
# ---------------------------------------------------------------------------

def _train_engine(model, tmpdir=None, **extra):
    conf = {"train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adam", "params": {"lr": 1e-3}}}
    conf.update(extra)
    eng, *_ = deeperspeed_tpu.initialize(
        model=model, config_params=conf, rng=jax.random.PRNGKey(0))
    return eng


class TestModuleOnlyCheckpoint:
    def test_module_only_skips_training_state(self, tmp_path):
        cfg = GPTNeoXConfig.tiny()
        model = GPTNeoX(config=cfg, use_pallas=False)
        eng = _train_engine(model)
        toks = np.random.default_rng(0).integers(
            1, cfg.vocab_size, size=(8, 32)).astype(np.int32)
        eng.train_batch(batch=(toks[None], toks[None]))
        eng.save_checkpoint(str(tmp_path), tag="t0")

        wte0 = np.asarray(eng.params_to_natural(
            eng.state.params)["embed"]["wte"])
        opt0 = jax.tree_util.tree_leaves(eng.state.opt_state)[0]
        steps0 = eng.global_steps

        # poison params; advance a counter the load must NOT touch
        eng.state = eng.state._replace(
            params=jax.tree_util.tree_map(lambda p: p * 0,
                                          eng.state.params))
        eng.global_steps = 777
        path, _ = eng.load_checkpoint(str(tmp_path), tag="t0",
                                      module_only=True)
        assert path is not None
        wte1 = np.asarray(eng.params_to_natural(
            eng.state.params)["embed"]["wte"])
        np.testing.assert_array_equal(wte0, wte1)
        assert eng.global_steps == 777            # counters untouched
        assert jax.tree_util.tree_leaves(
            eng.state.opt_state)[0] is opt0       # moments untouched
        assert steps0 == 1

    def test_module_only_verifies_manifest(self, tmp_path):
        cfg = GPTNeoXConfig.tiny()
        model = GPTNeoX(config=cfg, use_pallas=False)
        eng = _train_engine(model)
        eng.save_checkpoint(str(tmp_path), tag="good")
        # corrupt a payload byte: CRC must catch it on an explicit tag
        import glob
        victim = glob.glob(str(tmp_path / "good" / "*model_states*"))[0]
        blob = bytearray(open(victim, "rb").read())
        blob[len(blob) // 2] ^= 0xFF
        open(victim, "wb").write(bytes(blob))
        with pytest.raises(RuntimeError, match="manifest"):
            eng.load_checkpoint(str(tmp_path), tag="good",
                                module_only=True)

    def test_inference_engine_load_falls_back(self, tmp_path):
        """`latest` names a corrupt save → the serving load falls back
        to the previous committed tag (the fallback discipline rides
        into module-only loads unchanged)."""
        cfg = GPTNeoXConfig.tiny()
        model = GPTNeoX(config=cfg, use_pallas=False)
        eng = _train_engine(model)
        eng.save_checkpoint(str(tmp_path), tag="old")
        wte_old = np.asarray(eng.params_to_natural(
            eng.state.params)["embed"]["wte"])
        eng.state = eng.state._replace(
            params=jax.tree_util.tree_map(lambda p: p + 1,
                                          eng.state.params))
        eng.save_checkpoint(str(tmp_path), tag="new")
        import glob
        victim = glob.glob(str(tmp_path / "new" / "*model_states*"))[0]
        blob = bytearray(open(victim, "rb").read())
        blob[len(blob) // 2] ^= 0xFF
        open(victim, "wb").write(bytes(blob))

        ie = InferenceEngine(model, config=_engine_config(),
                             params=model.init_params(
                                 jax.random.PRNGKey(1)))
        ie.generate([[1, 2, 3]], max_new_tokens=2)    # warm some buckets
        warm = ie.compile_count()
        path, _ = ie.load_checkpoint(str(tmp_path))   # latest == new
        assert path is not None and path.endswith("old")
        np.testing.assert_array_equal(
            np.asarray(ie.params["embed"]["wte"]), wte_old)
        # weight hot-swap keeps the warmed executables (params are jit
        # arguments, same avals = cache hit)
        ie.generate([[1, 2, 3]], max_new_tokens=2)
        assert ie.compile_count() == warm


class TestBaseEngineInferenceAPI:
    def test_eval_batch_return_logits_and_inference_batch(self):
        cfg = GPTNeoXConfig.tiny()
        model = GPTNeoX(config=cfg, use_pallas=False)
        eng = _train_engine(model)
        toks = np.random.default_rng(0).integers(
            1, cfg.vocab_size, size=(8, 32)).astype(np.int32)
        batch = (toks, toks)
        loss = eng.eval_batch(batch)
        loss2, logits = eng.eval_batch(batch, return_logits=True)
        assert logits.shape == (8, 32, cfg.vocab_size)
        np.testing.assert_allclose(float(loss), float(loss2), rtol=1e-6)
        out = eng.inference_batch(batch=batch)
        np.testing.assert_allclose(np.asarray(out), np.asarray(logits),
                                   atol=1e-5)
        # logits really are the model forward
        ref = neox_forward(cfg, eng.params_to_natural(eng.state.params),
                           jnp.asarray(toks), use_pallas=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_loss_fn_only_model_raises(self, tiny_neox):
        cfg, model, params = tiny_neox
        eng, *_ = deeperspeed_tpu.initialize(
            model=model.loss_fn, model_parameters=params,
            config_params={"train_batch_size": 8,
                           "optimizer": {"type": "adam",
                                         "params": {"lr": 1e-3}}})
        toks = np.zeros((8, 16), np.int32)
        with pytest.raises(RuntimeError, match="apply"):
            eng.inference_batch(batch=(toks, toks))


# ---------------------------------------------------------------------------
# synthetic-stream soak (out of tier-1)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestServingSoak:
    def test_open_loop_stream_soak(self, tiny_neox):
        """A fixed-seed open-loop arrival stream over many steps: every
        request completes with its exact greedy continuation, the page
        pool drains to empty, and the compile count freezes after the
        warmup phase."""
        cfg, model, params = tiny_neox
        eng = InferenceEngine(model, config=_engine_config(num_pages=48),
                              params=params)
        rng = np.random.default_rng(6)

        # warm every bucket first
        eng.generate([list(rng.integers(1, 500, size=n))
                      for n in (5, 20, 40)], max_new_tokens=4)
        warm = eng.compile_count()

        # open loop: arrivals keep coming regardless of progress
        pending = {}
        arrivals = [(step, list(rng.integers(1, 500,
                                             size=rng.integers(3, 40))))
                    for step in range(0, 60, 2)]
        submitted = 0
        for step in range(400):
            while submitted < len(arrivals) and \
                    arrivals[submitted][0] <= step:
                rid = eng.submit(arrivals[submitted][1], max_new_tokens=6)
                pending[rid] = arrivals[submitted][1]
                submitted += 1
            if eng.scheduler.has_work:
                eng.step()
            elif submitted == len(arrivals):
                break
        assert not eng.scheduler.has_work
        assert eng.compile_count() == warm
        assert eng.cache.num_free == eng.cache.num_pages - 1
        by_id = {r.request_id: r for r in eng.scheduler.finished
                 if r.request_id in pending}    # warmup also finished
        assert len(by_id) == len(pending)
        for rid, prompt in list(pending.items())[::7]:  # spot-check
            assert list(by_id[rid].generated) == teacher_forced(
                cfg, params, neox_forward, prompt, 6)


# ---------------------------------------------------------------------------
# graceful drain (SIGTERM): stop admissions, finish in-flight, flush,
# exit 0 — serving must NOT inherit the training emergency-save handler
# ---------------------------------------------------------------------------

@pytest.mark.elastic
class TestGracefulDrain:
    def test_config_key(self):
        p = parse_inference_block({"inference": {"enabled": True}})
        assert p["drain_deadline_s"] == 30.0
        p = parse_inference_block({"inference": {
            "enabled": True, "drain_deadline_s": 5}})
        assert p["drain_deadline_s"] == 5.0
        with pytest.raises(DeepSpeedConfigError, match="drain_deadline"):
            parse_inference_block({"inference": {
                "enabled": True, "drain_deadline_s": -1}})

    def test_scheduler_stops_fresh_admissions_only(self):
        _, s = _sched()
        first = Request(prompt=list(range(1, 8)), max_new_tokens=4)
        s.add_request(first)
        plan = s.schedule()
        assert plan.prefills == [first]          # admitted while open
        s.add_request(Request(prompt=list(range(1, 8)),
                              max_new_tokens=4))
        s.stop_admissions()
        plan = s.schedule()
        assert plan.prefills == []               # fresh request held
        assert plan.decodes == [first]           # in-flight continues
        assert s.has_inflight_work
        # an EVICTED request still re-admits during drain (its partial
        # generation is in-flight work)
        s._evict_youngest()
        assert s.has_inflight_work
        plan = s.schedule()
        assert plan.prefills == [first]
        # finish it: only the fresh request remains -> no inflight work
        s.complete_prefill(first, 7)
        for _ in range(3):
            s.complete_decode(first, 7)
        assert first.done and first not in s.running
        assert not s.has_inflight_work
        assert s.has_work                        # the held fresh request

    def _drain_engine(self, **cfg_kw):
        cfg = GPTNeoXConfig.tiny()
        model = GPTNeoX(config=cfg, use_pallas=False)
        params = model.init_params(jax.random.PRNGKey(4))
        return InferenceEngine(model, config=_engine_config(**cfg_kw),
                               params=params), cfg, params

    def test_drain_finishes_inflight_and_holds_queue(self):
        eng, cfg, params = self._drain_engine()
        rng = np.random.default_rng(3)
        p1 = list(rng.integers(1, cfg.vocab_size, size=6))
        p2 = list(rng.integers(1, cfg.vocab_size, size=9))
        r1 = eng.submit(p1, max_new_tokens=4)
        eng.step()                                # p1 in flight
        eng.submit(p2, max_new_tokens=4)          # fresh, queued
        summary = eng.drain()
        assert summary["deadline_hit"] is False
        assert summary["inflight_abandoned"] == 0
        assert summary["unserved"] == 1           # p2 left for successor
        done = {r.request_id: r for r in eng.scheduler.pop_finished()}
        assert list(done[r1].generated) == teacher_forced(
            cfg, params, neox_forward, p1, 4)
        # drained engine flushed its signal handlers
        assert eng._prev_handlers == {}

    def test_drain_deadline_bounds_the_wait(self):
        eng, cfg, _ = self._drain_engine(drain_deadline_s=0)
        rng = np.random.default_rng(5)
        eng.submit(list(rng.integers(1, cfg.vocab_size, size=6)),
                   max_new_tokens=64)
        eng.step()
        summary = eng.drain(deadline_s=0.0)       # no time to finish
        assert summary["deadline_hit"] is True
        assert summary["inflight_abandoned"] == 1

    def test_run_exits_zero_on_drain_request(self):
        eng, cfg, _ = self._drain_engine()
        rng = np.random.default_rng(6)
        eng.submit(list(rng.integers(1, cfg.vocab_size, size=6)),
                   max_new_tokens=3)
        eng.step()
        eng.request_drain()                       # SIGTERM equivalent
        with pytest.raises(SystemExit) as ei:
            eng.run()
        assert ei.value.code == 0
        assert not eng.scheduler.has_inflight_work

    def test_run_honors_drain_on_idle_server(self):
        """SIGTERM while IDLE must still flush-and-exit-0: the drain
        contract cannot depend on traffic being present."""
        eng, _, _ = self._drain_engine()
        eng.request_drain()
        with pytest.raises(SystemExit) as ei:
            eng.run()
        assert ei.value.code == 0

    def test_sigterm_handler_is_flag_only(self):
        import signal
        eng, cfg, _ = self._drain_engine()
        eng.install_drain_handler()
        try:
            assert eng._drain_requested is False
            # deliver SIGTERM to ourselves: the handler must only set
            # the flag (no save, no exit) — acted on by run()
            signal.raise_signal(signal.SIGTERM)
            assert eng._drain_requested is True
            assert eng._drain_signum == signal.SIGTERM
        finally:
            eng.restore_signal_handlers()


# ---------------------------------------------------------------------------
# request-level observability (PR 10): latency histograms, queue/page
# gauges, per-request capture spans, Prometheus Serve/* families
# ---------------------------------------------------------------------------

class TestRequestObservability:
    def _engine(self, monitor=None, telemetry=None, **kw):
        cfg = GPTNeoXConfig.tiny()
        model = GPTNeoX(config=cfg, use_pallas=False)
        params = model.init_params(jax.random.PRNGKey(1))
        config = _engine_config(**kw)
        if telemetry:
            config["telemetry"] = telemetry
        return InferenceEngine(model, config=config, params=params,
                               monitor=monitor)

    def test_latency_histograms_populate(self):
        eng = self._engine()
        rng = np.random.default_rng(0)
        prompts = [list(rng.integers(1, 64, size=n)) for n in (5, 11)]
        eng.generate(prompts, max_new_tokens=4)
        m = eng.request_metrics
        assert m.ttft.count == 2                  # once per request
        assert m.admission_wait.count == 2
        # 2 requests x 3 decode steps after the prefill token
        assert m.inter_token.count == 6
        stats = eng.serve_stats()
        assert stats["ttft_p50_ms"] > 0
        assert stats["inter_token_p99_ms"] > 0
        assert 0.0 <= stats["page_pool_util"] <= 1.0

    def test_ttft_counted_once_despite_eviction(self):
        """An evicted request re-prefills (and resamples a token it
        already delivered) — TTFT must not be re-observed."""
        from deeperspeed_tpu.inference.scheduler import Request
        eng = self._engine()
        req = Request(prompt=[1, 2, 3], max_new_tokens=8)
        eng.scheduler.add_request(req, now=0.0)
        eng.step()                                 # prefill
        assert eng.request_metrics.ttft.count == 1
        # force an eviction round-trip through the scheduler
        eng.scheduler._evict_youngest(now=1.0)
        eng.step()                                 # re-prefill
        assert eng.request_metrics.ttft.count == 1
        assert req.evictions == 1

    def test_queue_depth_and_running_gauges_to_monitor(self):
        class Rec:
            def __init__(self):
                self.records = []

            def record(self, sample, scalars):
                self.records.append((sample, dict(scalars)))

            def observe_histogram(self, tag, value, edges=None):
                pass

        rec = Rec()
        eng = self._engine(monitor=rec)
        rng = np.random.default_rng(0)
        eng.generate([list(rng.integers(1, 64, size=5))],
                     max_new_tokens=3)
        keys = set()
        for _, sc in rec.records:
            keys |= set(sc)
        assert {"Serve/queue_depth", "Serve/page_pool_util",
                "Serve/running"} <= keys

    def test_prometheus_scrape_serves_serve_families(self, tmp_path):
        """Acceptance pin: a live scrape returns the Serve/* histogram
        families (TTFT / inter-token buckets) fed by real requests."""
        import urllib.request

        from deeperspeed_tpu.runtime.monitor import TensorBoardMonitor
        mon = TensorBoardMonitor(output_path=str(tmp_path), job_name="s",
                                 flush_interval=100,
                                 export={"prometheus_port": 0})
        try:
            eng = self._engine(monitor=mon)
            rng = np.random.default_rng(0)
            eng.generate([list(rng.integers(1, 64, size=5))],
                         max_new_tokens=4)
            eng.serve_stats()
            mon.flush()
            port = mon.prometheus.port
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics",
                timeout=5).read().decode()
            assert "# TYPE ds_serve_ttft_ms histogram" in body
            assert "# TYPE ds_serve_inter_token_ms histogram" in body
            assert 'ds_serve_ttft_ms_bucket{le="+Inf"} 1' in body
            assert "ds_serve_ttft_ms_count 1" in body
            # scalar families ride the same drain
            assert "ds_serve_queue_depth" in body
            assert "ds_serve_page_pool_util" in body
        finally:
            mon.close()

    def test_per_request_spans_in_capture_export(self, tmp_path):
        """Behind an open telemetry capture window, each FINISHED
        request lands one lifecycle event in the exported trace."""
        eng = self._engine(telemetry={
            "enabled": True, "mfu": False,
            "trace_dir": str(tmp_path),
            "capture": {"start_step": 0, "num_steps": 100}})
        # open the scheduled capture window manually (the serving loop
        # has no train-step counter driving on_step_start)
        eng.telemetry.on_step_start(0)
        rng = np.random.default_rng(0)
        eng.generate([list(rng.integers(1, 64, size=5))],
                     max_new_tokens=3)
        eng.telemetry.close()
        traces = list(tmp_path.glob("spans_*.json"))
        assert traces
        import json as _json
        doc = _json.load(open(traces[0]))
        names = {e["name"] for e in doc["traceEvents"]}
        assert any(n.startswith("request/") for n in names)
        assert {"schedule", "prefill", "decode"} <= names
