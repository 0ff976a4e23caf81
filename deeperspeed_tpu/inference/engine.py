"""Serving engine: continuous batching over a paged KV cache.

`InferenceEngine` is the serving-side sibling of the training
`DeepSpeedEngine`: it wraps the same model families (GPT-NeoX / GPT-2 —
their blocks share ONE implementation, `gpt_neox._block_qkv` /
`_block_post_attn`, so the decode path cannot drift from training
numerics), is driven by the same JSON config machinery (the validated
``"inference"`` block, `runtime.config.parse_inference_block`), loads
weights params-only through the manifest-verified checkpoint loader
(`checkpoint.load_module_checkpoint` — CRC verification and the
committed-tag fallback included, Adam moments never deserialized), and
applies `module_inject.prepare_inference_params` so weights rest in the
serving compute dtype.

Execution model (docs/inference.md):

- **Prefill/decode split.** New requests run one bucketed prefill
  (whole prompt, causal attention, K/V written to their pages in
  whole-page scatters); in-flight requests run one decode step each
  (one token through the Pallas paged decode-attention kernel,
  `ops/pallas/decode_attention.py`).
- **Fixed compiled shapes.** Prefill compiles per (batch bucket, length
  bucket), decode per batch bucket — the scheduler
  (`inference.scheduler`) only ever emits those shapes, so after the
  ladder warms up XLA never recompiles (`compile_count()` pins this in
  tests; every serve cell reports `serve_compiles_in_window`).
- **One walk.** Every model's layers are walked one way: the family
  says what to walk (`_Family.runs`: a planned model's plan, a
  homogeneous model as a plan of ONE run of all its layers), the weights
  are one stack a layer kind (`_stacked`), and the pools and page tables
  travel as `{cache kind: ...}` (`_pools`, `_tables`).
- **State.** The page pools are donated through every compiled call and
  rebound; everything else (params, rotary cache) is read-only. The
  decode program leaves the pools where they are (`_plan_token_layers`):
  they are the layer loop's carried state, the new row is written by a kernel
  that aliases the stacked pool (`paged_kv_write`), and the attention
  kernel indexes the layer itself, so a step moves the pages it touches
  and never a pool. Prefill's whole-page scatter updates in place too;
  the chunk programs (`_chunk_fn`) still scan the pools as inputs and
  outputs, which copies them.
- **One decode in flight.** `step()` enqueues its prefill and its
  decode before it reads the previous call's decode back; a continuing
  row's input token is gathered from that program's output on the
  device, so the chip does not wait for the host between steps
  (`_dispatch_decode`, `_settle`; docs/inference.md "The step's order").
  `Request.generated` only ever holds tokens that were read back.

Sampling is deterministic: temperature 0 (default) is argmax;
temperature > 0 draws from `jax.random.categorical` under a fixed
config seed folded with the step counter — the same request stream
always produces the same tokens.
"""

import itertools
import math
import random
import time
import types
from collections import deque
from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import scopes
from ..compat import shard_map
from ..models import gpt2 as gpt2_mod
from ..models import gpt_neox as neox
from ..module_inject.replace_module import prepare_inference_params
from ..ops.pallas.decode_attention import (_write_group,
                                           paged_decode_attention,
                                           paged_kv_write,
                                           paged_latent_decode,
                                           paged_latent_write)
from ..ops.autotune import whole_blocks
from ..ops.pallas.eva import eva_summarize
from ..ops.pallas.flash_attention import NEG_INF, flash_attention_supported
from ..parallel.mesh import MODEL_AXIS
from ..runtime.config import (DeepSpeedConfig, parse_inference_block,
                              parse_quantization_block)
from ..runtime.config_utils import (DeepSpeedConfigError, load_config_json)
from ..runtime.fault_injection import (FaultInjector, InjectedServingFault,
                                       SERVING_FAULT_KINDS)
from ..runtime.precision import resolve_kv_cache_dtype
from ..utils.kv_retry import backoff_delay
from ..utils.logging import logger
from .admission import (AdmissionController, DrainAborted, RequestFailed,
                        validate_priority)
from .handoff import (ACCEPTED, HandoffChannel, HandoffRejected,
                      check_geometry, encode_pages, write_pages)
from .kv_cache import (PagedKVCache, PrefixCache, QuantizedPages, StateCache,
                       pages_for_tokens, quantize_kv)
from .metrics import (EVA_PAGES_RELEASED, EVA_PENDING_PAGES,
                      EVA_WINDOWS_ROLLED, PREFIX_HIT_RATE,
                      PREFIX_PAGES_SHARED, PREFIX_SAVED_PREFILL_TOKENS,
                      REQUEST_STATUS_FAMILIES, SPEC_ACCEPTANCE_RATE,
                      ServeRequestMetrics)
from .scheduler import (FINISHED, RUNNING, ContinuousBatchingScheduler,
                        Request)


@dataclass
class _InFlight:
    """A dispatched program whose sampled tokens the host has not read:
    its dispatch serial, its phase (`prefill` | `decode`), the requests
    of its rows in row order, and the tokens on the device. A row is
    live while its request still lists the serial in `Request.owed`
    (every road out of `running` but a completed step clears that list):
    only a live row's token is recorded."""
    serial: int
    phase: str
    reqs: list
    tokens: object
    # every prediction head's logits of the rows, on the device (a model
    # with several heads; else None): read only for `engine.head_trace`
    logits: object = None

    def rows(self):
        """(row, request, live) of every row."""
        return [(i, r, self.serial in r.owed)
                for i, r in enumerate(self.reqs)]

    @property
    def live(self):
        return [r for _, r, live in self.rows() if live]


def _pow2_ladder(lo, hi):
    """lo, 2·lo, 4·lo, ... capped at hi (hi appended if not reached)."""
    out, v = [], lo
    while v < hi:
        out.append(v)
        v *= 2
    out.append(hi)
    return sorted(set(out))


# what a layer adds to a program's count of held pairs and of held
# experts touched where it routes nothing, or holds every expert
_NO_HELD = np.zeros((2,), np.float32)


class _Family:
    """The model-family seams the serving loop needs: token embedding,
    position stream, LM head. Everything between (the block body) is
    the shared `gpt_neox._block_qkv`/`_block_post_attn`."""

    def __init__(self, model, max_seq_len):
        self.cfg = cfg = model.config
        plan = getattr(cfg, "layer_plan", ())
        # (cos, sin, rot_dim) by attention kind: a planned model's layers
        # rotate by their kind; a homogeneous model's one kind, `full`, by
        # the model's own facts
        if isinstance(model, neox.GPTNeoX):
            # every architecture `GPTNeoXConfig` describes (its norm,
            # biases, QK norm, FFN kind): the seams below are the same
            self.kind = "gpt_neox"
            self._rotary = neox.plan_rotary(cfg, max_seq_len) if plan else \
                {"full": neox._rotary_cache(cfg, max_seq_len)}
        elif isinstance(model, gpt2_mod.GPT2):
            self.kind = "gpt2"      # order comes from wpe: nothing rotates
            none = jnp.zeros((max_seq_len, 0), jnp.float32)
            self._rotary = {"full": (none, none, 0)}
        else:
            raise DeepSpeedConfigError(
                f"InferenceEngine serves the GPT-NeoX / GPT-2 families; "
                f"got {type(model).__name__}")
        self.kv_heads = getattr(cfg, "kv_heads", cfg.num_heads)
        # a looped model (`GPTNeoXConfig.loop_steps`): the plan's stack
        # run that many times over the same weights, a pass's K/V in
        # cache layers of its own (docs/inference.md "Looped models")
        self.loop_steps = getattr(cfg, "loop_steps", 1)
        # the window of the model's window layers, and the width of a
        # latent layer's cache row (MLA: one pool with no head axis),
        # where its plan has such layers
        self.window = cfg.attn_window if self.cache_layers("window") else 0
        self.latent = cfg.latent_width if self.cache_layers("latent") else 0
        # a chunk-pooled (eva) model's window and chunk (0, 0: none), and
        # the prediction heads the output head holds
        self.eva = (cfg.eva_window, cfg.eva_chunk) \
            if self.cache_layers("eva") else (0, 0)
        self.pred_heads = getattr(cfg, "num_pred_heads", 1)
        # experts a token of a served (dropless) MoE; 0: a dense model
        self.moe_top_k = cfg.moe_top_k \
            if getattr(cfg, "moe_dropless", False) else 0
        # the layers that route, every pass of the loop counted, and
        # whether only a share of the router's experts is held here
        self.moe_layers = self.loop_steps * sum(
            n for spec, _, _, n in self.runs() if spec.ffn == "experts")
        self.moe_held = tuple(getattr(cfg, "moe_held", ()))
        # layers that read what another layer made (a memory, another
        # layer's K and V): the walks then carry it; and the first layer
        # from which a prefill computes the last row alone (the layer
        # count: none)
        self.shares = getattr(cfg, "plan_shares", False)
        self.last_row_from = getattr(cfg, "last_row_from", cfg.num_layers)

    def runs(self):
        """What the engine walks: the model's layers as runs of
        consecutive layers of one kind, [(spec, first layer, its index
        within the kind's stack, length)]. A planned model's plan
        (`GPTNeoXConfig.plan_runs`); a homogeneous model is a plan of ONE
        run of all its layers, of cache kind `full`, whose spec says what
        `spec=None` says to the block (the model's own heads, no window,
        not latent; its rotary facts are the model's, `_rotary`)."""
        if getattr(self.cfg, "layer_plan", ()):
            return self.cfg.plan_runs()
        spec = neox.LayerSpec(
            "full", self.cfg.num_heads,
            ffn="experts" if self.moe_top_k else "dense")
        return [(spec, 0, 0, self.cfg.num_layers)]

    def cache_layers(self, attn):
        """`GPTNeoXConfig.cache_layers`; a GPT-2's layers are all `full`."""
        return getattr(self.cfg, "cache_layers", lambda kind: (
            self.cfg.num_layers if kind == "full" else 0))(attn)

    def moe_buffer_rows(self, tokens):
        """Rows an MoE layer's sorted buffer holds in a program compiled
        for `tokens` token rows (host arithmetic, for `engine.stats`)."""
        from ..moe.layer import dropless_geometry
        return dropless_geometry(tokens, self.moe_top_k,
                                 self.cfg.experts_held)[0]

    def final_norm(self, params, x):
        """The model's own final norm (its kind rides the config)."""
        return neox.norm(self.cfg, params["final_ln"], x)

    @scopes.scoped("ds.embed")
    def embed_prefill(self, params, tokens):
        """tokens [B, S] → [B, S, H] at absolute positions 0..S-1."""
        x = params["embed"]["wte"][tokens]
        if self.kind == "gpt2":
            x = x + params["embed"]["wpe"][:tokens.shape[1]][None]
        return x

    @scopes.scoped("ds.embed")
    def embed_decode(self, params, tokens, positions):
        """tokens [B] at absolute `positions` [B] → [B, 1, H]."""
        x = params["embed"]["wte"][tokens][:, None, :]
        if self.kind == "gpt2":
            x = x + params["embed"]["wpe"][positions][:, None, :]
        return x

    @scopes.scoped("ds.embed")
    def embed_at(self, params, tokens, positions):
        """tokens [B, S] at per-token absolute `positions` [B, S] →
        [B, S, H] (the chunk programs: a window starting mid-sequence)."""
        x = params["embed"]["wte"][tokens]
        if self.kind == "gpt2":
            x = x + params["embed"]["wpe"][positions]
        return x

    def cos_sin_prefill(self, seqlen, attn="full"):
        cos, sin, rot_dim = self._rotary[attn]
        return (cos[:seqlen], sin[:seqlen], rot_dim)

    def cos_sin_decode(self, positions, attn="full"):
        """Per-batch rotary rows at `positions` [B] → ([B, 1, rot], ...)."""
        cos, sin, rot_dim = self._rotary[attn]
        return (cos[positions][:, None, :], sin[positions][:, None, :],
                rot_dim)

    def cos_sin_at(self, positions, attn="full"):
        """Per-token rotary rows at `positions` [B, S] →
        ([B, S, rot], ...) — `apply_rotary` takes the 3-D form."""
        cos, sin, rot_dim = self._rotary[attn]
        return (cos[positions], sin[positions], rot_dim)

    @scopes.scoped("ds.lm_head")
    def head(self, params, h):
        """Final-norm hidden [..., H] → logits [..., V] (fp32): a row a
        sequence, or every window position's (the speculative verify,
        a block's pass)."""
        if self.kind == "gpt2":
            wte = params["embed"]["wte"]
        else:
            wte = params.get("embed_out", params["embed"])["wte"]
        return jnp.einsum("...h,vh->...v", h, wte.astype(h.dtype),
                          preferred_element_type=jnp.float32)


class InferenceEngine:
    """Continuous-batching serving over the paged KV cache.

    ``model`` is a `models.gpt_neox.GPTNeoX` or `models.gpt2.GPT2`
    wrapper; ``config`` a dict / JSON path / `DeepSpeedConfig` holding
    the validated ``"inference"`` block; ``params`` an optional natural
    parameter pytree (else `load_checkpoint` or `model.init_params`).
    """

    def __init__(self, model, config=None, config_params=None, params=None,
                 mesh=None, rng=None, monitor=None, draft_model=None,
                 draft_params=None, owns_monitor=True,
                 handoff_transport=None):
        # one record a step, the spans' seconds into `stats`; the first
        # record is this constructor, `build` (docs/observability.md,
        # "Set-up"): whatever it costs and compiles is on that record
        from ..runtime.telemetry import StepTimeline
        self.stats = {}
        self.timeline = StepTimeline("serve", counters=self.stats)
        with self.timeline.build():
            self._build(model, config, config_params, params, mesh, rng,
                        monitor, draft_model, draft_params, owns_monitor,
                        handoff_transport)

    def _build(self, model, config, config_params, params, mesh, rng,
               monitor, draft_model, draft_params, owns_monitor,
               handoff_transport):
        """The constructor's body, inside the timeline's build record;
        its phases are the `_phase` spans below and `other`."""
        self.model = model
        cfg = model.config
        self._refuse_capacity_routing(cfg, "model")
        # a planned model (`GPTNeoXConfig.layer_plan`): layers of unequal
        # shape, run from one parameter stack a layer kind, with a page
        # pool a cache kind (docs/inference.md "Planned models")
        self.planned = bool(getattr(cfg, "layer_plan", ()))
        # a model that generates a BLOCK of tokens at a time
        # (`GPTNeoXConfig.generation_block`; docs/inference.md "Block
        # generation"): a decode pass carries the block's rows of every
        # sequence under the block-causal mask and unmasks by confidence
        self.block = cfg.generation_block if self.planned else 0
        if self.block:
            # rows a pass unmasks at least: the floor under the threshold
            self.block_floor = max(1, self.block // (
                cfg.generation_steps or self.block))
            self.block_threshold = cfg.generation_threshold
        if getattr(cfg, "attention_engine", "dense") != "dense":
            raise DeepSpeedConfigError(
                "serving needs attention_engine='dense' (the block-"
                "sparse engine has no decode variant)")
        if getattr(model, "_attn_fn", None) is not None:
            raise DeepSpeedConfigError(
                "serving a sequence-parallel model is not supported "
                "(decode is one token; there is no sequence to shard)")

        # -- config --------------------------------------------------------
        raw = config_params if config_params is not None else config
        if isinstance(raw, DeepSpeedConfig):
            self.inference_params = raw.inference_params
            telemetry_config = raw.telemetry_config
            quantization = raw.quantization_config
        else:
            if raw is None:
                raise DeepSpeedConfigError(
                    "InferenceEngine requires a config with an "
                    "'inference' block")
            d = raw if isinstance(raw, dict) else load_config_json(raw)
            self.inference_params = parse_inference_block(d)
            quantization = parse_quantization_block(d) or None
            # reuse the training parser's telemetry validation without
            # dragging in the batch triad it also wants
            ns = types.SimpleNamespace()
            DeepSpeedConfig._parse_telemetry_block(ns, d)
            telemetry_config = ns.telemetry_config
        if not self.inference_params:
            raise DeepSpeedConfigError(
                "the 'inference' config block is required (with "
                "\"enabled\": true) to build an InferenceEngine")
        ip = self.inference_params
        # -- telemetry (spans: schedule / prefill / decode; admission
        #    wait is a per-request scalar — docs/inference.md). First, so
        #    that the build's phases mirror as annotations too ------------
        from ..runtime.telemetry import build_telemetry
        self.monitor = monitor
        # co-residency contract (docs/rl.md): when the monitor is BORROWED
        # from a co-located training engine (owns_monitor=False), drain()
        # flushes it but must not close it — the training engine still
        # records Train/* scalars, and TensorBoardMonitor registers its
        # own weak atexit close, so no second registration happens here
        self._owns_monitor = bool(owns_monitor)
        self.telemetry = build_telemetry(telemetry_config, monitor=monitor,
                                         devices=jax.local_devices())
        if self.telemetry.enabled:
            self.telemetry.attach(self.timeline)

        self.page_size = ip["page_size"]
        self.max_seq_len = ip["max_seq_len"] or cfg.max_seq_len
        if self.max_seq_len > cfg.max_seq_len:
            raise DeepSpeedConfigError(
                f"inference.max_seq_len {self.max_seq_len} exceeds the "
                f"model's max_seq_len {cfg.max_seq_len}")
        if self.max_seq_len % self.page_size:
            raise DeepSpeedConfigError(
                f"the serving window max_seq_len {self.max_seq_len} must "
                f"be a multiple of page_size {self.page_size} (the paged "
                f"re-prefill ladder cannot cover a misaligned tail); set "
                f"inference.max_seq_len explicitly")
        if ip["num_pages"] - 1 < pages_for_tokens(self.max_seq_len,
                                                  self.page_size):
            raise DeepSpeedConfigError(
                f"inference.num_pages {ip['num_pages']} cannot hold even "
                f"one max_seq_len sequence "
                f"({pages_for_tokens(self.max_seq_len, self.page_size)} "
                f"pages + the reserved trash page)")
        self.max_batch_size = ip["max_batch_size"]
        self.temperature = ip["temperature"]
        self.seed = ip["seed"]
        self._attn_backend = (None if ip["kernel"] == "auto"
                              else ip["kernel"])

        if ip["prefill_lengths"]:
            bad = [b for b in ip["prefill_lengths"] if b > self.max_seq_len]
            if bad:
                raise DeepSpeedConfigError(
                    f"inference.prefill_lengths {bad} exceed the serving "
                    f"window max_seq_len {self.max_seq_len}")
            self.prefill_lengths = ip["prefill_lengths"]
        else:
            self.prefill_lengths = _pow2_ladder(self.page_size,
                                                self.max_seq_len)
        self.prefill_batch_sizes = ip["prefill_batch_sizes"] or \
            [b for b in (1, 2, 4) if b <= self.max_batch_size]
        self.decode_batch_sizes = ip["decode_batch_sizes"] or \
            _pow2_ladder(1, self.max_batch_size)

        # -- mesh / params -------------------------------------------------
        self.mesh = mesh
        self.mp = 1
        if mesh is not None and MODEL_AXIS in mesh.axis_names:
            self.mp = int(mesh.shape[MODEL_AXIS])
        if params is None:
            params = model.init_params(
                rng if rng is not None else jax.random.PRNGKey(0))
        # compute dtype comes from a matmul WEIGHT: 1-D leaves (biases,
        # norms) deliberately rest in fp32 (`prepare_inference_params`),
        # so the first leaf would read fp32 off a bf16 model and
        # silently double weight HBM
        leaves = jax.tree_util.tree_leaves(params)
        self.compute_dtype = next(
            (leaf.dtype for leaf in leaves
             if getattr(leaf, "ndim", 0) >= 2), leaves[0].dtype)
        # kv_cache_dtype overrides the CACHE pools only (K/V are cast —
        # or int8-quantized with per-page scales — on write, attention
        # runs at pool dtype) — it never re-casts the weights
        kv_dtype = ip["kv_cache_dtype"]
        self.kv_cache_dtype = (resolve_kv_cache_dtype(kv_dtype)
                               if kv_dtype else self.compute_dtype)
        self.kv_quant = self.kv_cache_dtype == jnp.int8
        # the validated "quantization" block (weights choice): int8
        # block matmul weights at rest (docs/quantization.md)
        self.weight_quant = (quantization or {}).get("weights")
        self.family = fam = _Family(model, self.max_seq_len)
        self.loop_steps, self.window, self.latent = \
            fam.loop_steps, fam.window, fam.latent
        self.eva_window, self.eva_chunk = fam.eva
        self._refuse_unplanned(ip, draft_model)
        if self.weight_quant and self.mp > 1:
            raise DeepSpeedConfigError(
                "quantization.weights with a model-parallel mesh is "
                "unsupported: the per-channel scale leaves have no "
                "tensor-parallel placement yet — serve quantized "
                "weights on a replicated (mp=1) mesh")
        if self.weight_quant and getattr(cfg, "moe_num_experts", 0):
            raise DeepSpeedConfigError(
                "quantization.weights with an MoE model is unsupported: "
                "the grouped expert matmul takes the experts' stacked "
                "weights in the compute dtype (no int8 variant)")
        # structure template for params-only checkpoint loads: the
        # QUANTIZED tree splits each weight into (qval, scale) leaves,
        # but checkpoints store the natural layout — keep an abstract
        # natural-structure template (shapes only, nothing resident)
        self._natural_like = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(jnp.shape(l),
                                           jnp.result_type(l)), params)
        with self._phase("weights"):
            # the cast, the placement and the stack of the layers, waited
            # for: the phase holds what they cost, not what it enqueued
            self._set_params(prepare_inference_params(
                params, self.compute_dtype, weight_quant=self.weight_quant))
            jax.block_until_ready((self.params, self.params_stacked))

        # -- cache / scheduler ---------------------------------------------
        # page pools by cache kind, as the programs take and return them
        # (`_pools`). `full` (or `latent`: a latent model's rows in ONE
        # pool, not a K and a V): a sequence's whole context, `num_pages`.
        # `window`: what a window layer keeps, at most window / page + 1
        # pages a sequence, so the pool is sized for `max_batch_size` of
        # those and the scheduler gives the rest back as a sequence grows
        # `eva`: a chunk-pooled model's ONE pool, of two populations of
        # rows in the `full` kind's layout: the exact rows of a sequence's
        # current window and one pooled row a chunk of its earlier windows
        # (docs/inference.md "Chunk-pooled pages"), `num_pages`
        primary = "latent" if self.latent else \
            "eva" if self.eva_window else "full"
        pages = {primary: ip["num_pages"]}
        if self.window:
            pages["window"] = self.max_batch_size * (
                self.window // self.page_size + 1) + 1
        with self._phase("pools"):
            self.caches = {kind: PagedKVCache(
                num_layers=self.family.cache_layers(kind), num_pages=n,
                num_heads=self.family.kv_heads, page_size=self.page_size,
                head_dim=cfg.head_dim, dtype=self.kv_cache_dtype, mesh=mesh,
                latent_width=self.latent) for kind, n in pages.items()}
            # the names the scheduler and a benchmark's probes read
            self.cache = self.caches[primary]
            self.window_cache = self.caches.get("window")
            # the cache kind WITHOUT pages (a model with state-space or
            # delta-rule layers): one slot of recurrent state a running
            # sequence, + the trash slot, in the shapes the model names
            self.state_cache = None
            if fam.cache_layers("state"):
                self.state_cache = StateCache(
                    fam.cache_layers("state"), self.max_batch_size + 1,
                    *cfg.state_shapes, dtype=self.compute_dtype)
        # -- prefix/radix cache + speculative decoding (both default-off:
        #    without their config sub-blocks the engine is bit-identical
        #    to the plain PR 8 serving loop) --------------------------------
        self.prefix_cache = None
        if ip["prefix_cache"] is not None:
            if self.mp > 1:
                raise DeepSpeedConfigError(
                    "inference.prefix_cache with a model-parallel mesh is "
                    "unsupported: the chunk-prefill attention gathers the "
                    "head-sharded pools without a shard_map yet — serve "
                    "the prefix cache on a replicated (mp=1) mesh")
            self.prefix_cache = PrefixCache(
                self.cache, max_pages=ip["prefix_cache"]["max_pages"])
        self.spec_k = 0
        self.draft_model = None
        self.draft_cache = None
        if ip["speculative"] is not None:
            sp = ip["speculative"]
            if draft_model is None:
                raise DeepSpeedConfigError(
                    "inference.speculative is enabled but no draft_model "
                    "was passed to InferenceEngine (the draft proposes "
                    "the tokens the target verifies)")
            if self.mp > 1:
                raise DeepSpeedConfigError(
                    "inference.speculative with a model-parallel mesh is "
                    "unsupported: the draft pools and the verify chunk "
                    "have no tensor-parallel placement yet — serve "
                    "speculation on a replicated (mp=1) mesh")
            dcfg = draft_model.config
            self._refuse_capacity_routing(dcfg, "draft model")
            if dcfg.vocab_size != cfg.vocab_size:
                raise DeepSpeedConfigError(
                    f"draft vocab_size {dcfg.vocab_size} != target "
                    f"vocab_size {cfg.vocab_size}: draft proposals would "
                    f"index a different token space")
            if dcfg.max_seq_len < self.max_seq_len:
                raise DeepSpeedConfigError(
                    f"draft max_seq_len {dcfg.max_seq_len} is smaller "
                    f"than the serving window {self.max_seq_len}: the "
                    f"draft could not reach every decode position")
            self.spec_k = sp["num_draft_tokens"]
            self.draft_model = draft_model
            with self._phase("draft"):
                if draft_params is None:
                    draft_params = draft_model.init_params(
                        jax.random.PRNGKey(self.seed))
                self.draft_family = _Family(draft_model, self.max_seq_len)
                self.draft_params, self.draft_stacked = self._stacked(
                    self.draft_family, prepare_inference_params(
                        draft_params, self.compute_dtype,
                        weight_quant=sp["draft_weight_quant"]))
                # the draft's shadow pools MIRROR the target allocator:
                # same num_pages/page_size, so one page id addresses a
                # sequence's K/V in both models and no second allocator
                # exists — every write path (prefill twin, chunk twin,
                # propose) lands draft K/V at the page ids the target's
                # scheduler handed out
                self.draft_cache = PagedKVCache(
                    num_layers=dcfg.num_layers, num_pages=ip["num_pages"],
                    num_heads=dcfg.num_heads, page_size=self.page_size,
                    head_dim=dcfg.head_dim, dtype=self.kv_cache_dtype)
            # host-side rejection sampling (temperature > 0): its own
            # deterministic stream, separate from the jax sampling keys
            self._spec_rng = np.random.default_rng(self.seed)

        self.scheduler = ContinuousBatchingScheduler(
            self.cache, max_seq_len=self.max_seq_len,
            token_budget=ip["token_budget"],
            max_batch_size=self.max_batch_size,
            prefill_lengths=self.prefill_lengths,
            prefill_batch_sizes=self.prefill_batch_sizes,
            decode_batch_sizes=self.decode_batch_sizes,
            prefix_cache=self.prefix_cache, spec_tokens=self.spec_k,
            window_cache=self.window_cache, window=self.window,
            block=self.block,
            mask_token_id=cfg.mask_token_id if self.block else 0,
            state_cache=self.state_cache, eva_window=self.eva_window,
            eva_chunk=self.eva_chunk, span=self._phase)
        self.n_pages_max = pages_for_tokens(self.max_seq_len,
                                            self.page_size)
        if self.eva_window:
            # the widest table: every whole window's pooled pages and one
            # window's pages
            sch = self.scheduler
            self.n_pages_max = sch.eva_pages_summary * (
                self.max_seq_len // self.eva_window) + sch.eva_pages_window
        # precision identity of this serving engine
        # (docs/quantization.md)
        self.dtypes = {
            "weight": self.weight_quant or
            str(jnp.dtype(self.compute_dtype)),
            "compute": str(jnp.dtype(self.compute_dtype)),
            "kv_cache": ("int8" if self.kv_quant
                         else str(jnp.dtype(self.kv_cache_dtype))),
        }

        self._compiled = {}
        self._steps = 0
        # programs dispatched and not read back, in device order; when
        # `step()` returns it holds at most the newest decode. `_carry`
        # is the last decode's token array: the next decode takes the
        # tokens of its continuing rows from it, on the device
        self._inflight = deque()
        self._dispatched = itertools.count()
        self._carry_width = max(self.decode_batch_sizes)
        # a model that holds a share of its experts counts, on the
        # device, the (token, choice) pairs that fell on a held expert:
        # one more entry behind every program's tokens, read back with
        # them
        self._counts_held = bool(self.family.moe_held)
        self._gdn_layers = sum(n for spec, _, _, n in self.family.runs()
                               if spec.attn == "gdn")
        self._carry = self._zero_carry()
        # a block model's passes as they are read back, where a list is
        # put here (None: not kept): one dict a live row-pass, the block
        # going in and coming out (`_complete_blocks`). What a test or a
        # benchmark's check replays against the reference
        self.block_trace = None
        # a model with several prediction heads: where a list is put here
        # (None: not kept), every read-back row's logits of ALL heads: one
        # dict a live row {"request", "at": the index in prompt +
        # generated of the token head 0 predicts, "logits" [heads * vocab]}
        self.head_trace = None
        self.stats.update({"steps": 0, "prefill_requests": 0,
                      "prefill_tokens": 0, "decode_tokens": 0,
                      # one-step lookahead (docs/inference.md): decode
                      # programs enqueued while the previous one was
                      # unread, and rows of a read-back decode whose
                      # token was dropped (the step past an EOS, a row
                      # evicted or expired since its dispatch)
                      "lookahead_steps": 0, "lookahead_discarded": 0,
                      "evictions": 0, "finished": 0,
                      "schedule_s": 0.0, "prefill_s": 0.0,
                      "decode_s": 0.0, "admission_wait_s": 0.0,
                      # the host phases inside prefill_s + decode_s
                      # (`_phase`), and the context tokens the decode
                      # steps attended over: what the paged kernel reads
                      "build_inputs_s": 0.0, "dispatch_s": 0.0,
                      "readback_s": 0.0, "complete_s": 0.0,
                      # inside readback_s: the wait for the device,
                      # before the transfer
                      "device_wait_s": 0.0,
                      # the step timeline's sums (runtime/telemetry.py,
                      # docs/observability.md "Slow steps"): steps that
                      # ran over their key's typical step, the seconds
                      # they ran over, and what held those seconds: the
                      # wait on the device, the collector, the thread's
                      # own CPU time, the caller between two steps; all
                      # collector seconds; steps that lowered a program,
                      # or saw a profiler trace start or stop
                      "slow_steps": 0, "slow_step_excess_s": 0.0,
                      "slow_excess_device_wait_s": 0.0,
                      "slow_excess_gc_s": 0.0, "slow_excess_host_s": 0.0,
                      "slow_excess_outside_s": 0.0, "gc_s": 0.0,
                      "compile_steps": 0, "profiler_steps": 0,
                      "decode_kv_tokens": 0,
                      # decode programs dispatched, and the passes of
                      # the layer stack all dispatched programs ran
                      # (`loop_steps` a program; 1 unless the model loops)
                      "decode_steps": 0, "loop_passes": 0,
                      # a block-generating model's passes, in ROW-passes
                      # (one sequence's two slots through one program):
                      # dispatched; read back as having committed a block
                      # (and of those: the commits that rode the pass
                      # that opened the successor; the passes that did
                      # nothing but commit); blocks a live request
                      # committed; rows its passes unmasked; the
                      # positions the dispatched rows attended (n + 2
                      # blocks a row-pass); the host seconds of the passes
                      # inside decode_s. `decode_tokens` stays the tokens
                      # DELIVERED (a block's contiguous unmasked prefix);
                      # requests whose FIRST row was unmasked (wherever
                      # it lies in the block: the answer begins to exist,
                      # the delivered prefix may wait for a row to its
                      # left) and their seconds since the submit
                      "block_passes": 0, "block_commit_passes": 0,
                      "block_fused_commits": 0,
                      "block_commit_only_passes": 0,
                      "blocks_committed": 0, "block_tokens_final": 0,
                      "decode_kv_tokens_block": 0, "block_pass_s": 0.0,
                      "block_first_unmasks": 0, "block_first_unmask_s": 0.0,
                      # the same for the window layers alone (a row
                      # attends over at most the window there), and the
                      # pages that held a decode step's context, by cache
                      # kind, summed over steps; pages the window kind
                      # gave back while its sequence ran
                      "decode_kv_tokens_window": 0,
                      "kv_page_steps_full": 0, "kv_page_steps_window": 0,
                      # the same of a latent model's one cache kind: the
                      # rows its absorbed decode kernel attended, and the
                      # pages that held them
                      "decode_kv_tokens_latent": 0,
                      "kv_page_steps_latent": 0,
                      "window_pages_released": 0,
                      # a chunk-pooled (eva) model (0 without one): the
                      # rows its decode steps read, by population (exact
                      # rows of the current window, pooled rows of the
                      # earlier ones) and the live contexts those stand
                      # for; the (query, key) pairs its prefills scored a
                      # head and layer; chunks its decode steps pooled;
                      # tables rolled at a window's end and the pages
                      # those gave back; pending pages held now; the bytes
                      # a row of either population takes, all layers, and
                      # the pooled rows' bytes a context byte behind the
                      # window comes to
                      "decode_kv_tokens_eva_window": 0,
                      "decode_kv_tokens_eva_summary": 0,
                      "decode_context_tokens_eva": 0,
                      "eva_prefill_pairs": 0,
                      "eva_chunks_pooled": 0, "eva_windows_rolled": 0,
                      "eva_pages_released": 0, "eva_pending_pages": 0,
                      "kv_bytes_per_row_eva": 0,
                      "kv_bytes_per_token_eva_summary": 0.0,
                      # the recurrent-state cache kind (0 without one):
                      # slots held now, the bytes one sequence's state
                      # takes, and both summed over the decode steps
                      # (slots live in a step; their bytes); the K/V bytes
                      # a live token holds in each page kind
                      "state_slots_in_use": 0, "state_bytes": 0,
                      "state_slot_steps": 0, "state_byte_steps": 0,
                      "kv_bytes_per_token_full": 0,
                      "kv_bytes_per_token_window": 0,
                      # rows a prefill's first and last layers computed
                      # (equal unless the model's later layers need the
                      # last row alone: `GPTNeoXConfig.last_row_from`)
                      "prefill_rows": 0, "prefill_rows_cross": 0,
                      # (token, choice) pairs the routers kept, all
                      # layers, and those that fell on an expert held
                      # here (all of them unless the model holds a share)
                      "moe_rows_routed": 0, "moe_rows_held": 0,
                      # held experts that got at least one row, summed over
                      # the decode steps' routing layers (0 unless the
                      # model holds a share of its experts)
                      "moe_experts_touched": 0,
                      # delta-rule states a decode step updated (rows x gdn
                      # layers) and real tokens a prefill put through the
                      # chunked delta rule (padding not counted), summed
                      "gdn_state_updates": 0, "gdn_prefill_tokens": 0,
                      # (token, expert) rows the MoE layers routed, all
                      # layers together, and the rows their buffers held
                      # with padding (0 for a dense model)
                      "moe_rows_prefill": 0, "moe_rows_decode": 0,
                      "moe_buffer_rows": 0,
                      "queue_depth": 0.0, "page_pool_util": 0.0,
                      # terminal-status set: every request reaches
                      # exactly one (docs/inference.md)
                      "requests_ok": 0, "requests_shed": 0,
                      "requests_deadline_exceeded": 0,
                      "requests_failed": 0,
                      "quarantines": 0, "retries": 0,
                      # speculative decoding: proposed/accepted draft
                      # tokens and verify steps (0 when speculation off)
                      "spec_steps": 0, "spec_proposed": 0,
                      "spec_accepted": 0,
                      # disaggregated prefill/decode handoff (all zero
                      # on a unified engine): prefill-side offers
                      # (sent/acked/rejected/expired) and decode-side
                      # verdicts (installed/refused)
                      "handoff_sent": 0, "handoff_acked": 0,
                      "handoff_rejected": 0, "handoff_expired": 0,
                      "handoff_installed": 0, "handoff_refused": 0})
        # tokens by the pass their exit gate chose (a looped model's
        # `t*`, from 1), read back with the tokens
        self.loop_exit_hist = [0] * self.loop_steps
        if self.state_cache is not None:
            self.stats["state_bytes"] = self.state_cache.bytes_per_sequence()
        for kind in ("full", "window"):
            if kind in self.caches:
                self.stats[f"kv_bytes_per_token_{kind}"] = \
                    self.caches[kind].bytes_per_token()
        if self.eva_window:
            # the one pool's two populations apart: an exact row (a byte
            # of the current window) and a pooled row (a chunk behind it)
            row = self.cache.bytes_per_token()
            self.stats["kv_bytes_per_row_eva"] = row
            self.stats["kv_bytes_per_token_eva_summary"] = \
                row / self.eva_chunk
        # whether the scheduler had work when the last step returned (the
        # caller's time before a step counts against it only then)
        self._busy = False
        # request-level latency histograms (inference/metrics.py):
        # admission-wait / TTFT / inter-token distributions, fanned out
        # to the monitor's export backends (Prometheus histogram
        # families) at observation time
        self.request_metrics = ServeRequestMetrics(monitor=monitor)

        # graceful drain (SIGTERM): flag-only handler, acted on at the
        # next serving-loop iteration — the PR 3 signal discipline
        self.drain_deadline_s = ip["drain_deadline_s"]
        self._drain_requested = False
        self._drain_signum = None
        self._prev_handlers = {}

        # -- robustness layer (docs/inference.md "Serving under
        #    failure"): admission control, retry/poison policy, hang
        #    watchdog, serving fault injection -------------------------
        self.default_priority = ip["default_priority"]
        self.retry_params = ip["retry"]
        self._retry_rng = random.Random(ip["seed"])
        self.admission = (AdmissionController(ip["admission"])
                          if ip["admission"] else None)
        self.fault_injector = FaultInjector.from_config_env(
            config_spec=ip["fault_injection"])
        self._step_faults = []      # serving faults fired this step
        self._pressure_pages = []   # page_pool_pressure seizures
        self.watchdog = None
        self.watchdog_fires = 0
        self.last_stack_dump = None
        if ip["hang_timeout_s"] > 0:
            from ..runtime.sentinel import HangWatchdog
            self.watchdog = HangWatchdog(ip["hang_timeout_s"], self,
                                         "_on_serving_hang")

        # -- disaggregated prefill/decode (docs/inference.md
        #    "Disaggregated serving"): role, pool identity, and the
        #    cross-pool KV-page handoff channel ---------------------------
        dg = ip["disaggregation"]
        self.role = dg["role"]
        self.pool_id = dg["pool_id"]
        self.handoff_timeout_s = dg["handoff_timeout_s"]
        # the validated inference.router weights (None when absent) —
        # a ServeRouter fronting this pool picks them up from here
        self.router_params = ip["router"]
        self.handoff = None
        self._handoff_outbox = []      # prefilled requests awaiting offer
        self._pending_handoff = {}     # offer key -> (request, offered_at)
        self._handoff_draining = False
        if self.role != "unified":
            if handoff_transport is None:
                raise DeepSpeedConfigError(
                    f"inference.disaggregation.role={self.role!r} needs a "
                    f"handoff_transport (the coordination-service KV the "
                    f"pages travel over — elasticity.heartbeat."
                    f"InMemoryTransport / CoordinationTransport)")
            if self.mp > 1:
                raise DeepSpeedConfigError(
                    "disaggregated serving with a model-parallel mesh is "
                    "unsupported: the page payload has no tensor-parallel "
                    "placement yet — split pools on replicated (mp=1) "
                    "meshes")
            self.handoff = HandoffChannel(handoff_transport, self.pool_id)
            if self.role == "decode":
                # a decode pool never prefills FRESH requests: the drain
                # gate blocks queue admissions permanently, while evicted
                # / quarantined sequences (whose K/V must be rebuilt
                # locally) still re-admit through it
                self.scheduler.stop_admissions()
            # stamp the scrape: every Serve/* family this pool exports
            # carries its role + pool identity
            if monitor is not None:
                hook = getattr(monitor, "set_export_labels", None)
                if hook is not None:
                    hook({"role": self.role, "host": self.pool_id})

    def _refuse_unplanned(self, ip, draft_model):
        """What a planned model (or its window or latent cache kind) does
        not do yet, refused by name."""
        if not self.planned:
            return
        what = None
        if self.mp > 1:
            what = ("a model-parallel mesh (mp > 1): the kind stacks and "
                    "the grouped KV heads have no tensor-parallel "
                    "placement")
        elif self.weight_quant:
            what = ("quantization.weights: the int8 surgery reads the "
                    "`blocks` list of a natural tree, not a stack a layer "
                    "kind")
        elif ip["prefix_cache"] is not None:
            what = ("inference.prefix_cache: a shared prefix page has no "
                    "counterpart in a window pool, whose pages behind the "
                    "window are gone, and the chunk program walks one run "
                    "of layers and attends one K/V pool pair through an "
                    "XLA gather")
        elif ip["speculative"] is not None or draft_model is not None:
            what = ("inference.speculative: the verify chunk (one run of "
                    "layers, one K/V pool pair through an XLA gather) and "
                    "the rollback of rejected pages know one pool")
        elif ip["disaggregation"]["role"] != "unified":
            what = ("handoff between pools (disaggregation.role != "
                    "'unified'): the page payload carries one pool's "
                    "pages")
        elif self.window and self.kv_quant:
            what = ("kv_cache_dtype int8 with a window cache kind: the "
                    "paged kernel's window has no int8 variant")
        elif self.eva_window and self.kv_quant:
            what = ("kv_cache_dtype int8 with a chunk-pooled (eva) cache "
                    "kind: a pooled row is a float32 sum rounded once to "
                    "the pages' type, and the pooling reads plain rows")
        elif self.family.cache_layers("state") and (
                self.kv_quant or ip["num_pages"] <= self.max_batch_size *
                self.max_seq_len // self.page_size):
            whole = self.max_batch_size * self.max_seq_len // self.page_size
            what = (f"a recurrent-state cache kind and int8 pages, or "
                    f"num_pages {ip['num_pages']} under max_batch_size x "
                    f"the pages of max_seq_len + 1 = {whole + 1}: a dry "
                    f"pool would preempt a request whose state no snapshot "
                    f"could resume, so the pools hold every running "
                    f"request whole")
        elif self.latent and any(s.attn != "latent"
                                 for s in self.model.config.layer_plan):
            what = ("latent layers beside full or window layers in one "
                    "plan: the programs carry one latent pool, or K and V "
                    "pools")
        elif self.latent and jnp.dtype(self.kv_cache_dtype).itemsize < 2:
            what = ("kv_cache_dtype int8 or fp8 with latent pages: the "
                    "absorbed decode kernel reads bf16 / float32 rows, "
                    "and a latent row has no per-head scale")
        elif self.loop_steps > 1 and self.kv_quant:
            what = ("kv_cache_dtype int8 with a looped model: prefill's "
                    "scatter of a pass's pages writes plain pools")
        elif self.block and self.kv_quant:
            what = ("kv_cache_dtype int8 with block generation: a block's "
                    "rows are written as one run into plain pools")
        elif self.block and self.temperature > 0.0:
            what = ("a sampled request (inference.temperature > 0) with "
                    "block generation: a pass unmasks the argmax of its "
                    "most confident rows; temperature / top-k / top-p "
                    "sampling inside a block is not computed")
        elif self.block and (ip["page_size"] % self.block or _write_group(
                ip["page_size"], self.kv_cache_dtype) % self.block):
            what = (f"page_size {ip['page_size']} and "
                    f"{jnp.dtype(self.kv_cache_dtype).name} pages: a block's rows lie in one page and one packed "
                    f"sublane group of it (`paged_kv_write`)")
        if what:
            looped = f", loop_steps={self.loop_steps}" \
                if self.loop_steps > 1 else ""
            looped += f", generation_block={self.block}" if self.block \
                else ""
            raise DeepSpeedConfigError(
                f"serving a planned model (layer_plan{looped}) with {what} "
                f"is not built")

    @staticmethod
    def _refuse_capacity_routing(cfg, what):
        if getattr(cfg, "moe_num_experts", 0) and \
                not getattr(cfg, "moe_dropless", False):
            raise DeepSpeedConfigError(
                f"serving a capacity-routed MoE {what} is not supported: "
                f"which token an expert drops depends on the token's "
                f"batch neighbours and on the pad rows of a prefill "
                f"bucket, so a request's output would depend on what it "
                f"was batched with. An MoE whose routing drops nothing "
                f"(moe_dropless) is served")

    # ------------------------------------------------------------------
    # weights
    # ------------------------------------------------------------------

    def _stacked(self, fam, params, mesh_specs=None):
        """A model's prepared tree as the programs read it: (the leaves
        beside the layers, {stack kind: the layers of that kind, every
        leaf with a leading layer axis}). The programs scan the stacks:
        stacking inside a compiled step would make a full copy of the
        block weights every call (params are runtime jit inputs, XLA
        cannot hoist the stack out).

        A planned model's own tree IS that layout (`params["stacks"]`):
        taken by reference, no copy. A homogeneous model's `blocks` are
        stacked here ONCE under its run's kind name and left out of what
        is returned, so the engine holds the layers' weights once.
        `mesh_specs`: the tree's tensor-parallel specs, where the mesh
        shards the model."""
        if "stacks" in params:
            return params, params["stacks"]
        (spec, _, _, _), = fam.runs()
        rest = {k: v for k, v in params.items() if k != "blocks"}
        stack = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                       *params["blocks"])
        if mesh_specs is not None:
            rest = jax.tree_util.tree_map(
                lambda p, s: jax.device_put(p, NamedSharding(self.mesh, s)),
                rest, {k: mesh_specs[k] for k in rest},
                is_leaf=lambda x: isinstance(x, P))
            stack = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(
                    x, NamedSharding(self.mesh, P(None, *s))),
                stack, mesh_specs["blocks"][0])
        return rest, {spec.kind: stack}

    def _set_params(self, params):
        """Take a prepared tree as the weights the programs run from
        (`_stacked`)."""
        self.params, self.params_stacked = self._stacked(
            self.family, params,
            self.model.param_specs(params, self.mesh) if self.mp > 1
            else None)
        # a weight hot-swap invalidates every registered prefix page:
        # the cached K/V is a function of the OLD weights, so new
        # requests must not share it — drop the registry and detach
        # waiting attachments (running requests keep decoding on their
        # old-weights K/V, the pre-existing hot-swap semantics)
        pc = getattr(self, "prefix_cache", None)
        if pc is not None:
            pc.clear()
            self.scheduler.detach_waiting_prefixes()

    def load_checkpoint(self, load_dir, tag=None):
        """Params-only restore through the manifest-verified loader:
        CRC verification and the committed-tag fallback run exactly as
        in training resume, but only the module tree is deserialized —
        a serving restart never touches Adam moments."""
        from ..checkpoint.checkpointing import load_module_checkpoint
        self._settle()
        path, natural, client_state = load_module_checkpoint(
            load_dir, tag=tag, like=self._natural_like)
        if path is None:
            return None, {}
        # the compiled programs take params as runtime arguments, so the
        # warmed bucket executables stay valid across a weight hot-swap
        # (same avals = jit cache hit) — no recompile ladder to repay
        self._set_params(prepare_inference_params(
            natural, self.compute_dtype, weight_quant=self.weight_quant))
        return path, client_state

    def hot_swap_weights(self, natural_params):
        """In-process train->serve weight flow (docs/rl.md): re-run
        `prepare_inference_params` (dtype cast + optional int8
        requantization — weights AND scales are runtime jit args) and
        swap via `_set_params`. The warmed bucket executables stay valid
        because every compiled program takes params as runtime
        arguments: same avals = jit cache hit, zero recompiles.

        Returns ``{"swap_ms", "compile_delta"}``; a non-zero
        compile_delta after warmup is the regression the satellite test
        pins to 0."""
        self._settle()      # the decode in flight ran on the old weights
        before = self.compile_count()
        t0 = time.perf_counter()
        self._set_params(prepare_inference_params(
            natural_params, self.compute_dtype,
            weight_quant=self.weight_quant))
        jax.block_until_ready((self.params, self.params_stacked))
        swap_ms = (time.perf_counter() - t0) * 1e3
        return {"swap_ms": swap_ms,
                "compile_delta": self.compile_count() - before}

    def sampler_state(self):
        """Deterministic-replay snapshot of every sampling stream: the
        fold_in step counter (`_next_rng`) and, when speculation is
        armed, the host-side rejection-sampling PCG64 state. Pure data —
        checkpointable via client_state."""
        state = {"steps": int(self._steps)}
        if self.spec_k:
            state["spec_rng"] = self._spec_rng.bit_generator.state
        return state

    def restore_sampler_state(self, state):
        """Restore `sampler_state()`; sampling is a pure function of
        (seed, steps), so a restored engine reproduces the exact token
        stream an uninterrupted run would have drawn."""
        self._steps = int(state["steps"])
        if self.spec_k and "spec_rng" in state:
            self._spec_rng.bit_generator.state = state["spec_rng"]

    # ------------------------------------------------------------------
    # compiled programs (one per bucket — the no-recompile discipline)
    # ------------------------------------------------------------------

    def compile_count(self):
        """Total compiled executables across all bucketed programs; the
        zero-recompile tests/bench pin that this stops growing once the
        bucket ladder has warmed up."""
        return sum(fn._cache_size() if hasattr(fn, "_cache_size") else 1
                   for fn in self._compiled.values())

    @scopes.scoped("ds.sample")
    def _sample(self, logits, rng):
        if self.family.pred_heads > 1:
            # head 0 is the next token's distribution
            logits = logits[..., :self.model.config.vocab_size]
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            rng, logits / self.temperature, axis=-1).astype(jnp.int32)

    def _attention(self, q, pools, layer, page_table, lengths, window=None,
                   sm_scale=None, cross=False):
        """Paged decode attention over layer `layer` of the stacked
        (K, V) `pools`, shard_mapped over the model axis when the mesh
        shards heads (attention is head-independent, so each shard runs
        the kernel on its local heads — no collective). Int8 pools
        arrive as `QuantizedPages`; the per-page scale pools ride the
        same head-sharded placement as the data pools. `lengths` [B, 2]:
        a block pass's, the ends of its two slots."""
        leaves = jax.tree_util.tree_leaves(pools)
        quant = isinstance(pools[0], QuantizedPages)
        block_pass = lengths.ndim == 2

        def attend(q, pt, ln, layer, *leaves):
            if quant:                           # (data, scale) of K, of V
                k, k_scale, v, v_scale = leaves
                extra = {"k_scales": k_scale, "v_scales": v_scale}
            else:
                (k, v), extra = leaves, {}
            if block_pass:
                extra["first_lengths"], ln = ln[:, 0], ln[:, 1]
            return paged_decode_attention(
                q, k, v, pt, ln, sm_scale=sm_scale,
                backend=self._attn_backend, layer=layer, window=window,
                block_pass=block_pass, cross=cross, **extra)

        if self.mp > 1:
            attend = shard_map(
                attend, mesh=self.mesh,
                in_specs=(P(None, MODEL_AXIS, None), P(None, None),
                          P(*(None,) * lengths.ndim),
                          P()) + self._pool_specs(leaves),
                out_specs=P(None, MODEL_AXIS, None), check_vma=False)
        return attend(q, page_table, lengths, layer, *leaves)

    @staticmethod
    def _pool_specs(leaves):
        """Head-sharded specs of stacked [L, P, H, ps(, D)] pools."""
        return tuple(P(None, None, MODEL_AXIS, *(None,) * (x.ndim - 3))
                     for x in leaves)

    def _write_rows(self, pools, k, v, layer, page_idx, slot):
        """One token's K and V rows [B, H, D] (or a block's run of rows
        [B, H, block, D], from `slot` on) into their page slots of
        layer `layer` of the stacked (K, V) `pools`, in place
        (`paged_kv_write`; under a model-parallel mesh each shard writes
        its own heads). Int8 pools quantize per (head) vector and land
        the scale in the page-aligned scale pool, through the same
        write. Returns the pools."""
        leaves, treedef = jax.tree_util.tree_flatten(pools)
        rows = (k, v)
        if isinstance(pools[0], QuantizedPages):
            # (data, scale) of K then of V: the leaves' own order
            rows = quantize_kv(k) + quantize_kv(v)
        n = len(leaves)

        def write(layer, page_idx, slot, *args):
            return paged_kv_write(args[:n], args[n:], layer, page_idx,
                                  slot, backend=self._attn_backend)

        if self.mp > 1:
            specs = self._pool_specs(leaves)
            write = shard_map(
                write, mesh=self.mesh,
                in_specs=(P(), P(None), P(None)) + specs + tuple(
                    P(None, MODEL_AXIS, *(None,) * (r.ndim - 2))
                    for r in rows),
                out_specs=specs, check_vma=False)
        return treedef.unflatten(write(layer, page_idx, slot, *leaves,
                                       *rows))

    @staticmethod
    def _write_state(pool, new, slots):
        """A prefill's recurrent state [layers, B, ...] into its rows'
        slots of `pool`, whole: the scan started from zero, so nothing of
        what a slot held before is left."""
        return pool.at[:, slots].set(new.astype(pool.dtype))

    @staticmethod
    def _run_xs(stack, at, n):
        """What a layer loop over layers [at, at + n) of one kind's
        `stack` takes: (the leaves a layer is sliced out of, the function
        that makes layer i's block params). The experts stay whole: the
        grouped-matmul kernel indexes the layer (`LayerOf`). A run of
        several layers that is only a PART of its stack (a kind whose
        layers another kind interrupts) takes no leaves at all: its loop
        indexes the whole stack at `at + i`, where slicing the run out
        would copy the run's weights at every call (Qwen3-Next's two runs
        of gdn layers: 0.37 GB a decode step)."""
        from ..ops.pallas.grouped_matmul import LayerOf
        whole = {k: v for k, v in stack["mlp"].items()
                 if k in ("w_in", "w_out")}
        sliced = dict(stack, mlp={k: v for k, v in stack["mlp"].items()
                                  if k not in whole})
        layers = jax.tree_util.tree_leaves(sliced)[0].shape[0]
        indexed = None
        if n > 1 and n < layers:
            indexed, sliced = sliced, {}
        elif n < layers:
            sliced = jax.tree_util.tree_map(lambda a: a[at:at + n], sliced)

        def layer_of(bp, i):
            if indexed is not None:
                bp = jax.tree_util.tree_map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, at + i, keepdims=False), indexed)
            experts = {k: LayerOf(v, jnp.asarray(at + i, jnp.int32))
                       for k, v in whole.items()}
            return dict(bp, mlp=dict(bp["mlp"], **experts))

        return sliced, layer_of

    def _plan_layers(self, fam, stacks, carry, layer_fn, loop_pass=0,
                     layers=None):
        """The layer loop of every model: `layer_fn(carry, bp, spec,
        cache_layer) -> (carry, ys)` over the family's runs
        (`_Family.runs`), a run of consecutive layers of one kind at a
        time (a scan where the run is longer than one layer; a
        homogeneous model is one run, one scan). `cache_layer` is the
        layer's index in its cache kind's pools: pass `loop_pass` of a
        looped model keeps its K/V behind those of the passes before it; a
        cross layer's is the full layer's it reads, a gmu layer has none.
        `layers` = (first, past-the-last): the runs that lie in that range
        of layers alone (a range ends where a run ends). Returns (carry,
        [(spec, ys stacked over the run)])."""
        cache_at = {kind: loop_pass * (fam.cache_layers(kind) //
                                       fam.loop_steps)
                    for kind in ("full", "window", "latent", "eva")}
        cache_at.update(ssm=0, gdn=0)
        out = []
        for spec, first, at, n in fam.runs():
            shared = spec.attn in ("cross", "gmu")
            if shared:
                base = cache_at["full"] - 1      # the full layer before it
            else:
                base = cache_at[spec.attn]
                cache_at[spec.attn] += n
            if layers is not None and not layers[0] <= first < layers[1]:
                continue
            xs, layer_of = self._run_xs(stacks[spec.kind], at, n)

            def body(carry, x, spec=spec, base=base, layer_of=layer_of,
                     shared=shared):
                bp, i = x
                return layer_fn(carry, layer_of(bp, i), spec,
                                base if shared else base + i)

            if n == 1:
                carry, ys = body(carry, (jax.tree_util.tree_map(
                    lambda a: a[0], xs), 0))
                ys = jax.tree_util.tree_map(lambda y: y[None], ys)
            else:
                carry, ys = jax.lax.scan(
                    body, carry, (xs, jnp.arange(n, dtype=jnp.int32)))
            out.append((spec, ys))
        return carry, out

    @staticmethod
    def _held_rows(fam, block_out):
        """(hidden states, [the (token, choice) pairs of this layer that
        fell on a held expert, the held experts that got at least one])
        of a block's return."""
        if isinstance(block_out, tuple) and fam.moe_held:
            lo, hi = fam.moe_held
            pairs = block_out[1][2, lo:hi]
            return block_out[0], jnp.stack(
                [jnp.sum(pairs), jnp.sum(pairs > 0, dtype=pairs.dtype)])
        return neox.block_hidden(block_out), _NO_HELD

    def _plan_token_layers(self, fam, stacks, x, pos, pools, tables,
                           lengths, loop_pass=0, layers=None, mem=None,
                           active=None):
        """The layer loop of a one-token step (decode; each of the
        draft's proposal steps) of the model `fam` describes: `pools` and
        `tables` are {cache kind: (K, V) pools} and {cache kind: page
        table}; a window layer writes and attends in the window kind's;
        `loop_pass` as `_plan_layers` takes it. Returns (x, pools, held
        pairs).

        The pools are the loop's CARRY, not scanned inputs: a scan slices
        each `xs` element out of its stack and stacks each `ys` element
        into a new one, which for a pool is a copy of the pool every
        step. As carried state, written by an aliased kernel and read by
        a kernel that takes the layer index, they stay where they are.

        `x` [B, R, hidden]: R = 1, a token a sequence at position `pos`
        [B]; or a block model's pass, R = 2 blocks of rows a sequence at
        positions `pos` .. `pos + R - 1`: two SLOTS, the block at `pos`
        and its successor, with `lengths` [B, 2] their ends and `active`
        [B, R] the rows that count (a dead slot's are routed to no expert
        and written to the trash page). Each slot's K and V rows go into
        their page as one run (a block lies in one packed group of one
        page; the two together need not) and a slot's rows attend the
        positions under the slot's end, their own among them and NO mask
        inside the slot: the R rows x the query heads of a KV head ride
        as that KV head's one group of the grouped paged kernel, K and V
        read once for both slots, under the name `ds.paged_decode_block`.

        A model whose layers share (`_Family.shares`): the `state` kind's
        pools are (convolution rows, scan states) and its "table" each
        row's slot; an ssm layer updates its row's state in place and
        leaves its scan output as the walk's memory, a gmu layer gates
        that, a cross layer attends over the full layer's pages and
        writes nothing. `layers` = (first, past-the-last) walks those
        layers alone, on pools that hold this token's rows of the full
        kind already, from the memory `mem` [B, 1, inner] (the second
        half of a prefill, on each prompt's last row)."""
        cfg, ps = fam.cfg, self.page_size
        B, R = x.shape[:2]
        kinds = [k for k in pools if k != "state"]
        diff = getattr(cfg, "attn_diff", False)
        scale = getattr(cfg, "attn_scale", None)
        # a cross layer brings no cache kind of its own
        rotary = kinds + [k for k in fam._rotary if k not in kinds]
        if R == 1:
            # an inactive row attends over nothing; an MoE routes it nowhere
            active = jnp.broadcast_to((lengths > 0)[:, None], (B, R))
            rot = {k: fam.cos_sin_decode(pos, k) for k in rotary}
            # the runs of rows written: (first position, rows, live or None)
            runs = [(pos, 0, None)]
        else:
            at = pos[:, None] + jnp.arange(R, dtype=pos.dtype)
            rot = {k: fam.cos_sin_at(at, k) for k in rotary}
            runs = [(pos + lo, slice(lo, lo + R // 2), active[:, lo])
                    for lo in (0, R // 2)]

        def page_of(table, start, live):
            page = jnp.take_along_axis(
                table, (start // ps)[:, None], axis=1)[:, 0]
            return page if live is None else jnp.where(live, page, 0)

        page_idx = {k: [page_of(tables[k], start, live)
                        for start, _, live in runs]
                    for k in kinds if k != "eva"}
        slot = [start % ps for start, _, _ in runs]
        G = fam.kv_heads
        # the rows a layer's decode attends, by cache kind
        attended = {k: lengths for k in kinds}
        W, C = fam.eva
        if W:
            # a chunk-pooled table: [the pooled rows' pages of the windows
            # that have ended | the current window's pages]
            # (`scheduler.eva_table_index` / `eva_length`, on the device)
            per_win = W // C
            window = pos // W
            row = pos - window * W
            live = lengths > 0
            page_idx["eva"] = [jnp.where(live, jnp.take_along_axis(
                tables["eva"], (window * (per_win // ps) + row // ps)[:, None],
                axis=1)[:, 0], 0)]
            attended["eva"] = jnp.where(
                live, window * per_win + row + 1, 0).astype(lengths.dtype)
            # the token that closes a chunk: its pooled row's place in the
            # pending pages, which no table names yet
            chunk = row // C
            closing = live & (pos % C == C - 1)
            pending = (jnp.take_along_axis(
                tables["eva_pending"], (chunk // ps)[:, None],
                axis=1)[:, 0], chunk % ps)

        def kv_rows(t, rows):
            """A run's K or V of [B, R, G, D] as `_write_rows` takes it."""
            return t[:, rows] if R == 1 else jnp.swapaxes(t[:, rows], 1, 2)

        def q_rows(q):
            """q [B, R, H, D] as the paged kernel's [B, heads, D]: a KV
            head's R x H / G query rows together."""
            if R == 1:
                return q[:, 0]
            D = q.shape[-1]
            return jnp.swapaxes(q.reshape(B, R, G, -1, D), 1, 2).reshape(
                B, -1, D)

        def attn_rows(attn):
            """The inverse, flattened to [B, R, H * D]."""
            if R == 1:
                return attn.reshape(B, 1, -1)
            D = attn.shape[-1]
            return jnp.swapaxes(attn.reshape(B, G, R, -1, D), 1, 2).reshape(
                B, R, -1)

        def latent_attn(x, kv, bp, spec, cache_layer):
            """The absorbed form: the token's latent row into its page,
            q' = q_nope W_uk^T against the latent pages, o = u W_uv."""
            q_nope, q_rope, row = neox._latent_rows(
                cfg, bp, x, *rot["latent"][:2], spec.heads)
            pool = paged_latent_write(
                kv[0], row[:, 0], cache_layer, page_idx["latent"][0],
                slot[0], backend=self._attn_backend)
            with scopes.scope("ds.attn"):
                q = neox.latent_absorb_q(cfg, bp, q_nope[:, 0], q_rope[:, 0])
                u = paged_latent_decode(
                    q.astype(pool.dtype), pool, tables["latent"], lengths,
                    1.0 / math.sqrt(cfg.mla_nope_dim + cfg.mla_rope_dim),
                    cfg.mla_kv_rank, cache_layer,
                    backend=self._attn_backend).astype(x.dtype)
                attn = neox.latent_absorb_out(cfg, bp, u)
            return attn.reshape(B, 1, -1), (pool,)

        def cache_kind(spec):
            # a cross layer reads the full kind's pages and writes none
            return "full" if spec.attn == "cross" else spec.attn

        def paged_attn(x, kv, bp, spec, cache_layer):
            kind = cache_kind(spec)
            q, k, v = neox._block_qkv(cfg, bp, x, *rot[spec.attn],
                                      spec.heads)
            if k is not None and layers is None:
                for i, (_, rows, _) in enumerate(runs):
                    kv = self._write_rows(
                        kv, kv_rows(k, rows), kv_rows(v, rows), cache_layer,
                        page_idx[kind][i], slot[i])
            if kind == "eva":
                kv = eva_summarize(
                    kv, bp["attn"]["eva_phi"], bp["attn"]["eva_mu"],
                    cache_layer, page_idx[kind][0], slot[0], closing,
                    *pending, C, scale or 1.0 / math.sqrt(cfg.head_dim),
                    backend=self._attn_backend)
            with scopes.scope("ds.attn"):
                if diff:
                    q = neox.diff_queries(q)
                # int8 pages dequantize inside the kernel: q stays as it is
                q = q_rows(q)
                if not isinstance(kv[0], QuantizedPages):
                    q = q.astype(kv[0].dtype)
                attn = self._attention(
                    q, kv, cache_layer, tables[kind], attended[kind],
                    window=self.window if kind == "window" else None,
                    sm_scale=scale,
                    cross=spec.attn == "cross").astype(x.dtype)
                if diff:
                    attn = neox.diff_combine(cfg, bp["attn"], attn)
            return attn_rows(attn), kv

        def state_mixer(x, pools, mem, bp, spec, cache_layer):
            """An ssm or gdn layer's step on its rows' states, or a gmu
            layer's gate on the memory: (the mixer's output, pools,
            memory)."""
            with scopes.scope("ds.attn"):
                a = neox.norm(cfg, bp["ln_attn"], x)
            if spec.attn == "gmu":
                return neox.gmu_mixer(bp["attn"], a, mem), pools, mem
            if spec.attn == "gdn":
                mixed, state = neox.gdn_token(
                    cfg, bp["attn"], a, pools["state"], tables["state"],
                    cache_layer, lengths > 0)
                return mixed, dict(pools, state=state), mem
            mixed, state, mem = neox.ssm_token(
                cfg, bp["attn"], a, pools["state"], tables["state"],
                cache_layer, lengths > 0, backend=self._attn_backend)
            return mixed, dict(pools, state=state), mem

        @scopes.scoped("ds.block")
        def layer(carry, bp, spec, cache_layer):
            x, pools, held, aux = carry
            if spec.attn in ("ssm", "gmu", "gdn"):
                mixed, pools, mem = state_mixer(
                    x, pools, aux.get("mem"), bp, spec, cache_layer)
                out, rows = self._held_rows(fam, neox._block_post_attn(
                    cfg, bp, x, mixed, reduce_fn=lambda t: t,
                    token_mask=active, projected=True))
                return (out, pools, held + rows,
                        {"mem": mem} if fam.shares else aux), None
            kind = cache_kind(spec)
            attend = latent_attn if spec.attn == "latent" else paged_attn
            attn, kv = attend(x, pools[kind], bp, spec, cache_layer)
            out, rows = self._held_rows(fam, neox._block_post_attn(
                cfg, bp, x, attn, reduce_fn=lambda t: t, token_mask=active))
            return (out, dict(pools, **{kind: kv}), held + rows, aux), None

        aux = {}
        if fam.shares:
            aux["mem"] = mem if mem is not None else jnp.zeros(
                (B, R, cfg.ssm_inner), jnp.float32)
        with scopes.scope("ds.layers"):
            carry, _ = self._plan_layers(
                fam, stacks, (x, pools, _NO_HELD, aux),
                layer, loop_pass, layers)
        return carry[:3]

    def _loop(self, params, x, state, one_pass, rows):
        """The model's layers `loop_steps` times over the SAME weights:
        `one_pass(x, state, loop_pass) -> (x, state)` walks them once
        (it closes over the weights: loop-invariant operands, held once
        whatever the passes, never stacked a pass), `state` what it
        carries beside the hidden states (the page pools: carried,
        aliased state, as `_plan_token_layers` says why), `rows(x) ->
        [B, h]` the rows the head reads. The final norm follows EVERY
        pass of a looped model and its output is the next pass's input;
        the head reads the pass the exit gate names (`neox.loop_exit`).
        Returns (the head's input [B, h], state, each row's exit pass
        [B] or None where the model does not loop)."""
        fam = self.family
        if fam.loop_steps == 1:
            x, state = one_pass(x, state, 0)
            return fam.final_norm(params, rows(x)), state, None

        def body(carry, loop_pass):
            with scopes.scope("ds.loop"):
                x, state = one_pass(*carry, loop_pass)
                with scopes.scope("ds.loop_exit"):
                    x = fam.final_norm(params, x)
                return (x, state), rows(x)

        (_, state), passes = jax.lax.scan(
            body, (x, state), jnp.arange(fam.loop_steps, dtype=jnp.int32))
        h, exit_pass = neox.loop_exit(fam.cfg, params, passes)
        return h, state, exit_pass

    def _with_held(self, tokens, held, exit_pass=None):
        """A program's tokens, and behind them each row's exit pass where
        the model loops (padded to the tokens' length), then the count of
        held pairs and of held experts touched (`_held_rows`) where the
        model holds a share of its experts: one
        array, one read-back."""
        parts = [tokens]
        if exit_pass is not None:
            parts.append(jnp.pad(
                exit_pass, (0, tokens.shape[0] - exit_pass.shape[0])))
        if self._counts_held:
            parts.append(held.astype(jnp.int32))
        return jnp.concatenate(parts) if len(parts) > 1 else tokens

    def _prefill_fn(self, batch, seqlen):
        key = ("prefill", batch, seqlen)
        if key in self._compiled:
            return self._compiled[key]
        cfg = self.model.config
        fam = self.family
        use_pallas = getattr(self.model, "use_pallas", True)
        ps = self.page_size
        n_pages_row = seqlen // ps

        def last_rows(x, lengths):
            """[B, h]: each row's last real position of x [B, S, h]."""
            B, S = x.shape[:2]
            return x[jnp.arange(B), jnp.clip(lengths - 1, 0, S - 1)]

        def attention(q, k, v, segment_ids, window=None):
            """`_block_core`'s attention of a prefill bucket. A bucket the
            flash forward does not take as it is (fewer rows than its
            128-row block: a 64-token bucket) is padded up to whole blocks
            for the attention alone and its real rows given back: the pad
            keys lie behind every real query and are segment 0, so a real
            row sums over its own keys; the projections and the MLP run
            the bucket's rows."""
            B, S, H, D = q.shape
            pad = whole_blocks(S) - S
            if use_pallas and pad and \
                    not flash_attention_supported(q.shape) and \
                    flash_attention_supported((B, S + pad, H, D)):
                q, k, v, segment_ids = (
                    jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                    for t in (q, k, v, segment_ids))
            # a block model reads its prompt too under the block-causal mask
            return neox.causal_attention(
                q, k, v, use_pallas=use_pallas, segment_ids=segment_ids,
                window=window, block=self.block,
                sm_scale=getattr(cfg, "attn_scale", None))[:, :S]

        def planned_prefill(params, stacks, tokens, lengths, tables, pools,
                            rng):
            """Every model's prefill: the layers a run of one kind at a
            time, each cache kind's K/V scattered as whole pages into
            its own `pools` through its own page table of `tables` (a
            window layer's pages behind the window are table entry 0,
            the trash page; a latent kind's one pool takes its layers'
            latent rows). Returns (the first tokens, the pools)."""
            B, S = tokens.shape
            pos = jnp.arange(S, dtype=jnp.int32)[None, :]
            # 1 = real token, 0 = pad: the segmented attention kernels
            # (and the XLA fallback's segment mask) then give each row
            # causal attention over its own tokens only
            seg = (pos < lengths[:, None]).astype(jnp.int32)
            x = fam.embed_prefill(params, tokens)
            rot = {k: fam.cos_sin_prefill(S, k) for k in pools
                   if k != "state"}
            # a model whose later layers need a prompt's LAST row alone:
            # every row through the layers before `split`, the rest as a
            # one-token walk over the pages this program has written
            split = fam.last_row_from
            L = cfg.num_layers

            def layer(carry, bp, spec, cache_layer):
                x, held, aux = carry
                y, kv = neox._block_core(
                    cfg, bp, x, rot.get(spec.attn), use_pallas, mp=1,
                    reduce_fn=lambda t: t, return_kv=True,
                    attn_fn=partial(
                        attention, window=cfg.attn_window
                        if spec.attn == "window" else None),
                    segment_ids=seg, spec=spec, shared=aux)
                if spec.attn == "ssm":
                    # its state into the slot; its scan output onwards
                    kv, aux = kv[:2], {"mem": kv[2]}
                if spec.attn == "eva" and S > fam.eva[0]:
                    # the pool keeps exact rows of the prompt's LAST window
                    # alone (`_eva_tables`), and pooled rows of the rest
                    W = fam.eva[0]
                    start = jnp.minimum(lengths // W * W, S - W)
                    kv = tuple(jax.vmap(
                        lambda t, at: jax.lax.dynamic_slice_in_dim(t, at, W)
                    )(t, start) for t in kv[:2]) + kv[2:]
                out, rows = self._held_rows(fam, y)
                return (out, held + rows, aux), kv

            def keys_values(x, bp, spec, cache_layer):
                """The layer `split`'s K and V of every row, and nothing
                else of it."""
                with scopes.scope("ds.block"):
                    _, k, v = neox._block_qkv(cfg, bp, x, *rot[spec.attn],
                                              spec.heads)
                return x, (k, v)

            G, D = fam.kv_heads, cfg.head_dim

            def scatter(kind, pool, new, loop_pass, table=None):
                """One pool of cache kind `kind` with a pass's new rows
                [L_kind, B, S, ...] written as whole pages into that
                pass's cache layers at the page-table ids (pad rows hold
                table id 0, the trash page, so duplicates only ever
                collide there): K or V rows [G, D] a token as [G, ps, D]
                tiles, a latent layer's rows [width] as [ps, row], padded
                to the pool's row. Int8 pages: each (head, slot) vector
                quantized, data and scale through the same page ids.

                ONE form for every model, the general one (a pass's layers
                of a looped pool, several runs of a kind), and not the
                fastest at every shape: alone on the chip, us a pool,
                Pythia's 24 layers x 1,024 tokens 914.5 against 679.9 for
                a `vmap` over the layers and 542.1 for a loop over pages,
                Laguna's 2 x 8,192 239.4 / 236.5 / 374.2 (chip runs, PR
                46: PERF.md section 6; ROADMAP S5 has the rule)."""
                if kind == "state":
                    return self._write_state(pool, new, tables[kind])
                table = tables[kind] if table is None else table
                flat_pt = table.reshape(-1)
                n_pages_row = table.shape[1]

                def tiles(rows):
                    """A layer's rows [B, S, ...] as its B * S / ps page
                    tiles, in page-table order."""
                    if kind == "latent":
                        rows = jnp.pad(rows, ((0, 0), (0, 0), (
                            0, pool.shape[-1] - rows.shape[-1])))
                        return rows.reshape(B * n_pages_row, ps, -1)
                    rows = rows.reshape(B, n_pages_row, ps, G, D)
                    return jnp.moveaxis(rows, 3, 2).reshape(
                        B * n_pages_row, G, ps, D)

                new = jax.vmap(tiles)(new)
                n = new.shape[0]
                layers = loop_pass * n + jnp.arange(n, dtype=jnp.int32)
                at = (layers[:, None], flat_pt[None, :])
                if isinstance(pool, QuantizedPages):
                    q8, sc = quantize_kv(new)
                    return QuantizedPages(
                        pool.data.at[at].set(q8),
                        pool.scale.at[at].set(sc.astype(pool.scale.dtype)))
                return pool.at[at].set(new.astype(pool.dtype))

            def one_pass(x, state, loop_pass):
                """The layers once over x [B, S, h], the pass's K/V into
                its own cache layers of the pools."""
                pools, held = state
                aux = {"mem": jnp.zeros((B, S, cfg.ssm_inner), jnp.float32)
                       } if fam.shares else {}
                with scopes.scope("ds.layers"):
                    (x, held, aux), runs = self._plan_layers(
                        fam, stacks, (x, held, aux), layer, loop_pass,
                        (0, split) if split < L else None)
                    if split < L:
                        _, full = self._plan_layers(
                            fam, stacks, x, keys_values, loop_pass,
                            (split, split + 1))
                        runs += full

                def made(kind, i):
                    """Entry i of what the layers of cache kind `kind`
                    returned, in order: [L_kind, B, rows, ...]."""
                    mixers = neox.STATE_MIXERS if kind == "state" \
                        else (kind,)
                    return jnp.concatenate(
                        [kv[i] for spec, kv in runs if spec.attn in mixers])

                with scopes.scope("ds.kv_write"):
                    pools = {kind: tuple(
                        scatter(kind, pool, made(kind, i), loop_pass)
                        for i, pool in enumerate(kind_pools))
                        for kind, kind_pools in pools.items()}
                    if "eva" in pools:
                        # the pooled rows of every chunk, a page at a
                        # time: a whole window's into the table's prefix,
                        # the last window's into the pending pages, the
                        # rest (no whole chunk's) into the trash page
                        pools["eva"] = tuple(
                            scatter("eva", pool, made("eva", 2 + i),
                                    loop_pass, tables["eva_pooled"])
                            for i, pool in enumerate(pools["eva"]))
                if split < L:
                    x, pools, rows = self._plan_token_layers(
                        fam, stacks, last_rows(x, lengths)[:, None],
                        jnp.maximum(lengths - 1, 0), pools, tables, lengths,
                        loop_pass, layers=(split, L),
                        mem=last_rows(aux["mem"], lengths)[:, None])
                    held = held + rows
                return x, (pools, held)

            if self.block:
                # a block model's prefill caches the context's whole
                # blocks and samples nothing: no final norm, no head. What
                # the host reads back is a [B] of zeros that waits on the
                # last layer, so a device error still surfaces there
                x, (pools, _) = one_pass(
                    x, (pools, _NO_HELD), 0)
                done = (last_rows(x, lengths)[:, 0] * 0).astype(jnp.int32)
                return done, pools
            h, (pools, held), exit_pass = self._loop(
                params, x, (pools, _NO_HELD),
                one_pass, (lambda x: last_rows(x, lengths)) if split == L
                else (lambda x: x[:, 0]))
            logits = fam.head(params, h)
            nxt = self._with_held(self._sample(logits, rng), held, exit_pass)
            if fam.pred_heads > 1:
                return nxt, pools, logits
            return nxt, pools

        fn = jax.jit(planned_prefill, donate_argnums=(5,))
        self._compiled[key] = fn
        return fn

    def _decode_fn(self, batch):
        key = ("decode", batch)
        if key in self._compiled:
            return self._compiled[key]
        cfg = self.model.config
        fam = self.family
        width = self._carry_width

        def planned_decode(params, stacks, tokens, lengths, tables, pools,
                           rng, carried, src):
            """Every model's one-token step (`_plan_token_layers`)."""
            # a row that continues from the decode still in flight takes
            # its token from that program's output `carried`, at row
            # `src` (-1: the host's `tokens` entry stands), so nothing of
            # the previous step has to reach the host before this one is
            # enqueued
            tokens = jnp.where(src >= 0, carried[jnp.maximum(src, 0)],
                               tokens)
            # lengths INCLUDE the token decoded this step; 0 marks an
            # inactive (padding) row whose page table is all trash
            pos = jnp.maximum(lengths - 1, 0)
            x = fam.embed_decode(params, tokens, pos)

            def one_pass(x, state, loop_pass):
                pools, held = state
                x, pools, rows = self._plan_token_layers(
                    fam, stacks, x, pos, pools, tables, lengths, loop_pass)
                return x, (pools, held + rows)

            h, (pools, held), exit_pass = self._loop(
                params, x, (pools, _NO_HELD),
                one_pass, lambda x: x[:, 0])
            # one shape for every bucket's tokens: what the next decode
            # (of any bucket) gathers from
            logits = fam.head(params, h)
            nxt = self._with_held(jnp.pad(
                self._sample(logits, rng), (0, width - batch)), held,
                exit_pass)
            if fam.pred_heads > 1:
                return nxt, pools, logits
            return nxt, pools

        B_ = self.block

        def planned_block_decode(params, stacks, state, lengths, tables,
                                 pools, rng, carried, src):
            """One PASS of a block-generating model over every row's TWO
            SLOTS: slot A, the row's block, and slot B, the block behind
            it. `state` [batch, 4 block + 1] int32 is a row as the host
            knows it: slot A's tokens and which of its rows are masked
            (slot B's columns and the last, the duty of the pass before,
            0); a row that continues from the pass in flight takes the
            state from that program's output `carried` at row `src`
            instead, and where that pass committed its slot A the row's
            block is ITS slot B. So rows of one batch are at different
            passes of different blocks and nothing reaches the host
            between passes. `lengths` [batch, 2] = the ends of the two
            slots, the block's first position + block (0: an inactive row)
            and + 2 block (or the first again: the request's last block,
            which has no successor), which the host knows
            (`scheduler.block_ends`).

            The pass decides its own duty from the state. A block with a
            mask left is DENOISED in slot A: every masked row whose
            confidence, the softmax probability of its argmax in float32,
            is over the threshold is unmasked, and never fewer than
            `block_floor` of the most confident masked rows (ties: the
            lower position); slot B is then dead (`_plan_token_layers`).
            A block with no mask left is COMMITTED in slot A (its rows,
            written like any pass's, are now the final ones) and slot B
            is the first denoising pass of its successor, all masks, whose
            rows see the committed block's K and V of the same layer, as a
            block's rows see their own: rows `p .. p + 2 block - 1` of ONE
            forward under the block-causal mask. The head and the
            unmasking run on the denoising slot's rows alone. A denoising
            pass's K/V rows are provisional: only this pass's own rows
            read them, and the block's next pass overwrites them. Returns
            [slot A's tokens, masked | slot B's | duty: 0 denoised, 1
            committed alone, 2 committed and opened slot B] in `state`'s
            layout, padded to the widest batch, and the pools."""
            st = jnp.where((src >= 0)[:, None], carried[jnp.maximum(src, 0)],
                           state)
            moved = (st[:, 4 * B_] > 0)[:, None]
            tok = jnp.where(moved, st[:, 2 * B_:3 * B_], st[:, :B_])
            masked = jnp.where(moved, st[:, 3 * B_:4 * B_],
                               st[:, B_:2 * B_]) > 0
            active = lengths[:, 0] > 0
            pos = jnp.maximum(lengths[:, 0] - B_, 0)
            commit = active & ~jnp.any(masked, axis=1)
            opens = (commit & (lengths[:, 1] > lengths[:, 0]))[:, None]
            fresh = jnp.full_like(tok, cfg.mask_token_id)
            at = pos[:, None] + jnp.arange(2 * B_, dtype=pos.dtype)
            x = fam.embed_at(params, jnp.concatenate([tok, fresh], 1), at)
            x, pools, _ = self._plan_token_layers(
                fam, stacks, x, pos, pools, tables, lengths,
                active=jnp.repeat(jnp.concatenate(
                    [active[:, None], opens], 1), B_, axis=1))
            # the denoising slot: B where the pass opened it, else A (whose
            # block has no mask left where the pass committed alone)
            x = jnp.where(opens[..., None], x[:, B_:], x[:, :B_])
            new, masked_new = jnp.where(opens, fresh, tok), masked | opens
            logits = fam.head(params, fam.final_norm(params, x))
            with scopes.scope("ds.unmask"):
                top = jnp.max(logits, axis=-1)
                best = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                conf = 1.0 / jnp.sum(jnp.exp(logits - top[..., None]),
                                     axis=-1)
                conf = jnp.where(masked_new, conf, -jnp.inf)
                # a row's rank among its block's masked rows by confidence
                row = jnp.arange(B_)
                ahead = (conf[:, None, :] > conf[:, :, None]) | (
                    (conf[:, None, :] == conf[:, :, None]) &
                    (row[None, None, :] < row[None, :, None]))
                rank = jnp.sum(ahead, axis=-1)
                unmask = masked_new & active[:, None] & (
                    (conf > self.block_threshold) |
                    (rank < self.block_floor))
                new = jnp.where(unmask, best, new)
                masked_new = masked_new & ~unmask
                duty = commit[:, None].astype(jnp.int32) + opens
                out = jnp.concatenate(
                    [jnp.where(opens, tok, new), masked_new & ~opens,
                     jnp.where(opens, new, fresh), masked_new | ~opens,
                     duty], axis=1).astype(jnp.int32)
            return jnp.pad(out, ((0, width - batch), (0, 0))), pools

        fn = jax.jit(planned_block_decode if self.block else planned_decode,
                     donate_argnums=(5,))
        self._compiled[key] = fn
        return fn

    def _chunk_fn(self, batch, seqlen, which, mode):
        """The mid-sequence window program: run `seqlen` tokens per row
        starting at per-row absolute positions (`start`, `n_new` valid),
        writing their K/V into the row's pages and attending over the
        WHOLE page table (earlier positions included — that is what
        makes it a continuation, not a fresh prefill). One program
        serves three duties, compiled per (model, duty, shape):

        - ``("target", "sample")`` — prefix-cache suffix prefill: the
          shared pages already hold the prefix K/V, the window covers
          only the suffix, and the first token samples at the last
          valid position;
        - ``("target", "verify")`` — speculative verify: the window is
          [last token, k proposals]; returns per-position argmax tokens
          (greedy) or fp32 next-token probs (sampled acceptance);
        - ``("draft", "write")`` — draft-pool twin of any prefill
          (full or suffix): writes draft K/V only, no head.
        """
        key = ("chunk", which, mode, batch, seqlen)
        if key in self._compiled:
            return self._compiled[key]
        model = self.model if which == "target" else self.draft_model
        fam = self.family if which == "target" else self.draft_family
        cfg = model.config
        ps = self.page_size
        H, D = cfg.num_heads, cfg.head_dim
        NP = self.n_pages_max
        window = self.max_seq_len
        sm_scale = 1.0 / math.sqrt(D)

        def chunk(params, stacked, tokens, start, n_new, page_table,
                  k_pool, v_pool, rng):
            B, S = tokens.shape
            offs = jnp.arange(S, dtype=jnp.int32)[None, :]
            pos = start[:, None] + offs
            valid = offs < n_new[:, None]
            pos_c = jnp.clip(pos, 0, window - 1)
            x = fam.embed_at(params, tokens, pos_c)
            cos, sin, rot_dim = fam.cos_sin_at(pos_c)
            # invalid window slots write to the trash page (the padding
            # idiom everywhere else in this engine)
            page_idx = jnp.take_along_axis(page_table, pos_c // ps, axis=1)
            page_idx = jnp.where(valid, page_idx, 0)
            slot = pos_c % ps
            # per-query attention bound: position p sees cache slots
            # 0..p; invalid rows see nothing (safe-softmax zeros them)
            qpos = jnp.where(valid, pos_c, -1)

            @scopes.scoped("ds.kv_write")
            def store(pool, new):
                """Window K/V rows [B, S, H, D] into their (page, slot)
                cells; int8 pools quantize per (head, token) vector —
                the same `quantize_kv` every other write path uses, so
                identical tokens produce identical page bytes."""
                if isinstance(pool, QuantizedPages):
                    q8, sc = quantize_kv(new)
                    return QuantizedPages(
                        pool.data.at[page_idx, :, slot].set(q8),
                        pool.scale.at[page_idx, :, slot].set(
                            sc.astype(pool.scale.dtype)))
                return pool.at[page_idx, :, slot].set(
                    new.astype(pool.dtype))

            def gather(pool):
                """Row-gathered cache [B, H, NP·ps, D] (the XLA decode
                fallback's layout; int8 dequantizes at the gather)."""
                if isinstance(pool, QuantizedPages):
                    d = pool.data[page_table].astype(jnp.float32) * \
                        pool.scale[page_table].astype(jnp.float32)[..., None]
                else:
                    d = pool[page_table]
                return jnp.moveaxis(d, 2, 1).reshape(B, H, NP * ps, D)

            @scopes.scoped("ds.attn_xla")
            def attend(q, kp, vp):
                k = gather(kp)
                v = gather(vp)
                q = jnp.moveaxis(q, 2, 1)              # [B, H, S, D]
                q = (q.astype(jnp.float32)
                     if isinstance(kp, QuantizedPages)
                     else q.astype(k.dtype))
                s = jnp.einsum("bhsd,bhkd->bhsk", q, k,
                               preferred_element_type=jnp.float32)
                s = s * sm_scale
                kpos = jnp.arange(NP * ps, dtype=jnp.int32)
                mask = kpos[None, None, None, :] <= qpos[:, None, :, None]
                s = jnp.where(mask, s, NEG_INF)
                m = jnp.max(s, axis=-1, keepdims=True)
                prob = jnp.exp(s - m)
                prob = jnp.where(s <= NEG_INF * 0.5, 0.0, prob)
                l = jnp.sum(prob, axis=-1, keepdims=True)
                l = jnp.where(l == 0.0, 1.0, l)
                out = jnp.einsum("bhsk,bhkd->bhsd",
                                 (prob / l).astype(v.dtype), v,
                                 preferred_element_type=jnp.float32)
                return jnp.moveaxis(out, 1, 2).reshape(B, S, H * D)

            # the one run of a homogeneous model (`_refuse_unplanned`)
            (spec, _, at, n), = fam.runs()
            block_xs, layer_of = self._run_xs(stacked[spec.kind], at, n)

            @scopes.scoped("ds.block")
            def body(carry, xs):
                bp, kp, vp = layer_of(*xs[0]), xs[1], xs[2]
                q, k, v = neox._block_qkv(cfg, bp, carry, cos, sin,
                                          rot_dim, H)
                # write BEFORE attending: every window key is visible,
                # causal masking (qpos) keeps attention autoregressive
                kp = store(kp, k)
                vp = store(vp, v)
                with scopes.scope("ds.attn"):
                    attn = attend(q, kp, vp).astype(carry.dtype)
                out = neox.block_hidden(neox._block_post_attn(
                    cfg, bp, carry, attn, reduce_fn=lambda t: t,
                    token_mask=valid))
                return out, (kp, vp)

            with scopes.scope("ds.layers"):
                x, (k_pool, v_pool) = jax.lax.scan(
                    body, x, ((block_xs, jnp.arange(n, dtype=jnp.int32)),
                              k_pool, v_pool))
            if mode == "write":
                return k_pool, v_pool
            if mode == "sample":
                idx = jnp.clip(n_new - 1, 0, S - 1)
                h_last = x[jnp.arange(B), idx][:, None, :]
                h_last = fam.final_norm(params, h_last)
                logits = fam.head(params, h_last[:, 0])
                return self._sample(logits, rng), k_pool, v_pool
            # mode == "verify": every position's next-token view
            h = fam.final_norm(params, x)
            logits = fam.head(params, h)
            with scopes.scope("ds.sample"):
                if self.temperature <= 0.0:
                    out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                else:
                    out = jax.nn.softmax(logits / self.temperature,
                                         axis=-1)
            return out, k_pool, v_pool

        fn = jax.jit(chunk, donate_argnums=(6, 7))
        self._compiled[key] = fn
        return fn

    def _propose_fn(self, batch):
        """The draft program: k+1 unrolled decode steps through the
        draft model against the draft pools (same page ids as the
        target's). Steps 0..k-1 argmax-propose the next token; the
        final step writes the last proposal's K/V without sampling, so
        the draft cache always covers every token the target may
        accept. Per-row `windows` gate writes (and attention) past a
        row's speculative window to the trash page — a row at its
        max_new_tokens edge (window 0) still gets its pending token's
        draft K/V written and nothing else."""
        key = ("spec_propose", batch)
        if key in self._compiled:
            return self._compiled[key]
        fam = self.draft_family
        k_steps = self.spec_k
        window = self.max_seq_len

        def propose(params, stacked, tokens, lengths, windows, page_table,
                    k_pool, v_pool):
            base = jnp.maximum(lengths - 1, 0)
            proposed = []
            tok = tokens
            pools = {"full": (k_pool, v_pool)}
            for j in range(k_steps + 1):
                pos = jnp.clip(base + j, 0, window - 1)
                active = (j <= windows) & (lengths > 0)
                x = fam.embed_decode(params, tok, pos)
                # an inactive row's table is all trash and it attends
                # over nothing
                x, pools, _ = self._plan_token_layers(
                    fam, stacked, x, pos, pools,
                    {"full": jnp.where(active[:, None], page_table, 0)},
                    jnp.where(active, pos + 1, 0))
                if j < k_steps:
                    h = fam.final_norm(params, x)
                    logits = fam.head(params, h[:, 0])
                    with scopes.scope("ds.sample"):
                        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    proposed.append(tok)
            return (jnp.stack(proposed, axis=1), *pools["full"])

        fn = jax.jit(propose, donate_argnums=(6, 7))
        self._compiled[key] = fn
        return fn

    # ------------------------------------------------------------------
    # the serving loop
    # ------------------------------------------------------------------

    def submit(self, prompt, max_new_tokens, eos_token_id=None,
               request_id=None, priority=None, deadline_ms=None,
               ttft_slo_ms=None):
        """Enqueue one request; returns its id.

        ``priority`` is a class name (``interactive``/``batch``;
        config ``inference.default_priority`` when omitted) — typos
        raise with the choices listed. ``deadline_ms`` bounds the
        request's total wall clock (expired requests terminate with a
        typed `DeadlineExceeded`); ``ttft_slo_ms`` is its
        time-to-first-token objective (admission sheds the request when
        the measured TTFT EMA already exceeds it).

        Under overload the admission controller raises a typed
        `RequestRejected` (terminal status ``shed``) carrying a
        retry-after hint from the measured drain rate — the request
        never enters the queue."""
        if self.role == "decode":
            raise RuntimeError(
                f"decode-role pool {self.pool_id!r} does not accept "
                f"fresh requests — submit to a prefill pool (or the "
                f"front-end ServeRouter); its work arrives as KV-page "
                f"handoffs")
        priority = self.default_priority if priority is None else priority
        validate_priority(priority)
        for name, value in (("deadline_ms", deadline_ms),
                            ("ttft_slo_ms", ttft_slo_ms)):
            if value is not None and (
                    not isinstance(value, (int, float)) or
                    isinstance(value, bool) or value <= 0):
                raise ValueError(
                    f"{name} must be a number > 0 (milliseconds), got "
                    f"{value!r}")
        req = Request(prompt=[int(t) for t in prompt],
                      max_new_tokens=int(max_new_tokens),
                      eos_token_id=eos_token_id, request_id=request_id,
                      priority=priority,
                      deadline_ms=(None if deadline_ms is None
                                   else float(deadline_ms)),
                      ttft_slo_ms=(None if ttft_slo_ms is None
                                   else float(ttft_slo_ms)))
        if self.admission is not None:
            usable = max(self.cache.num_pages - 1, 1)
            try:
                self.admission.admit(
                    req, queue_depth=len(self.scheduler.waiting) +
                    len(self.scheduler.quarantined),
                    page_pool_util=1.0 - self.cache.num_free / usable)
            except Exception:
                self.stats["requests_shed"] += 1
                raise
        return self.scheduler.add_request(req, now=time.perf_counter())

    def _next_rng(self):
        self._steps += 1
        return jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                  self._steps)

    def step(self):
        """One scheduler step: admit + prefill new requests, decode one
        token for every in-flight sequence. Returns a summary dict.

        One-step lookahead (docs/inference.md): the call enqueues its
        prefill and its decode first, the decode taking the tokens of
        its continuing rows from the previous decode's output on the
        device, and only then reads back the PREVIOUS call's decode and
        this call's prefill. When it returns, its own decode is still in
        flight; `generated` holds exactly the tokens read back, and
        ``summary["decoded"]`` / ``stats["decode_tokens"]`` count those.
        The speculative path (the host accepts drafts between its two
        programs) and a `prefill`-role engine read every program back
        before they return.

        A prefill/decode exception QUARANTINES the implicated batch
        (evict, free pages, capped-jittered retry; poisoned after
        ``retry.max_attempts`` consecutive failures) instead of killing
        the server — `step()` only raises on scheduler-invariant
        violations. A device error surfaces at the read-back of the
        program that raised it, and that program's batch is the one
        quarantined. The hang watchdog (``inference.hang_timeout_s``)
        is armed for the call once its programs are warm (an XLA compile
        is not a hang) — a program hung on the device stops the next
        call's read-back — and fed on exit, including when the step DIES
        rather than hangs."""
        self.timeline.begin(busy=self._busy)
        summary = None
        try:
            self._plan_step_faults()
            self._apply_page_pressure()
            summary = self._step_inner()
            return summary
        finally:
            if self.watchdog is not None:
                self.watchdog.feed()
            self._release_page_pressure()
            self._busy = self.scheduler.has_work
            slow = self.timeline.end(
                rows=summary["decoded"] if summary else 0)
            if slow is not None:
                self.telemetry.on_anomaly(self, "slow_step",
                                          step=slow["serial"])

    def _step_inner(self):
        now = time.perf_counter()
        finished_before = len(self.scheduler.finished)
        with self._phase("schedule"):
            if self.handoff is not None:
                # pool discovery rides every step: the prefill side's
                # dst pick and the router's gauges read the freshest
                # announce
                self.handoff.announce(self.role, self._pool_load())
                if self.role == "decode":
                    # install BEFORE schedule(): a page set acked this
                    # step joins this step's decode batch
                    self._install_handoffs(now)
                else:
                    self._poll_handoff_acks(now)
            plan = self.scheduler.schedule(now=now)
        self.stats["evictions"] += len(plan.evicted)
        if plan.empty and self.scheduler.quarantined:
            # nothing dispatchable until a quarantine backoff window
            # closes: sleep toward the earliest retry_at (capped so
            # run()/drain() stay responsive to drain requests and
            # deadlines) instead of busy-spinning step() at full CPU —
            # an uncapped spin would also flood the monitor and burn
            # scripted fault-injection step windows on idle serials
            wake = min((r.retry_at for r in self.scheduler.quarantined
                        if r.retry_at is not None), default=now)
            time.sleep(min(max(wake - time.perf_counter(), 0.0), 0.05))
        for req in plan.prefills:
            if req.admitted_at is not None and req.enqueued_at is not None:
                wait = req.admitted_at - req.enqueued_at
                self.stats["admission_wait_s"] += wait
                self.request_metrics.observe_admission_wait(wait)
        # per-step gauges: scheduler backlog + KV page-pool occupancy —
        # the two saturation signals an autoscaler watches (and the
        # admission controller sheds on)
        usable = max(self.cache.num_pages - 1, 1)
        self.stats["queue_depth"] = float(len(self.scheduler.waiting))
        self.stats["page_pool_util"] = 1.0 - self.cache.num_free / usable

        if self.watchdog is not None and (
                self._programs_warm(plan) or
                (plan.empty and self._inflight)):
            self.watchdog.arm()

        # lookahead is what the step does whenever its decode is the
        # plain program: nothing the host must see lies between this
        # call's programs. Speculation accepts drafts on the host
        # between its two, and a prefill pool hands its first tokens off
        lookahead = not self.spec_k and self.role != "prefill"
        decoded_before = self.stats["decode_tokens"]
        newest = None       # the decode this call leaves in flight
        touched = [r for rec in self._inflight for r in rec.reqs] + \
            plan.prefills

        if plan.prefills:
            # the phase closes before a failure settles the engine: the
            # reads of what is in flight open phases of their own
            try:
                with self._phase("prefill"):
                    fault = self._fault_fired("prefill_error")
                    if fault is not None:
                        raise InjectedServingFault(
                            "injected prefill_error fault")
                    self._dispatch_prefill(plan)
            except Exception as e:  # noqa: BLE001 - quarantine, don't die
                self._quarantine_batch(plan.prefills, e, "prefill")
            if not lookahead:
                self._settle()

        if self.role == "prefill":
            # a prefill pool never decodes: freshly prefilled sequences
            # (first token sampled, K/V resident) leave the scheduler
            # for the handoff outbox before the next schedule() can
            # plan a decode batch over them
            self._collect_handoffs()
            self._dispatch_handoffs(now)

        # a failed prefill settled what was in flight (a row may have
        # finished on its EOS there) and may have run cache-loss
        # recovery, evicting EVERY running sequence (their K/V is
        # gone): the planned decode batch would read trash pages and
        # append garbage tokens — skip it; the evicted requests
        # re-prefill on later steps
        decodes_intact = all(r.state == RUNNING for r in plan.decodes)
        if plan.decodes and decodes_intact:
            stall = self._fault_fired("decode_stall")
            if stall is not None:
                time.sleep(stall["seconds"])   # drives the watchdog
            try:
                with self._phase("decode"):
                    fault = self._fault_fired("decode_error")
                    if fault is not None:
                        raise InjectedServingFault(
                            "injected decode_error fault")
                    if self.spec_k:
                        self.stats["decode_tokens"] += \
                            self._run_speculative(plan)
                    elif self.block:
                        with self._phase("block_pass"):
                            newest = self._dispatch_block_decode(plan)
                    else:
                        newest = self._dispatch_decode(plan)
            except Exception as e:  # noqa: BLE001
                self._quarantine_batch(plan.decodes, e, "decode")

        # read back in device order: the previous call's decode, then
        # this call's prefill (TTFT is stamped in the call that ran the
        # prefill, with this call's decode already queued behind it).
        # The newest decode stays in flight, unless none of its rows is
        # live any more: then no later call is owed
        self._settle(keep=newest)
        if self._inflight and not newest.live:
            self._settle()
        produced = self.stats["decode_tokens"] - decoded_before

        finished = len(self.scheduler.finished) - finished_before
        self.stats["finished"] += finished
        self.stats["steps"] += 1
        self._sync_status_counts()
        if self.admission is not None and finished:
            self.admission.note_finished(finished)
        self._record_request_spans(touched)
        if self.monitor is not None:
            # per-step saturation series keyed by total generated tokens
            # (the Serve/* convention); buffered — no per-step flush
            total = self.stats["prefill_tokens"] + \
                self.stats["decode_tokens"]
            scalars = {
                "Serve/queue_depth": self.stats["queue_depth"],
                "Serve/page_pool_util": self.stats["page_pool_util"],
                "Serve/running": float(len(self.scheduler.running)),
                "Serve/slow_steps": float(self.stats["slow_steps"])}
            # per-status terminal counters: exported through every
            # monitor backend (Prometheus gauges + JSONL events)
            for status, tag in REQUEST_STATUS_FAMILIES.items():
                scalars[tag] = float(self.stats[f"requests_{status}"])
            if self.prefix_cache is not None:
                pcs = self.prefix_cache.stats
                scalars[PREFIX_HIT_RATE] = \
                    pcs["hits"] / max(pcs["lookups"], 1)
                scalars[PREFIX_PAGES_SHARED] = float(pcs["pages_shared"])
                scalars[PREFIX_SAVED_PREFILL_TOKENS] = \
                    float(pcs["saved_prefill_tokens"])
            if self.spec_k:
                scalars[SPEC_ACCEPTANCE_RATE] = \
                    self.stats["spec_accepted"] / \
                    max(self.stats["spec_proposed"], 1)
            if self.eva_window:
                scalars[EVA_WINDOWS_ROLLED] = float(
                    self.stats["eva_windows_rolled"])
                scalars[EVA_PAGES_RELEASED] = float(
                    self.stats["eva_pages_released"])
                scalars[EVA_PENDING_PAGES] = float(
                    self.stats["eva_pending_pages"])
            if self.role != "unified":
                for key in ("handoff_sent", "handoff_acked",
                            "handoff_rejected", "handoff_expired",
                            "handoff_installed", "handoff_refused"):
                    scalars[f"Serve/{key}"] = float(self.stats[key])
            self.monitor.record(total, scalars)
        return {"prefilled": len(plan.prefills), "decoded": produced,
                "evicted": len(plan.evicted), "finished": finished}

    def _sync_status_counts(self):
        """Mirror the scheduler's terminal-status tallies into the
        engine stats (``shed`` is engine-owned: shed requests never
        enter the scheduler)."""
        sc = self.scheduler.status_counts
        self.stats["window_pages_released"] = \
            self.scheduler.window_pages_released
        self.stats["eva_windows_rolled"] = self.scheduler.eva_windows_rolled
        self.stats["eva_pages_released"] = self.scheduler.eva_pages_released
        self.stats["requests_ok"] = sc["ok"]
        self.stats["requests_deadline_exceeded"] = sc["deadline_exceeded"]
        self.stats["requests_failed"] = sc["failed"]

    # ------------------------------------------------------------------
    # step-failure quarantine + serving fault injection
    # ------------------------------------------------------------------

    def _plan_step_faults(self):
        """One injector turn per serving step: pop the serving-kind
        host faults fired for this step (training kinds in a shared
        DS_FAULT_INJECT plan are ignored here)."""
        self._step_faults = []
        if self.fault_injector is None:
            return
        self.fault_injector.plan_next_step()
        self._step_faults = [
            f for f in self.fault_injector.take_host_faults()
            if f["kind"] in SERVING_FAULT_KINDS]

    def _fault_fired(self, kind):
        return next((f for f in self._step_faults if f["kind"] == kind),
                    None)

    def _apply_page_pressure(self):
        """``page_pool_pressure`` fault: seize a fraction of the FREE
        pool for this step so scheduling runs under memory pressure
        (eviction, admission shedding); released at step end."""
        fault = self._fault_fired("page_pool_pressure")
        if fault is None:
            return
        n = int(self.cache.num_free * fault["factor"])
        got = self.cache.allocate(n)
        if got:
            self._pressure_pages.extend(got)
            logger.warning(
                f"fault injection: page_pool_pressure seized {len(got)} "
                f"free page(s) for this step")

    def _release_page_pressure(self):
        if self._pressure_pages:
            self.cache.free(self._pressure_pages)
            self._pressure_pages = []

    def _quarantine_batch(self, requests, exc, phase):
        """A prefill/decode step failed: quarantine every implicated
        request (attribution is batch-granular — the failing request
        cannot be identified inside one compiled call; innocent
        co-batched requests reset their failure run at their next
        completed step). Transient failures get capped-jittered
        retries; a request failing ``retry.max_attempts`` consecutive
        steps is poisoned permanently with a typed `RequestFailed`
        (the serving mirror of PR 9's poison-step detector)."""
        # what is still in flight lands first: its tokens are the
        # requests' own (a program that fails at ITS read-back
        # quarantines its own batch there, `_read_failed`), and recovery
        # below needs a settled engine
        self._settle()
        now = time.perf_counter()
        self._recover_cache_if_lost(now)
        # the exception rides on poisoned requests (RequestFailed.
        # last_error) that live until the caller pops them: drop its
        # traceback NOW, or the stored frame graph pins this step's
        # plan/batch arrays (and the engine) for that whole lifetime
        exc.__traceback__ = None
        # one fault, one quarantine: the settle above may have failed at
        # a read-back that held some of these requests (`_read_failed`
        # quarantined them there), and cache-loss recovery may have
        # failed some
        parked = {id(r) for r in self.scheduler.quarantined}
        requests = [r for r in requests
                    if r.state != FINISHED and id(r) not in parked]
        if not requests:
            logger.warning(
                f"serving {phase} step failed ({type(exc).__name__}: "
                f"{exc}) — its requests are quarantined already")
            return
        rp = self.retry_params
        poisoned = 0
        for req in requests:
            req.failures += 1
            if req.failures >= rp["max_attempts"]:
                poisoned += 1
                self.scheduler.finish_failed(req, RequestFailed(
                    f"request {req.request_id} failed {req.failures} "
                    f"consecutive {phase} steps — poisoned "
                    f"({type(exc).__name__}: {exc})",
                    last_error=exc, attempts=req.failures))
            else:
                delay_ms = backoff_delay(
                    req.failures, rp["backoff_base_ms"],
                    rp["backoff_cap_ms"], rp["jitter"], self._retry_rng)
                self.scheduler.quarantine_request(
                    req, retry_at=now + delay_ms / 1e3, now=now)
                self.stats["retries"] += 1
        self.stats["quarantines"] += 1
        logger.warning(
            f"serving {phase} step failed ({type(exc).__name__}: {exc}) "
            f"— quarantined {len(requests)} request(s) "
            f"({poisoned} poisoned); the server stays up")

    def _recover_cache_if_lost(self, now):
        """A compiled call that died MID-EXECUTION consumed the donated
        K/V pools: rebuild them zeroed and evict every running sequence
        (their cached context is gone — eviction re-prefills it from
        the full token history on readmission). Errors raised before
        dispatch (the common case, incl. injected faults) leave the
        donated buffers intact and skip this entirely."""
        k_data = self.cache.data_array(self.cache.k)
        deleted = getattr(k_data, "is_deleted", lambda: False)()
        if not deleted:
            return
        logger.error(
            "serving step died mid-execution with the KV pools donated "
            "— rebuilding zeroed pools and re-prefilling every running "
            "sequence")
        for cache in self.caches.values():
            cache.reset_pools()
        if self.state_cache is not None:
            self.state_cache.reset_pools()
        if self.draft_cache is not None:
            # the draft pools ride the same compiled calls (donated):
            # assume them consumed too and rebuild — the re-prefills
            # rewrite both models' K/V from the full token history
            self.draft_cache.reset_pools()
        if self.prefix_cache is not None:
            # registered prefix K/V died with the pools: drop every
            # chain and detach not-yet-admitted attachments, or new
            # requests would share zeroed pages
            self.prefix_cache.clear()
            self.scheduler.detach_waiting_prefixes()
        while self.scheduler.running:
            self.scheduler._evict_victim(now)
        # outbox/pending-offer requests hold pages the loss consumed
        # too: withdraw their offers and requeue for full re-prefill
        for key, (req, _) in list(self._pending_handoff.items()):
            self.handoff.withdraw(key)
            self.stats["handoff_expired"] += 1
            self.scheduler.requeue_handoff(req, now=now)
        self._pending_handoff = {}
        for req in self._handoff_outbox:
            self.scheduler.requeue_handoff(req, now=now)
        self._handoff_outbox = []

    # ------------------------------------------------------------------
    # disaggregated prefill/decode handoff (docs/inference.md)
    # ------------------------------------------------------------------

    def _pool_load(self):
        """The load gauge this pool announces: backlog plus page-pool
        occupancy (the fraction breaks ties between pools with equal
        request counts) — the prefill side's least-loaded dst pick and
        the router's weighted score both consume it."""
        usable = max(self.cache.num_pages - 1, 1)
        return (len(self.scheduler.running) +
                len(self.scheduler.waiting) +
                len(self.scheduler.quarantined) +
                len(self._handoff_outbox) + len(self._pending_handoff) +
                (1.0 - self.cache.num_free / usable))

    def _collect_handoffs(self):
        """Move every running sequence with a sampled token out of the
        scheduler into the handoff outbox (prefill role only). The
        request keeps its pages (freed on the accepted ack) but stops
        being schedulable here — its decode happens on the other
        pool."""
        moved = [r for r in self.scheduler.running if r.generated]
        for req in moved:
            self.scheduler.running.remove(req)
            self._handoff_outbox.append(req)

    def _encode_handoff(self, req, now):
        """One offer payload: the page bytes (`encode_pages` — int8
        scales included) plus the request metadata the decode pool
        rebuilds the `Request` from. Clocks do not cross the wire —
        the deadline travels as REMAINING milliseconds."""
        payload = encode_pages(self.cache, req.pages)
        deadline_remaining_ms = None
        if req.deadline_at is not None:
            deadline_remaining_ms = (req.deadline_at - now) * 1e3
        payload["request"] = {
            "request_id": req.request_id,
            "prompt": [int(t) for t in req.prompt],
            "generated": [int(t) for t in req.generated],
            "max_new_tokens": int(req.max_new_tokens),
            "eos_token_id": req.eos_token_id,
            "priority": req.priority,
            "deadline_remaining_ms": deadline_remaining_ms,
            "ttft_slo_ms": req.ttft_slo_ms,
            "cached": int(req.cached),
            "evictions": int(req.evictions),
        }
        return payload

    def _dispatch_handoffs(self, now):
        """Offer every outbox request to the least-loaded announced
        decode pool. No decode pool announced yet → the outbox simply
        waits (the requests hold their pages and re-offer next step)."""
        if not self._handoff_outbox:
            return
        dst = self.handoff.choose_decode_pool()
        if dst is None:
            return
        for req in self._handoff_outbox:
            key = self.handoff.offer(dst, str(req.request_id),
                                     self._encode_handoff(req, now))
            self._pending_handoff[key] = (req, now)
            self.stats["handoff_sent"] += 1
        self._handoff_outbox = []

    def _poll_handoff_acks(self, now):
        """Prefill-side verdict sweep: free pages on ``accepted``
        (the decode pool owns the sequence now), requeue with eviction
        semantics on ``rejected``, and withdraw + requeue offers older
        than ``handoff_timeout_s`` (a late ack for a withdrawn offer is
        dropped as stale)."""
        for key, _, payload in self.handoff.poll_acks():
            entry = self._pending_handoff.pop(key, None)
            self.handoff.retire(key)
            if entry is None:
                continue               # ack for a withdrawn offer
            req, offered_at = entry
            if payload.get("state") == ACCEPTED:
                self.stats["handoff_acked"] += 1
                self.request_metrics.observe_handoff(now - offered_at)
                # registry-shared pages just lose this request's ref
                self.scheduler._release_pages(req)
                req.state = FINISHED
            else:
                self.stats["handoff_rejected"] += 1
                self.scheduler.requeue_handoff(req, now=now)
        for key, (req, offered_at) in list(self._pending_handoff.items()):
            if now - offered_at <= self.handoff_timeout_s:
                continue
            del self._pending_handoff[key]
            self.handoff.withdraw(key)
            self.stats["handoff_expired"] += 1
            self.scheduler.requeue_handoff(req, now=now)

    def _install_handoffs(self, now):
        """Decode-side sweep: install every offer addressed to this
        pool, acking each with its verdict (the ack overwrites the
        offer slot — the page bytes never outlive one trip)."""
        for key, payload in self.handoff.poll_offers():
            try:
                self._install_handoff(payload, now)
            except HandoffRejected as e:
                self.stats["handoff_refused"] += 1
                self.handoff.ack(key, ok=False, reason=e.reason)
            else:
                self.stats["handoff_installed"] += 1
                self.handoff.ack(key, ok=True)

    def _install_handoff(self, payload, now):
        """Land one offered request in this pool: geometry/capacity
        checks, prefix-cache dedupe (chain pages this pool already
        holds are retained, not rewritten), page allocation + batched
        scatter, then mid-stream admission straight into `running` —
        sampled tokens, priority, and remaining deadline intact. Raises
        typed `HandoffRejected`; every rejection path leaves this
        pool's free list and refcounts exactly as it found them."""
        if self._handoff_draining or self._drain_requested:
            raise HandoffRejected(
                f"pool {self.pool_id!r} is draining", reason="draining")
        if len(self.scheduler.running) >= self.max_batch_size:
            raise HandoffRejected(
                f"pool {self.pool_id!r} decode batch is full "
                f"({self.max_batch_size})", reason="busy")
        check_geometry(self.cache, payload)
        meta = payload["request"]
        prompt = [int(t) for t in meta["prompt"]]
        shared_pages, prefix_node = [], None
        if self.prefix_cache is not None:
            chain = self.prefix_cache.lookup(prompt)
            if chain:
                shared_pages = [n.page for n in chain]
                prefix_node = chain[-1]
        n_shared = len(shared_pages)
        # retain the chain BEFORE allocating: an allocation-shortfall
        # reclaim sweep skips pages with live request references, so
        # the matched chain cannot be reclaimed out from under us
        self.cache.retain(shared_pages)
        own = self.cache.allocate(payload["n"] - n_shared)
        if own is None:
            self.cache.free(shared_pages)
            raise HandoffRejected(
                f"pool {self.pool_id!r} has no room for "
                f"{payload['n'] - n_shared} page(s)", reason="pool_full")
        try:
            write_pages(self.cache, own, payload, skip=n_shared)
        except HandoffRejected:
            self.cache.free(own + shared_pages)
            raise
        req = Request(
            prompt=prompt,
            max_new_tokens=int(meta["max_new_tokens"]),
            eos_token_id=meta["eos_token_id"],
            request_id=meta["request_id"],
            priority=meta.get("priority", self.default_priority),
            ttft_slo_ms=meta.get("ttft_slo_ms"),
            generated=[int(t) for t in meta["generated"]],
            pages=shared_pages + own,
            cached=int(meta["cached"]),
            n_shared=n_shared,
            prefix_node=prefix_node,
            evictions=int(meta.get("evictions", 0)),
            # TTFT was observed ONCE, on the prefill pool: a non-None
            # first_token_at blocks any re-count here (a local eviction
            # re-prefill included); inter-token starts at install
            submitted_at=now, first_token_at=now, last_token_at=now)
        remaining_ms = meta.get("deadline_remaining_ms")
        if remaining_ms is not None:
            req.deadline_ms = float(remaining_ms)
            req.deadline_at = now + float(remaining_ms) / 1e3
        self.scheduler.admit_handoff(req, now=now)
        if self.prefix_cache is not None:
            self.scheduler._register_prefix(req)
        return req

    def _programs_warm(self, plan):
        """True when every compiled program this plan dispatches has
        at least one executable — the watchdog must not count a
        first-call XLA compile as a hang (the PR 4 discipline)."""
        def warm(key):
            fn = self._compiled.get(key)
            if fn is None:
                return False
            return (fn._cache_size() if hasattr(fn, "_cache_size")
                    else 1) >= 1
        if plan.empty:
            return False
        if plan.prefills:
            B, S = plan.prefill_batch, plan.prefill_len
            pkey = (("chunk", "target", "sample", B, S)
                    if plan.prefill_kind == "chunk"
                    else ("prefill", B, S))
            if not warm(pkey):
                return False
            if self.spec_k and not warm(("chunk", "draft", "write", B, S)):
                return False
        if plan.decodes:
            B = plan.decode_batch
            if self.spec_k:
                if not warm(("spec_propose", B)) or not warm(
                        ("chunk", "target", "verify", B, self.spec_k + 1)):
                    return False
            elif not warm(("decode", B)):
                return False
        return True

    def _on_serving_hang(self):
        """Watchdog expiry (watchdog thread): the serving step blew its
        wall-clock deadline. Dump every thread's stack, then request a
        drain-style emergency flush — admissions stop NOW (flag write,
        async-signal-safe) and `run()` performs the full drain + typed
        in-flight failure + metrics flush if/when the stuck step
        returns."""
        from ..runtime.sentinel import dump_all_stacks
        self.watchdog_fires += 1
        self.last_stack_dump = dump_all_stacks()
        logger.error(
            f"serving hang watchdog: step exceeded "
            f"{self.watchdog.timeout_s:.1f}s — requesting an emergency "
            f"drain; all-thread stacks:\n{self.last_stack_dump}")
        self._drain_requested = True
        try:
            if self.monitor is not None:
                self.monitor.flush()
        except Exception:  # noqa: BLE001 - best-effort from the thread
            pass

    def _record_request_spans(self, requests):
        """Per-request lifecycle records behind the telemetry capture
        machinery: while a capture window is open, every request (of
        `requests`: the rows read back this step) that FINISHED this
        step lands in the span buffer as one event
        covering submit → last token (exported in the Chrome trace next
        to the schedule/prefill/decode spans), with the serials of the
        step records that read back its prefill and its last token.
        Zero cost outside a window."""
        tracer = getattr(self.telemetry, "tracer", None)
        if tracer is None or not tracer.capturing:
            return
        now = time.perf_counter()
        for req in requests:
            if req.state == FINISHED and req.submitted_at is not None:
                tracer.record_event(
                    f"request/{req.request_id}", req.submitted_at,
                    (req.last_token_at or now) - req.submitted_at,
                    step=self.timeline.serial,
                    prefill_step=req.prefill_step)

    def _chunk_arrays(self, reqs, B, S):
        """Window inputs for the chunk programs: each request's suffix
        (everything past its shared prefix pages — the whole context
        when nothing is shared) at its absolute positions, plus the
        full-width page table (shared pages included: the window
        attends over the prefix K/V it did not write)."""
        tokens = np.zeros((B, S), np.int32)
        start = np.zeros((B,), np.int32)
        n_new = np.zeros((B,), np.int32)
        for i, req in enumerate(reqs):
            shared = req.n_shared * self.page_size
            suffix = req.context[shared:]
            tokens[i, :len(suffix)] = suffix
            start[i] = shared
            n_new[i] = len(suffix)
        return (tokens, start, n_new,
                self._tables(reqs, B, self.n_pages_max)["full"])

    def _draft_prefill_twin(self, reqs, B, S):
        """Mirror a prefill into the draft pools (speculation on): the
        draft's K/V for every newly written position lands at the SAME
        page ids, so the next propose step attends over a complete
        draft view of the sequence. Shared prefix pages already hold
        the registrant's draft K/V and are not rewritten. The rng slot
        is dead in write mode — a constant key keeps the target
        sampling stream identical to a non-speculative run."""
        with self._phase("build_inputs"):
            args = [jnp.asarray(a)
                    for a in self._chunk_arrays(reqs, B, S)]
        fn = self._chunk_fn(B, S, "draft", "write")
        with self._phase("dispatch"):
            self.draft_cache.k, self.draft_cache.v = fn(
                self.draft_params, self.draft_stacked, *args,
                self.draft_cache.k, self.draft_cache.v,
                jax.random.PRNGKey(0))

    def _phase(self, name):
        """One host phase of a serve step, a span of the step timeline:
        `schedule`; the step's `prefill` and `decode`, and inside them
        `build_inputs` (numpy arrays and their puts), `dispatch` (the
        call of the compiled program, which returns when it is
        enqueued), `readback` (the wait for the device, `device_wait`
        inside it, then the transfer of the sampled tokens), `complete`
        (scheduler bookkeeping and latency observations). Its seconds
        land in `stats[name + "_s"]` and, less the phases inside it, in
        the open step record; with a `telemetry` block it is a span of
        that name there too."""
        return self.timeline.span(name)

    def _readback(self, arr):
        """The step's small result on the host, and the time it got
        there: the one stamp this step's tokens carry (TTFT, inter-token
        gaps, `last_token_at`)."""
        with self._phase("readback"):
            with self._phase("device_wait"):
                arr.block_until_ready()
            out = np.asarray(arr)
        return out, time.perf_counter()

    def _complete_prefills(self, rec, nxt, now):
        for i, req, live in rec.rows():
            if not live:
                continue    # evicted by cache-loss recovery meanwhile
            req.owed.remove(rec.serial)
            req.prefill_step = self.timeline.serial
            self.stats["prefill_requests"] += 1
            if self.block:
                # no token comes of a block model's prefill: TTFT is
                # stamped by the pass that delivers the first one
                self.scheduler.complete_prefill(req)
                self.stats["prefill_tokens"] += req.cached
                continue
            self.scheduler.complete_prefill(req, int(nxt[i]))
            # req.cached is the pre-sampling context length (complete_
            # prefill pins it before appending the first token) —
            # len(req.context) here would double-count that token once
            # decode accounting starts
            self.stats["prefill_tokens"] += req.cached
            self._observe_first_token(req, now)
            req.last_token_at = now

    def _observe_first_token(self, req, now):
        """TTFT: once per request, from the ORIGINAL submit — an evicted
        request's re-prefill resamples a token it already delivered and
        must not re-count. True where `now` is the request's first."""
        if req.first_token_at is not None or req.submitted_at is None:
            return False
        req.first_token_at = now
        ttft_s = now - req.submitted_at
        self.request_metrics.observe_ttft(ttft_s)
        if self.admission is not None:
            # the shedding signal: measured TTFT EMA vs SLOs
            self.admission.observe_ttft(ttft_s * 1e3)
        return True

    def _count_moe_rows(self, phase, tokens, program_tokens=None):
        """What a step's MoE layers route, from shapes the host already
        has: `tokens` real tokens, top_k experts each, in every layer,
        and the rows their buffers hold (padding included) in a program
        compiled for `program_tokens` token rows (None: tokens more of a
        program already counted)."""
        fam = self.family
        if fam.moe_top_k:
            layers = fam.moe_layers
            routed = tokens * fam.moe_top_k * layers
            self.stats[f"moe_rows_{phase}"] += routed
            self.stats["moe_rows_routed"] += routed
            if not self._counts_held:
                self.stats["moe_rows_held"] += routed   # every expert here
            if program_tokens is not None:
                self.stats["moe_buffer_rows"] += \
                    fam.moe_buffer_rows(program_tokens) * layers

    def _dispatch_prefill(self, plan):
        """Build and enqueue the plan's prefill (and its draft twin);
        its first tokens are read back by `_settle`."""
        B, S = plan.prefill_batch, plan.prefill_len
        if plan.prefill_kind == "chunk":
            # prefix-cache hit batch: suffix-only window through the
            # chunk program (the full-prefill scatter would overwrite
            # the shared pages other requests are reading)
            with self._phase("build_inputs"):
                arrays = self._chunk_arrays(plan.prefills, B, S)
                self._count_moe_rows("prefill", int(arrays[2].sum()), B * S)
                args = [jnp.asarray(a) for a in arrays]
            fn = self._chunk_fn(B, S, "target", "sample")
            self.timeline.enqueued(f"chunk {B}x{S}")
        else:
            self.timeline.enqueued(f"prefill {B}x{S}")
            with self._phase("build_inputs"):
                tokens = np.zeros((B, S), np.int32)
                lengths = np.zeros((B,), np.int32)
                for i, req in enumerate(plan.prefills):
                    # a block model's whole blocks; the rest opens the
                    # first generated block
                    ctx = req.context
                    ctx = ctx[:self.scheduler.prefill_tokens(len(ctx))]
                    tokens[i, :len(ctx)] = ctx
                    lengths[i] = len(ctx)
                self._count_moe_rows("prefill", int(lengths.sum()), B * S)
                self.stats["gdn_prefill_tokens"] += int(lengths.sum()) * \
                    self._gdn_layers
                if self.eva_window:
                    self.stats["eva_prefill_pairs"] += sum(
                        self._eva_pairs(int(n)) for n in lengths)
                self.stats["prefill_rows"] += B * S
                self.stats["prefill_rows_cross"] += B * (
                    S if self.family.last_row_from ==
                    self.model.config.num_layers else 1)
                args = [jnp.asarray(tokens), jnp.asarray(lengths),
                        jax.device_put(self._tables(
                            plan.prefills, B, S // self.page_size,
                            prefill=lengths))]
            fn = self._prefill_fn(B, S)
        # the chunk program takes and returns the full kind's K and V apart
        chunk = plan.prefill_kind == "chunk"
        pools = self._pools()
        with self._phase("dispatch"):
            nxt, *out = fn(self.params, self.params_stacked, *args,
                           *(pools["full"] if chunk else (pools,)),
                           self._next_rng())
            self._rebind_pools({"full": tuple(out)} if chunk else out[0])
        # a model with several prediction heads: their logits behind the
        # pools
        self._enqueued("prefill", plan.prefills, nxt,
                       out[1] if not chunk and len(out) > 1 else None)
        if self.spec_k:
            self._draft_prefill_twin(plan.prefills, B, S)

    def _dispatch_decode(self, plan):
        """Build and enqueue the plan's decode step. A row whose last
        token is itself unread (`pending`) names its row of the decode in
        flight, and the program takes the token from there; `lengths`,
        the page table and the page growth behind it are counts the host
        already has."""
        B = plan.decode_batch
        prev, prev_row = self._decode_in_flight()
        with self._phase("build_inputs"):
            tokens = np.zeros((B,), np.int32)
            src = np.full((B,), -1, np.int32)
            lengths = np.zeros((B,), np.int32)
            for i, req in enumerate(plan.decodes):
                if req.pending:
                    src[i] = prev_row[id(req)]
                else:
                    tokens[i] = req.generated[-1]
                lengths[i] = req.cached + req.pending + 1
            tables = self._tables(plan.decodes, B, self.n_pages_max)
            self.stats["decode_steps"] += 1
            if not self.eva_window:
                self.stats["decode_kv_tokens"] += int(lengths.sum())
                self.stats["kv_page_steps_latent" if self.latent
                           else "kv_page_steps_full"] += int(
                    (-(-lengths // self.page_size)).sum())
            if self.latent:
                self.stats["decode_kv_tokens_latent"] += int(lengths.sum())
            if self.window:
                self.stats["decode_kv_tokens_window"] += int(
                    np.minimum(lengths, self.window).sum())
                self.stats["kv_page_steps_window"] += int(
                    np.count_nonzero(tables["window"]))
            if self.eva_window:
                # what the paged kernel reads, by population: the window's
                # rows up to the token, the pooled rows before the window
                pos = lengths[:len(plan.decodes)] - 1
                read = int(self.scheduler.eva_length(pos).sum())
                rows = int((pos % self.eva_window + 1).sum())
                self.stats["decode_kv_tokens_eva_window"] += rows
                self.stats["decode_kv_tokens_eva_summary"] += read - rows
                # `decode_kv_tokens` stays the rows the kernel READS; the
                # contexts those stand for are counted beside it
                self.stats["decode_kv_tokens"] += read
                self.stats["decode_context_tokens_eva"] += int(
                    lengths.sum())
                self.stats["eva_chunks_pooled"] += int(np.count_nonzero(
                    pos % self.eva_chunk == self.eva_chunk - 1))
                self.stats["eva_pending_pages"] = sum(
                    len(r.eva_pending) for r in self.scheduler.running)
            if self.state_cache is not None:
                self.stats["state_slots_in_use"] = self.state_cache.in_use
                self.stats["state_slot_steps"] += len(plan.decodes)
                self.stats["state_byte_steps"] += len(plan.decodes) * \
                    self.stats["state_bytes"]
                self.stats["gdn_state_updates"] += len(plan.decodes) * \
                    self._gdn_layers
            self._count_moe_rows("decode", len(plan.decodes), B)
            args = [jnp.asarray(tokens), jnp.asarray(lengths),
                    jax.device_put(tables)]
            src = jnp.asarray(src)
        return self._launch_decode(plan, f"decode x{B}", args, src, prev)

    def _decode_in_flight(self):
        """(the decode program in flight or None, {id(request): its row
        there}): where a continuing row's input lies on the device."""
        prev = next((rec for rec in reversed(self._inflight)
                     if rec.phase == "decode"), None)
        return prev, {id(r): i for i, r in enumerate(prev.reqs)} \
            if prev else {}

    def _launch_decode(self, plan, key, args, src, prev):
        """Enqueue the plan's decode program on `args`, its continuing
        rows taking their input from `_carry` at `src`; its output is the
        next program's `_carry`."""
        fn = self._decode_fn(plan.decode_batch)
        self.timeline.enqueued(key)
        with self._phase("dispatch"):
            nxt, pools, *logits = fn(
                self.params, self.params_stacked, *args, self._pools(),
                self._next_rng(), self._carry, src)
            self._rebind_pools(pools)
        if prev is not None:
            self.stats["lookahead_steps"] += 1
        self._carry = nxt
        return self._enqueued("decode", plan.decodes, nxt,
                              logits[0] if logits else None)

    def _dispatch_block_decode(self, plan):
        """`_dispatch_decode` of a block-generating model: one pass over
        the two slots of every decoding row. A row whose last pass is
        unread names its row of the pass in flight and the program takes
        the block's state from there; any other row brings the state the
        host last read (`Request.block_tokens` / `block_masked`). Where
        the slots lie (`scheduler.block_ends`), the page table and the
        page growth behind it the host knows without the read-back."""
        B, blk = plan.decode_batch, self.block
        prev, prev_row = self._decode_in_flight()
        with self._phase("build_inputs"):
            state = np.zeros((B, 4 * blk + 1), np.int32)
            src = np.full((B,), -1, np.int32)
            lengths = np.zeros((B, 2), np.int32)
            for i, req in enumerate(plan.decodes):
                if req.pending:
                    src[i] = prev_row[id(req)]
                else:
                    state[i, :blk] = req.block_tokens
                    state[i, blk:2 * blk] = req.block_masked
                lengths[i] = self.scheduler.block_ends(req)
            # what the kernel reads: up to the later slot's end
            kv_tokens = int(lengths[:, 1].sum())
            self.stats["decode_steps"] += 1
            self.stats["block_passes"] += len(plan.decodes)
            self.stats["decode_kv_tokens"] += kv_tokens
            self.stats["decode_kv_tokens_block"] += kv_tokens
            self.stats["kv_page_steps_full"] += int(
                (-(-lengths[:, 1] // self.page_size)).sum())
            # slot A's rows; an opened slot B's are counted at the read-back
            self._count_moe_rows("decode", len(plan.decodes) * blk,
                                 B * 2 * blk)
            args = [jnp.asarray(state), jnp.asarray(lengths),
                    jax.device_put(self._tables(
                        plan.decodes, B, self.n_pages_max))]
            src = jnp.asarray(src)
        return self._launch_decode(plan, f"block x{B}", args, src, prev)

    def _complete_blocks(self, rec, nxt, now):
        """A read-back pass of a block model into its requests: what each
        live row's pass did (its duty, `planned_block_decode`): a commit
        of the block the host knew, a denoising of that block or of its
        successor (`scheduler.complete_block`), the tokens it made final
        counted where they were unmasked and `decode_tokens` where they
        were delivered."""
        blk = self.block
        opened = 0

        def note(req, start, committed, tokens_in, masked_in, tokens, masked):
            # tuples of numbers: a record kept through a whole run is then
            # nothing the collector has to walk
            if self.block_trace is not None:
                self.block_trace.append({
                    "request": req.request_id, "program": rec.serial,
                    "start": start, "committed": committed,
                    "tokens_in": tuple(tokens_in),
                    "masked_in": tuple(masked_in),
                    "tokens": tuple(tokens.tolist()),
                    "masked": tuple(bool(m) for m in masked)})

        for i, req, live in rec.rows():
            duty = int(nxt[i, 4 * blk])
            committed = duty > 0
            at = 2 * blk if committed else 0      # the denoised slot
            tokens, masked = nxt[i, at:at + blk], nxt[i, at + blk:at + 2 * blk]
            self.stats["block_commit_passes"] += committed
            self.stats["block_fused_commits"] += duty == 2
            self.stats["block_commit_only_passes"] += duty == 1
            opened += duty == 2
            if not live:
                self.stats["lookahead_discarded"] += 1
                continue
            req.owed.remove(rec.serial)
            start = req.cached
            tokens_in, masked_in = req.block_tokens, req.block_masked
            if committed:
                self.stats["blocks_committed"] += 1
                note(req, start, True, tokens_in, masked_in, nxt[i, :blk],
                     nxt[i, blk:2 * blk])
                # its successor came into the pass all masks
                start += blk
                tokens_in = [self.model.config.mask_token_id] * blk
                masked_in = [True] * blk
            if duty != 1:
                note(req, start, False, tokens_in, masked_in, tokens, masked)
                final = sum(masked_in) - int(masked.sum())
                self.stats["block_tokens_final"] += final
                if final and req.first_unmask_at is None and \
                        req.submitted_at is not None:
                    req.first_unmask_at = now
                    self.stats["block_first_unmasks"] += 1
                    self.stats["block_first_unmask_s"] += \
                        now - req.submitted_at
            delivered = self.scheduler.complete_block(req, tokens, masked,
                                                      committed)
            if not delivered:
                continue
            self.stats["decode_tokens"] += delivered
            if not self._observe_first_token(req, now) and \
                    req.last_token_at is not None:
                self.request_metrics.observe_inter_token(
                    (now - req.last_token_at) / delivered)
            req.last_token_at = now
        self._count_moe_rows("decode", opened * blk)

    def _zero_carry(self):
        # a decode program's output (`_with_held`): the tokens, a looped
        # model's exit passes behind them, the two held counts; a block
        # model's is its rows' block state (`planned_block_decode`)
        if self.block:
            return jnp.asarray(np.zeros(
                (self._carry_width, 4 * self.block + 1), np.int32))
        return jnp.asarray(np.zeros(
            (self._carry_width * (2 if self.loop_steps > 1 else 1) +
             2 * int(self._counts_held),), np.int32))

    def _pools(self):
        """{cache kind: (K, V) pools | (latent pool,)} as the programs
        take them, donated, and give them back (`_rebind_pools`)."""
        pools = {kind: (c.k,) if c.v is None else (c.k, c.v)
                 for kind, c in self.caches.items()}
        if self.state_cache is not None:
            pools["state"] = (self.state_cache.conv, self.state_cache.ssm)
        return pools

    def _rebind_pools(self, pools):
        for kind, c in self.caches.items():
            c.k, c.v = (*pools[kind], None)[:2]     # a latent kind: no V
        if self.state_cache is not None:
            self.state_cache.conv, self.state_cache.ssm = pools["state"]

    def _tables(self, reqs, batch, width, prefill=None):
        """{cache kind: page table [batch, width]} of the rows `reqs`, on
        the host: a request's pages of that kind (the window kind's are
        `Request.window_pages`, those inside `width`), the rest the trash
        page 0. A chunk-pooled (eva) model's: `_eva_tables` (`prefill`:
        each row's prompt length, where the tables are a prefill's)."""
        if self.eva_window:
            return self._eva_tables(reqs, batch, width, prefill)
        tables = {kind: np.zeros((batch, width), np.int32)
                  for kind in self.caches}
        for kind, table in tables.items():
            for i, req in enumerate(reqs):
                pages = req.window_pages[:width] if kind == "window" \
                    else req.pages
                table[i, :len(pages)] = pages
        if self.state_cache is not None:
            # the state kind's "table": each row's slot (padding: trash)
            tables["state"] = np.zeros((batch,), np.int32)
            tables["state"][:len(reqs)] = [r.state_slot for r in reqs]
        return tables

    def _eva_pairs(self, n):
        """(query, key) pairs a chunk-pooled prefill of `n` tokens scores
        a head and layer: each query's own window up to itself and one
        pooled row a chunk of the windows before it."""
        W, per_win = self.eva_window, self.eva_window // self.eva_chunk
        full, rest = divmod(n, W)
        return full * W * (W + 1) // 2 + W * per_win * full * (full - 1) // 2 \
            + rest * (rest + 1) // 2 + rest * per_win * full

    def _eva_tables(self, reqs, batch, width, prefill):
        """A chunk-pooled model's tables. A decode step's: `eva` [batch,
        width], each request's table as it is (`Request.pages`: the pooled
        rows' pages of its ended windows, then its window's), and
        `eva_pending` [batch, pages a window's pooled rows fill], the
        pages that take the rows pooled now. A prefill's, of prompts of
        `prefill` tokens in a bucket of `width` pages: `eva` [batch, a
        window's pages], the pages of the prompt's last, partial window
        (the program hands that window's rows alone to the scatter; all
        trash where the prompt is whole windows), and `eva_pooled` [batch,
        width / chunk] by CHUNK: the table's prefix, then the pending
        pages for the last window's chunks, trash behind them."""
        sch = self.scheduler
        per_win, win = sch.eva_pages_summary, sch.eva_pages_window
        if prefill is None:
            tables = {"eva": np.zeros((batch, width), np.int32),
                      "eva_pending": np.zeros((batch, per_win), np.int32)}
            for i, req in enumerate(reqs):
                tables["eva"][i, :len(req.pages)] = req.pages
                tables["eva_pending"][i] = req.eva_pending
            return tables
        tables = {"eva": np.zeros((batch, min(win, width)), np.int32),
                  "eva_pooled": np.zeros(
                      (batch, width // self.eva_chunk), np.int32)}
        for i, req in enumerate(reqs):
            ended = int(prefill[i]) // self.eva_window
            pooled = (req.pages[:per_win * ended] + req.eva_pending)[
                :tables["eva_pooled"].shape[1]]
            tables["eva_pooled"][i, :len(pooled)] = pooled
            if int(prefill[i]) % self.eva_window:
                rows = req.pages[per_win * ended:]
                tables["eva"][i, :len(rows)] = rows
        return tables

    def _enqueued(self, phase, reqs, tokens, logits=None):
        rec = _InFlight(next(self._dispatched), phase, list(reqs), tokens,
                        logits)
        self.stats["loop_passes"] += self.loop_steps
        for req in reqs:
            req.owed.append(rec.serial)
        self._inflight.append(rec)
        return rec

    def _settle(self, keep=None):
        """Read back what is in flight, oldest first, and record it: all
        of it, or all that was enqueued before the program `keep` (the
        decode a step leaves in flight). Whatever needs the engine
        between steps as the synchronous loop left it (drain, a weight
        swap, quarantine, the end of `run`) calls this first. The tokens
        land in the requests, the counts in `stats`."""
        while self._inflight and self._inflight[0] is not keep:
            self._read(self._inflight.popleft())

    def _read(self, rec):
        """One program's tokens to the host and into its requests. A row
        whose request left `running` since the dispatch (it finished on
        an EOS the step before, was evicted, quarantined or expired) is
        dropped: never appended, never counted in `decode_tokens`, its
        pages already released."""
        failure = None
        with self._phase(rec.phase):
            try:
                nxt, now = self._readback(rec.tokens)
            except Exception as e:  # noqa: BLE001 - the device error is here
                failure = e
            else:
                if self._counts_held:
                    # the program's count of pairs on a held expert rides
                    # behind its tokens: the same read-back
                    # (and of held experts that got a row, which says
                    # what share of the experts a DECODE step streams)
                    self.stats["moe_rows_held"] += int(nxt[-2])
                    if rec.phase == "decode":
                        self.stats["moe_experts_touched"] += int(nxt[-1])
                    nxt = nxt[:-2]
                if self.loop_steps > 1:
                    # and so does each row's exit pass, behind the tokens
                    exits = nxt[len(nxt) // 2:]
                    for i, _, live in rec.rows():
                        if live:
                            self.loop_exit_hist[int(exits[i]) - 1] += 1
                if self.head_trace is not None and rec.logits is not None:
                    heads = np.asarray(rec.logits)
                    self.head_trace += [
                        {"request": req.request_id, "logits": heads[i],
                         "at": len(req.prompt) + len(req.generated)}
                        for i, req, live in rec.rows() if live]
                with self._phase("complete"):
                    if rec.phase == "prefill":
                        self._complete_prefills(rec, nxt, now)
                    elif self.block:
                        self._complete_blocks(rec, nxt, now)
                    else:
                        self._complete_decodes(rec, nxt, now)
        if failure is not None:
            self._read_failed(rec, failure)

    def _complete_decodes(self, rec, nxt, now):
        for i, req, live in rec.rows():
            if not live:
                self.stats["lookahead_discarded"] += 1
                continue
            req.owed.remove(rec.serial)
            self.scheduler.complete_decode(req, int(nxt[i]))
            self.stats["decode_tokens"] += 1
            if req.last_token_at is not None:
                self.request_metrics.observe_inter_token(
                    now - req.last_token_at)
            req.last_token_at = now

    def _read_failed(self, rec, exc):
        """A program's device error surfaces at its read-back, when the
        next decode is already enqueued behind it. That one consumed this
        one's tokens and pools, so whatever it holds for the same
        requests is dropped with them (it may fail in its turn, or
        succeed on a token that was never delivered): their `owed` is
        cleared BEFORE the quarantine settles the rest. The batch is
        quarantined once, as the synchronous loop did; rows the successor
        holds for other requests are read and judged on their own."""
        batch = rec.live
        for req in batch:
            req.owed.clear()
        # the failed program's output may be what `_carry` holds; no row
        # reads it once everything is settled, but the next decode takes
        # the array as an argument
        self._carry = self._zero_carry()
        self._quarantine_batch(batch, exc, rec.phase)

    # ------------------------------------------------------------------
    # speculative decoding (docs/inference.md "Speculative decoding")
    # ------------------------------------------------------------------

    @staticmethod
    def _accept_greedy(tgt, proposed, w):
        """Greedy acceptance: `tgt[j]` (the verify forward's argmax at
        window index j) IS the token sequential greedy decode would
        produce there — proposals only decide how many of them land in
        one step. Accept while the draft agrees; the first disagreement
        appends the target's correction and stops; full agreement earns
        the bonus token `tgt[w]`. Token-identical to non-speculative
        greedy decode by construction (pinned by test)."""
        out = []
        for j in range(w):
            out.append(int(tgt[j]))
            if int(proposed[j]) != int(tgt[j]):
                return out
        out.append(int(tgt[w]))
        return out

    def _accept_sampled(self, probs, proposed, w):
        """Rejection-sampling acceptance against the target's
        temperature-scaled distributions (`probs` [S, V] fp32). The
        draft proposes greedily — a delta distribution q = δ(x) — so
        the standard accept test `u < p(x)/q(x)` reduces to `u < p(x)`
        and the residual (p - q)⁺ to p with x zeroed: each emitted
        token is distributed exactly as sequential sampling from p,
        whatever the draft proposed."""
        out = []
        for j in range(w):
            p = np.asarray(probs[j], np.float64)
            x = int(proposed[j])
            if self._spec_rng.random() < p[x]:
                out.append(x)
                continue
            p[x] = 0.0
            total = p.sum()
            if total <= 0.0:
                out.append(x)     # p WAS the delta at x: accept it
            else:
                out.append(int(self._spec_rng.choice(len(p),
                                                     p=p / total)))
            return out
        p = np.asarray(probs[w], np.float64)
        out.append(int(self._spec_rng.choice(len(p), p=p / p.sum())))
        return out

    def _run_speculative(self, plan):
        """One speculative decode step: the draft proposes up to k
        tokens per row, the target verifies the whole window in ONE
        chunk forward, and acceptance appends 1..k+1 tokens per row.
        Pages grown for tokens the shrinking window will never reach
        roll back through the allocator (`_rollback_spec_pages`).
        Returns the number of tokens appended across the batch."""
        B = plan.decode_batch
        reqs = plan.decodes
        with self._phase("build_inputs"):
            tokens = np.zeros((B,), np.int32)
            lengths = np.zeros((B,), np.int32)
            windows = np.full((B,), -1, np.int32)
            for i, req in enumerate(reqs):
                tokens[i] = req.generated[-1]
                lengths[i] = req.cached + 1
                windows[i] = self.scheduler._spec_window(req)
            self.stats["decode_kv_tokens"] += int(lengths.sum())
            pt = jnp.asarray(self._tables(reqs, B, self.n_pages_max)["full"])
            args = [jnp.asarray(a) for a in (tokens, lengths, windows)]
        fn = self._propose_fn(B)
        self.timeline.enqueued(f"speculate x{B}")
        with self._phase("dispatch"):
            proposed, self.draft_cache.k, self.draft_cache.v = fn(
                self.draft_params, self.draft_stacked, *args, pt,
                self.draft_cache.k, self.draft_cache.v)
        proposed, _ = self._readback(proposed)

        # verify window per row: [pending token, proposals[:w]] at
        # positions cached..cached+w — the pending token's K/V enters
        # the target cache here, exactly like a plain decode step
        S = self.spec_k + 1
        with self._phase("build_inputs"):
            wtokens = np.zeros((B, S), np.int32)
            n_new = np.zeros((B,), np.int32)
            for i in range(len(reqs)):
                w = int(windows[i])
                wtokens[i, 0] = tokens[i]
                wtokens[i, 1:1 + w] = proposed[i, :w]
                n_new[i] = w + 1
            start = np.maximum(lengths - 1, 0).astype(np.int32)
            args = [jnp.asarray(a) for a in (wtokens, start, n_new)]
        vfn = self._chunk_fn(B, S, "target", "verify")
        with self._phase("dispatch"):
            out, self.cache.k, self.cache.v = vfn(
                self.params, self.params_stacked, *args, pt, self.cache.k,
                self.cache.v, self._next_rng())
        out, now = self._readback(out)

        produced = 0
        with self._phase("complete"):
            for i, req in enumerate(reqs):
                w = int(windows[i])
                if self.temperature <= 0.0:
                    accepted = self._accept_greedy(out[i], proposed[i], w)
                else:
                    accepted = self._accept_sampled(out[i], proposed[i], w)
                self.stats["spec_proposed"] += w
                self.stats["spec_accepted"] += len(accepted) - 1
                appended = self.scheduler.complete_speculative(req,
                                                               accepted)
                produced += appended
                if req.last_token_at is not None and appended:
                    # the user-visible cadence: one step emitted
                    # `appended` tokens, so each token's inter-token gap
                    # is dt/appended
                    per_token = (now - req.last_token_at) / appended
                    for _ in range(appended):
                        self.request_metrics.observe_inter_token(per_token)
                req.last_token_at = now
        self.stats["spec_steps"] += 1
        return produced

    # ------------------------------------------------------------------
    # graceful drain (SIGTERM from the pod scheduler)
    # ------------------------------------------------------------------
    #
    # Serving must NOT inherit the training engine's emergency-save
    # handler semantics: there is no state worth checkpointing mid-
    # decode, and dying mid-step wastes every in-flight sequence. The
    # right shutdown is: stop admitting, finish what's running (bounded
    # by `inference.drain_deadline_s`), flush the Serve/* telemetry,
    # exit 0 so the orchestrator sees a clean termination.

    def install_drain_handler(self):
        """Register SIGTERM/SIGINT to REQUEST a drain (flag only — the
        same async-signal-safe discipline as the training preemption
        handler); `run()` performs the actual drain at its next loop
        iteration. Weakly bound: the signal registry must not pin the
        engine (and its page pools) for the process lifetime."""
        import signal as _signal
        import threading
        import weakref
        if threading.current_thread() is not threading.main_thread():
            return self
        engine_ref = weakref.ref(self)

        def handler(signum, frame):  # noqa: ARG001
            engine = engine_ref()
            if engine is not None:
                engine._drain_requested = True
                engine._drain_signum = signum

        for sig in (_signal.SIGTERM, _signal.SIGINT):
            try:
                self._prev_handlers[sig] = _signal.signal(sig, handler)
            except (ValueError, OSError):  # pragma: no cover
                pass
        return self

    def restore_signal_handlers(self):
        import signal as _signal
        for sig, handler in self._prev_handlers.items():
            try:
                _signal.signal(sig, handler)
            except (ValueError, OSError):  # pragma: no cover
                pass
        self._prev_handlers = {}

    def request_drain(self):
        """Programmatic equivalent of the SIGTERM handler."""
        self._drain_requested = True

    def drain(self, deadline_s=None):
        """Stop admissions, finish in-flight sequences for at most
        `deadline_s` (config `inference.drain_deadline_s` by default),
        then flush Serve/* telemetry. Returns a summary dict; fresh
        queued requests are left unserved (`unserved` counts them) for
        the replacement instance.

        When the deadline elapses, still-in-flight requests are FAILED
        with a typed `DrainAborted` terminal status and flushed to the
        metrics before the process exits — previously they were
        silently abandoned, so a client could never distinguish a
        drain from a crash."""
        deadline_s = (self.drain_deadline_s if deadline_s is None
                      else float(deadline_s))
        self.scheduler.stop_admissions()
        # a draining decode pool refuses fresh handoff offers (typed
        # ``draining`` rejection — the prefill side re-offers to a
        # surviving pool); a draining prefill pool still steps until
        # its outbox and pending offers resolve
        self._handoff_draining = True
        t0 = time.perf_counter()
        deadline_hit = False
        while (self.scheduler.has_inflight_work or
               self._handoff_outbox or self._pending_handoff):
            if time.perf_counter() - t0 > deadline_s:
                deadline_hit = True
                break
            self.step()
        self._settle()      # the deadline cut the loop mid-flight
        abandoned = 0
        for key, (req, _) in list(self._pending_handoff.items()):
            self.handoff.withdraw(key)
            self.scheduler.finish_failed(req, DrainAborted(
                f"graceful-drain deadline ({deadline_s:.1f}s) elapsed "
                f"with request {req.request_id}'s handoff offer still "
                f"unacked", attempts=req.failures))
            abandoned += 1
        self._pending_handoff = {}
        for req in self._handoff_outbox:
            self.scheduler.finish_failed(req, DrainAborted(
                f"graceful-drain deadline ({deadline_s:.1f}s) elapsed "
                f"with request {req.request_id} still awaiting a decode "
                f"pool", attempts=req.failures))
            abandoned += 1
        self._handoff_outbox = []
        for req in self.scheduler.inflight_requests():
            self.scheduler.finish_failed(req, DrainAborted(
                f"graceful-drain deadline ({deadline_s:.1f}s) elapsed "
                f"with request {req.request_id} still in flight "
                f"({len(req.generated)}/{req.max_new_tokens} tokens "
                f"generated)", attempts=req.failures))
            abandoned += 1
        self._sync_status_counts()
        summary = {
            "drained_s": time.perf_counter() - t0,
            "deadline_hit": deadline_hit,
            "inflight_abandoned": abandoned,
            "unserved": sum(1 for r in self.scheduler.waiting
                            if not r.evictions),
        }
        self.serve_stats()          # pushes Serve/* scalars (incl. the
        # per-status terminal counters — the DrainAborted failures land
        # in Serve/requests_failed BEFORE the monitor closes)
        if self.monitor is not None:
            if self._owns_monitor:
                self.monitor.close()  # drain the buffered scalar queue
            else:
                # borrowed from a co-resident training engine: flush the
                # Serve/* scalars but leave it open for Train/* records
                flush = getattr(self.monitor, "flush", None)
                if flush is not None:
                    flush()
        self.telemetry.close()
        self.restore_signal_handlers()
        logger.info(f"inference drain complete: {summary}")
        return summary

    def run(self, max_steps=None):
        """Drive steps until the queue drains (or `max_steps`). A
        pending drain request (SIGTERM via `install_drain_handler`, or
        `request_drain()`) switches to the graceful-drain path and exits
        the process with code 0 once in-flight work is finished — also
        on an IDLE server (nothing in flight ⇒ the drain is just the
        telemetry flush + exit; the SIGTERM contract must not depend on
        traffic being present)."""
        steps = 0
        while True:
            if self._drain_requested:
                self.drain()
                raise SystemExit(0)
            if not (self.scheduler.has_work or self._handoff_outbox or
                    self._pending_handoff):
                break
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                self._settle()
                break
        return steps

    def generate(self, prompts, max_new_tokens, eos_token_id=None):
        """Batch convenience: submit every prompt, drain the queue, and
        return the generated token lists in submission order. Consumes
        `scheduler.pop_finished()` (including any requests already
        finished by earlier manual `step()` driving), so the finished
        list cannot grow across repeated calls."""
        ids = [self.submit(p, max_new_tokens, eos_token_id=eos_token_id)
               for p in prompts]
        done = {}
        while self.scheduler.has_work:
            self.step()
            for r in self.scheduler.pop_finished():
                done[r.request_id] = r
        return [list(done[i].generated) for i in ids]

    def generate_rollouts(self, prompts, max_new_tokens, eos_token_id=None):
        """RL rollout batch API (docs/rl.md): `generate` plus the
        throughput/speculation accounting the driver's `Train/RL/*`
        scalars need. Returns ``(outputs, stats)``
        where ``outputs[i]`` is prompt ``i``'s generated token list and
        ``stats`` carries rollout wall time, generated-token counts and
        the serve-side deltas (compile count, spec acceptance) for THIS
        call only."""
        before = {"compile": self.compile_count(),
                  "spec_proposed": self.stats["spec_proposed"],
                  "spec_accepted": self.stats["spec_accepted"]}
        t0 = time.perf_counter()
        outputs = self.generate(prompts, max_new_tokens,
                                eos_token_id=eos_token_id)
        rollout_s = time.perf_counter() - t0
        tokens = sum(len(o) for o in outputs)
        stats = {
            "rollout_s": rollout_s,
            "rollout_tokens": tokens,
            "tokens_per_s": tokens / max(rollout_s, 1e-9),
            "compile_delta": self.compile_count() - before["compile"],
        }
        if self.spec_k:
            proposed = self.stats["spec_proposed"] - before["spec_proposed"]
            accepted = self.stats["spec_accepted"] - before["spec_accepted"]
            stats["spec_acceptance_rate"] = accepted / max(proposed, 1)
        return outputs, stats

    def serve_stats(self):
        """Counters + phase seconds + request-latency percentiles
        (p50/p99 of admission wait / TTFT / inter-token, from the
        fixed-bucket histograms); also pushed to the monitor (as
        ``Serve/*`` scalars keyed by total generated tokens) when one
        was attached."""
        out = dict(self.stats)
        out.update(self.request_metrics.summary())
        if self.prefix_cache is not None:
            pcs = self.prefix_cache.stats
            out["prefix_lookups"] = pcs["lookups"]
            out["prefix_hits"] = pcs["hits"]
            out["prefix_hit_rate"] = pcs["hits"] / max(pcs["lookups"], 1)
            out["prefix_pages_shared"] = pcs["pages_shared"]
            out["prefix_saved_prefill_tokens"] = \
                pcs["saved_prefill_tokens"]
        if self.spec_k:
            out["spec_acceptance_rate"] = self.stats["spec_accepted"] / \
                max(self.stats["spec_proposed"], 1)
        # the page pools' bytes a cached token occupies, every cache
        # layer (a looped model's: `loop_steps` a layer)
        out["kv_bytes_per_token"] = sum(
            cache.bytes_per_token() for cache in self.caches.values())
        total = out["prefill_tokens"] + out["decode_tokens"]
        if self.monitor is not None:
            self.monitor.record(
                total, {f"Serve/{k}": float(v) for k, v in out.items()})
        if self.loop_steps > 1:
            # tokens by the pass their exit gate chose (entry 0: pass 1)
            out["loop_exit_hist"] = list(self.loop_exit_hist)
        return out
