"""GPT-NeoX family model, TPU-first.

This is the flagship model the reference stack exists to train (DeeperSpeed
is GPT-NeoX's training engine). Architecture follows GPT-NeoX: rotary
position embeddings on a fraction of head dims, parallel attention+MLP
residual, untied final layernorm + output projection.

TPU-first choices:
- bf16 activations, fp32 layernorm/softmax accumulation (MXU-friendly).
- Tensor-parallel PartitionSpecs over the ``model`` mesh axis following the
  Megatron pattern: QKV/MLP-in column-sharded, attn-out/MLP-out
  row-sharded, embeddings vocab-sharded — collectives ride ICI via GSPMD.
- Static shapes; attention via the fused Pallas flash-attention kernel
  (`deeperspeed_tpu.ops.pallas.flash_attention`) for the shapes it
  supports, XLA otherwise — the choice is recorded, and logged on a TPU.
- `jax.checkpoint`-friendly block structure (the engine's activation-
  checkpoint interval remats whole blocks).

Layer factories for pipeline parallelism (`to_layer_specs`) mirror the
reference's GPT-NeoX pipelined topology: embedding → N blocks → final
norm → (tied or untied) output head.
"""

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import scopes
from ..parallel.mesh import DATA_AXIS, MODEL_AXIS, per_shard


@dataclass(frozen=True)
class LayerSpec:
    """One layer of a `GPTNeoXConfig.layer_plan`: what its attention sees
    (`full`: every earlier position; `window`: the last
    `GPTNeoXConfig.attn_window`; `latent`: every earlier position through
    one low-rank row a token, `GPTNeoXConfig.mla_*`; each is also the
    layer's KV cache kind), its query heads (the KV heads and the head dim
    are the model's), its rotary facts, and its FFN (`dense`: one gated MLP of width
    `ffn_width`; `experts`: the routed experts of width
    `moe_expert_width`, with the shared expert where the model has one).
    `rope` is () for plain rotary, or ("yarn", factor, original_max,
    beta_fast, beta_slow, attention_factor).

    Three more mixers take the attention's place and keep no pages of
    their own: `ssm`, a state-space layer (Mamba-1; `GPTNeoXConfig.ssm_*`)
    whose cache is a fixed recurrent state a sequence (the `state` cache
    kind); `gmu`, a gated memory unit on the scan output of the nearest
    `ssm` layer before it, of the same token; `cross`, attention with the
    layer's own queries over the K and V of the model's ONE `full` layer,
    which lies before it. `heads` is 0 for `ssm` and `gmu`.

    `gdn` (a Gated DeltaNet layer, arXiv:2412.06464;
    `GPTNeoXConfig.gdn_*`): linear attention by the gated delta rule, whose
    cache is a MATRIX state a value head and the last rows of a causal
    convolution's input (the `state` cache kind too); it reads no other
    layer. `heads` is 0: its key and value heads are the model's facts.

    `eva` (EVA attention, arXiv:2302.04542, as a byte model uses it): exact
    causal attention inside the query's own window of
    `GPTNeoXConfig.eva_window` positions and, in the same softmax, ONE
    pooled key and value for each chunk of `eva_chunk` positions of every
    EARLIER window (`eva_attention`); its cache kind holds both populations
    of rows in one page pool (docs/inference.md "Chunk-pooled pages")."""
    attn: str = "full"
    heads: int = 0
    rotary_pct: float = 1.0
    rotary_base: float = 10000.0
    rope: tuple = ()
    ffn: str = "dense"

    @property
    def kind(self):
        """The name of the parameter stack that holds such layers."""
        return f"{self.attn}{self.heads}.{self.ffn}"


@dataclass(frozen=True)
class GPTNeoXConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 2048
    rotary_pct: float = 0.25
    rotary_emb_base: int = 10000
    intermediate_mult: int = 4
    layernorm_eps: float = 1e-5
    use_parallel_residual: bool = True
    tie_word_embeddings: bool = False
    param_dtype: object = jnp.float32
    # What a published architecture fixes about the block (a family file
    # sets these from its `config.json`; none is a tuning switch):
    # the norm ("layernorm": scale and bias; "rmsnorm": scale alone),
    norm: str = "layernorm"
    # whether the projections carry biases,
    use_bias: bool = True
    # an RMS norm on q and on k: True, over ALL their features, before
    # the split into heads (OLMoE; the homogeneous block's); "head", over
    # the features of EACH head, one scale vector [head_dim] shared by the
    # heads, before the rotary (a planned block's),
    qk_norm: object = False
    # the FFN's activation ("gelu": the tanh form; "silu") and whether
    # it is gated, act(x Wgate) * (x Wup),
    hidden_act: str = "gelu"
    ffn_gated: bool = False
    # and the FFN's inner width (of the dense MLP, or of ONE expert)
    # where it is not a whole multiple of the hidden size (0: it is
    # `intermediate_mult * hidden_size`).
    ffn_width: int = 0
    # MoE FFN (GShard/Switch; 0 experts = dense MLP). Config-drivable
    # via the JSON `moe` block (engine `apply_ds_config`).
    moe_num_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_jitter_eps: float = 0.0
    moe_aux_loss_coef: float = 0.01
    # GShard G dim; 1 (default) = single global-capacity group — the
    # reference's routing numerics. 0 = auto-size groups (opt-in: capacity
    # becomes per-group, changing token-drop patterns and aux loss).
    # Matches MoELayer's groups=1 default so the two entry points agree.
    moe_num_groups: int = 1
    # dispatch engine: "einsum" (reference GShard one-hot) or "sort"
    # (argsort permutation + Pallas grouped matmul — the fast path)
    moe_dispatch: str = "einsum"
    # expert-parallel all_to_all/compute pipeline depth (sort engine)
    moe_a2a_overlap_chunks: int = 1
    # renormalize top-2 combine weights over capacity-surviving choices
    moe_renorm_kept_choices: bool = False
    # routing that drops nothing (`moe.layer.moe_ffn_dropless`): every
    # token keeps all `moe_top_k` experts (any k), there is no capacity,
    # and the kept weights are renormalised only if `moe_norm_topk_prob`
    # (the published `norm_topk_prob`). The GShard capacity router above
    # is top-1 / top-2 and always normalises its pair.
    moe_dropless: bool = False
    moe_norm_topk_prob: bool = False
    # Train/MoE routing observability (sort dispatch only): per-expert
    # load + capacity-drop stats emitted host-side via async callback
    moe_observability: bool = False
    # packed ragged batches (runtime/packing.py): loss_fn REQUIRES
    # (tokens, labels, segment_ids) and attention/rotary/loss all become
    # segment-aware. Config-drivable via the JSON `packing` block. A
    # 3-tuple batch activates the same path without the flag; the flag
    # makes a missing segment_ids a loud error instead of silent
    # cross-document attention.
    use_segment_ids: bool = False
    # long-context attention engine: "dense" (flash, default) or
    # "sparse" (SparseSelfAttention over the JSON `sparse_attention`
    # block's pattern — local+global / strided per the reference)
    attention_engine: str = "dense"
    # delayed-scaling quantized FFN (ops/pallas/quant_matmul): None =
    # full-precision, "int8"/"fp8" quantize both FFN matmul operands
    # against per-layer amax histories threaded through the block scan.
    # Config-drivable via the JSON `quantization.ffn` block.
    ffn_quant_recipe: object = None
    ffn_quant_margin: float = 1.0
    ffn_quant_history: int = 16
    # A PLANNED model: its layers are not one kind repeated. `layer_plan`
    # names each layer's kind (a `LayerSpec` a layer; empty: the
    # homogeneous block above, `num_layers` times), and the facts below
    # are what such architectures fix beside it (again a family file's,
    # from the published `config.json`; a value the code does not compute
    # raises by name in `check_block`):
    layer_plan: tuple = ()
    # the head dim where it is not `hidden_size / num_heads`,
    attn_head_dim: int = 0
    # KV heads under the query heads (0: one a query head),
    num_kv_heads: int = 0
    # the window of a `window` layer,
    attn_window: int = 0
    # a gate on the attention output before the output projection
    # ("none" | "per-head": sigmoid(a Wg), one scalar a head and token,
    # from the normed input `a` | "elementwise": the same with one gate a
    # FEATURE of every head, `gate_w` [h, heads x head_dim]; published as
    # the second half of each head's query projection),
    attn_gate: str = "none"
    # how the router scores, in float32 ("softmax": over all experts;
    # "sigmoid": each expert alone, with a learned correction bias that
    # is added for the CHOICE of the top k and is no part of the kept
    # weights, the published `noaux_tc`), and over how many groups of
    # experts the choice is limited (1: no groups; more is not computed),
    moe_router_score: str = "softmax"
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # the width of ONE expert where `ffn_width` is a dense layer's (0:
    # `intermediate_size`), a shared expert's width (0: none; it is not
    # gated by the router; with `moe_shared_gate` by a sigmoid of its own,
    # one scalar a token from a leaf `shared_gate` [h, 1]), the factor on
    # the routed experts' sum,
    moe_expert_width: int = 0
    moe_shared_width: int = 0
    moe_shared_gate: bool = False
    moe_routing_scale: float = 1.0
    # and WHICH experts are held here: (first, past-the-last) of the
    # `moe_num_experts` the router scores; () holds them all. The layer
    # computes the held experts' part of the result; what the others
    # would have added is left out (one chip's share of an
    # expert-parallel deployment, without its exchange).
    moe_held: tuple = ()
    # a `latent` layer's attention (MLA): the ranks of the query's and of
    # the keys' and values' low-rank rows, and a head's dims: `nope` (q.k
    # without position), `rope` (q.k with rotary; ONE key row for all
    # heads), `v`. A token's cache row is `mla_kv_rank + mla_rope_dim`
    # wide and has no head axis,
    mla_q_rank: int = 0
    mla_kv_rank: int = 0
    mla_nope_dim: int = 0
    mla_rope_dim: int = 0
    mla_v_dim: int = 0
    # and the multi-token-prediction (`nextn`) block behind the last
    # layer (0 or 1): from the last layer's hidden state at i and the
    # embedding of token i+1, one more layer and the shared head predict
    # token i+2. Training's (an extra loss term of this weight) and a
    # drafter's; serving does not load it.
    mtp_layers: int = 0
    mtp_loss_weight: float = 0.0
    # an RMS norm on each sublayer's OUTPUT as well as its input, before
    # the residual add ("sandwich" norms: two more scale leaves a layer,
    # `ln_attn_out` and `ln_mlp_out`),
    sublayer_out_norm: bool = False
    # a LOOPED model: the whole stack of layers applied `loop_steps`
    # times over the SAME weights, the final norm after every pass (its
    # output is the next pass's input), every pass with a KV cache of its
    # own: pass t of layer l keeps cache layer t * num_layers + l. An
    # exit gate (`loop_exit`: one output a token, sigmoid) gives each
    # pass the probability p_t = g_t prod_{j<t}(1 - g_j) of being the
    # last; the head reads the first pass whose cumulative probability
    # reaches `loop_exit_threshold`, else the last (`loop_exit`). Every
    # pass is computed whatever the gate says: pass t of a later token
    # attends to pass t of this one.
    loop_steps: int = 1
    loop_exit_threshold: float = 1.0
    # a model that GENERATES A BLOCK of tokens at a time (diffusion over
    # blocks): positions are cut into blocks of `generation_block`, aligned
    # to absolute multiples of it, and position i sees j wherever
    # j // block <= i // block (BLOCK-causal: everything before its block
    # and all of its block). A block to be generated starts as
    # `mask_token_id` and is unmasked pass by pass (InferenceEngine,
    # docs/inference.md "Block generation"): a pass unmasks every masked
    # row whose confidence is over `generation_threshold` and never fewer
    # than block / `generation_steps` rows (0: the block length, one row
    # a pass). 0: a token at a time under the causal mask, as every other
    # model.
    generation_block: int = 0
    mask_token_id: int = 0
    generation_steps: int = 0
    generation_threshold: float = 0.9
    # DIFFERENTIAL attention (arXiv:2410.05258) in every attention layer
    # of the plan: a head here is a PAIR of published heads, `head_dim` the
    # pair's width: q_p = [q_p1 | q_p2], a KV head's key [k_g1 | k_g2],
    # its value the whole width; o_p = softmax(q_p1 k_g1 / s) v_g -
    # lam softmax(q_p2 k_g2 / s) v_g with s = sqrt(head_dim / 2), then an
    # RMS norm over o_p's features (one scale `subln` a layer) times
    # (1 - lam0); lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0 from four
    # vectors a layer, lam0 = `diff_lambda_init(layer)`. Computed on the
    # kernels every attention uses (`diff_queries`).
    attn_diff: bool = False
    # a state-space (`ssm`) layer's facts (Mamba-1): inner channels, state
    # size, the causal depthwise convolution's taps, the rank of the
    # step's low-rank projection (0: the plan has no such layer)
    ssm_inner: int = 0
    ssm_state: int = 0
    ssm_conv: int = 0
    ssm_dt_rank: int = 0
    # a Gated DeltaNet (`gdn`) layer's facts: key heads, value heads (a
    # multiple of them: a key head serves consecutive value heads), the
    # width of a head's key and of its value, the taps of the causal
    # depthwise convolution over [q | k | v] (0: the plan has no such
    # layer),
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 0
    gdn_value_dim: int = 0
    gdn_conv: int = 0
    # an `eva` layer's facts: the window inside which attention is exact
    # and the chunk whose rows ONE pooled key and value stand for once
    # their window has ended (0: the plan has no such layer),
    eva_window: int = 0
    eva_chunk: int = 0
    # an RMS norm whose scale is 1 + w (the leaf holds w, zero at init;
    # the norms on each head of q and k, `qk_norm="head"`, follow it),
    norm_unit_offset: bool = False
    # and a head of `num_pred_heads` x `vocab_size` logits a position:
    # head m is the distribution of token t + 1 + m. Greedy decoding
    # reads head 0; every head is computed.
    num_pred_heads: int = 1

    @property
    def head_dim(self):
        return self.attn_head_dim or self.hidden_size // self.num_heads

    @property
    def attn_scale(self):
        """The softmax scale where it is not 1 / sqrt(head_dim) (None):
        differential attention scores half a pair's width."""
        return 1.0 / math.sqrt(self.head_dim // 2) if self.attn_diff \
            else None

    @property
    def plan_shares(self):
        """Whether layers of the plan read what another layer made (a
        memory, another layer's K and V): the layer walks then carry it."""
        return any(s.attn in ("ssm", "gmu", "cross")
                   for s in self.layer_plan)

    @property
    def last_row_from(self):
        """The first layer from which a prefill needs the LAST row alone
        (`num_layers`: every row everywhere): the one `full` layer of a
        plan whose later layers are all `gmu` or `cross`, which read the
        same token's memory and that layer's K and V, never another
        row's output."""
        full = [i for i, s in enumerate(self.layer_plan) if s.attn == "full"]
        if len(full) == 1 and full[0] + 1 < len(self.layer_plan) and all(
                s.attn in ("gmu", "cross")
                for s in self.layer_plan[full[0] + 1:]):
            return full[0]
        return self.num_layers

    @property
    def gdn_channels(self):
        """Channels a gdn layer's convolution runs over: [q | k | v]."""
        return 2 * self.gdn_key_heads * self.gdn_key_dim + \
            self.gdn_value_heads * self.gdn_value_dim

    @property
    def state_shapes(self):
        """What ONE layer of the `state` cache kind keeps a sequence: (the
        convolution's last rows, the recurrent state), as the kernels
        read them. A gdn layer: K - 1 rows of its channels and a [d_k,
        d_v] matrix a value head; an ssm layer: K - 1 rows and N state
        rows of its inner channels; a row of channels as
        `ops.pallas.ssm.state_tile` lays them out."""
        from ..ops.pallas.ssm import state_tile
        if self.gdn_value_heads:
            return ((self.gdn_conv - 1, *state_tile(self.gdn_channels)),
                    (self.gdn_value_heads, self.gdn_key_dim,
                     self.gdn_value_dim))
        tile = state_tile(self.ssm_inner)
        return ((self.ssm_conv - 1, *tile), (self.ssm_state, *tile))

    @property
    def latent_width(self):
        """Features of a latent layer's cache row: [c_kv | rot(k_r)]."""
        return self.mla_kv_rank + self.mla_rope_dim

    @property
    def kv_heads(self):
        return self.num_kv_heads or self.num_heads

    @property
    def intermediate_size(self):
        return self.ffn_width or self.intermediate_mult * self.hidden_size

    @property
    def expert_width(self):
        return self.moe_expert_width or self.intermediate_size

    @property
    def experts_held(self):
        """How many of the router's experts this model holds."""
        lo, hi = self.moe_held or (0, self.moe_num_experts)
        return hi - lo

    def plan_kinds(self):
        """The plan's parameter stacks in order of first appearance:
        {kind name: (its LayerSpec, the layers of that kind)}."""
        kinds = {}
        for i, spec in enumerate(self.layer_plan):
            kinds.setdefault(spec.kind, (spec, []))[1].append(i)
        return kinds

    def plan_runs(self):
        """The plan as runs of consecutive layers of one kind:
        [(spec, first layer, its index within the kind's stack, length)]:
        what a layer loop scans at a time."""
        seen, runs = {}, []
        for i, spec in enumerate(self.layer_plan):
            at = seen.get(spec.kind, 0)
            seen[spec.kind] = at + 1
            if runs and runs[-1][0] == spec:
                runs[-1][3] += 1
            else:
                runs.append([spec, i, at, 1])
        return [tuple(r) for r in runs]

    def cache_layers(self, attn):
        """How many cache layers of kind `attn` ("full" | "window" |
        "latent": pages; "state": a recurrent state a sequence) the model
        keeps: one a layer of that kind (a `state` layer is an `ssm` or a
        `gdn` layer; a `cross` or `gmu` layer keeps none) and pass of the
        loop (`loop_steps`); a homogeneous model's are all "full"."""
        if not self.layer_plan:
            return self.num_layers if attn == "full" else 0
        kinds = STATE_MIXERS if attn == "state" else (attn,)
        return self.loop_steps * sum(1 for s in self.layer_plan
                                     if s.attn in kinds)

    def _planned_params(self, held):
        """Parameters of a planned model by layer kind; `held` counts the
        experts held here (else all the router scores)."""
        h, d, G = self.hidden_size, self.head_dim, self.kv_heads
        E = self.experts_held if held else self.moe_num_experts
        ln = h * (2 if self.norm == "layernorm" else 1)
        total = self.vocab_size * h * (
            1 if self.tie_word_embeddings else 1 + self.num_pred_heads) + ln
        if self.loop_steps > 1:
            total += h + 1              # the exit gate; the loop's weights once
        bias = 1 if self.use_bias else 0
        # differential attention: four lambda vectors and the norm's scale
        diff = 2 * d + d if self.attn_diff else 0

        def layer(spec):
            if spec.attn == "latent":
                attn = self._latent_params(spec.heads)
            elif spec.attn == "ssm":
                di, N, R = self.ssm_inner, self.ssm_state, self.ssm_dt_rank
                attn = h * 2 * di + (self.ssm_conv + 1) * di + \
                    di * (R + 2 * N) + (R + 1) * di + di * N + di + di * h
            elif spec.attn == "gmu":
                attn = 2 * h * self.ssm_inner
            elif spec.attn == "gdn":
                nv, wide = self.gdn_value_heads, \
                    self.gdn_value_heads * self.gdn_value_dim
                ch = self.gdn_channels
                # [q | k | v | z] and [b | a], the convolution, A_log and
                # dt_bias, the output norm's scale, the out-projection
                attn = h * (ch + wide) + h * 2 * nv + self.gdn_conv * ch + \
                    2 * nv + self.gdn_value_dim + wide * h
            elif spec.attn == "cross":
                attn = 2 * h * spec.heads * d + \
                    bias * (spec.heads * d + h) + diff
            else:
                attn = 2 * h * spec.heads * d + 2 * h * G * d + \
                    bias * (spec.heads * d + 2 * G * d + h) + diff
                if self.qk_norm == "head":
                    attn += 2 * d
                if spec.attn == "eva":
                    attn += 2 * spec.heads * d      # phi and mu
            if self.attn_gate != "none":
                attn += h * spec.heads * (
                    d if self.attn_gate == "elementwise" else 1)
            if spec.ffn == "dense":
                ffn = 3 * h * self.intermediate_size
            else:
                ffn = h * self.moe_num_experts + \
                    3 * h * (E * self.expert_width + self.moe_shared_width)
                if self.moe_shared_gate:
                    ffn += h
                if self.moe_router_score == "sigmoid":
                    ffn += self.moe_num_experts     # the correction bias
            return attn + ffn + (4 if self.sublayer_out_norm else 2) * ln

        total += sum(layer(spec) for spec in self.layer_plan)
        if self.mtp_layers:
            # the nextn block: two norms, the [2h, h] projection, one
            # layer of the last layer's kind, its own final norm
            total += 2 * ln + 2 * h * h + layer(self.layer_plan[-1]) + ln
        return total

    def _latent_params(self, heads):
        """A latent layer's seven attention leaves."""
        h, qr, kr = self.hidden_size, self.mla_q_rank, self.mla_kv_rank
        nope, rope, v = self.mla_nope_dim, self.mla_rope_dim, self.mla_v_dim
        return (h * qr + qr + qr * heads * (nope + rope) +
                h * (kr + rope) + kr + kr * heads * (nope + v) +
                heads * v * h)

    def num_params(self, held=True):
        """Parameters of the model. A planned model's are counted by
        layer kind; with a held share of the experts (`moe_held`) the
        count is of what is held here, or with `held=False` of the
        published model."""
        if self.layer_plan:
            return self._planned_params(held)
        h, v, L = self.hidden_size, self.vocab_size, self.num_layers
        i = self.intermediate_size
        bias = 1 if self.use_bias else 0
        norm = h * (1 if self.norm == "rmsnorm" else 2)
        ffn = (3 if self.ffn_gated else 2) * h * i + bias * (i + h)
        if self.moe_num_experts:
            ffn = h * self.moe_num_experts + self.moe_num_experts * ffn
        attn = 4 * h * h + bias * 4 * h + (2 * h if self.qk_norm else 0)
        embed = v * h * (1 if self.tie_word_embeddings else 2)
        return embed + L * (attn + ffn + 2 * norm) + norm

    def check_block(self):
        """Refuse, by name, a block the code does not compute (rather
        than compute something else)."""
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"norm must be 'layernorm' or 'rmsnorm', "
                             f"got {self.norm!r}")
        if self.hidden_act not in FFN_ACTIVATIONS:
            raise ValueError(f"hidden_act must be one of "
                             f"{sorted(FFN_ACTIVATIONS)}, got "
                             f"{self.hidden_act!r}")
        moe, dropless = bool(self.moe_num_experts), self.moe_dropless
        if dropless and not moe:
            raise ValueError("moe_dropless needs moe_num_experts > 0")
        self._check_plan()
        if self.ffn_gated and not dropless and not self.layer_plan:
            raise NotImplementedError(
                "ffn_gated is computed by the dropless MoE experts only "
                "(moe_dropless): the dense MLP and the GShard capacity "
                "experts are not gated")
        if dropless and not self.ffn_gated:
            raise NotImplementedError(
                "dropless MoE experts are gated (ffn_gated): w_in holds "
                "[gate | up]; ungated dropless experts are not computed")
        if dropless and self.use_bias:
            raise NotImplementedError(
                "dropless MoE experts carry no biases (use_bias=False)")
        if moe and not dropless and (self.moe_top_k not in (1, 2)
                                     or self.hidden_act != "gelu"
                                     or not self.use_bias):
            raise NotImplementedError(
                "the GShard capacity router computes top-1 / top-2 biased "
                "GELU experts; any other top_k, activation or bias "
                "setting needs moe_dropless")
        if dropless and self.ffn_quant_recipe is not None:
            raise NotImplementedError(
                "quantization.ffn with dropless MoE experts: the delayed-"
                "scaling path quantizes the fixed-span expert matmuls only")
        if self.ffn_quant_recipe is not None and not self.use_bias:
            raise NotImplementedError(
                "quantization.ffn without biases (use_bias=False): the "
                "delayed-scaling FFN is the biased GPT-NeoX MLP")
        if dropless and self.moe_jitter_eps:
            raise NotImplementedError(
                "moe_jitter_eps with dropless routing: the published "
                "router has no jitter")

    def _check_plan(self):
        """The planned model's facts, and the facts only a planned model
        computes, each refused by name where the code has no path."""
        plan = self.layer_plan
        planned_only = [
            f"{k}={getattr(self, k)!r}" for k, plain in
            (("attn_head_dim", 0), ("num_kv_heads", 0), ("attn_window", 0),
             ("attn_gate", "none"), ("moe_expert_width", 0),
             ("moe_shared_width", 0), ("moe_shared_gate", False),
             ("moe_routing_scale", 1.0), ("moe_held", ()), ("moe_router_score", "softmax"),
             ("mla_q_rank", 0), ("mla_kv_rank", 0), ("mla_nope_dim", 0),
             ("mla_rope_dim", 0), ("mla_v_dim", 0), ("mtp_layers", 0),
             ("sublayer_out_norm", False), ("loop_steps", 1),
             ("loop_exit_threshold", 1.0), ("generation_block", 0),
             ("attn_diff", False), ("ssm_inner", 0), ("ssm_state", 0),
             ("ssm_conv", 0), ("ssm_dt_rank", 0), ("gdn_key_heads", 0),
             ("gdn_value_heads", 0), ("gdn_key_dim", 0),
             ("gdn_value_dim", 0), ("gdn_conv", 0), ("eva_window", 0),
             ("eva_chunk", 0), ("norm_unit_offset", False),
             ("num_pred_heads", 1))
            if getattr(self, k) != plain]
        if self.qk_norm not in (False, True, "head"):
            raise NotImplementedError(
                f"qk_norm {self.qk_norm!r}: False, True (over all of q's "
                f"and k's features, the homogeneous block's) or 'head' "
                f"(over each head's features, a planned block's)")
        if not plan and self.qk_norm == "head":
            planned_only.append("qk_norm='head'")
        if self.moe_router_score not in ("softmax", "sigmoid"):
            raise NotImplementedError(
                f"moe_router_score {self.moe_router_score!r}: the router "
                f"scores in float32 by a 'softmax' over all experts, or "
                f"by a 'sigmoid' of each with a correction bias that "
                f"chooses and does not weigh; no other scoring is computed")
        if (self.moe_n_group, self.moe_topk_group) != (1, 1):
            raise NotImplementedError(
                f"moe_n_group={self.moe_n_group}, moe_topk_group="
                f"{self.moe_topk_group}: the router chooses among all "
                f"experts at once (one group); group-limited routing is "
                f"not computed")
        if self.mtp_layers not in (0, 1):
            raise NotImplementedError(
                f"mtp_layers={self.mtp_layers}: one next-token-prediction "
                f"block (or none) is computed")
        if self.attn_gate not in ("none", "per-head", "elementwise"):
            raise NotImplementedError(
                f"attn_gate {self.attn_gate!r}: 'none', 'per-head' "
                f"(sigmoid(a Wg) a head and token on the attention output "
                f"before the output projection) or 'elementwise' (a "
                f"feature of a head and token)")
        if not plan:
            if planned_only:
                raise NotImplementedError(
                    f"{', '.join(planned_only)} without a layer_plan: the "
                    f"homogeneous block has one KV head a query head of "
                    f"hidden_size / num_heads features, full attention, "
                    f"no gate, a softmax router, no shared expert, every "
                    f"expert held, no latent attention, no "
                    f"next-token-prediction block, no norm on a "
                    f"sublayer's output, no loop, one token a step "
                    f"under the causal mask, no differential attention, "
                    f"no state-space or delta-rule layer, no chunk-pooled "
                    f"(eva) attention, a norm scale without a unit offset "
                    f"and one prediction head")
            return
        if len(plan) != self.num_layers:
            raise ValueError(f"layer_plan names {len(plan)} layers, "
                             f"num_layers is {self.num_layers}")
        other = [f"{k}={getattr(self, k)!r}" for k, want in
                 (("use_parallel_residual", False), ("ffn_gated", True),
                  ("attention_engine", "dense"), ("ffn_quant_recipe", None))
                 if getattr(self, k) != want]
        if (self.norm == "layernorm") != bool(self.use_bias):
            other.append(f"norm={self.norm!r} and use_bias={self.use_bias!r}")
        if self.qk_norm is True:
            other.append("qk_norm=True")
        if other:
            raise NotImplementedError(
                f"a planned block with {', '.join(other)} is not computed: "
                f"it is pre-norm, two norms a layer, a sequential residual, "
                f"either RMSNorm without biases or LayerNorm (scale and "
                f"bias) with biases on the attention projections and none "
                f"on the FFN, no norm on q or k but the one over each "
                f"head's features (qk_norm='head'), gated FFNs")
        for i, spec in enumerate(plan):
            if spec.attn not in MIXERS or \
                    spec.ffn not in ("dense", "experts"):
                raise NotImplementedError(
                    f"layer {i}: attention {spec.attn!r} / FFN "
                    f"{spec.ffn!r}; the kinds are full | window | latent | "
                    f"ssm | gmu | cross | eva | gdn and dense | experts")
            if spec.attn == "latent":
                self._check_latent(i, spec)
            elif spec.attn in ("ssm", "gmu", "gdn"):
                if spec.heads:
                    raise ValueError(f"layer {i}: an {spec.attn} layer has "
                                     f"no heads, got {spec.heads}")
            elif spec.heads < 1 or (spec.attn != "cross" and
                                    spec.heads % self.kv_heads):
                raise ValueError(
                    f"layer {i}: {spec.heads} query heads over "
                    f"{self.kv_heads} KV heads")
            if spec.attn == "window" and self.attn_window < 1:
                raise ValueError(f"layer {i} is a window layer and "
                                 f"attn_window is {self.attn_window}")
            if spec.rope and (spec.rope[0] != "yarn" or len(spec.rope) != 6):
                raise NotImplementedError(
                    f"layer {i}: rope {spec.rope!r}; plain rotary (()) or "
                    f"('yarn', factor, original_max, beta_fast, "
                    f"beta_slow, attention_factor) are computed")
            if spec.ffn == "experts" and not self.moe_dropless:
                raise NotImplementedError(
                    f"layer {i}: a planned model's experts are routed "
                    f"without capacity (moe_dropless); the GShard capacity "
                    f"router is not told which experts are held")
        self._check_loop()
        self._check_generation_block()
        self._check_shared()
        self._check_eva()
        self._check_gdn()
        if self.moe_shared_gate and not self.moe_shared_width:
            raise ValueError("moe_shared_gate without a shared expert "
                             "(moe_shared_width 0)")
        if self.moe_held:
            lo, hi = self.moe_held
            if not 0 <= lo < hi <= self.moe_num_experts:
                raise ValueError(
                    f"moe_held {self.moe_held} is not a range of the "
                    f"router's {self.moe_num_experts} experts")

    def _check_shared(self):
        """The facts of a plan whose layers read what another layer made
        (`ssm` / `gmu` / `cross`) and of differential attention, each
        refused by name where the code has no path."""
        plan, kinds = self.layer_plan, [s.attn for s in self.layer_plan]
        dims = {k: getattr(self, k) for k in
                ("ssm_inner", "ssm_state", "ssm_conv", "ssm_dt_rank")}
        if "ssm" in kinds and (min(dims.values()) < 1 or
                               self.ssm_conv < 2):
            raise ValueError(
                f"an ssm layer needs every one of {dims} positive and a "
                f"convolution of at least 2 taps")
        if "ssm" not in kinds and any(dims.values()):
            raise ValueError(f"{dims} without an ssm layer in the plan")
        for i, kind in enumerate(kinds):
            if kind == "gmu" and "ssm" not in kinds[:i]:
                raise ValueError(
                    f"layer {i} is a gmu layer with no ssm layer before it "
                    f"whose scan output it could gate")
            if kind == "cross" and kinds[:i].count("full") != 1:
                raise NotImplementedError(
                    f"layer {i} is a cross layer behind "
                    f"{kinds[:i].count('full')} full layers: it reads the "
                    f"K and V of the plan's ONE full layer, which lies "
                    f"before it")
            if kind == "cross" and plan[i].heads % self.kv_heads:
                raise ValueError(
                    f"layer {i}: {plan[i].heads} query heads over "
                    f"{self.kv_heads} KV heads")
        if self.attn_diff and (
                self.head_dim % 2 or self.qk_norm or
                self.attn_gate != "none" or "latent" in kinds or
                any(s.rotary_pct for s in plan
                    if s.attn in ("full", "window", "cross"))):
            raise NotImplementedError(
                "attn_diff with an odd head_dim, a norm on q or k, an "
                "attention gate, a latent layer or a rotary: differential "
                "attention is computed over pairs of half-width heads "
                "without positions (a rotary would have to turn each half "
                "of a pair apart)")
        if not self.plan_shares and not self.attn_diff:
            return
        held = [f"{k}={getattr(self, k)!r}" for k, plain in
                (("loop_steps", 1), ("mtp_layers", 0),
                 ("generation_block", 0), ("sublayer_out_norm", False))
                if getattr(self, k) != plain]
        if held or any(s.ffn != "dense" for s in plan):
            raise NotImplementedError(
                f"a plan with an ssm, gmu or cross layer or differential "
                f"attention, with {', '.join(held) or 'an experts layer'}: "
                f"such a plan is computed run once, a token a step, with "
                f"dense FFNs")

    def _check_gdn(self):
        """A `gdn` layer's facts, each refused by name where the code has
        no path."""
        kinds = [s.attn for s in self.layer_plan]
        dims = {k: getattr(self, k) for k in
                ("gdn_key_heads", "gdn_value_heads", "gdn_key_dim",
                 "gdn_value_dim", "gdn_conv")}
        if "gdn" not in kinds:
            if any(dims.values()):
                raise ValueError(f"{dims} without a gdn layer in the plan")
            return
        if min(dims.values()) < 1 or self.gdn_conv < 2 or \
                self.gdn_value_heads % self.gdn_key_heads:
            raise ValueError(
                f"a gdn layer needs every one of {dims} positive, a "
                f"convolution of at least 2 taps and value heads that are "
                f"a multiple of the key heads")
        held = [f"{k}={getattr(self, k)!r}" for k, plain in
                (("loop_steps", 1), ("mtp_layers", 0),
                 ("generation_block", 0), ("sublayer_out_norm", False),
                 ("attn_diff", False), ("use_bias", False))
                if getattr(self, k) != plain]
        other = sorted(set(kinds) - {"gdn", "full"})
        if held or other:
            raise NotImplementedError(
                f"a gdn layer with {', '.join(held + other)}: the delta "
                f"rule is computed in a plan of gdn and full layers run "
                f"once, a token a step, without biases (an ssm layer "
                f"beside it would need a second shape of state a slot)")

    def _check_eva(self):
        """An `eva` layer's facts, the unit-offset norm and the prediction
        heads, each refused by name where the code has no path."""
        plan = self.layer_plan
        W, C = self.eva_window, self.eva_chunk
        if self.norm_unit_offset and self.norm != "rmsnorm":
            raise NotImplementedError(
                "norm_unit_offset with norm='layernorm': the scale 1 + w is "
                "computed for the RMS norm")
        if self.num_pred_heads < 1 or (self.num_pred_heads > 1 and
                                       self.tie_word_embeddings):
            raise ValueError(
                f"num_pred_heads={self.num_pred_heads} with "
                f"tie_word_embeddings={self.tie_word_embeddings}: at least "
                f"one head, and a head of several is no embedding's "
                f"transpose")
        eva = [i for i, s in enumerate(plan) if s.attn == "eva"]
        if not eva:
            if W or C:
                raise ValueError(f"eva_window={W}, eva_chunk={C} without an "
                                 f"eva layer in the plan")
            return
        if W < 1 or C < 1 or W % C:
            raise ValueError(
                f"an eva layer needs a window and a chunk that divides it, "
                f"got eva_window={W}, eva_chunk={C}")
        if len(eva) != len(plan):
            raise NotImplementedError(
                f"eva layers {eva} beside layers of another attention kind "
                f"in one plan: the serving programs carry ONE page pool "
                f"whose table the window's end rewrites")
        if any(plan[i].heads != self.kv_heads for i in eva):
            raise NotImplementedError(
                f"an eva layer of {plan[eva[0]].heads} query heads over "
                f"{self.kv_heads} KV heads: a chunk is pooled by its KV "
                f"head's own phi and mu, one query head a KV head")
        held = [f"{k}={getattr(self, k)!r}" for k, plain in
                (("loop_steps", 1), ("mtp_layers", 0),
                 ("generation_block", 0), ("sublayer_out_norm", False),
                 ("attn_diff", False), ("attn_gate", "none"),
                 ("qk_norm", False), ("use_bias", False))
                if getattr(self, k) != plain]
        if held:
            raise NotImplementedError(
                f"an eva layer with {', '.join(held)}: chunk-pooled "
                f"attention is computed run once, a token a step, with "
                f"plain rotary queries and keys, no bias and no gate")

    def _check_loop(self):
        """A looped model's facts, and the norm on a sublayer's output."""
        if self.loop_steps < 1 or not 0.0 < self.loop_exit_threshold <= 1.0:
            raise ValueError(
                f"loop_steps={self.loop_steps}, loop_exit_threshold="
                f"{self.loop_exit_threshold}: at least one pass, and a "
                f"threshold on a cumulative probability in (0, 1]")
        if self.loop_steps == 1 and self.loop_exit_threshold != 1.0:
            raise ValueError(
                f"loop_exit_threshold={self.loop_exit_threshold} with "
                f"loop_steps=1: one pass has no exit gate")
        if self.loop_steps > 1 and self.mtp_layers:
            raise NotImplementedError(
                f"loop_steps={self.loop_steps} with mtp_layers="
                f"{self.mtp_layers}: which pass's hidden state a "
                f"next-token-prediction block reads is not computed")
        if self.sublayer_out_norm and any(s.ffn != "dense"
                                          for s in self.layer_plan):
            raise NotImplementedError(
                "sublayer_out_norm with an experts layer: the norm on a "
                "sublayer's output is computed for the dense gated MLP")

    def _check_generation_block(self):
        """A block-generating model's facts."""
        B = self.generation_block
        if not B:
            return
        if B < 2 or B & (B - 1) or B > 128:
            raise ValueError(
                f"generation_block={B}: a power of two from 2 to 128 (the "
                f"block-causal mask is `col <= row | (block - 1)`, and a "
                f"block lies inside one attention tile and one page)")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(
                f"mask_token_id={self.mask_token_id} is no token of a "
                f"vocabulary of {self.vocab_size}")
        if not 0 <= self.generation_steps <= B or \
                not 0.0 <= self.generation_threshold <= 1.0:
            raise ValueError(
                f"generation_steps={self.generation_steps}, "
                f"generation_threshold={self.generation_threshold}: at "
                f"most a step a row of the block of {B}, and a probability "
                f"(1: never over it, so block / steps rows a pass)")
        held = [f"{k}={getattr(self, k)!r}" for k, plain in
                (("loop_steps", 1), ("mtp_layers", 0), ("attn_window", 0),
                 ("moe_held", ()))
                if getattr(self, k) != plain]
        if held or any(s.attn != "full" for s in self.layer_plan):
            raise NotImplementedError(
                f"generation_block={B} with "
                f"{', '.join(held) or 'a window or latent layer'}: a block "
                f"pass is computed for a plan of full-attention layers run "
                f"once with every expert held (a window or a latent row "
                f"under the block-causal mask, a loop's pass a block, a "
                f"next-token-prediction block and a held share's count "
                f"are not)")

    def _check_latent(self, i, spec):
        """A latent layer's facts: all five dims, an even rotary part, a
        query.key width the prefill's flash kernel is given as ONE head
        dim beside the value's, plain rotary over the whole rotary part,
        no gate."""
        dims = {k: getattr(self, k) for k in
                ("mla_q_rank", "mla_kv_rank", "mla_nope_dim",
                 "mla_rope_dim", "mla_v_dim")}
        if spec.heads < 1 or min(dims.values()) < 1 or \
                self.mla_rope_dim % 2:
            raise ValueError(
                f"layer {i} is a latent layer of {spec.heads} heads and "
                f"{dims}: every dim is positive and mla_rope_dim even")
        if self.mla_nope_dim + self.mla_rope_dim != self.mla_v_dim:
            raise NotImplementedError(
                f"layer {i}: mla_nope_dim + mla_rope_dim = "
                f"{self.mla_nope_dim + self.mla_rope_dim} and mla_v_dim = "
                f"{self.mla_v_dim}: the prefill's attention kernel takes "
                f"one head dim for q.k and for v; unequal ones are not "
                f"computed")
        if spec.rope or spec.rotary_pct != 1.0 or self.attn_gate != "none":
            raise NotImplementedError(
                f"layer {i}: a latent layer with rope={spec.rope!r}, "
                f"rotary_pct={spec.rotary_pct}, attn_gate="
                f"{self.attn_gate!r}: plain rotary over all mla_rope_dim "
                f"features and no gate are computed (a scaled rotary "
                f"would also scale the softmax)")

    # ---- presets mirroring the config ladder (BASELINE.md) -------------

    @classmethod
    def tiny(cls, **kw):
        return cls(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
                   max_seq_len=128, **kw)

    @classmethod
    def small(cls, **kw):  # GPT-2 small scale
        return cls(hidden_size=768, num_layers=12, num_heads=12, **kw)

    @classmethod
    def xl_1_5b(cls, **kw):  # Megatron-GPT2 1.5B rung
        return cls(hidden_size=1600, num_layers=48, num_heads=25, **kw)

    @classmethod
    def neox_20b(cls, **kw):  # GPT-NeoX-20B rung
        return cls(vocab_size=50432, hidden_size=6144, num_layers=44,
                   num_heads=64, rotary_pct=0.25, **kw)


# what takes the attention's place in a planned layer (`LayerSpec.attn`)
MIXERS = ("full", "window", "latent", "ssm", "gmu", "cross", "eva", "gdn")
# those whose cache is a slot of the `state` kind
STATE_MIXERS = ("ssm", "gdn")


def diff_lambda_init(layer):
    """Differential attention's lam0 of 0-based layer `layer`."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _dense_init(key, shape, dtype, scale=0.02):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def init_norm_params(cfg):
    """A norm's leaves: scale, and LayerNorm's bias."""
    h, dt = cfg.hidden_size, cfg.param_dtype
    # a unit-offset scale holds w of 1 + w
    p = {"scale": jnp.zeros((h,), dt)
         if getattr(cfg, "norm_unit_offset", False) else jnp.ones((h,), dt)}
    if getattr(cfg, "norm", "layernorm") == "layernorm":
        p["bias"] = jnp.zeros((h,), dt)
    return p


def _without_biases(cfg, params):
    """`params` without its `*_b` leaves where the block has none."""
    if getattr(cfg, "use_bias", True):
        return params
    return {k: v for k, v in params.items() if not k.endswith("_b")}


def init_block_params(cfg, key):
    h = cfg.hidden_size
    keys = jax.random.split(key, 4)
    out_scale = 0.02 / math.sqrt(2 * cfg.num_layers)
    dt = cfg.param_dtype
    attn = {
        "qkv_w": _dense_init(keys[0], (h, 3 * h), dt),
        "qkv_b": jnp.zeros((3 * h,), dt),
        "out_w": _dense_init(keys[1], (h, h), dt, scale=out_scale),
        "out_b": jnp.zeros((h,), dt),
    }
    if getattr(cfg, "qk_norm", False):
        attn["q_norm"] = {"scale": jnp.ones((h,), dt)}
        attn["k_norm"] = {"scale": jnp.ones((h,), dt)}
    return {
        "ln_attn": init_norm_params(cfg),
        "ln_mlp": init_norm_params(cfg),
        "attn": _without_biases(cfg, attn),
        "mlp": _init_ffn_params(cfg, keys[2], keys[3], out_scale),
    }


def _init_ffn_params(cfg, k_in, k_out, out_scale):
    h, i, dt = cfg.hidden_size, cfg.intermediate_size, cfg.param_dtype
    E = getattr(cfg, "moe_num_experts", 0)
    if not E:
        return _without_biases(cfg, {
            "in_w": _dense_init(k_in, (h, i), dt),
            "in_b": jnp.zeros((i,), dt),
            "out_w": _dense_init(k_out, (i, h), dt, scale=out_scale),
            "out_b": jnp.zeros((h,), dt),
        })
    kg, ki = jax.random.split(k_in)
    if getattr(cfg, "moe_dropless", False):
        # gated experts, no biases: w_in = [gate | up] along its last dim
        return {
            "gate": _dense_init(kg, (h, E), dt),
            "w_in": _dense_init(ki, (E, h, 2 * i), dt),
            "w_out": _dense_init(k_out, (E, i, h), dt, scale=out_scale),
        }
    return {
        "gate": _dense_init(kg, (h, E), dt),
        "w_in": _dense_init(ki, (E, h, i), dt),
        "b_in": jnp.zeros((E, i), dt),
        "w_out": _dense_init(k_out, (E, i, h), dt, scale=out_scale),
        "b_out": jnp.zeros((E, h), dt),
    }


def _stack_init(key, lead, shape, dtype, scale=0.02):
    """`_dense_init` of `lead` + `shape`, one `shape` matrix at a time
    (a map over their keys): a kind's stack of experts is made without
    its float32 original ever being whole on the device."""
    n = int(np.prod(lead))
    out = jax.lax.map(lambda k: _dense_init(k, shape, dtype, scale),
                      jax.random.split(key, n))
    return out.reshape(*lead, *shape)


def _init_ssm_params(cfg, n, ks, out_scale):
    """An `ssm` layer's leaves (Mamba-1), in the layout the scan runs
    from (channels last): `in_w` [h, 2 d_i] ([u | z]), `conv_w` [K, d_i]
    and `conv_b` [d_i] (tap k meets u_{t-(K-1)+k}), `x_w` [d_i, R + 2N]
    ([step | B | C]), `dt_w` [R, d_i] and `dt_b` [d_i], `A_log` [N, d_i],
    `D` [d_i], `out_w` [d_i, h]. The published initialisers where they
    keep the scan alive over thousands of steps: A = -(1 .. N) a channel,
    `dt_b` the inverse softplus of a step drawn log-uniformly from
    [0.001, 0.1], `dt_w` at R ** -0.5, D = 1."""
    h, dt = cfg.hidden_size, cfg.param_dtype
    di, N, K, R = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_conv, cfg.ssm_dt_rank
    step = jnp.exp(jax.random.uniform(ks[3], (n, di)) *
                   (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return {"in_w": _stack_init(ks[0], (n,), (h, 2 * di), dt),
            "conv_w": _stack_init(ks[1], (n,), (K, di), dt, K ** -0.5),
            "conv_b": jnp.zeros((n, di), dt),
            "x_w": _stack_init(ks[9], (n,), (di, R + 2 * N), dt),
            "dt_w": _stack_init(ks[10], (n,), (R, di), dt, R ** -0.5),
            "dt_b": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
            "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                1, N + 1, dtype=jnp.float32))[None, :, None],
                (n, N, di)).astype(dt),
            "D": jnp.ones((n, di), dt),
            "out_w": _stack_init(ks[2], (n,), (di, h), dt, out_scale)}


def _init_gdn_params(cfg, n, ks, out_scale):
    """A `gdn` layer's leaves: `in_w` [h, channels + n_v d_v] ([q | k | v
    | z]: the convolution's channels first, q and k a key head at a time,
    v and z a value head at a time), `ba_w` [h, 2 n_v] ([b | a]),
    `conv_w` [K, channels] (tap k meets row t - (K - 1) + k; no bias),
    `A_log`, `dt_bias` [n_v], `norm` [d_v] (the output norm's plain scale),
    `out_w` [n_v d_v, h]. The decay's leaves are drawn so that heads
    forget at rates from a few tokens to thousands: `A_log` = log(U(0,
    16)), `dt_bias` the inverse softplus of a step drawn log-uniformly
    from [0.001, 0.1]; `conv_w` uniform at K ** -0.5."""
    h, dt = cfg.hidden_size, cfg.param_dtype
    nv, K, ch = cfg.gdn_value_heads, cfg.gdn_conv, cfg.gdn_channels
    wide = nv * cfg.gdn_value_dim
    step = jnp.exp(jax.random.uniform(ks[3], (n, nv)) *
                   (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return {"in_w": _stack_init(ks[0], (n,), (h, ch + wide), dt),
            "ba_w": _stack_init(ks[1], (n,), (h, 2 * nv), dt),
            "conv_w": jax.random.uniform(
                ks[9], (n, K, ch), minval=-K ** -0.5,
                maxval=K ** -0.5).astype(dt),
            "A_log": jnp.log(jax.random.uniform(
                ks[10], (n, nv), minval=1e-3, maxval=16.0)).astype(dt),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
            "norm": jnp.ones((n, cfg.gdn_value_dim), dt),
            "out_w": _stack_init(ks[2], (n,), (wide, h), dt, out_scale)}


def init_stack_params(cfg, spec, n, key, layers=None):
    """The parameter stack of `n` layers of kind `spec`, every leaf with
    the leading dim `n` (`layers`: their indices in the model, which
    differential attention's lam0 follows). Biases only with
    `cfg.use_bias`, on the attention projections (`q_b`, `kv_b`, `out_b`);
    with `cfg.attn_diff` four lambda vectors `lam_q1`, `lam_k1`, `lam_q2`,
    `lam_k2` [d / 2] (normal 0.1), the scale `subln` [d] of the norm over
    a pair's features, and `lam0` [n] float32, the layer's constant
    (`diff_lambda_init`; no parameter, stored so that a layer's slice of
    the stack carries it). A `cross` layer has `q_w`, `out_w` and those
    alone; an `ssm` layer `_init_ssm_params`' leaves; a `gmu` layer
    `in_w` [h, d_i] and `out_w` [d_i, h]; a `gdn` layer
    `_init_gdn_params`' leaves. Attention: `q_w` [h, H*d], `kv_w`
    [h, 2*G*d] ([K | V], each G heads of d), `out_w` [H*d, h], with
    `qk_norm='head'` the scales `q_norm`, `k_norm` [d] of the norm on each
    head of q and k (zeros, w of 1 + w, with `norm_unit_offset`), and with
    a per-head gate `gate_w` [h, H], with an elementwise one `gate_w`
    [h, H*d]; an `eva` layer's pooling leaves
    `eva_phi`, `eva_mu` [H, d]; a latent layer's seven leaves:
    `q_a` [h, q_rank], `q_a_norm` [q_rank], `q_b` [q_rank, H*(nope+rope)]
    (a head's [nope | rope]), `kv_a` [h, kv_rank+rope] ([c_kv | k_r]),
    `kv_a_norm` [kv_rank], `kv_b` [kv_rank, H*(nope+v)] (a head's
    [W_uk | W_uv]), `out_w` [H*v, h]. FFN, dense: `in_w` [h, 2i]
    ([gate | up]), `out_w` [i, h]. Experts: the router `gate`
    [h, E scored] (and a sigmoid router's `gate_bias` [E scored], seeded
    non-zero at a quarter of the spread of the scores, so that a bias
    that is dropped, or leaks into the weights, shows), `w_in` [E held, h, 2w],
    `w_out` [E held, w, h], and a shared expert's `shared_in` [h, 2s],
    `shared_out` [s, h] (and `shared_gate` [h, 1] where it is gated).
    Norms: `ln_attn`, `ln_mlp` on the sublayers' inputs, and with
    `sublayer_out_norm` `ln_attn_out`, `ln_mlp_out` on their outputs."""
    h, d, dt = cfg.hidden_size, cfg.head_dim, cfg.param_dtype
    H, G = spec.heads, cfg.kv_heads
    out_scale = 0.02 / math.sqrt(2 * cfg.num_layers)
    ks = jax.random.split(key, 12)
    if spec.attn == "latent":
        qr, kr = cfg.mla_q_rank, cfg.mla_kv_rank
        nope, rope, vd = cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
        attn = {"q_a": _stack_init(ks[0], (n,), (h, qr), dt),
                "q_a_norm": jnp.ones((n, qr), dt),
                "q_b": _stack_init(ks[1], (n,), (qr, H * (nope + rope)), dt),
                "kv_a": _stack_init(ks[9], (n,), (h, kr + rope), dt),
                "kv_a_norm": jnp.ones((n, kr), dt),
                "kv_b": _stack_init(ks[10], (n,), (kr, H * (nope + vd)), dt),
                "out_w": _stack_init(ks[2], (n,), (H * vd, h), dt,
                                     out_scale)}
    elif spec.attn == "ssm":
        attn = _init_ssm_params(cfg, n, ks, out_scale)
    elif spec.attn == "gmu":
        attn = {"in_w": _stack_init(ks[0], (n,), (h, cfg.ssm_inner), dt),
                "out_w": _stack_init(ks[2], (n,), (cfg.ssm_inner, h), dt,
                                     out_scale)}
    elif spec.attn == "gdn":
        attn = _init_gdn_params(cfg, n, ks, out_scale)
    else:
        attn = {"q_w": _stack_init(ks[0], (n,), (h, H * d), dt),
                "kv_w": _stack_init(ks[1], (n,), (h, 2 * G * d), dt),
                "out_w": _stack_init(ks[2], (n,), (H * d, h), dt, out_scale)}
        if cfg.use_bias:
            attn.update(q_b=jnp.zeros((n, H * d), dt),
                        kv_b=jnp.zeros((n, 2 * G * d), dt),
                        out_b=jnp.zeros((n, h), dt))
        if spec.attn == "cross":        # another layer's K and V
            attn = {k: v for k, v in attn.items() if not k.startswith("kv")}
        if cfg.qk_norm == "head":
            one = jnp.zeros((n, d), dt) if cfg.norm_unit_offset \
                else jnp.ones((n, d), dt)
            attn.update(q_norm=one, k_norm=one)
        if spec.attn == "eva":
            # phi at the keys' own spread, so that the pooling softmax is
            # no plain mean; mu a fifth of it, so that a mu left out shows
            attn.update(eva_phi=_stack_init(ks[3], (n,), (H, d), dt, 1.0),
                        eva_mu=_stack_init(ks[9], (n,), (H, d), dt, 0.25))
        if cfg.attn_diff:
            for i, name in enumerate(("lam_q1", "lam_k1", "lam_q2",
                                      "lam_k2")):
                attn[name] = _stack_init(jax.random.fold_in(ks[3], i), (n,),
                                         (d // 2,), dt, 0.1)
            attn["subln"] = jnp.ones((n, d), dt)
            attn["lam0"] = jnp.asarray(
                [diff_lambda_init(i) for i in layers], jnp.float32)
    if cfg.attn_gate != "none" and H:
        attn["gate_w"] = _stack_init(
            ks[3], (n,), (h, H * d if cfg.attn_gate == "elementwise" else H),
            dt)
    if spec.ffn == "dense":
        i = cfg.intermediate_size
        mlp = {"in_w": _stack_init(ks[4], (n,), (h, 2 * i), dt),
               "out_w": _stack_init(ks[5], (n,), (i, h), dt, out_scale)}
    else:
        E, w = cfg.experts_held, cfg.expert_width
        mlp = {"gate": _stack_init(ks[4], (n,), (h, cfg.moe_num_experts),
                                   dt),
               "w_in": _stack_init(ks[5], (n, E), (h, 2 * w), dt),
               "w_out": _stack_init(ks[6], (n, E), (w, h), dt, out_scale)}
        if cfg.moe_router_score == "sigmoid":
            # sigmoid(m Wr) spreads by about a quarter of the logits'
            # 0.02 * sqrt(h); the bias by a quarter of THAT: non-zero, so
            # that a bias that is dropped, or one that leaks into the
            # weights, moves a fifth of the choices, and small, because a
            # trained bias balances the load: here a decode step of 32
            # rows touches 50 of 64 experts (uniform routing: 55). A bias
            # at the scores' own spread decides most choices and leaves 23
            # touched: half the expert bytes of a deployment's step
            # (PERF.md section 6, PR 35).
            mlp["gate_bias"] = _dense_init(
                ks[11], (n, cfg.moe_num_experts), dt,
                min(0.0625, 0.00125 * math.sqrt(h)))
        if cfg.moe_shared_width:
            sw = cfg.moe_shared_width
            mlp["shared_in"] = _stack_init(ks[7], (n,), (h, 2 * sw), dt)
            mlp["shared_out"] = _stack_init(ks[8], (n,), (sw, h), dt,
                                            out_scale)
            if cfg.moe_shared_gate:
                mlp["shared_gate"] = _stack_init(
                    jax.random.fold_in(ks[7], 1), (n,), (h, 1), dt)
    ln = {"scale": jnp.zeros((n, h), dt) if cfg.norm_unit_offset
          else jnp.ones((n, h), dt)}
    if cfg.norm == "layernorm":
        ln["bias"] = jnp.zeros((n, h), dt)
    norms = {"ln_attn": ln, "ln_mlp": dict(ln)}
    if cfg.sublayer_out_norm:
        norms.update(ln_attn_out=dict(ln), ln_mlp_out=dict(ln))
    return dict(norms, attn=attn, mlp=mlp)


def init_mtp_params(cfg, key):
    """The next-token-prediction block: a norm on the last layer's hidden
    state (`hnorm`) and on the next token's embedding (`enorm`), the
    projection `proj` [2h, h] of [hidden | embedding], one layer of the
    last layer's kind (`block`: a stack of one) and the block's own final
    norm. The embedding and the head are the model's."""
    h, dt = cfg.hidden_size, cfg.param_dtype
    k_proj, k_block = jax.random.split(key)
    return {"hnorm": init_norm_params(cfg), "enorm": init_norm_params(cfg),
            "proj": _dense_init(k_proj, (2 * h, h), dt),
            "block": init_stack_params(cfg, cfg.layer_plan[-1], 1, k_block),
            "final_ln": init_norm_params(cfg)}


def init_params(cfg, rng):
    """The parameter tree. A homogeneous model's blocks are a list, one
    entry a layer (`blocks`); a planned model's are one stack a layer
    kind (`stacks`: {`LayerSpec.kind`: leaves [layers of the kind, ...]}),
    the layout the serving engine runs from as it is, so that it holds
    the weights once."""
    keys = jax.random.split(rng, cfg.num_layers + 2)
    dt = cfg.param_dtype
    if cfg.layer_plan:
        kinds = cfg.plan_kinds()
        params = {
            "embed": {"wte": _dense_init(keys[0], (cfg.vocab_size,
                                                   cfg.hidden_size), dt)},
            "stacks": {name: init_stack_params(cfg, spec, len(layers),
                                               keys[1 + layers[0]], layers)
                       for name, (spec, layers) in kinds.items()},
            "final_ln": init_norm_params(cfg),
        }
        if not cfg.tie_word_embeddings:
            # head m's rows are [m * vocab, (m + 1) * vocab)
            params["embed_out"] = {"wte": _dense_init(
                keys[-1], (cfg.num_pred_heads * cfg.vocab_size,
                           cfg.hidden_size), dt)}
        if cfg.mtp_layers:
            params["mtp"] = init_mtp_params(cfg, jax.random.fold_in(rng, 1))
        if cfg.loop_steps > 1:
            # the exit gate, one output a token: a weight row and a bias
            params["loop_exit"] = {
                "w": _dense_init(jax.random.fold_in(rng, 2),
                                 (cfg.hidden_size,), dt),
                "b": jnp.zeros((1,), dt)}
        return params
    params = {
        "embed": {"wte": _dense_init(keys[0], (cfg.vocab_size,
                                               cfg.hidden_size), dt)},
        "blocks": [init_block_params(cfg, keys[i + 1])
                   for i in range(cfg.num_layers)],
        "final_ln": init_norm_params(cfg),
    }
    if not cfg.tie_word_embeddings:
        params["embed_out"] = {
            "wte": _dense_init(keys[-1], (cfg.vocab_size, cfg.hidden_size),
                               dt)}
    return params


# ---------------------------------------------------------------------------
# tensor-parallel specs (Megatron pattern over the 'model' axis)
# ---------------------------------------------------------------------------

def block_param_specs():
    return {
        "ln_attn": {"scale": P(), "bias": P()},
        "ln_mlp": {"scale": P(), "bias": P()},
        "attn": {
            "qkv_w": P(None, MODEL_AXIS),   # column parallel
            "qkv_b": P(MODEL_AXIS),
            "out_w": P(MODEL_AXIS, None),   # row parallel
            "out_b": P(),
        },
        "mlp": {
            "in_w": P(None, MODEL_AXIS),
            "in_b": P(MODEL_AXIS),
            "out_w": P(MODEL_AXIS, None),
            "out_b": P(),
        },
    }


def param_specs(cfg, params):
    specs = {
        "embed": {"wte": P(MODEL_AXIS, None)},  # vocab-sharded
        "blocks": [block_param_specs() for _ in range(cfg.num_layers)],
        "final_ln": {"scale": P(), "bias": P()},
    }
    if "embed_out" in params:
        specs["embed_out"] = {"wte": P(MODEL_AXIS, None)}
    return specs


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def layer_norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    out = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) +
            bias.astype(jnp.float32)).astype(x.dtype)


def rms_norm(x, scale, eps):
    """x / sqrt(mean(x^2, -1) + eps) * scale, computed in float32."""
    x32 = x.astype(jnp.float32)
    out = x32 * jax.lax.rsqrt(
        jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def _rms_scale(cfg, w):
    """An RMS norm's scale from its leaf: 1 + w with a unit offset."""
    if getattr(cfg, "norm_unit_offset", False):
        return 1.0 + w.astype(jnp.float32)
    return w


def norm(cfg, p, x):
    """The model's own norm (`cfg.norm`) with the leaves `p`."""
    if getattr(cfg, "norm", "layernorm") == "rmsnorm":
        return rms_norm(x, _rms_scale(cfg, p["scale"]), cfg.layernorm_eps)
    return layer_norm(x, p["scale"], p["bias"], cfg.layernorm_eps)


# the FFN's activation by its published name ("gelu": the tanh form)
FFN_ACTIVATIONS = {"gelu": jax.nn.gelu, "silu": jax.nn.silu}


def _plus_bias(y, p, name):
    """y + p[name] where the block has that bias."""
    return y + p[name].astype(y.dtype) if name in p else y


def block_hidden(out):
    """The hidden states of a block's return, whatever rides beside them
    (an MoE block's aux statistics, a quantized FFN's amax row)."""
    return out[0] if isinstance(out, tuple) else out


def rope_inv_freq(head_dim, rotary_pct, base, rope=()):
    """(inv_freq [rot/2], the factor on cos and sin, rot_dim) of a
    layer's rotary. `rope` = ("yarn", factor, original_max, beta_fast,
    beta_slow, attention_factor) blends, dimension by dimension, the
    plain frequencies with the same divided by `factor`, as Hugging
    Face's `rope_type: yarn` does: the correction range is
    [floor(c(beta_fast)), ceil(c(beta_slow))] clipped to the dims, with
    c(b) = rot * ln(original_max / (2 pi b)) / (2 ln base), the blend a
    linear ramp over it, and cos and sin are scaled by
    `attention_factor`."""
    rot_dim = int(head_dim * rotary_pct)
    rot_dim -= rot_dim % 2
    inv_freq = 1.0 / (base **
                      (np.arange(0, rot_dim, 2, dtype=np.float32) / rot_dim))
    if not rope:
        return inv_freq, 1.0, rot_dim
    _, factor, original, beta_fast, beta_slow, attention_factor = rope

    def correction_dim(rotations):
        return rot_dim * math.log(original / (rotations * 2 * math.pi)) / \
            (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rot_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot_dim // 2, dtype=np.float32) - low) /
                   (high - low), 0.0, 1.0).astype(np.float32)
    blended = inv_freq / factor * ramp + inv_freq * (1.0 - ramp)
    return blended.astype(np.float32), float(attention_factor), rot_dim


def _rotary_table(inv_freq, factor, rot_dim, seq_len, dtype):
    t = np.arange(seq_len, dtype=np.float32)
    freqs = np.outer(t, inv_freq)                      # [S, rot/2]
    emb = np.concatenate([freqs, freqs], axis=-1)      # [S, rot]
    return (jnp.asarray(np.cos(emb) * factor, dtype),
            jnp.asarray(np.sin(emb) * factor, dtype), rot_dim)


def _rotary_cache(cfg, seq_len, dtype=jnp.float32, spec=None):
    """(cos, sin, rot_dim) over `seq_len` positions: the model's own
    rotary, or with `spec` (a planned model's `LayerSpec`) that layer
    kind's."""
    if spec is not None:
        # a latent layer rotates its mla_rope_dim features, all of them
        dim = cfg.mla_rope_dim if spec.attn == "latent" else cfg.head_dim
        return _rotary_table(*rope_inv_freq(
            dim, spec.rotary_pct, spec.rotary_base, spec.rope),
            seq_len, dtype)
    return _rotary_table(*rope_inv_freq(
        cfg.head_dim, cfg.rotary_pct, cfg.rotary_emb_base), seq_len, dtype)


def plan_rotary(cfg, seq_len):
    """A planned model's rotary tables, one an attention kind:
    {"full" | "window" | "latent": (cos, sin, rot_dim)} (a kind's layers share their
    rotary facts; `check_block` does not hold that, the family file
    does)."""
    return {spec.attn: _rotary_cache(cfg, seq_len, spec=spec)
            for spec in reversed(cfg.layer_plan)
            if spec.attn not in ("ssm", "gmu", "gdn")}


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rotary_rows(x, cos, sin):
    """Rotate-half rotary over ALL features of x [B, S, H, rot]; cos/sin
    are [S, rot] (a shared position stream) or [B, S, rot] (per-batch
    positions)."""
    if cos.ndim == 2:
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    return x * cos.astype(x.dtype) + _rotate_half(x) * sin.astype(x.dtype)


def apply_rotary(q, k, cos, sin, rot_dim):
    """Rotary embedding on the first rot_dim dims of q/k [B, S, H, D].

    cos/sin are [S, rot] (shared position stream) or [B, S, rot]
    (per-batch positions — packed batches gather the cache at each
    token's INTRA-document position, so a packed document sees the same
    rotary stream as the same document padded alone).

    Passes over q and k in XLA: a training call the flash kernels admit
    rotates inside them instead (`_rotary_in_kernel`);
    `ops.dispatch_report()["flash"]["rotary"]` counts both."""
    from ..ops.pallas.flash_attention import _ROTARY
    _ROTARY["xla"] += 1
    return tuple(
        jnp.concatenate([_rotary_rows(x[..., :rot_dim], cos, sin),
                         x[..., rot_dim:]], axis=-1) for x in (q, k))


def _flash_route(shape, kv_heads, use_pallas=True, segment_ids=None,
                 window=None, block=0, sm_scale=None):
    """Which flash entry `causal_attention` takes for q `shape`
    [B, S, H, D] over `kv_heads`: "training" (`flash_attention`, forward
    and backward; the tiled call reads the heads in place), "segmented"
    (`flash_attention_segmented`: packed documents, and every serving
    forward's window, grouped KV heads, block mask or scale) or None
    (XLA)."""
    from ..ops.pallas.flash_attention import flash_attention_supported
    if not (use_pallas and flash_attention_supported(shape)):
        return None
    grouped = window is not None or kv_heads != shape[2] or \
        bool(block) or sm_scale is not None
    return "segmented" if grouped or segment_ids is not None else "training"


def causal_attention(q, k, v, use_pallas=True, segment_ids=None,
                     window=None, block=0, sm_scale=None, rotary=None):
    """Causal MHA core on [B, S, H, D]; fp32 softmax accumulation.
    `k` / `v` may hold fewer (KV) heads than `q`: query head h reads KV
    head h // (H / G); `window` keeps the keys less than `window`
    positions behind their query (both a planned model's, on the
    segmented forward kernel and the XLA fallback alike). `block` (a
    power of two, `GPTNeoXConfig.generation_block`; 0: causal) makes the
    mask BLOCK-causal: query i sees key j wherever j // block <= i //
    block, all of its own block included. `sm_scale`: the softmax scale
    where it is not 1 / sqrt(D) (differential attention's, on the grouped
    forward and the fallback). `rotary` = (cos, sin, rot_dim): q and k are
    NOT rotated yet and the training flash kernels rotate them; only for
    a call `_rotary_in_kernel` admitted.

    Uses the Pallas flash-attention kernel on TPU when shapes allow;
    XLA-fused fallback otherwise (the fallback still fuses well — softmax
    and the PV matmul land on the MXU). The kernel takes its block
    geometry from the shape it is called at (`ops.autotune.flash_blocks`).

    `segment_ids` [B, S] int32 (packed ragged batches, 0 = pad) makes
    attention intra-document: the segmented kernels skip fully-cross-
    document blocks and mask the stragglers; the XLA fallback ANDs the
    segment-equality mask into the causal mask.

    Every path tags its output with the `attn_residuals` remat name (the
    flash custom_vjp additionally tags its saved out/LSE residuals), so
    the `attn_residuals` policy pins attention results across remat
    boundaries on kernel and fallback paths alike."""
    from ..runtime.activation_checkpointing.checkpointing import \
        tag_attn_residual
    from ..ops.pallas.flash_attention import (
        _LAST_BACKEND, flash_attention, flash_attention_segmented,
        note_xla_on_tpu)
    route = _flash_route(q.shape, k.shape[2], use_pallas, segment_ids,
                         window, block, sm_scale)
    assert rotary is None or route == "training", route
    if route:
        _LAST_BACKEND["attention"] = "pallas"

        def kernel(q, k, v, *seg):
            if seg:
                return flash_attention_segmented(q, k, v, seg[0], True,
                                                 sm_scale=sm_scale,
                                                 window=window,
                                                 mask_block=block)
            # the tables ride the closure: every shard's are the whole
            return flash_attention(q, k, v, True, rotary=rotary)

        if route == "segmented" and segment_ids is None:
            # one kernel path for a window or grouped KV heads: the
            # segmented forward, every token of one document
            segment_ids = jnp.ones(q.shape[:2], jnp.int32)
        seg = () if segment_ids is None else (segment_ids,)
        return per_shard(kernel, (q, k, v) + seg,
                         {0: DATA_AXIS, 2: MODEL_AXIS})
    _LAST_BACKEND["attention"] = "xla"
    if use_pallas:
        note_xla_on_tpu(
            "causal_attention",
            f"[B, S, H, D] = {tuple(q.shape)}: the flash kernel needs a "
            f"head dim of 64/128/256 and a sequence some 128-multiple "
            f"block divides")
    B, S, H, D = q.shape
    scale = sm_scale or 1.0 / math.sqrt(D)
    with scopes.scope("ds.attn_xla"):
        if k.shape[2] != H:
            k = jnp.repeat(k, H // k.shape[2], axis=2)
            v = jnp.repeat(v, H // v.shape[2], axis=2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32) * scale
        mask = jnp.tril(jnp.ones((S, S), jnp.bool_))[None, :, :]
        if block:
            pos = jnp.arange(S)
            mask = (pos[None, :] <= (pos[:, None] | (block - 1)))[None]
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((S, S), jnp.bool_),
                                    -int(window))[None, :, :]
        if segment_ids is not None:
            mask = mask & (segment_ids[:, :, None] ==
                           segment_ids[:, None, :])
        logits = jnp.where(mask[:, None, :, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        return tag_attn_residual(jnp.einsum("bhqk,bkhd->bqhd", probs, v))


def _wmat(x, w):
    """``x @ w`` for a plain weight leaf or a serving-time
    `QuantizedWeight` (int8 at rest + per-output-channel scales,
    `ops/pallas/quant_matmul`). Training params are never quantized, so
    every training trace keeps the plain matmul; the serving engine's
    `prepare_inference_params(weight_quant="int8")` swaps the block
    matmul weights and this ONE dispatch point covers prefill and decode
    on every family that shares the block body."""
    from ..ops.pallas.quant_matmul import QuantizedWeight, quant_matmul
    if isinstance(w, QuantizedWeight):
        return quant_matmul(x, w)
    return x @ w.astype(x.dtype)


def _heads_dot(x, w):
    """`_wmat(x, w)` for a result `[B, S, out]` that its caller reshapes
    to heads. XLA folds that reshape into the dot and re-lays out the
    weight `[k, out]` for it: right under many rows, wrong for a decode
    step's few (the layer's weight copied on chip every layer; a
    loop-invariant stack copied whole, every step). Where the shapes say
    the result is the small operand (`autotune.head_projection_plain`)
    a barrier keeps the reshape off the dot, which then reads the weight
    where it lies. Same operands, same arithmetic, in both forms; a
    `QuantizedWeight`'s matmul is a kernel no reshape enters."""
    from ..ops.autotune import head_projection_plain
    from ..ops.pallas.flash_attention import _HEAD_PROJECTIONS
    from ..ops.pallas.quant_matmul import QuantizedWeight
    y = _wmat(x, w)
    if isinstance(w, QuantizedWeight):
        return y
    plain = head_projection_plain(math.prod(x.shape[:-1]), x.shape[-1])
    _HEAD_PROJECTIONS["plain" if plain else "folded"] += 1
    return jax.lax.optimization_barrier(y) if plain else y


def _split_heads_dots(x, a, heads, d):
    """The fused QKV projection of `x` [B, S, K] as THREE dots against
    the q, k and v columns of the ONE `qkv_w` leaf ([K, heads x (q | k |
    v) x d]; `qkv_b` sliced the same way): (q, k, v), each
    [B, S, heads, d]. The same operands and the same K-long dot product
    an element as the fused dot and its split; what differs is where XLA
    writes the results (`autotune.head_projection_split`)."""
    from ..ops.pallas.flash_attention import _HEAD_PROJECTIONS
    _HEAD_PROJECTIONS["split"] += 1
    B, S, K = x.shape
    w = a["qkv_w"].reshape(K, heads, 3, d)
    b = a["qkv_b"].reshape(heads, 3, d) if "qkv_b" in a else None

    def part(i):
        y = _wmat(x, w[:, :, i].reshape(K, heads * d))
        if b is not None:
            y = y + b[:, i].reshape(heads * d).astype(y.dtype)
        return y.reshape(B, S, heads, d)
    return part(0), part(1), part(2)


def _qkv_split(attn, shape, attn_fn, **call):
    """Does a block whose attention is `causal_attention(q [shape], k, v,
    **call)` (no `attn_fn` in its place) project its fused QKV weight
    `attn["qkv_w"]` (as many KV heads as query heads) as three dots?
    Where that call is the training flash call on heads in place and
    `autotune.head_projection_split` says the shape gains."""
    from ..ops.autotune import head_projection_split
    from ..ops.pallas.flash_attention import tiled_in_place
    from ..ops.pallas.quant_matmul import QuantizedWeight
    if attn_fn is not None or "qkv_w" not in attn or \
            isinstance(attn["qkv_w"], QuantizedWeight):
        return False
    heads = shape[2]
    return head_projection_split(
        shape[3], _flash_route(shape, heads, **call) == "training"
        and tiled_in_place(shape, heads))


def _rotary_in_kernel(attn, shape, dtype, cos, rot_dim, attn_fn, return_kv,
                      **call):
    """Does a block whose attention is `causal_attention(q [shape], k, v,
    **call)` leave the rotary of q and k to the flash kernels? Where that
    call is the training flash call and its kernels take it
    (`flash_attention.rotates_in_kernel`, of q's `shape` and `dtype`:
    forward and fused backward on heads in place, `rot_dim` whole sublane
    tiles), every row reads ONE position stream (`cos` [S, rot]; a packed
    batch's are per row), the projection is the plain block's fused one
    (as many KV heads as query heads; a planned layer's is `q_w` and
    `kv_w`), nothing stands in the attention's place (`attn_fn`) and the
    caller does not want the rotated k (`return_kv`: a prefill writes it
    to its pages). Every other block rotates in XLA (`apply_rotary`)."""
    from ..ops.pallas.flash_attention import rotates_in_kernel
    return attn_fn is None and not return_kv and "qkv_w" in attn and \
        cos.ndim == 2 and \
        _flash_route(shape, shape[2], **call) == "training" and \
        rotates_in_kernel(shape, shape[2], rot_dim, dtype)


def _gated_mlp(x, w_in, w_out, act):
    """(act(x Wgate) * (x Wup)) Wdown with `w_in` = [Wgate | Wup]."""
    hmid = _wmat(x, w_in)
    inter = hmid.shape[-1] // 2
    return _wmat(act(hmid[..., :inter]) * hmid[..., inter:], w_out)


@scopes.scoped("ds.attn")
def _block_qkv(cfg, params, x, cos, sin, rot_dim, nh_local, split=False,
               rotate=True):
    """ln1 + QKV projection + rotary; shared by training and decode.
    `split` (`_block_core`'s to say: `_qkv_split`): the fused projection
    as three dots, for the tiled flash kernels' reading in place.
    `rotate` False (`_rotary_in_kernel`; the plain block's): q and k as
    projected, for kernels that rotate them."""
    B, S, _ = x.shape
    ln1 = norm(cfg, params["ln_attn"], x)
    if "q_w" in params["attn"]:
        # a planned block: `nh_local` query heads over the model's KV
        # heads, [K | V] fused, head dim a fact of the model
        d, G = cfg.head_dim, cfg.kv_heads
        a = params["attn"]
        q = _plus_bias(_heads_dot(ln1, a["q_w"]), a, "q_b").reshape(
            B, S, nh_local, d)
        if "kv_w" not in a:         # a cross layer: another layer's K and V
            return q, None, None
        kv = _plus_bias(_heads_dot(ln1, a["kv_w"]), a, "kv_b").reshape(
            B, S, 2, G, d)
        k, v = kv[:, :, 0], kv[:, :, 1]
        if cfg.qk_norm == "head":
            # over the features of each head, one scale for all heads
            q = rms_norm(q, _rms_scale(cfg, a["q_norm"]), cfg.layernorm_eps)
            k = rms_norm(k, _rms_scale(cfg, a["k_norm"]), cfg.layernorm_eps)
        q, k = apply_rotary(q, k, cos, sin, rot_dim)
        return q, k, v
    if split:
        q, k, v = _split_heads_dots(ln1, params["attn"], nh_local,
                                    cfg.head_dim)
    else:
        qkv = _plus_bias(_heads_dot(ln1, params["attn"]["qkv_w"]),
                         params["attn"], "qkv_b")
        qkv = qkv.reshape(B, S, nh_local, 3 * cfg.head_dim)
        q, k, v = jnp.split(qkv, 3, axis=-1)
    if getattr(cfg, "qk_norm", False):
        # over all of q's (k's) features at once, before the heads part
        def all_features(t, p):
            return rms_norm(t.reshape(B, S, -1), p["scale"],
                            cfg.layernorm_eps).reshape(t.shape)
        q = all_features(q, params["attn"]["q_norm"])
        k = all_features(k, params["attn"]["k_norm"])
    if rotate:
        q, k = apply_rotary(q, k, cos, sin, rot_dim)
    return q, k, v


@scopes.scoped("ds.attn")
def _latent_rows(cfg, params, x, cos, sin, heads):
    """A latent (MLA) layer's ln1 and its two low-rank projections:
    (q_nope [B, S, H, nope], rot(q_rope) [B, S, H, rope], latent
    [B, S, kv_rank + rope]). `latent` = [rms(c_kv) | rot(k_r)] is the
    token's cache row: everything a later query needs of this token, with
    ONE rotary key row for all heads. Shared by the expanded form
    (training, prefill) and the absorbed one (decode)."""
    a = params["attn"]
    B, S, _ = x.shape
    nope, kr, eps = cfg.mla_nope_dim, cfg.mla_kv_rank, cfg.layernorm_eps
    ln1 = norm(cfg, params["ln_attn"], x)
    with scopes.scope("ds.mla_q"):
        c_q = rms_norm(_wmat(ln1, a["q_a"]), a["q_a_norm"], eps)
        q = _wmat(c_q, a["q_b"]).reshape(B, S, heads, -1)
        q_nope = q[..., :nope]
        q_rope = _rotary_rows(q[..., nope:], cos, sin)
    with scopes.scope("ds.mla_kv"):
        ckv = _wmat(ln1, a["kv_a"])
        c_kv = rms_norm(ckv[..., :kr], a["kv_a_norm"], eps)
        k_r = _rotary_rows(ckv[..., None, kr:], cos, sin)[:, :, 0]
        latent = jnp.concatenate([c_kv, k_r], axis=-1)
    return q_nope, q_rope, latent


def _latent_up(cfg, params, heads):
    """`kv_b` as (W_uk [kv_rank, H, nope], W_uv [kv_rank, H, v])."""
    w = params["attn"]["kv_b"].reshape(cfg.mla_kv_rank, heads, -1)
    return w[..., :cfg.mla_nope_dim], w[..., cfg.mla_nope_dim:]


@scopes.scoped("ds.mla_expand")
def _latent_expand(cfg, params, latent, heads):
    """The expanded form: every head's keys and values from the latent
    rows, (k [B, S, H, nope + rope], v [B, S, H, v]); a head's key is
    [k_nope_h | rot(k_r)], the rotary part the same for all heads."""
    B, S, _ = latent.shape
    kr, nope = cfg.mla_kv_rank, cfg.mla_nope_dim
    kv = _wmat(latent[..., :kr], params["attn"]["kv_b"]).reshape(
        B, S, heads, -1)
    k_r = jnp.broadcast_to(latent[:, :, None, kr:],
                           (B, S, heads, cfg.mla_rope_dim))
    return (jnp.concatenate([kv[..., :nope], k_r], axis=-1), kv[..., nope:])


@scopes.scoped("ds.mla_absorb")
def latent_absorb_q(cfg, params, q_nope, q_rope):
    """The absorbed form's query [B, H, kv_rank + rope] of one token a
    row: q'_h = q_nope_h W_uk_h^T meets c_kv where q_nope_h met
    k_nope_h = c_kv W_uk_h, the same sum in another order."""
    w_uk, _ = _latent_up(cfg, params, q_nope.shape[-2])
    q_lat = jnp.einsum("bhn,khn->bhk", q_nope, w_uk.astype(q_nope.dtype))
    return jnp.concatenate([q_lat, q_rope], axis=-1)


@scopes.scoped("ds.mla_absorb")
def latent_absorb_out(cfg, params, u):
    """o_h = u_h W_uv_h of the absorbed form: `u` [B, H, kv_rank] is the
    attention's weighted sum of c_kv rows; returns [B, H, v]."""
    _, w_uv = _latent_up(cfg, params, u.shape[-2])
    return jnp.einsum("bhk,khv->bhv", u, w_uv.astype(u.dtype))


def eva_attention(cfg, attn_p, q, k, v, real, core):
    """An `eva` layer's attention over whole rows (training's forward, a
    serving prefill): q, k, v [B, S, H, D] (k after the rotary), `real`
    [B, S] the rows that are tokens (None: all), `core(q, k, v,
    segment_ids)` the causal attention of rows of one segment.

    Query t, in window j = t // W, sees the exact rows of its own window
    up to itself and ONE pooled row (`ops.pallas.eva.eva_pool`) for each
    chunk of C rows of every EARLIER window, all under one softmax. That
    is plain causal attention over the rows [pooled rows of windows < j |
    window j's rows], so each window becomes a sequence of its own:
    P slots of pooled rows (segment 1 where the chunk lies in an earlier
    window, 0 where it is padding), then the window's W rows, through the
    segmented kernel every prefill runs. The tiles of a padding slot are
    skipped there; the queries that ride in the pooled slots are zeros
    whose output is dropped. Returns (out [B, S, H, D], (K~, V~) [B, S / C,
    H, D] of EVERY chunk, whole or not: the caller keeps the whole ones)."""
    from ..ops.pallas.eva import eva_pool
    B, S, H, D = q.shape
    W, C = cfg.eva_window, cfg.eva_chunk
    scale = cfg.attn_scale or 1.0 / math.sqrt(D)
    if real is None:
        real = jnp.ones((B, S), jnp.bool_)
    n_win = -(-S // W)
    pad = n_win * W - S
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (q, k, v))
        real = jnp.pad(real, ((0, 0), (0, pad)))
    with scopes.scope("ds.eva_summarize"):
        # the rows as the pages hold them: a decode step pools what it
        # reads back from a page, so a prefill pools the same numbers
        # (XLA would else fuse the projection's unrounded output in)
        bits = jnp.finfo(k.dtype)
        held = (jax.lax.reduce_precision(t, bits.nexp, bits.nmant).reshape(
            B, -1, C, H, D) for t in (k, v))
        pooled = tuple(t.astype(q.dtype) for t in eva_pool(
            *held, attn_p["eva_phi"], attn_p["eva_mu"], scale))
    with scopes.scope("ds.eva_prefill"):
        per_win = W // C
        if n_win == 1:
            out = core(q, k, v, real.astype(jnp.int32))
        else:
            # slots for the pooled rows of n_win - 1 windows, in a unit
            # the attention kernel's blocks divide
            unit = min(512, W)
            P = -(-(n_win - 1) * per_win // unit) * unit
            slot = jnp.arange(P)
            visible = slot[None, :] < per_win * jnp.arange(n_win)[:, None]

            def windows(rows, prefix):
                """rows [B, n_win * W, ...] as n_win sequences a batch row,
                each behind the `prefix` [B, P, ...] all of them share."""
                rows = rows.reshape(B, n_win, W, *rows.shape[2:])
                prefix = jnp.broadcast_to(
                    prefix[:, None], (B, n_win, *prefix.shape[1:]))
                return jnp.concatenate([prefix, rows], axis=2).reshape(
                    B * n_win, P + W, *rows.shape[3:])

            def slots(t):
                have = t.shape[1]
                return jnp.pad(t, ((0, 0), (0, max(P - have, 0)), (0, 0),
                                   (0, 0)))[:, :P]

            seg = jnp.concatenate(
                [jnp.broadcast_to(visible[None], (B, n_win, P)),
                 real.reshape(B, n_win, W)], axis=2).astype(
                     jnp.int32).reshape(B * n_win, P + W)
            out = core(windows(q, jnp.zeros((B, P, H, D), q.dtype)),
                       windows(k, slots(pooled[0])),
                       windows(v, slots(pooled[1])), seg)
            out = out.reshape(B, n_win, P + W, H, D)[:, :, P:].reshape(
                B, n_win * W, H, D)
    return out[:, :S], pooled


@scopes.scoped("ds.attn_diff")
def diff_queries(q):
    """Differential attention on the kernels every attention uses: pair
    p's query [q_p1 | q_p2] becomes two query heads, [q_p1 | 0] and
    [0 | q_p2], which against the KV head's key [k_g1 | k_g2] score
    q_p1 . k_g1 and q_p2 . k_g2 to the bit (a product with 0 adds 0), so
    the two softmaxes of a pair are two heads of ordinary grouped
    attention over the value's whole width. q [..., P, d] -> [..., 2P, d]."""
    half = q.shape[-1] // 2
    zeros = jnp.zeros_like(q[..., :half])
    heads = jnp.stack([jnp.concatenate([q[..., :half], zeros], axis=-1),
                       jnp.concatenate([zeros, q[..., half:]], axis=-1)],
                      axis=-2)
    return heads.reshape(*q.shape[:-2], 2 * q.shape[-2], q.shape[-1])


@scopes.scoped("ds.attn_diff")
def diff_combine(cfg, attn_p, out):
    """o_p = out_{p,1} - lam out_{p,2}, an RMS norm over o_p's features
    with the layer's scale `subln`, times (1 - lam0); lam = exp(lq1 . lk1)
    - exp(lq2 . lk2) + lam0, in float32. out [..., 2P, d] -> [..., P, d]."""
    f32 = jnp.float32
    lam0 = attn_p["lam0"].astype(f32)
    lam = jnp.exp(jnp.sum(attn_p["lam_q1"].astype(f32) *
                          attn_p["lam_k1"].astype(f32))) - \
        jnp.exp(jnp.sum(attn_p["lam_q2"].astype(f32) *
                        attn_p["lam_k2"].astype(f32))) + lam0
    pairs = out.reshape(*out.shape[:-2], out.shape[-2] // 2, 2,
                        out.shape[-1]).astype(f32)
    o = pairs[..., 0, :] - lam * pairs[..., 1, :]
    o = rms_norm(o, attn_p["subln"], cfg.layernorm_eps) * (1.0 - lam0)
    return o.astype(out.dtype)


def _ssm_conv(p, taps):
    """c = silu(conv_b + sum_k conv_w[k] * taps[k]) in float32: `taps`
    are u_{t-K+1} .. u_t, each [..., d_i]."""
    w = p["conv_w"].astype(jnp.float32)
    c = p["conv_b"].astype(jnp.float32)
    for k, tap in enumerate(taps):
        c = c + w[k] * tap.astype(jnp.float32)
    return jax.nn.silu(c)


def _ssm_coefficients(cfg, p, c, real):
    """The scan's operands from the convolution's output `c` [..., d_i]
    (float32): (the step dt [..., d_i], zero where `real` is False, B and
    C [..., N], A [N, d_i]), all float32."""
    N, R = cfg.ssm_state, cfg.ssm_dt_rank
    proj = _wmat(c.astype(p["x_w"].dtype), p["x_w"])
    dt = jax.nn.softplus(
        _wmat(proj[..., :R], p["dt_w"]).astype(jnp.float32) +
        p["dt_b"].astype(jnp.float32))
    if real is not None:
        dt = jnp.where(real[..., None], dt, 0.0)
    return (dt, proj[..., R:R + N].astype(jnp.float32),
            proj[..., R + N:].astype(jnp.float32),
            -jnp.exp(p["A_log"].astype(jnp.float32)))


@scopes.scoped("ds.ssm_out")
def _ssm_out(p, s, z):
    """(s * silu(z)) W_out, the gate in float32."""
    gated = s * jax.nn.silu(z.astype(jnp.float32))
    return _wmat(gated.astype(z.dtype), p["out_w"])


def ssm_mixer(cfg, p, a, real=None, use_pallas=True):
    """A state-space layer (Mamba-1) over whole sequences from a zero
    state: `a` [B, S, h] the normed input, `real` [B, S] marks the real
    rows (a prefill bucket's padding moves no state: its step is 0 and
    its input to the convolution is 0). Returns (the mixer's output
    [B, S, h], (the convolution's state, the last K - 1 real rows of u
    [B, K - 1, sub, lanes]; the scan's state after the last real row
    [B, N, sub, lanes]; the scan's output s [B, S, d_i], float32: the
    MEMORY a later gmu layer gates, after the skip and before the
    gate))."""
    from ..ops.pallas.ssm import ssm_scan
    B, S, _ = a.shape
    di, K = cfg.ssm_inner, cfg.ssm_conv
    with scopes.scope("ds.ssm_in"):
        uz = _wmat(a, p["in_w"])
        u, z = uz[..., :di], uz[..., di:]
        if real is not None:
            u = jnp.where(real[..., None], u, 0)
        padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
        c = _ssm_conv(p, [padded[:, k:k + S] for k in range(K)])
        dt, Bm, Cm, A = _ssm_coefficients(cfg, p, c, real)
        # u_{n-K+1} .. u_{n-1} of a row of n real tokens: padded[n + j]
        n = jnp.sum(real, axis=1) if real is not None else \
            jnp.full((B,), S, jnp.int32)
        tail = jnp.take_along_axis(
            padded, (n[:, None] + jnp.arange(K - 1))[..., None], axis=1)
    s, h = ssm_scan(dt, c, Bm, Cm, A, p["D"],
                    backend=None if use_pallas else "xla")
    return _ssm_out(p, s, z), (tail.reshape(B, K - 1, *h.shape[2:]), h, s)


def ssm_token(cfg, p, a, state, slots, layer, active, backend=None):
    """The same layer for ONE token a row: `a` [B, 1, h]; `state` the
    stacked (convolution rows [L, slots, K - 1, sub, lanes], scan state
    [L, slots, N, sub, lanes]) pools, row b's at `slots[b]` of layer
    `layer`, updated in place; an inactive row (`active` False) moves
    nothing. Returns (output [B, 1, h], the pools, the memory
    [B, 1, d_i])."""
    from ..ops.pallas.ssm import ssm_step
    conv = state[0]
    B = a.shape[0]
    di, K = cfg.ssm_inner, cfg.ssm_conv
    with scopes.scope("ds.ssm_in"):
        uz = _wmat(a[:, 0], p["in_w"])
        u, z = uz[..., :di], uz[..., di:]
        rows = jnp.concatenate(
            [conv[layer, slots].reshape(B, K - 1, di),
             u[:, None].astype(conv.dtype)], axis=1)
        c = _ssm_conv(p, [rows[:, k] for k in range(K)])
        tail = jnp.where(active[:, None, None], rows[:, 1:], rows[:, :-1])
        dt, Bm, Cm, A = _ssm_coefficients(cfg, p, c, active)
    s, state = ssm_step(state, tail, slots, layer, dt, c, Bm, Cm, A, p["D"],
                        backend=backend)
    return _ssm_out(p, s, z)[:, None], state, s[:, None]


def _gdn_project(cfg, p, a):
    """A gdn layer's projections of the normed input `a` [..., h]: ([q | k
    | v] before the convolution [..., channels], z [..., n_v d_v], b, a
    [..., n_v])."""
    ch, nv = cfg.gdn_channels, cfg.gdn_value_heads
    proj, ba = _wmat(a, p["in_w"]), _wmat(a, p["ba_w"])
    return proj[..., :ch], proj[..., ch:], ba[..., :nv], ba[..., nv:]


def _gdn_conv(p, taps):
    """silu(sum_k conv_w[k] * taps[k]) in float32: `taps` are rows t - K +
    1 .. t of [q | k | v], each [..., channels]."""
    w = p["conv_w"].astype(jnp.float32)
    return jax.nn.silu(sum(w[k] * tap.astype(jnp.float32)
                           for k, tap in enumerate(taps)))


def _gdn_operands(cfg, p, c, b, a, real):
    """The delta rule's operands from the convolution's output `c` [...,
    channels] (float32): q = l2norm(q) / sqrt(d_k) and k = l2norm(k) [...,
    n_k, d_k], v [..., n_v, d_v], the decay g = -exp(A_log) softplus(a +
    dt_bias) and the step beta = sigmoid(b) [..., n_v], both zero where
    `real` is False: float32."""
    f32 = jnp.float32
    nk, nv = cfg.gdn_key_heads, cfg.gdn_value_heads
    dk, dv = cfg.gdn_key_dim, cfg.gdn_value_dim
    lead = c.shape[:-1]

    def unit(t):
        return t * jax.lax.rsqrt(
            jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)

    q = unit(c[..., :nk * dk].reshape(*lead, nk, dk)) * dk ** -0.5
    k = unit(c[..., nk * dk:2 * nk * dk].reshape(*lead, nk, dk))
    v = c[..., 2 * nk * dk:].reshape(*lead, nv, dv)
    beta = jax.nn.sigmoid(b.astype(f32))
    g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
        a.astype(f32) + p["dt_bias"].astype(f32))
    if real is not None:
        beta = jnp.where(real[..., None], beta, 0.0)
        g = jnp.where(real[..., None], g, 0.0)
    return q, k, v, g, beta


@scopes.scoped("ds.gdn_out")
def _gdn_out(cfg, p, o, z):
    """(o / rms(o) * w_n) * silu(z) a value head, in float32, then W_o:
    `o` [..., n_v, d_v] float32, `z` [..., n_v d_v]."""
    f32 = jnp.float32
    normed = o * jax.lax.rsqrt(
        jnp.mean(jnp.square(o), axis=-1, keepdims=True) +
        cfg.layernorm_eps) * p["norm"].astype(f32)
    gated = normed.reshape(z.shape) * jax.nn.silu(z.astype(f32))
    return _wmat(gated.astype(z.dtype), p["out_w"])


def gdn_mixer(cfg, p, a, real=None):
    """A Gated DeltaNet layer over whole sequences from a zero state: `a`
    [B, S, h] the normed input, `real` [B, S] marks the real rows (a
    prefill bucket's padding moves no state: its decay and its step are
    0 and its input to the convolution is 0). Returns (the mixer's output
    [B, S, h], (the last K - 1 real rows of [q | k | v] before the
    convolution [B, K - 1, sub, lanes] as the pool lays the channels out,
    the state after the last real row [B, n_v, d_k, d_v] float32))."""
    from ..ops.pallas.gdn import gdn_chunk
    B, S, _ = a.shape
    K = cfg.gdn_conv
    with scopes.scope("ds.gdn_in"):
        qkv, z, b, a_ = _gdn_project(cfg, p, a)
        if real is not None:
            qkv = jnp.where(real[..., None], qkv, 0)
        padded = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
        c = _gdn_conv(p, [padded[:, k:k + S] for k in range(K)])
        operands = _gdn_operands(cfg, p, c, b, a_, real)
        # rows n - K + 1 .. n - 1 of a row of n real tokens: padded[n + j]
        n = jnp.sum(real, axis=1) if real is not None else \
            jnp.full((B,), S, jnp.int32)
        tail = jnp.take_along_axis(
            padded, (n[:, None] + jnp.arange(K - 1))[..., None], axis=1)
    o, state = gdn_chunk(*operands)
    return _gdn_out(cfg, p, o, z), (
        tail.reshape(B, *cfg.state_shapes[0]), state)


def gdn_token(cfg, p, a, state, slots, layer, active):
    """The same layer for ONE token a row: `a` [B, 1, h]; `state` the
    stacked (convolution rows [L, slots, K - 1, sub, lanes], matrix states
    [L, slots, n_v, d_k, d_v] float32) pools, row b's at `slots[b]` of
    layer `layer`, updated in place; an inactive row (`active` False)
    moves nothing. Returns (output [B, 1, h], the pools)."""
    from ..ops.pallas.gdn import gdn_step
    conv = state[0]
    B, K = a.shape[0], cfg.gdn_conv
    with scopes.scope("ds.gdn_in"):
        qkv, z, b, a_ = _gdn_project(cfg, p, a[:, 0])
        rows = jnp.concatenate(
            [conv[layer, slots].reshape(B, K - 1, -1),
             qkv[:, None].astype(conv.dtype)], axis=1)
        c = _gdn_conv(p, [rows[:, k] for k in range(K)])
        tail = jnp.where(active[:, None, None], rows[:, 1:], rows[:, :-1])
        operands = _gdn_operands(cfg, p, c, b, a_, active)
    o, state = gdn_step(state, tail, slots, layer, *operands)
    return _gdn_out(cfg, p, o, z)[:, None], state


@scopes.scoped("ds.gmu")
def gmu_mixer(p, a, mem):
    """A gated memory unit: (mem * silu(a W_1)) W_2 with `mem` the memory
    state-space layer's scan output of the same tokens (float32)."""
    gate = jax.nn.silu(_wmat(a, p["in_w"]).astype(jnp.float32))
    return _wmat((mem * gate).astype(a.dtype), p["out_w"])


def _block_post_attn(cfg, params, x, attn_flat, reduce_fn, rng=None,
                     ffn_quant=None, token_mask=None, projected=False):
    """Everything after the attention core: out projection, residuals,
    ln2, MLP (dense or MoE) — shared by training and decode.
    `attn_flat` is the flattened [B, S, h/mp] attention output. With
    MoE enabled the return is (out, aux): the GShard router's
    load-balance loss, or the dropless router's [2, E] statistics
    (`block_hidden` takes the hidden states of either).
    `ffn_quant` = (recipe, margin, amax_row [4, H]) runs the dense FFN
    under delayed-scaling quantization and makes the return
    (out, new_amax_row) — see `ops/pallas/quant_matmul`.
    `token_mask` [B, S] marks the real tokens for a router that drops
    nothing: a padded row is routed to no expert. `projected`: `attn_flat`
    is the mixer's output [B, S, h] already (an ssm or gmu layer's, whose
    out-projection is its own)."""
    out_b = params["attn"]["out_b"].astype(x.dtype) \
        if "out_b" in params["attn"] else 0
    if "gate_w" in params["attn"]:
        # the gate: sigmoid(a Wg), a scalar a head and token or one a
        # feature (its width says which), from the normed input `a` (the
        # norm `_block_qkv` took: one value)
        with scopes.scope("ds.attn_gate"):
            B, S, _ = attn_flat.shape
            a = norm(cfg, params["ln_attn"], x)
            gate = jax.nn.sigmoid(
                _wmat(a, params["attn"]["gate_w"]).astype(jnp.float32))
            attn_flat = (attn_flat.reshape(B, S, gate.shape[-1], -1) *
                         gate[..., None].astype(attn_flat.dtype)
                         ).reshape(B, S, -1)
    if projected:
        attn_partial = attn_flat
    else:
        with scopes.scope("ds.attn"):
            attn_partial = _wmat(attn_flat, params["attn"]["out_w"])

    if cfg.use_parallel_residual:
        ln2_in = x
    else:
        attn_out = reduce_fn(attn_partial) + out_b
        if "ln_attn_out" in params:      # a norm on the sublayer's output
            with scopes.scope("ds.attn"):
                attn_out = norm(cfg, params["ln_attn_out"], attn_out)
        ln2_in = x + attn_out
    with scopes.scope("ds.mlp"):
        ln2 = norm(cfg, params["ln_mlp"], ln2_in)

    if getattr(cfg, "moe_dropless", False) and "w_in" in params["mlp"]:
        from ..moe.layer import moe_ffn_dropless
        B, S, h = ln2.shape
        act = FFN_ACTIVATIONS[cfg.hidden_act]
        with scopes.scope("ds.mlp"):
            y, stats = moe_ffn_dropless(
                params["mlp"], ln2.reshape(B * S, h), cfg.moe_top_k,
                norm_topk_prob=cfg.moe_norm_topk_prob,
                activation=act,
                token_mask=None if token_mask is None
                else token_mask.reshape(B * S),
                held=cfg.moe_held or None, scale=cfg.moe_routing_scale,
                score=cfg.moe_router_score)
            y = y.reshape(ln2.shape)
            if "shared_in" in params["mlp"]:
                # the shared expert: every token, no router weight
                with scopes.scope("ds.moe_shared"):
                    shared = _gated_mlp(ln2, params["mlp"]["shared_in"],
                                        params["mlp"]["shared_out"], act)
                    if "shared_gate" in params["mlp"]:
                        shared = shared * jax.nn.sigmoid(_wmat(
                            ln2, params["mlp"]["shared_gate"]).astype(
                                jnp.float32)).astype(shared.dtype)
                    y = y + shared
        if cfg.use_parallel_residual:
            return x + reduce_fn(attn_partial) + out_b + y, stats
        return ln2_in + y, stats

    if getattr(cfg, "moe_num_experts", 0) and "w_in" in params["mlp"]:
        from ..moe.layer import moe_ffn_dense
        B, S, h = ln2.shape
        with scopes.scope("ds.mlp"):
            y = moe_ffn_dense(
                params["mlp"], ln2.reshape(B * S, h),
                capacity_factor=cfg.moe_capacity_factor,
                top_k=cfg.moe_top_k, rng=rng,
                jitter_eps=cfg.moe_jitter_eps,
                groups=getattr(cfg, "moe_num_groups", 1),
                dispatch=getattr(cfg, "moe_dispatch", "einsum"),
                renorm_kept_choices=getattr(
                    cfg, "moe_renorm_kept_choices", False),
                observe=getattr(cfg, "moe_observability", False),
                ffn_quant=ffn_quant)
        new_amax_row = None
        if ffn_quant is not None:
            y, aux, new_amax_row = y
        else:
            y, aux = y
        moe_out = y.reshape(ln2.shape)
        if cfg.use_parallel_residual:
            out = x + reduce_fn(attn_partial) + out_b + moe_out
        else:
            out = ln2_in + moe_out
        if ffn_quant is not None:
            return out, aux, new_amax_row
        return out, aux

    mlp_b = params["mlp"]["out_b"].astype(x.dtype) \
        if "out_b" in params["mlp"] else 0
    if ffn_quant is not None:
        # delayed-scaling quantized FFN (ops/pallas/quant_matmul):
        # amax_row [4, H] carries the histories for in-x/in-w/out-x/out-w
        from ..ops.pallas.quant_matmul import ffn_scaled_matmuls
        recipe, margin, amax_row = ffn_quant
        B, S, h = ln2.shape
        with scopes.scope("ds.mlp"):
            y2d, new_amax_row = ffn_scaled_matmuls(
                ln2.reshape(B * S, h), params["mlp"]["in_w"],
                params["mlp"]["in_b"], params["mlp"]["out_w"],
                amax_row, recipe, margin)
        mlp_partial = y2d.reshape(B, S, -1)
        if cfg.use_parallel_residual:
            out = x + reduce_fn(attn_partial + mlp_partial) + out_b + mlp_b
        else:
            out = ln2_in + reduce_fn(mlp_partial) + mlp_b
        return out, new_amax_row
    act = FFN_ACTIVATIONS[getattr(cfg, "hidden_act", "gelu")]
    with scopes.scope("ds.mlp"):
        if getattr(cfg, "layer_plan", ()):       # a planned dense FFN is gated
            mlp_partial = _gated_mlp(ln2, params["mlp"]["in_w"],
                                     params["mlp"]["out_w"], act)
            if "ln_mlp_out" in params:
                mlp_partial = norm(cfg, params["ln_mlp_out"], mlp_partial)
        else:
            hmid = act(_plus_bias(_wmat(ln2, params["mlp"]["in_w"]),
                                  params["mlp"], "in_b"))
            mlp_partial = _wmat(hmid, params["mlp"]["out_w"])

    if cfg.use_parallel_residual:
        # one reduce for both partials (the Megatron fusion win)
        return x + reduce_fn(attn_partial + mlp_partial) + out_b + mlp_b
    return ln2_in + reduce_fn(mlp_partial) + mlp_b


@scopes.scoped("ds.block")
def _block_core(cfg, params, x, cos_sin, use_pallas, mp, reduce_fn,
                return_kv=False, rng=None, attn_fn=None,
                segment_ids=None, ffn_quant=None, spec=None, shared=None):
    """Shared block body: `mp == 1` with identity `reduce_fn` is the
    dense block; TP callers pass pre-sliced params (column/row parallel)
    and a psum reduce; the KV-cached decode step reuses the same
    `_block_qkv`/`_block_post_attn` pieces — one implementation, so the
    paths cannot drift. Biases of row-parallel matmuls are added after
    the reduce (algebraically identical in the dense case).

    `segment_ids` [B, S] (packed ragged batches) makes attention
    intra-document on every path: the default flash/XLA core and any
    segment-capable `attn_fn` (the SP ring accepts the kwarg).

    `spec` (a planned model's `LayerSpec`, `params` a layer of that
    kind's stack): the layer's own query heads, and its window if it is
    a window layer. A latent layer runs expanded here (every head's keys
    and values from the latent rows, then the same attention core), and
    what `return_kv` hands back is (its latent rows [B, S, kv_rank +
    rope],): the cache's one pool's.

    An `eva` layer's attention is `eva_attention` around the same core,
    and its `return_kv` is (k, v, K~, V~): its rows and every chunk's
    pooled row.

    `shared` (a plan whose layers read what another made,
    `GPTNeoXConfig.plan_shares`): {"mem": the memory of the nearest ssm
    layer before this one, "kv": the K and V of the plan's full layer}.
    An ssm layer's `return_kv` is `ssm_mixer`'s (convolution state, scan
    state, memory); a gmu's or a cross layer's is (). A gdn layer's is
    `gdn_mixer`'s (convolution rows, matrix states)."""
    B, S, h = x.shape
    kind = spec.attn if spec is not None else "full"
    if kind in ("ssm", "gmu", "gdn"):
        token_mask = None if segment_ids is None else segment_ids > 0
        with scopes.scope("ds.attn"):
            a = norm(cfg, params["ln_attn"], x)
        if kind == "ssm":
            mixed, kv = ssm_mixer(cfg, params["attn"], a, token_mask,
                                  use_pallas)
        elif kind == "gdn":
            mixed, kv = gdn_mixer(cfg, params["attn"], a, token_mask)
        else:
            mixed, kv = gmu_mixer(params["attn"], a, shared["mem"]), ()
        out = _block_post_attn(cfg, params, x, mixed, reduce_fn,
                               token_mask=token_mask, projected=True)
        return (out, kv) if return_kv else out
    cos, sin, rot_dim = cos_sin
    heads = spec.heads if spec is not None else cfg.num_heads
    window = cfg.attn_window \
        if spec is not None and spec.attn == "window" else None
    # the attention core's call: `_qkv_split` reads it before q, k, v are
    call = dict(use_pallas=use_pallas, segment_ids=segment_ids,
                window=window, block=getattr(cfg, "generation_block", 0),
                sm_scale=getattr(cfg, "attn_scale", None))
    if spec is not None and spec.attn == "latent":
        q_nope, q_rope, latent = _latent_rows(cfg, params, x, cos, sin,
                                              heads)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k, v = _latent_expand(cfg, params, latent, heads)
        kv = (latent,)
    else:
        shape = (B, S, heads // mp, cfg.head_dim)
        in_kernel = _rotary_in_kernel(
            params["attn"], shape, x.dtype, cos, rot_dim, attn_fn, return_kv,
            **call)
        q, k, v = _block_qkv(
            cfg, params, x, cos, sin, rot_dim, heads // mp,
            split=_qkv_split(params["attn"], shape, attn_fn, **call),
            rotate=not in_kernel)
        if in_kernel:
            call["rotary"] = cos_sin
        kv = (k, v)
        if kind == "cross":
            (k, v), kv = shared["kv"], ()
    diff = getattr(cfg, "attn_diff", False)
    with scopes.scope("ds.attn"):
        if diff:
            q = diff_queries(q)
        if kind == "eva":
            def core(q, k, v, seg):
                if attn_fn is not None:
                    return attn_fn(q, k, v, segment_ids=seg)
                return causal_attention(q, k, v, use_pallas=use_pallas,
                                        segment_ids=seg)
            attn, pooled = eva_attention(
                cfg, params["attn"], q, k, v,
                None if segment_ids is None else segment_ids > 0, core)
            kv = kv + pooled
        elif attn_fn is not None:
            attn = attn_fn(q, k, v) if segment_ids is None else \
                attn_fn(q, k, v, segment_ids=segment_ids)
        else:
            attn = causal_attention(q, k, v, **call)
        if diff:
            attn = diff_combine(cfg, params["attn"], attn)
    if return_kv and ffn_quant is not None:
        raise ValueError("return_kv and ffn_quant cannot combine (the "
                         "KV-returning decode path serves quantized "
                         "WEIGHTS, not the delayed-scaling FFN)")
    # segment 0 is padding (a packed batch's tail, a prefill bucket's):
    # a router that drops nothing routes it nowhere
    out = _block_post_attn(
        cfg, params, x, attn.reshape(B, S, -1), reduce_fn, rng=rng,
        ffn_quant=ffn_quant,
        token_mask=None if segment_ids is None else segment_ids > 0)
    if return_kv:
        return out, kv
    return out


def block_forward(cfg, params, x, cos_sin, compute_dtype=None,
                  use_pallas=True, rng=None, attn_fn=None,
                  segment_ids=None, ffn_quant=None):
    """One GPT-NeoX block with parallel residual:
    x + attn(ln1(x)) + ffn(ln2(x)). With `cfg.moe_num_experts` the FFN
    is the MoE layer and the return is (out, aux_loss); with `ffn_quant`
    (delayed-scaling quantized FFN) it is (out, new_amax_row)."""
    return _block_core(cfg, params, x, cos_sin, use_pallas, mp=1,
                       reduce_fn=lambda t: t, rng=rng, attn_fn=attn_fn,
                       segment_ids=segment_ids, ffn_quant=ffn_quant)


def block_forward_tp(cfg, params, x, cos_sin, model_axis, mp,
                     use_pallas=True):
    """`block_forward` with explicit Megatron tensor parallelism for use
    inside `shard_map`: params arrive pre-sliced over `model_axis` (qkv/
    mlp-in column-sharded → local heads, attn-out/mlp-out row-sharded →
    partial sums), and ONE `psum` per block combines the attention and
    MLP partials (the parallel-residual form needs a single collective —
    the fusion Megatron gets from its row-parallel allreduce).

    x is replicated over `model_axis`; mp = mesh size of that axis.
    """
    if getattr(cfg, "moe_num_experts", 0):
        raise NotImplementedError(
            "tensor-parallel blocks with an MoE FFN are not supported "
            "yet; use expert parallelism (mesh axis 'expert') instead")
    _require_neox_block(cfg, "tensor parallelism")
    return _block_core(cfg, params, x, cos_sin, use_pallas, mp=mp,
                       reduce_fn=lambda t: jax.lax.psum(t, model_axis))


def _require_neox_block(cfg, what):
    """`what` (a parallel layout, a quantized path) is written for the
    block GPT-NeoX published: LayerNorm with bias, biased projections,
    no norm on q and k, an ungated FFN. Refuse any other by name."""
    other = [f"{k}={getattr(cfg, k)!r}" for k, plain in
             (("norm", "layernorm"), ("use_bias", True), ("qk_norm", False),
              ("ffn_gated", False), ("moe_dropless", False))
             if getattr(cfg, k, plain) != plain]
    if other:
        raise NotImplementedError(
            f"{what} is not computed for a block with "
            f"{', '.join(other)}: its parameter layout and its sharding "
            f"rules are the GPT-NeoX block's")


def block_param_specs_tp(pipe_axis=None):
    """`block_param_specs` with an optional leading stacked-layer dim
    sharding (for [L, ...]-stacked pipeline params inside shard_map)."""
    lead = (pipe_axis,) if pipe_axis is not None else ()
    return jax.tree_util.tree_map(lambda s: P(*lead, *s),
                                  block_param_specs(),
                                  is_leaf=lambda x: isinstance(x, P))


def scan_stacked_blocks(block_fn, x, blocks):
    """Run identically-shaped transformer blocks as ONE `lax.scan` over
    their stacked parameters: the compiled program holds a single block
    body, so XLA compile time is O(1) in depth instead of O(L) (the
    unrolled 48-layer GPT2-XL remat program took >20 min on a v5e; the
    scanned one compiles like a 1-layer model). The stack is built
    inside the traced function; grads flow back through it to the
    natural per-block list layout, so engine state / checkpoints are
    unchanged. Shared by the GPT-NeoX and GPT-2 families."""
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    return jax.lax.scan(
        lambda carry, bp: (block_fn(bp, carry), None), x, stacked)[0]


def segment_sizes(n_layers, n_segments):
    """Span lengths for segmented checkpointing: n_segments as-equal-as-
    possible groups over n_layers (earlier spans get the remainder).
    Shared by the scan (NeoX/GPT-2) and loop (BERT) segment paths so the
    partitioning can never drift between families."""
    n = max(1, min(int(n_segments), n_layers))
    return [n_layers // n + (1 if i < n_layers % n else 0)
            for i in range(n)]


def segmented_scan_blocks(block_fn, x, blocks, n_segments, policy=None,
                          boundary_fn=None):
    """Segmented-scan checkpointing: remat at SEGMENT boundaries instead
    of per block (the reference's `number_checkpoints` semantics —
    `deepspeed/runtime/activation_checkpointing/checkpointing.py:687`
    splits the layer stack into `num_checkpoints` recompute spans).

    The L blocks are grouped into `n_segments` spans; each span is ONE
    `jax.checkpoint(policy=...)` region whose interior is a `lax.scan`
    over its k stacked block params — so only segment-boundary carries
    (plus whatever the policy names) are saved, and backward recomputes
    k blocks per span. With L % n == 0 the segments themselves ride an
    outer `lax.scan`, keeping compile time O(1) in depth (composes with
    `scan_stacked_blocks`); ragged layer counts fall back to a Python
    loop over segments (≤ 2 distinct span lengths → ≤ 2 traced bodies).

    `boundary_fn` (optional) transforms the carry at every segment edge —
    the hook `partition_activations` uses to shard saved residuals over
    the `model` axis. `block_fn(block_params, x) -> x` must be uniform
    across blocks (no MoE aux threading, no hidden collection).
    """
    L = len(blocks)
    sizes = segment_sizes(L, n_segments)
    n = len(sizes)
    edge = boundary_fn if boundary_fn is not None else (lambda c: c)

    def seg_body(carry, seg_stacked):
        return jax.lax.scan(
            lambda c, bp: (block_fn(bp, c), None), carry, seg_stacked)[0]

    ck = jax.checkpoint(seg_body, policy=policy)

    if L % n == 0:
        k = L // n
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
        grouped = jax.tree_util.tree_map(
            lambda a: a.reshape((n, k) + a.shape[1:]), stacked)
        return jax.lax.scan(
            lambda c, gp: (ck(edge(c), gp), None), x, grouped)[0]

    idx = 0
    for size in sizes:
        seg = blocks[idx:idx + size]
        idx += size
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *seg)
        x = ck(edge(x), stacked)
    return x


def resolve_remat(remat_blocks, remat_policy, number_checkpoints):
    """Shared knob resolution for the model families: returns
    (do_remat, policy_object, number_checkpoints). `remat_blocks=True`
    with no explicit policy keeps today's whole-block save-nothing remat
    ('full'); a policy or segment count alone also switches remat on
    ('none' resolves to no remat at all — save everything)."""
    from ..runtime.activation_checkpointing.checkpointing import \
        make_remat_policy
    do_remat = bool(remat_blocks or remat_policy is not None
                    or number_checkpoints is not None)
    if not do_remat:
        return False, None, None
    policy, is_remat = make_remat_policy(remat_policy)
    if not is_remat and number_checkpoints is None:
        return False, None, None   # 'none': saving everything == no remat
    return True, policy, number_checkpoints


def forward_hidden(cfg, params, tokens, use_pallas=True, remat_blocks=False,
                   collect_hidden=False, rng=None, attn_fn=None,
                   scan_blocks=False, remat_policy=None,
                   number_checkpoints=None, boundary_fn=None,
                   segment_ids=None, ffn_amax=None):
    """tokens [B, S] int32 → final-norm hidden states [B, S, H]; with
    `collect_hidden` also returns [embed, block outputs..., final norm]
    (the activation-capture path shares this exact forward). With MoE
    enabled, returns (out, aux_loss_total[, hidden]).

    `segment_ids` [B, S] int32 (packed ragged batches, 0 = pad — see
    `runtime.packing`): attention becomes intra-document on every block,
    and the rotary cache is gathered at each token's INTRA-document
    position, so a packed document sees the identical position stream as
    the same document padded alone.

    `scan_blocks` compiles the (identically-shaped) blocks as ONE
    `lax.scan` body — XLA compile time O(1) in depth (the GPT-NeoX-20B
    shape has 44 layers; see gpt2.forward_hidden for the measured
    unrolled-compile pathology). Falls back to the Python loop when the
    per-block structure varies (collect_hidden / MoE aux threading).

    Remat knobs (see `resolve_remat`): `remat_policy` names a
    `jax.checkpoint` policy ('none'/'full'/'dots'/'attn_residuals'/
    'offload_dots'); `number_checkpoints` switches from per-block remat
    to `segmented_scan_blocks` (k-grouped spans, remat at group
    boundaries); `boundary_fn` constrains segment-boundary carries
    (partition_activations)."""
    moe = bool(getattr(cfg, "moe_num_experts", 0))
    do_remat, policy, n_ckpt = resolve_remat(remat_blocks, remat_policy,
                                             number_checkpoints)
    if cfg.layer_plan:
        if do_remat or collect_hidden or attn_fn is not None or \
                ffn_amax is not None:
            raise NotImplementedError(
                "a planned model's forward is the plain one: no remat, "
                "hidden-state capture, custom attention or quantized FFN "
                "(training of a planned block is not built)")
        out = _forward_hidden_planned(cfg, params, tokens, use_pallas,
                                      segment_ids)
        return (out, jnp.asarray(0.0, jnp.float32)) if moe else out
    quant = None
    if ffn_amax is not None:
        # delayed-scaling quantized FFN: `ffn_amax` [L, 4, H] carries
        # per-layer amax histories; each block consumes its row and the
        # advanced rows come back stacked as an extra return value.
        # `ffn_quant_recipe`/`ffn_quant_margin` ride the config
        # (apply_ds_config wires the "quantization" JSON block).
        quant = (cfg.ffn_quant_recipe, getattr(cfg, "ffn_quant_margin",
                                               1.0))
        if n_ckpt is not None:
            raise ValueError(
                "quantization.ffn + number_checkpoints (segmented-scan "
                "checkpointing) is unsupported: the amax rows do not "
                "thread through the segment spans; use a remat policy "
                "without number_checkpoints")
        if collect_hidden:
            raise ValueError(
                "quantization.ffn does not thread amax through the "
                "hidden-state capture path (collect_hidden)")
    with scopes.scope("ds.embed"):
        x = params["embed"]["wte"][tokens]
    cos, sin, rot_dim = _rotary_cache(cfg, tokens.shape[1])
    if segment_ids is not None and rot_dim:
        # gather the rotary cache at intra-document positions: [B, S, rot]
        from ..runtime.packing import segment_relative_positions
        pos = segment_relative_positions(segment_ids)
        cos, sin = cos[pos], sin[pos]
    hidden = [x] if collect_hidden else None

    def _quant_arg(arow):
        return None if arow is None else (quant[0], quant[1], arow)

    plain_block = lambda bp, x, r, arow=None: block_forward(  # noqa: E731
        cfg, bp, x, (cos, sin, rot_dim), use_pallas=use_pallas,
        rng=r, attn_fn=attn_fn, segment_ids=segment_ids,
        ffn_quant=_quant_arg(arow))
    if do_remat and n_ckpt is None:
        # rot_dim must stay a STATIC python int: routed through
        # jax.checkpoint's traced args it becomes an int32 tracer and
        # the rotary slice bound blows up; close over it instead
        # (segment_ids rides as an explicit traced arg so per-block remat
        # replays see the same operand, not a stale closure constant;
        # the amax row rides the same way — its advanced value is a
        # block OUTPUT, recomputed identically in the backward replay)
        ck = jax.checkpoint(
            lambda bp, x, cos, sin, seg, r, arow: block_forward(
                cfg, bp, x, (cos, sin, rot_dim), use_pallas=use_pallas,
                rng=r, attn_fn=attn_fn, segment_ids=seg,
                ffn_quant=_quant_arg(arow)), policy=policy)
        # boundary_fn on every block input: per-block remat saves each
        # block's carry, so partition_activations constrains them all
        edge = boundary_fn if boundary_fn is not None else (lambda c: c)
        block_fn = lambda bp, x, r, arow=None: ck(  # noqa: E731
            bp, edge(x), cos, sin, segment_ids, r, arow)
    else:
        block_fn = plain_block
    aux_total = jnp.asarray(0.0, jnp.float32)
    new_amax = None
    uniform = not moe and not collect_hidden
    if n_ckpt is not None and not uniform:
        raise ValueError(
            "number_checkpoints (segmented-scan checkpointing) needs a "
            "uniform block stack: incompatible with MoE aux-loss "
            "threading and collect_hidden — drop number_checkpoints or "
            "use per-block remat (a policy alone)")
    # the loop or scan over the blocks: a container scope, so that the
    # scan's own slicing and stacking shows apart from the blocks' work
    with scopes.scope("ds.layers"):
        if n_ckpt is not None:
            # segment spans own the remat; blocks inside run bare
            x = segmented_scan_blocks(
                lambda bp, x: plain_block(bp, x, None), x, params["blocks"],
                n_ckpt, policy=policy, boundary_fn=boundary_fn)
        elif scan_blocks and uniform and len(params["blocks"]) > 1:
            if quant is not None:
                stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                                 *params["blocks"])

                def sbody(carry, xs):
                    bp, arow = xs
                    return block_fn(bp, carry, None, arow)

                x, new_amax = jax.lax.scan(sbody, x, (stacked, ffn_amax))
            else:
                x = scan_stacked_blocks(lambda bp, x: block_fn(bp, x, None),
                                        x, params["blocks"])
        else:
            new_rows = []
            for i, bp in enumerate(params["blocks"]):
                brng = jax.random.fold_in(rng, i) \
                    if (moe and rng is not None) else None
                y = block_fn(bp, x, brng,
                             ffn_amax[i] if quant is not None else None)
                if moe and quant is not None:
                    x, aux, row = y
                    aux_total = aux_total + aux
                    new_rows.append(row)
                elif moe:
                    x, aux = y
                    aux_total = aux_total + aux
                elif quant is not None:
                    x, row = y
                    new_rows.append(row)
                else:
                    x = y
                if collect_hidden:
                    hidden.append(x)
            if quant is not None:
                new_amax = jnp.stack(new_rows)

    out = norm(cfg, params["final_ln"], x)
    if moe:
        if collect_hidden:
            return out, aux_total, hidden + [out]
        if quant is not None:
            return out, aux_total, new_amax
        return out, aux_total
    if collect_hidden:
        return out, hidden + [out]
    if quant is not None:
        return out, new_amax
    return out


def plan_layer_params(cfg, stacks):
    """A planned model's layers in order: [(spec, that layer's slice of
    its kind's stack)]."""
    at, out = {}, []
    for spec in cfg.layer_plan:
        i = at.get(spec.kind, 0)
        at[spec.kind] = i + 1
        out.append((spec, jax.tree_util.tree_map(lambda a, i=i: a[i],
                                                 stacks[spec.kind])))
    return out


def _forward_hidden_planned(cfg, params, tokens, use_pallas, segment_ids,
                            with_last=False):
    """`forward_hidden` of a planned model: the same block code, a layer
    at a time with its own `LayerSpec`. `with_last` also returns the last
    layer's hidden states, before the final norm (what the
    next-token-prediction block reads)."""
    S = tokens.shape[1]
    if segment_ids is not None and (cfg.plan_shares or cfg.gdn_conv):
        raise NotImplementedError(
            "packed rows (segment_ids) through a plan with an ssm, gmu, "
            "cross or gdn layer: the scan, the delta rule and the "
            "convolution do not start anew at a document's edge; one "
            "prompt a row is computed")
    if segment_ids is not None and cfg.eva_window:
        raise NotImplementedError(
            "packed rows (segment_ids) through an eva layer: its windows "
            "and chunks are counted from the row's start; one prompt a row "
            "is computed")
    with scopes.scope("ds.embed"):
        x = params["embed"]["wte"][tokens]
    rotary = plan_rotary(cfg, S)
    if segment_ids is not None:
        from ..runtime.packing import segment_relative_positions
        pos = segment_relative_positions(segment_ids)
        rotary = {k: (c[pos], s_[pos], r) for k, (c, s_, r) in rotary.items()}
    layers = plan_layer_params(cfg, params["stacks"])
    shares = cfg.plan_shares

    def stack(x):
        shared = {}
        with scopes.scope("ds.layers"):
            for spec, bp in layers:
                out = _block_core(
                    cfg, bp, x, rotary.get(spec.attn), use_pallas, mp=1,
                    reduce_fn=lambda t: t, segment_ids=segment_ids,
                    spec=spec, return_kv=shares, shared=shared)
                if shares:
                    # what a later gmu or cross layer reads
                    out, kv = out
                    if spec.attn == "ssm":
                        shared["mem"] = kv[2]
                    elif spec.attn == "full":
                        shared["kv"] = kv
                x = block_hidden(out)
        return x

    if cfg.loop_steps > 1:
        # the same layers `loop_steps` times, the final norm after every
        # pass; the head reads the pass the exit gate names
        passes = []
        for _ in range(cfg.loop_steps):
            with scopes.scope("ds.loop"):
                x = stack(x)
                with scopes.scope("ds.loop_exit"):
                    x = norm(cfg, params["final_ln"], x)
            passes.append(x)
        out = loop_exit(cfg, params, jnp.stack(passes))[0]
        # no next-token-prediction block reads a looped stack's hidden
        # states (`_check_loop`)
        return (out, None) if with_last else out
    x = stack(x)
    out = norm(cfg, params["final_ln"], x)
    return (out, x) if with_last else out


@scopes.scoped("ds.loop_exit")
def loop_exit(cfg, params, passes):
    """The exit gate of a looped model on `passes` [T, ..., h], every
    pass's final-norm hidden state: g_t = sigmoid(z_t . w + b) in
    float32; pass t < T is the last with probability p_t = g_t prod_{j<t}
    (1 - g_j), and T with what is left; the head reads t* = the first
    pass whose cumulative probability c_t = p_1 + ... + p_t reaches
    `cfg.loop_exit_threshold`, else T. Returns (z_{t*} [..., h], t*
    [...], from 1). At threshold 1 t* is T unless a gate saturates."""
    gate = params["loop_exit"]
    g = jax.nn.sigmoid(
        jnp.einsum("t...h,h->t...", passes[:-1].astype(jnp.float32),
                   gate["w"].astype(jnp.float32)) +
        gate["b"].astype(jnp.float32)[0])
    stay = jnp.cumprod(1.0 - g, axis=0)
    p = g * jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    short = jnp.cumsum(p, axis=0) < cfg.loop_exit_threshold
    t_star = 1 + jnp.sum(short, axis=0, dtype=jnp.int32)
    chosen = jnp.take_along_axis(passes, (t_star - 1)[None, ..., None],
                                 axis=0)[0]
    return chosen, t_star


def mtp_hidden(cfg, params, tokens, last, use_pallas=True):
    """The next-token-prediction block's final-norm hidden states
    [B, S, h]: position i from the last layer's hidden state at i (`last`
    [B, S, h], before the final norm) and the embedding of token i + 1,
    through the projection of [rms(hidden) | rms(embedding)], one layer
    of the last layer's kind and the block's own final norm. Under the
    model's head, position i predicts token i + 2. Position S - 1 has no
    next token (it is given token 0's, and by causality moves no earlier
    position): a caller reads [:, :S - 1]."""
    mtp = params["mtp"]
    spec = cfg.layer_plan[-1]
    with scopes.scope("ds.embed"):
        emb = params["embed"]["wte"][jnp.roll(tokens, -1, axis=1)]
    x = _wmat(jnp.concatenate([norm(cfg, mtp["hnorm"], last),
                               norm(cfg, mtp["enorm"], emb)], axis=-1),
              mtp["proj"])
    bp = jax.tree_util.tree_map(lambda a: a[0], mtp["block"])
    x = block_hidden(_block_core(
        cfg, bp, x, _rotary_cache(cfg, tokens.shape[1], spec=spec),
        use_pallas, mp=1, reduce_fn=lambda t: t, spec=spec))
    return norm(cfg, mtp["final_ln"], x)


def forward(cfg, params, tokens, use_pallas=True, remat_blocks=False,
            scan_blocks=False, remat_policy=None, number_checkpoints=None):
    """tokens [B, S] int32 → logits [B, S, V]."""
    x = forward_hidden(cfg, params, tokens, use_pallas=use_pallas,
                       remat_blocks=remat_blocks, scan_blocks=scan_blocks,
                       remat_policy=remat_policy,
                       number_checkpoints=number_checkpoints)
    if getattr(cfg, "moe_num_experts", 0):
        x, _ = x
    out_embed = params.get("embed_out", params["embed"])["wte"]
    logits = jnp.einsum("bsh,vh->bsv", x, out_embed.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    return logits


# calls of the CE head traced in this process, by the rule that ran:
# the custom_vjp's forward rule (loss and both gradients from one logits
# tile a chunk) or the primal (loss only); `ops.dispatch_report()["ce_head"]`
_CE_HEAD_TRACED = {"loss_and_grads": 0, "loss_only": 0}


def _ce_head_chunks(x, labels, ignore_index, chunk_rows):
    """The head's rows as the scan walks them: x[:, :-1] against
    labels[:, 1:], padded with ignored rows to whole chunks."""
    H = x.shape[-1]
    xs = x[:, :-1, :].reshape(-1, H)
    ts = labels[:, 1:].reshape(-1)
    n_pad = (-xs.shape[0]) % chunk_rows
    if n_pad:
        xs = jnp.pad(xs, ((0, n_pad), (0, 0)))
        ts = jnp.pad(ts, (0, n_pad), constant_values=ignore_index)
    n_chunks = xs.shape[0] // chunk_rows
    return (xs.reshape(n_chunks, chunk_rows, H),
            ts.reshape(n_chunks, chunk_rows))


def _ce_head_tile(xc, tc, w, ignore_index):
    """One chunk's float32 logits tile `xc w^T` and what the loss takes
    from it: (tile, lse, valid, safe labels, the chunk's summed -log p)."""
    valid = tc != ignore_index
    safe = jnp.where(valid, tc, 0)
    logits = jnp.einsum("ch,vh->cv", xc, w,
                        preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    # the label's logit as a row-dot against the gathered label
    # embeddings ([chunk, H]) rather than a take_along_axis on the tile
    # (a gather over 824 MB). The tile IS written to HBM, once a chunk:
    # the matmul's fusion reduces the row max through its output and
    # `exp`, the row sum and the gradients' matmuls read it back
    picked = jnp.einsum("ch,ch->c", xc, w[safe],
                        preferred_element_type=jnp.float32)
    return logits, lse, valid, safe, -jnp.sum((picked - lse) * valid)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ce_head(x, wte, labels, ignore_index, chunk_rows):
    _CE_HEAD_TRACED["loss_only"] += 1
    xs, ts = _ce_head_chunks(x, labels, ignore_index, chunk_rows)
    w = wte.astype(x.dtype)

    def body(carry, xt):
        loss_sum, count = carry
        _, _, valid, _, nll = _ce_head_tile(*xt, w, ignore_index)
        return (loss_sum + nll, count + jnp.sum(valid)), None

    (loss_sum, count), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (xs, ts))
    return loss_sum / jnp.maximum(count, 1)


def _ce_head_fwd(x, wte, labels, ignore_index, chunk_rows):
    _CE_HEAD_TRACED["loss_and_grads"] += 1
    B, S, H = x.shape
    xs, ts = _ce_head_chunks(x, labels, ignore_index, chunk_rows)
    w = wte.astype(x.dtype)
    vocab = jax.lax.broadcasted_iota(jnp.int32, (1, w.shape[0]), 1)

    def body(carry, xt):
        loss_sum, count, dw = carry
        xc, tc = xt
        logits, lse, valid, safe, nll = _ce_head_tile(xc, tc, w,
                                                      ignore_index)
        # d(sum of -log p)/d(logits): at most 1 in magnitude, so it
        # rounds to the operands' dtype without a scale; the one-hot is
        # a compare inside the tile's consumers, not a scatter
        d = ((jnp.exp(logits - lse[:, None]) - (vocab == safe[:, None]))
             * valid[:, None]).astype(xc.dtype)
        dx = jnp.einsum("cv,vh->ch", d, w,
                        preferred_element_type=jnp.float32)
        dw = dw + jnp.einsum("cv,ch->vh", d, xc,
                             preferred_element_type=jnp.float32)
        return (loss_sum + nll, count + jnp.sum(valid), dw), \
            dx.astype(xc.dtype)

    (loss_sum, count, dw), dxs = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32),
               jnp.zeros(w.shape, jnp.float32)), (xs, ts))
    dx = dxs.reshape(-1, H)[:B * (S - 1)].reshape(B, S - 1, H)
    dx = jnp.pad(dx, ((0, 0), (0, 1), (0, 0)))
    count = jnp.maximum(count, 1).astype(jnp.float32)
    return loss_sum / count, (dx, dw, count, jnp.zeros((0,), wte.dtype))


def _ce_head_bwd(ignore_index, chunk_rows, res, g):
    dx, dw, count, like_wte = res
    with scopes.scope("ds.ce_head"):
        scale = g.astype(jnp.float32) / count
        return ((dx.astype(jnp.float32) * scale).astype(dx.dtype),
                (dw * scale).astype(like_wte.dtype), None)


_ce_head.defvjp(_ce_head_fwd, _ce_head_bwd)


@scopes.scoped("ds.ce_head")
def fused_lm_head_loss(x, wte, labels, ignore_index=-100, chunk_rows=4096):
    """Next-token cross entropy fused with the LM head, chunked over rows.

    Never holds the full [B, S, V] fp32 logits (6 GB at batch 32 x seq
    1024 x vocab 50k): a scan forms one [chunk, V] float32 tile at a time
    (824 MB at 4,096 x 50,304), which XLA DOES write to HBM, once a chunk.
    A `jax.custom_vjp`: differentiated, its forward rule takes the loss
    AND both gradients from that one tile (`d = softmax - onehot`, then
    `dx = d W` and `dW += d^T x` into a float32 carry: three matmuls a
    chunk), keeps `dx` (x's dtype, unscaled), `dW` (float32) and the
    valid count, and its backward rule only multiplies them by the
    upstream cotangent over the count, in float32 (a float16 loss scale
    meets the sums after the matmuls). Not differentiated (evaluation),
    the same scan computes the loss alone, one matmul a chunk. Nothing is
    recomputed. No forward-mode rule (`jax.jvp` of it raises).

    x: [B, S, H] final-norm hidden states; wte: [V, H]; labels: [B, S].
    chunk_rows is the scan tile: bigger tiles amortize scan overhead,
    smaller ones cap the [chunk, V] fp32 logits tile's HBM.
    """
    return _ce_head(x, wte, labels, ignore_index, chunk_rows)


@scopes.scoped("ds.ce_head")
def lm_loss(logits, labels, ignore_index=-100):
    """Next-token cross entropy; labels already shifted or == tokens (we
    shift internally when labels is tokens)."""
    logits = logits[:, :-1, :]
    targets = labels[:, 1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    valid = targets != ignore_index
    safe_targets = jnp.where(valid, targets, 0)
    ll = jnp.take_along_axis(logp, safe_targets[..., None],
                             axis=-1).squeeze(-1)
    return -jnp.sum(ll * valid) / jnp.maximum(jnp.sum(valid), 1)


def make_partition_boundary(mesh, model_axis=MODEL_AXIS):
    """Segment-boundary carry constraint for `partition_activations`:
    saved [B, S, H] residuals shard their sequence dim over the `model`
    axis, so each MP rank stores 1/mp of every checkpoint (the
    reference's partitioned-activation layout). None when the mesh has
    no (or a trivial) model axis — nothing to partition over."""
    if mesh is None or model_axis not in mesh.axis_names or \
            mesh.shape[model_axis] <= 1:
        return None
    from jax.sharding import NamedSharding
    sharding = NamedSharding(mesh, P(None, model_axis, None))

    def constrain(x):
        if getattr(x, "ndim", 0) == 3:
            try:
                return jax.lax.with_sharding_constraint(x, sharding)
            except Exception:
                return x
        return x

    return constrain


def reject_unsupported_ds_blocks(ds_config, family):
    """Families without MoE / sequence-parallel / block-sparse support
    must fail LOUDLY when a config enables them — the engine calls
    `apply_ds_config` expecting the blocks to be consumed, and accepting
    the call would silently train a dense/non-SP model. Shared by GPT-2
    and BERT."""
    if getattr(ds_config, "moe_params", None) or \
            getattr(ds_config, "sequence_parallel_params", None):
        raise NotImplementedError(
            f"{family} does not implement the moe/sequence_parallel "
            "config blocks; use models.gpt_neox.GPTNeoX")
    if getattr(ds_config, "sparse_attention", None):
        raise NotImplementedError(
            f"{family} does not implement the sparse_attention config "
            "block (the run would silently train with dense attention); "
            "the block-sparse engine lives on models.gpt_neox.GPTNeoX")
    qz = getattr(ds_config, "quantization_config", None)
    if qz and qz.get("ffn"):
        raise NotImplementedError(
            f"{family} does not implement the quantization.ffn block "
            "(the run would silently train full-precision); the "
            "delayed-scaling FFN lives on models.gpt_neox.GPTNeoX")


def apply_activation_checkpointing_config(model, ds_config, mesh=None):
    """Thread the JSON `activation_checkpointing` block into a model
    wrapper's remat knobs (shared by the GPT-NeoX / GPT-2 / BERT
    families — the engine calls this through `apply_ds_config`).

    Mapping of the reference keys: `number_checkpoints` → segmented-scan
    spans; `cpu_checkpointing` → host-offload remat policy
    (`offload_dots`); `partition_activations` → model-axis sharding
    constraint on segment-boundary carries; fork key `policy` → named
    `jax.checkpoint` policy. Validates `number_checkpoints` against the
    model's layer count (parse time cannot — it doesn't know it).

    An active block always implies remat (the reference block is a
    checkpointing block): with no explicit policy, knobs like
    `partition_activations` get whole-block 'full' remat with their
    constraint applied to every saved carry."""
    ac = getattr(ds_config, "activation_checkpointing_config", None)
    if ac is None or not getattr(ac, "active", False):
        return
    from ..runtime.activation_checkpointing.checkpointing import \
        resolve_policy_name
    from ..runtime.config_utils import DeepSpeedConfigError
    n_layers = getattr(model.config, "num_layers", None)
    if ac.number_checkpoints is not None and n_layers is not None and \
            ac.number_checkpoints > n_layers:
        raise DeepSpeedConfigError(
            f"activation_checkpointing.number_checkpoints "
            f"({ac.number_checkpoints}) exceeds the model's num_layers "
            f"({n_layers})")
    policy = resolve_policy_name(ac.policy, ac.cpu_checkpointing)
    model.remat_policy = policy if policy is not None else "full"
    model.number_checkpoints = ac.number_checkpoints
    if ac.partition_activations:
        model._ckpt_boundary_fn = make_partition_boundary(mesh)


def make_sparse_attention(cfg, sparse_params=None):
    """Build the config-selectable block-sparse long-context attention
    engine (`cfg.attention_engine == "sparse"`): a `SparseSelfAttention`
    over the JSON `sparse_attention` block's pattern (local+global
    `fixed`/`variable` layouts à la the reference's SparseSelfAttention),
    used as the transformer's attention core.

    A causal LM needs a unidirectional pattern — `attention` defaults to
    "unidirectional" here (the reference's block default is
    bidirectional, which would leak future tokens into the LM loss), and
    an explicitly bidirectional pattern (incl. the structurally
    bidirectional bigbird/bslongformer modes) is rejected loudly.

    `SparseSelfAttention`'s auto dispatch hands dense-ish layouts to the
    masked dense-flash kernel.

    Returns `attn_fn(q, k, v)` for `forward_hidden(attn_fn=...)`."""
    d = dict(sparse_params or {})
    d.setdefault("mode", "fixed")
    d.setdefault("block", 128)
    d["num_heads"] = cfg.num_heads
    if d.get("attention") is None:
        # the JSON parse leaves an unset `attention` as None so this
        # path can tell "unset" from "asked for bidirectional" — only
        # the latter should be a hard error on a causal LM
        d["attention"] = "unidirectional"
    from ..ops.sparse_attention import SparseSelfAttention
    from ..ops.sparse_attention.sparsity_config import \
        sparsity_config_from_dict
    sc = sparsity_config_from_dict(d)
    # Default the probe to "bidirectional": a config class that does not
    # store an `attention` attribute (e.g. DenseSparsityConfig) cannot
    # express directionality, and the kernel side (get_layout) treats a
    # missing attribute as bidirectional — accepting it here would
    # silently leak future tokens.
    if getattr(sc, "attention", "bidirectional") != "unidirectional":
        raise ValueError(
            f"attention_engine 'sparse' on a causal LM needs a "
            f"unidirectional sparsity pattern; mode {d['mode']!r} with "
            f"attention={getattr(sc, 'attention', None)!r} attends "
            f"bidirectionally (future-token leak). Use mode 'fixed' or "
            f"'variable' with attention='unidirectional'")
    sp = SparseSelfAttention(sc, max_seq_length=cfg.max_seq_len)

    def attn_fn(q, k, v, segment_ids=None):
        if segment_ids is not None:
            raise NotImplementedError(
                "the block-sparse attention engine is not segment-aware; "
                "packed batches need attention_engine='dense'")
        return sp(q, k, v)

    return attn_fn


def split_lm_batch(batch):
    """(tokens, labels, segment_ids) from an engine batch: bare array,
    (tokens, labels) pair, or packed (tokens, labels, segment_ids)
    triple. Shared by the GPT-NeoX and GPT-2 loss paths."""
    if isinstance(batch, (tuple, list)):
        if len(batch) == 3:
            return batch[0], batch[1], batch[2]
        tokens, labels = batch
        return tokens, labels, None
    return batch, batch, None


class GPTNeoX:
    """Engine-protocol wrapper: loss_fn / init_params / param_specs."""

    def __init__(self, config=None, use_pallas=True, remat_blocks=False,
                 scan_blocks=False, remat_policy=None,
                 number_checkpoints=None, **kwargs):
        self.config = config or GPTNeoXConfig(**kwargs)
        self.use_pallas = use_pallas
        self.remat_blocks = remat_blocks
        self.scan_blocks = scan_blocks
        self.remat_policy = remat_policy
        self.number_checkpoints = number_checkpoints
        self._ckpt_boundary_fn = None  # partition_activations constraint
        # set by apply_ds_config (sequence parallel / sparse engine)
        self._attn_fn = None
        self._sparse_params = None
        if self.config.attention_engine not in ("dense", "sparse"):
            raise ValueError(
                f"attention_engine must be 'dense' or 'sparse', got "
                f"{self.config.attention_engine!r}")
        self.config.check_block()

    def _refuse_planned_training(self, what):
        if self.config.layer_plan:
            from ..runtime.config_utils import DeepSpeedConfigError
            raise DeepSpeedConfigError(
                f"{what}: training of a planned model (layer_plan: window "
                f"layers, grouped KV heads, an attention gate, a shared "
                f"expert, a held share of the experts, latent attention, "
                f"a stack looped over its weights, generation by blocks, "
                f"whose masking schedule no configuration key gives, a "
                f"state-space layer, whose scan has no backward, "
                f"differential attention, chunk-pooled (eva) attention, "
                f"whose pooling has no backward) "
                f"is not built; the flash backward, the parameter specs "
                f"and the pipeline layers are the homogeneous block's. "
                f"InferenceEngine serves it (`loss_fn` alone computes a "
                f"plan of latent layers, packing apart)")

    def _attention_fn(self):
        """The attention core `forward_hidden` should use: the SP/sparse
        attn_fn when configured, with a lazily-built sparse engine for
        `attention_engine='sparse'` set directly on the config (no JSON
        block)."""
        if self._attn_fn is None and \
                self.config.attention_engine == "sparse":
            self._attn_fn = make_sparse_attention(self.config,
                                                  self._sparse_params)
        return self._attn_fn

    def apply_ds_config(self, ds_config, mesh=None):
        """Wire the JSON `moe` / `sequence_parallel` /
        `activation_checkpointing` blocks into the model — the engine
        calls this before parameter init, so a user config alone (no
        library imports) drives all three axes."""
        import dataclasses
        self._refuse_planned_training("deeperspeed_tpu.initialize")
        moe = getattr(ds_config, "moe_params", None)
        if moe and self.config.moe_dropless:
            raise NotImplementedError(
                "the JSON `moe` block configures the GShard capacity "
                "router (capacity_factor, groups, dispatch engine); a "
                "model whose routing drops nothing (moe_dropless) takes "
                "its experts from the published architecture")
        if moe:
            self.config = dataclasses.replace(
                self.config,
                moe_num_experts=moe["num_experts"],
                moe_top_k=moe["top_k"],
                moe_capacity_factor=moe["capacity_factor"],
                moe_jitter_eps=moe["jitter_eps"],
                moe_aux_loss_coef=moe["aux_loss_coef"],
                moe_num_groups=moe.get("num_groups", 1),
                moe_dispatch=moe.get("dispatch", "einsum"),
                moe_a2a_overlap_chunks=moe.get("a2a_overlap_chunks", 1),
                moe_renorm_kept_choices=moe.get("renorm_kept_choices",
                                                False),
                moe_observability=moe.get("observability", False))
            if self.config.moe_a2a_overlap_chunks > 1:
                # the GSPMD model path lets XLA insert the expert
                # exchange — explicit a2a chunking only exists on the
                # shard_map expert-parallel path (moe.MoELayer); don't
                # let the knob look like it shaped this model's schedule
                from ..utils.logging import logger
                logger.warning(
                    "moe.a2a_overlap_chunks > 1 has no effect on the "
                    "GSPMD GPT-NeoX MoE path (XLA schedules the expert "
                    "exchange); it applies to the explicit shard_map "
                    "expert-parallel layer (deeperspeed_tpu.moe.MoELayer)")
        sp = getattr(ds_config, "sequence_parallel_params", None)
        if sp:
            from ..parallel.sequence import SequenceParallel
            if mesh is None or sp["axis"] not in mesh.axis_names:
                raise ValueError(
                    f"sequence_parallel needs a mesh with axis "
                    f"{sp['axis']!r}")
            self._attn_fn = SequenceParallel(mesh, axis=sp["axis"],
                                             mode=sp["mode"])
        packing = getattr(ds_config, "packing_params", None)
        if packing:
            self.config = dataclasses.replace(self.config,
                                              use_segment_ids=True)
        qz = getattr(ds_config, "quantization_config", None)
        if qz and qz.get("ffn"):
            f = qz["ffn"]
            if self.config.moe_num_experts and \
                    self.config.moe_dispatch != "sort":
                raise ValueError(
                    "quantization.ffn on an MoE model requires "
                    "moe.dispatch = \"sort\" (the delayed-scaling path "
                    "quantizes the grouped expert matmul; the einsum "
                    "engine's flops sit in the one-hot dispatch tensor)")
            self.config = dataclasses.replace(
                self.config,
                ffn_quant_recipe=f["recipe"],
                ffn_quant_margin=f["margin"],
                ffn_quant_history=f["amax_history_len"])
        sparse = getattr(ds_config, "sparse_attention", None)
        if sparse:
            if packing:
                # also rejected at config parse; kept here for direct
                # apply_ds_config callers
                raise ValueError(
                    "packing + sparse_attention is unsupported: the "
                    "sparse kernels are not segment-aware")
            if sp:
                raise NotImplementedError(
                    "sparse_attention + sequence_parallel is unsupported "
                    "(the sparse engine runs full-sequence layouts)")
            self.config = dataclasses.replace(self.config,
                                              attention_engine="sparse")
            self._sparse_params = dict(sparse)
            self._attn_fn = make_sparse_attention(self.config,
                                                  self._sparse_params)
        self.config.check_block()
        apply_activation_checkpointing_config(self, ds_config, mesh)

    def init_params(self, rng):
        return init_params(self.config, rng)

    def param_specs(self, params, mesh):
        self._refuse_planned_training("param_specs")
        has_mp = MODEL_AXIS in mesh.axis_names and \
            mesh.shape[MODEL_AXIS] > 1
        has_ep = ("expert" in mesh.axis_names
                  and mesh.shape["expert"] > 1
                  and self.config.moe_num_experts > 0)
        if has_mp and self.config.moe_num_experts:
            raise NotImplementedError(
                "tensor parallel + MoE FFN is unsupported; shard experts "
                "over an 'expert' mesh axis")
        if has_mp:
            _require_neox_block(self.config, "tensor parallelism")
            return param_specs(self.config, params)
        if has_ep:
            _require_neox_block(self.config, "expert parallelism")
        specs = jax.tree_util.tree_map(lambda p: P(), params)
        if has_ep:
            # expert dim sharded over the expert axis; XLA inserts the
            # dispatch/combine exchange (GSPMD expert parallelism)
            ep_specs = {"gate": P(), "w_in": P("expert"),
                        "b_in": P("expert"), "w_out": P("expert"),
                        "b_out": P("expert")}
            for b in specs["blocks"]:
                b["mlp"] = ep_specs
        return specs

    def apply(self, params, tokens):
        return forward(self.config, params, tokens,
                       use_pallas=self.use_pallas,
                       remat_blocks=self.remat_blocks,
                       scan_blocks=self.scan_blocks,
                       remat_policy=self.remat_policy,
                       number_checkpoints=self.number_checkpoints)

    def _lm_forward(self, params, batch, rng=None, ffn_amax=None):
        """Shared body of `loss_fn` / `loss_and_logits`: one block-stack
        forward → (final-norm hidden, masked labels, moe aux or None,
        advanced amax state or None)."""
        tokens, labels, seg = split_lm_batch(batch)
        if self.config.use_segment_ids and seg is None:
            raise ValueError(
                "packing is enabled (use_segment_ids) but the batch has "
                "no segment_ids: feed (tokens, labels, segment_ids) "
                "triples (runtime.packing.PackedDataset emits them)")
        if seg is not None:
            # cross-document and pad targets carry no signal: their
            # predictor is a different document's token (or padding) —
            # ignore_index them so packing changes the loss ONLY via
            # removed cross-document attention
            from ..runtime.packing import mask_cross_document_labels
            labels = mask_cross_document_labels(labels, seg)
        hidden = forward_hidden(self.config, params, tokens,
                                use_pallas=self.use_pallas,
                                remat_blocks=self.remat_blocks,
                                rng=rng, attn_fn=self._attention_fn(),
                                scan_blocks=self.scan_blocks,
                                remat_policy=self.remat_policy,
                                number_checkpoints=self.number_checkpoints,
                                boundary_fn=self._ckpt_boundary_fn,
                                segment_ids=seg, ffn_amax=ffn_amax)
        aux = None
        new_amax = None
        if self.config.moe_num_experts and ffn_amax is not None:
            hidden, aux, new_amax = hidden
        elif self.config.moe_num_experts:
            hidden, aux = hidden
        elif ffn_amax is not None:
            hidden, new_amax = hidden
        return hidden, labels, aux, new_amax

    def _head_loss(self, params, hidden, labels, aux):
        out_embed = params.get("embed_out", params["embed"])["wte"]
        loss = fused_lm_head_loss(hidden, out_embed, labels)
        cfg = self.config
        if aux is not None and cfg.moe_dropless:
            # `aux` is the sum over the layers of [f, P] (each [E]):
            # E * sum_e f_e P_e over ALL layers' routed tokens
            f, prob = aux / max(cfg.num_layers, 1)
            loss = loss + cfg.moe_aux_loss_coef * cfg.moe_num_experts * \
                jnp.sum(f * prob)
        elif aux is not None:
            loss = loss + cfg.moe_aux_loss_coef * \
                aux / max(cfg.num_layers, 1)
        return loss

    def loss_fn(self, params, batch, rng=None, ffn_amax=None):
        """Scalar LM loss; with `ffn_amax` (delayed-scaling quantized
        FFN state, [L, 4, H]) the return is (loss, new_ffn_amax) — the
        engine threads the state through `EngineState.quant`."""
        if self.config.layer_plan:
            return self._planned_loss(params, batch)
        hidden, labels, aux, new_amax = self._lm_forward(
            params, batch, rng, ffn_amax=ffn_amax)
        loss = self._head_loss(params, hidden, labels, aux)
        if ffn_amax is not None:
            return loss, new_amax
        return loss

    def _planned_hidden(self, params, tokens):
        """(final-norm hidden states, the nextn block's or None) of a
        planned model."""
        cfg = self.config
        hidden, last = _forward_hidden_planned(
            cfg, params, tokens, self.use_pallas, None, with_last=True)
        if not cfg.mtp_layers:
            return hidden, None
        return hidden, mtp_hidden(cfg, params, tokens, last,
                                  self.use_pallas)

    def _planned_loss(self, params, batch):
        """A planned model's loss, as the model's description and not a
        training path (`initialize` refuses a planned model): next-token
        cross entropy, plus `mtp_loss_weight` times the nextn block's
        (position i against token i + 2). No load-balancing term: the
        sigmoid router's bias is corrected outside the gradient. Only a
        plan of latent layers has every backward it needs (the window and
        grouped-head flash forward has none)."""
        cfg = self.config
        tokens, labels, seg = split_lm_batch(batch)
        if seg is not None or any(s.attn != "latent"
                                  for s in cfg.layer_plan):
            self._refuse_planned_training("loss_fn")
        hidden, mtp = self._planned_hidden(params, tokens)
        head = params["embed_out"]["wte"]
        loss = fused_lm_head_loss(hidden, head, labels)
        if mtp is not None:
            # position i of `mtp` against labels[i + 2]
            loss = loss + cfg.mtp_loss_weight * fused_lm_head_loss(
                mtp[:, :-1], head, labels[:, 1:])
        return loss

    def mtp_logits(self, params, tokens):
        """The next-token-prediction block's logits [B, S - 1, V]:
        position i, from the last layer's hidden state at i and token
        i + 1, predicts token i + 2."""
        if not self.config.mtp_layers:
            raise ValueError("the model has no next-token-prediction "
                             "block (mtp_layers=0)")
        _, mtp = self._planned_hidden(params, tokens)
        return jnp.einsum("bsh,vh->bsv", mtp[:, :-1],
                          params["embed_out"]["wte"].astype(mtp.dtype),
                          preferred_element_type=jnp.float32)

    def init_ffn_amax(self):
        """Zero amax-history state for `loss_fn(..., ffn_amax=)` —
        [num_layers, 4, ffn_quant_history] (quant_matmul layout); None
        when the config has no quantized-FFN recipe."""
        if self.config.ffn_quant_recipe is None:
            return None
        from ..ops.pallas.quant_matmul import init_amax_history
        return init_amax_history(self.config.num_layers,
                                 self.config.ffn_quant_history)

    def loss_and_logits(self, params, batch, rng=None):
        """(loss, [B, S, V] fp32 logits) from ONE forward — what
        `eval_batch(return_logits=True)` compiles, instead of tracing
        the block stack twice for loss and `apply`."""
        hidden, labels, aux, _ = self._lm_forward(params, batch, rng)
        out_embed = params.get("embed_out", params["embed"])["wte"]
        logits = jnp.einsum("bsh,vh->bsv", hidden,
                            out_embed.astype(hidden.dtype),
                            preferred_element_type=jnp.float32)
        return self._head_loss(params, hidden, labels, aux), logits

    def generate(self, params, prompt, max_new_tokens, temperature=0.0,
                 rng=None):
        """KV-cached autoregressive generation (jittable)."""
        return generate(self.config, params, prompt, max_new_tokens,
                        temperature=temperature, rng=rng,
                        use_pallas=self.use_pallas)

    # -- ZeRO-Infinity parameter offload (layer streaming) ----------------

    def stream_plan(self):
        """`StreamPlan` decomposition for the engine's param-offload
        executor (reference `zero/stage3.py:916-935` NVMe param path):
        embed → N uniform blocks (one shared compilation) → LM head. The
        tied embedding appears in both the embed and head segments; the
        stream executor sums their gradients by shared leaf index."""
        from ..runtime.zero.param_offload import StreamPlan

        cfg = self.config
        if cfg.use_segment_ids:
            # the streamed per-segment block forward below does not
            # thread segment_ids; silently ignoring them would attend
            # across documents
            raise NotImplementedError(
                "packing (use_segment_ids) is not supported on the "
                "ZeRO-Infinity param-offload stream path yet")
        use_pallas = self.use_pallas

        def tok_lab(batch):
            if isinstance(batch, (tuple, list)):
                return batch[0], batch[1]
            return batch, batch

        def embed_fwd(sp, carry, batch, rng):
            tokens, _ = tok_lab(batch)
            return sp["wte"][tokens]

        def block_fwd(sp, carry, batch, rng):
            tokens, _ = tok_lab(batch)
            cos_sin = _rotary_cache(cfg, tokens.shape[-1])
            return block_forward(cfg, sp, carry, cos_sin,
                                 use_pallas=use_pallas)

        def head_fwd(sp, carry, batch, rng):
            _, labels = tok_lab(batch)
            x = norm(cfg, sp["final_ln"], carry)
            return fused_lm_head_loss(x, sp["wte"], labels)

        segments = [("embed", lambda p: {"wte": p["embed"]["wte"]})]
        forward = {"embed": embed_fwd, "head": head_fwd}
        kinds = {}
        for i in range(cfg.num_layers):
            name = f"block_{i}"
            segments.append((name, (lambda j: lambda p: p["blocks"][j])(i)))
            forward[name] = block_fwd
            kinds[name] = "block"
        segments.append((
            "head",
            lambda p: {"final_ln": p["final_ln"],
                       "wte": p.get("embed_out", p["embed"])["wte"]}))
        return StreamPlan(segments, forward, kinds)

    # -- layer-activation capture (engine.set_layers_to_hook) ------------

    def layer_names(self):
        return ["embedding"] + \
            ["transformerlayer"] * self.config.num_layers + ["final_ln"]

    def hidden_states(self, params, batch, rng=None):
        """Per-layer outputs for the engine's activation-capture hooks
        (fork: `engine.py:222-254` forward hooks); shares
        `forward_hidden` so the capture can never drift from the real
        forward."""
        tokens, _, seg = split_lm_batch(batch)
        res = forward_hidden(self.config, params, tokens,
                             use_pallas=self.use_pallas,
                             collect_hidden=True,
                             attn_fn=self._attention_fn(),
                             segment_ids=seg)
        return res[-1]

    # -- config-driven pipeline parallelism (the "pipeline" JSON block) --

    def to_pipe_spmd(self, mesh, n_micro, fp32_comm=None, wire_latency=1):
        """Wrap this model for the compiled 1F1B executor (engine calls
        this when the validated "pipeline" block is present): blocks
        stack [L, ...] sharded over the ``pipe`` mesh axis, the loss
        runs the microbatched 1F1B tick loop inside shard_map."""
        from ..parallel.pipeline_spmd import GPTNeoXPipeSPMD
        _require_neox_block(self.config, "the compiled pipeline")
        return GPTNeoXPipeSPMD(self.config, mesh, n_micro,
                               fp32_comm=fp32_comm,
                               use_pallas=self.use_pallas,
                               wire_latency=wire_latency)

    # -- explicit-dataflow ZeRO-3 (zero_optimization.schedule.mode =
    #    "explicit"; parallel/schedule.py) ------------------------------

    def build_explicit_zero3_loss(self, mesh, data_axis, param_specs,
                                  param_padinfo, schedule):
        """Build ``loss_and_grads(params, batch, rng, scale)`` running
        the block stack under the explicit shard_map ZeRO-3 schedule:
        params stay in the engine's stage-3 storage layout (dp-sharded
        at rest), the layer loop issues bucketed all-gathers
        ``schedule.prefetch_depth`` layers ahead of compute, and the
        remat-group backward re-gathers params while the gather
        transposes reduce-scatter each gradient to its owner shard.

        Pure reordering vs the GSPMD stage-3 path: same math modulo
        float reassociation (the loss is the dp-mean of per-rank means —
        the reference's allreduce-of-means — identical to the global
        mean whenever every rank sees the same valid-target count).

        ``param_specs``/``param_padinfo`` are the engine's per-leaf
        PartitionSpecs and FlatPad descriptors for the CURRENT state
        layout, so the shard_map in/out specs can never drift from the
        placement."""
        cfg = self.config
        if getattr(cfg, "moe_num_experts", 0):
            raise NotImplementedError(
                "the explicit ZeRO-3 schedule does not support MoE "
                "blocks yet (aux-loss threading); use the GSPMD "
                "schedule (zero_optimization.schedule.mode \"gspmd\")")
        if cfg.attention_engine == "sparse" or self._attn_fn is not None:
            raise NotImplementedError(
                "the explicit ZeRO-3 schedule runs the dense flash/XLA "
                "attention core; sparse_attention and sequence_parallel "
                "need the GSPMD schedule")
        from ..compat import shard_map
        from ..parallel.schedule import (LayerPlan, gather_leaf,
                                         leaf_placement,
                                         prefetched_block_scan)
        from ..runtime.activation_checkpointing.checkpointing import \
            make_remat_policy

        P_ = P
        world = int(mesh.shape[data_axis])
        use_pallas = self.use_pallas
        depth = schedule.prefetch_depth
        L = cfg.num_layers
        if self.number_checkpoints:
            # the model's segmented-checkpoint knob IS the remat-group
            # geometry here: groups == recompute spans
            group = max(1, -(-L // int(self.number_checkpoints)))
        else:
            group = schedule.group_layers
        policy = None
        # schedule.remat False skips the group checkpoint (no backward
        # re-gather; gathered buffers become residuals) — unless the
        # model itself asked for remat, which wins
        remat = (schedule.remat or self.remat_blocks
                 or self.remat_policy is not None
                 or bool(self.number_checkpoints))
        if self.remat_policy is not None:
            policy, _ = make_remat_policy(self.remat_policy)

        block_specs = param_specs["blocks"][0]
        block_pads = param_padinfo["blocks"][0]
        state = {"plan": None, "outer": None}

        def map_with_specs(fn, tree, spec_tree, pad_tree):
            """tree_map that treats PartitionSpec values as leaves (a
            PartitionSpec is itself a pytree, so a naive tree_map over
            mixed trees mis-aligns)."""
            leaves, tdef = jax.tree_util.tree_flatten(tree)
            specs = jax.tree_util.tree_leaves(
                spec_tree, is_leaf=lambda x: isinstance(x, P))
            pads = jax.tree_util.tree_leaves(pad_tree)
            return tdef.unflatten(
                [fn(l, s, p) for l, s, p in zip(leaves, specs, pads)])

        def get_plan(params):
            if state["plan"] is None:
                state["plan"] = LayerPlan(
                    params["blocks"][0], block_specs, block_pads,
                    data_axis, world, schedule.bucket_bytes)
                outer = {}
                for key in ("embed", "final_ln", "embed_out"):
                    if key not in params:
                        continue
                    outer[key] = map_with_specs(
                        lambda l, s, p: leaf_placement(
                            np.shape(l), np.result_type(l), s, p or None,
                            data_axis, world),
                        params[key], param_specs[key],
                        param_padinfo[key])
                state["outer"] = outer
            return state["plan"], state["outer"]

        def loss_and_grads(params, batch, rng, scale=None, ef=None):
            tokens, labels, seg = split_lm_batch(batch)
            if cfg.use_segment_ids and seg is None:
                raise ValueError(
                    "packing is enabled (use_segment_ids) but the batch "
                    "has no segment_ids")
            plan, outer = get_plan(params)
            if scale is None:
                scale = jnp.asarray(1.0, jnp.float32)

            def body(lp, ef_l, tokens, labels, seg, rng, scale):
                if ef_l is not None:
                    ef_l = ef_l[0]      # [1, L, world, S] local block

                def gathered(sub, placements):
                    return jax.tree_util.tree_map(
                        lambda l, pl: gather_leaf(l, pl, data_axis,
                                                  world),
                        sub, placements,
                        is_leaf=lambda x: hasattr(x, "kind"))

                def local_loss(lp, ef_l):
                    embed_wte = gathered(lp["embed"],
                                         outer["embed"])["wte"]
                    x = embed_wte[tokens]
                    cos, sin, rot_dim = _rotary_cache(cfg,
                                                      tokens.shape[1])
                    lab = labels
                    if seg is not None:
                        from ..runtime.packing import (
                            mask_cross_document_labels,
                            segment_relative_positions)
                        lab = mask_cross_document_labels(labels, seg)
                        if rot_dim:
                            pos = segment_relative_positions(seg)
                            cos, sin = cos[pos], sin[pos]

                    def block_fn(bp, x):
                        return block_forward(
                            cfg, bp, x, (cos, sin, rot_dim),
                            use_pallas=use_pallas, segment_ids=seg)

                    layer_leaves = [
                        jax.tree_util.tree_flatten(bp)[0]
                        for bp in lp["blocks"]]
                    x = prefetched_block_scan(
                        block_fn, x, layer_leaves, plan, L,
                        prefetch_depth=depth, group_layers=group,
                        policy=policy, remat=remat, ef=ef_l)

                    fl = gathered(lp["final_ln"], outer["final_ln"])
                    x = norm(cfg, fl, x)
                    if "embed_out" in lp:
                        head_wte = gathered(lp["embed_out"],
                                            outer["embed_out"])["wte"]
                    else:
                        head_wte = embed_wte
                    loss = fused_lm_head_loss(x, head_wte, lab)
                    return loss * scale.astype(loss.dtype), loss

                # the error-feedback state is a differentiated INPUT:
                # its "gradient" is the advanced error buffer smuggled
                # out of the compressed reduce-scatter's custom_vjp
                # (parallel.schedule.make_ef_gather)
                argnums = (0,) if ef_l is None else (0, 1)
                (_, loss), grads = jax.value_and_grad(
                    local_loss, argnums=argnums, has_aux=True)(lp, ef_l)
                new_ef = None
                if ef_l is not None:
                    grads, new_ef = grads
                    new_ef = new_ef[None]       # restore the dp dim
                else:
                    grads = grads[0]
                # gather transposes delivered each sharded leaf's grad
                # as the rank-SUM reduce-scatter: divide for the dp
                # mean; replicated leaves pmean their per-rank grads
                grads = map_with_specs(
                    lambda g, s, p: g / world
                    if (p or any(a is not None for a in s))
                    else jax.lax.pmean(g, data_axis),
                    grads, param_specs, param_padinfo)
                loss = jax.lax.pmean(loss, data_axis)
                if ef_l is not None:
                    return loss, grads, new_ef
                return loss, grads

            batch_spec = P_(data_axis)
            seg_in = seg if seg is not None else jnp.zeros((), jnp.int32)
            seg_spec = batch_spec if seg is not None else P_()
            if ef is None:
                mapped = shard_map(
                    lambda lp, t, lb, sg, r, sc: body(
                        lp, None, t, lb,
                        sg if seg is not None else None, r, sc),
                    mesh=mesh,
                    in_specs=(param_specs, batch_spec, batch_spec,
                              seg_spec, P_(), P_()),
                    out_specs=(P_(), param_specs),
                    check_vma=False)
                return mapped(params, tokens, labels, seg_in, rng, scale)
            mapped = shard_map(
                lambda lp, e, t, lb, sg, r, sc: body(
                    lp, e, t, lb, sg if seg is not None else None, r,
                    sc),
                mesh=mesh,
                in_specs=(param_specs, P_(data_axis), batch_spec,
                          batch_spec, seg_spec, P_(), P_()),
                out_specs=(P_(), param_specs, P_(data_axis)),
                check_vma=False)
            return mapped(params, ef, tokens, labels, seg_in, rng, scale)

        return loss_and_grads

    # -- tiered parameter/optimizer offload on the explicit schedule
    #    (offload_param + zero_optimization.schedule.mode = "explicit";
    #    runtime/zero/offload_engine.py) -------------------------------

    def build_tiered_offload_step(self, mesh, data_axis, schedule,
                                  host_params):
        """Per-segment jitted programs for the tiered-offload executor:
        embed / block-group / head forward+backward, each a shard_map
        over ``data_axis`` consuming rank-major parameter ROWS (the
        `offload_layer_plan` layout the host store uploads). Inside
        each group program the rows all-gather bucketed and
        ``schedule.prefetch_depth`` layers ahead (`make_group_body` —
        the SAME body the in-jit explicit schedule scans) and the
        backward's gather transposes reduce-scatter each grad row to
        its owner shard. ``host_params`` is the compute-dtype natural
        host tree (template for shapes/dtypes only)."""
        cfg = self.config
        if getattr(cfg, "moe_num_experts", 0):
            raise NotImplementedError(
                "the tiered-offload executor does not support MoE "
                "blocks (aux-loss threading)")
        if cfg.attention_engine == "sparse" or self._attn_fn is not None:
            raise NotImplementedError(
                "the tiered-offload executor runs the dense flash/XLA "
                "attention core; sparse_attention and sequence_parallel "
                "are unsupported")
        if cfg.use_segment_ids:
            raise NotImplementedError(
                "packing (use_segment_ids) is not supported on the "
                "tiered-offload executor yet")
        from ..compat import shard_map
        from ..parallel.schedule import (_segment_sizes, make_group_body,
                                         offload_layer_plan)
        from ..runtime.zero.offload_engine import TieredPrograms

        P_ = P
        world = int(mesh.shape[data_axis])
        depth = schedule.prefetch_depth
        L = cfg.num_layers
        if self.number_checkpoints:
            group = max(1, -(-L // int(self.number_checkpoints)))
        else:
            group = schedule.group_layers
        use_pallas = self.use_pallas
        bucket = schedule.bucket_bytes
        tied = "embed_out" not in host_params

        plans = {
            "embed": offload_layer_plan(
                {"wte": host_params["embed"]["wte"]}, data_axis, world,
                bucket),
            "block": offload_layer_plan(
                host_params["blocks"][0], data_axis, world, bucket),
            "final_ln": offload_layer_plan(
                host_params["final_ln"], data_axis, world, bucket),
            "embed_out": None,
        }
        if not tied:
            plans["embed_out"] = offload_layer_plan(
                {"wte": host_params["embed_out"]["wte"]}, data_axis,
                world, bucket)
        we_plan = plans["embed"] if tied else plans["embed_out"]

        R, RG, B = P_(data_axis), P_(None, data_axis), P_(data_axis)

        def rebuild1(plan, local_row):
            return plan.rebuild(plan.gather_row(local_row), [])

        def smap(f, in_specs, out_specs, donate):
            return jax.jit(
                shard_map(f, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False),
                donate_argnums=donate)

        # --- embed ----------------------------------------------------
        def _embed_fwd(row, tokens):
            return rebuild1(plans["embed"], row)["wte"][tokens]

        embed_fwd = smap(_embed_fwd, (R, B), B, (0,))

        def _embed_grad(row, tokens, dx):
            def f(r):
                return rebuild1(plans["embed"], r)["wte"][tokens]

            _, vjp = jax.vjp(f, row)
            (drow,) = vjp(dx)
            return drow

        embed_grad = smap(_embed_grad, (R, B, B), R, (0, 2))

        # --- block groups ---------------------------------------------
        def group_chain(g):
            def chain(rows, x):
                cos_sin = _rotary_cache(cfg, x.shape[1])

                def block_fn(bp, xx):
                    return block_forward(cfg, bp, xx, cos_sin,
                                         use_pallas=use_pallas)

                body = make_group_body(block_fn, plans["block"], depth)
                return body(x, [rows[j] for j in range(g)],
                            [[] for _ in range(g)])
            return chain

        group_fwd, group_grad = {}, {}
        sizes = _segment_sizes(L, -(-L // max(1, int(group))))
        for g in sorted(set(sizes)):
            chain = group_chain(g)
            group_fwd[g] = smap(chain, (RG, B), B, (0,))

            def _grad(rows, x_in, ct, _chain=chain):
                _, vjp = jax.vjp(_chain, rows, x_in)
                drows, dx = vjp(ct)
                return dx, drows

            group_grad[g] = smap(_grad, (RG, B, B), (B, RG), (0, 1, 2))

        # --- head (final_ln + LM head; tied reuses the embed row) -----
        def head_core(row_ln, row_we, x, labels):
            ln = rebuild1(plans["final_ln"], row_ln)
            wte = rebuild1(we_plan, row_we)["wte"]
            h = norm(cfg, ln, x)
            return fused_lm_head_loss(h, wte, labels)

        def _head_loss(row_ln, row_we, x, labels):
            return jax.lax.pmean(head_core(row_ln, row_we, x, labels),
                                 data_axis)

        head_loss = smap(_head_loss, (R, R, B, B), P_(), (0, 1, 2))

        def _head_grad(row_ln, row_we, x, labels, scale):
            def f(r_ln, r_we, xx):
                loss = head_core(r_ln, r_we, xx, labels)
                return loss * scale.astype(loss.dtype), loss

            scaled, vjp, loss = jax.vjp(f, row_ln, row_we, x,
                                        has_aux=True)
            d_ln, d_we, dx = vjp(jnp.ones((), scaled.dtype))
            return jax.lax.pmean(loss, data_axis), dx, d_ln, d_we

        head_grad = smap(_head_grad, (R, R, B, B, P_()),
                         (P_(), B, R, R), (0, 1, 2))

        def split_batch(batch):
            tokens, labels, _ = split_lm_batch(batch)
            return tokens, labels

        return TieredPrograms(
            plans=plans, group_sizes=sizes, tied=tied,
            embed_fwd=embed_fwd, embed_grad=embed_grad,
            group_fwd=group_fwd, group_grad=group_grad,
            head_loss=head_loss, head_grad=head_grad,
            split_batch=split_batch)


# ---------------------------------------------------------------------------
# autoregressive generation (KV cache; single jitted prefill + scan decode)
# ---------------------------------------------------------------------------

@scopes.scoped("ds.block")
def _block_decode(cfg, bp, x, kv, pos, cos_sin):
    """One block for one new position: `_block_qkv` with the rotary
    slice at `pos`, cached attention over [0, pos], then the shared
    `_block_post_attn`. x [B, 1, H]; kv = (k_cache, v_cache)
    [B, S_max, nh, hd]."""
    B = x.shape[0]
    cos_full, sin_full, rot_dim = cos_sin
    k_cache, v_cache = kv

    cos = jax.lax.dynamic_slice_in_dim(cos_full, pos, 1, 0)
    sin = jax.lax.dynamic_slice_in_dim(sin_full, pos, 1, 0)
    q, k, v = _block_qkv(cfg, bp, x, cos, sin, rot_dim, cfg.num_heads)

    with scopes.scope("ds.kv_write"):
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), pos, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), pos, axis=1)

    S_max = k_cache.shape[1]
    scale = 1.0 / math.sqrt(cfg.head_dim)
    with scopes.scope("ds.attn"), scopes.scope("ds.attn_xla"):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache,
                            preferred_element_type=jnp.float32) * scale
        mask = jnp.arange(S_max)[None, None, None, :] <= pos
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(v_cache.dtype)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v_cache)

    out = _block_post_attn(cfg, bp, x, attn.reshape(B, 1, cfg.hidden_size),
                           reduce_fn=lambda t: t)
    return block_hidden(out), (k_cache, v_cache)   # decode takes no aux


def _prefill(cfg, params, tokens, s_max, use_pallas=True):
    """Run the prompt through the model, filling KV caches sized s_max.
    Returns (last-position hidden [B, 1, H], caches per layer)."""
    B, S_p = tokens.shape
    with scopes.scope("ds.embed"):
        x = params["embed"]["wte"][tokens]
    cos_sin = _rotary_cache(cfg, S_p)
    caches = []
    pad = [(0, 0), (0, s_max - S_p), (0, 0), (0, 0)]
    with scopes.scope("ds.layers"):
        for bp in params["blocks"]:
            x, (k, v) = _block_core(cfg, bp, x, cos_sin, use_pallas, mp=1,
                                    reduce_fn=lambda t: t, return_kv=True)
            x = block_hidden(x)
            with scopes.scope("ds.kv_write"):
                caches.append((jnp.pad(k, pad), jnp.pad(v, pad)))
    return x[:, -1:, :], caches


def generate(cfg, params, prompt, max_new_tokens, temperature=0.0,
             rng=None, use_pallas=True):
    """Greedy / temperature sampling with a KV cache: one jittable
    function — prefill, then `lax.scan` over decode steps (static
    shapes; cache updated in-place via dynamic_update_slice).

    prompt [B, S_p] int32 → generated tokens [B, max_new_tokens].
    """
    B, S_p = prompt.shape
    if cfg.layer_plan:
        raise NotImplementedError(
            f"generate: this cache is the homogeneous block's, one K and "
            f"V a layer, and its step one token under the causal mask; a "
            f"planned model (layer_plan; loop_steps={cfg.loop_steps}: a "
            f"cache a pass; generation_block={cfg.generation_block}: a "
            f"block of tokens a step under the block-causal mask) is "
            f"served by InferenceEngine")
    if max_new_tokens <= 0:
        return jnp.zeros((B, 0), jnp.int32)
    s_max = S_p + max_new_tokens
    if s_max > cfg.max_seq_len:
        raise ValueError(f"prompt + max_new_tokens = {s_max} exceeds "
                         f"max_seq_len {cfg.max_seq_len}")
    if rng is None:
        rng = jax.random.PRNGKey(0)

    hidden, caches = _prefill(cfg, params, prompt, s_max,
                              use_pallas=use_pallas)
    cos_sin = _rotary_cache(cfg, s_max)
    out_embed = params.get("embed_out", params["embed"])["wte"]

    @scopes.scoped("ds.lm_head")
    def logits_of(x):
        h = norm(cfg, params["final_ln"], x)
        return jnp.einsum("bsh,vh->bsv", h, out_embed.astype(h.dtype),
                          preferred_element_type=jnp.float32)[:, 0, :]

    @scopes.scoped("ds.sample")
    def sample(logits, key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / temperature, axis=-1).astype(jnp.int32)

    first_tok = sample(logits_of(hidden), rng)

    def step(carry, key):
        tok, caches, pos = carry
        with scopes.scope("ds.embed"):
            x = params["embed"]["wte"][tok[:, None]]
        new_caches = []
        with scopes.scope("ds.layers"):
            for bp, kv in zip(params["blocks"], caches):
                x, kv = _block_decode(cfg, bp, x, kv, pos, cos_sin)
                new_caches.append(kv)
        nxt = sample(logits_of(x), key)
        return (nxt, new_caches, pos + 1), nxt

    # max_new_tokens - 1 decode steps, each emitting the token it samples;
    # the prefill already produced the first token, so nothing is wasted.
    keys = jax.random.split(jax.random.fold_in(rng, 1),
                            max(max_new_tokens - 1, 0))
    (_, _, _), toks = jax.lax.scan(
        step, (first_tok, caches, jnp.asarray(S_p, jnp.int32)), keys)
    toks = jnp.concatenate([first_tok[None], toks], axis=0)
    return jnp.moveaxis(toks, 0, 1)  # [B, max_new_tokens]


# ---------------------------------------------------------------------------
# pipeline layer factories
# ---------------------------------------------------------------------------

class EmbeddingPipe:
    """Embedding as a pipeline layer: tokens [B,S] → hidden [B,S,H]."""

    def __init__(self, cfg):
        self.cfg = cfg

    def init(self, rng, x):
        return {"wte": _dense_init(rng, (self.cfg.vocab_size,
                                         self.cfg.hidden_size),
                                   self.cfg.param_dtype)}

    def apply(self, params, tokens, rng=None):
        return params["wte"][tokens]


class TransformerBlockPipe:
    """One GPT-NeoX block as a pipeline layer."""

    def __init__(self, cfg, use_pallas=True):
        self.cfg = cfg
        self.use_pallas = use_pallas

    def init(self, rng, x):
        return init_block_params(self.cfg, rng)

    def apply(self, params, x, rng=None):
        cos_sin = _rotary_cache(self.cfg, x.shape[1])
        return block_forward(self.cfg, params, x, cos_sin,
                             use_pallas=self.use_pallas)


class FinalNormPipe:
    def __init__(self, cfg):
        self.cfg = cfg

    def init(self, rng, x):
        return init_norm_params(self.cfg)

    def apply(self, params, x, rng=None):
        return norm(self.cfg, params, x)


class OutputHeadPipe:
    """Hidden → logits; usable as TiedLayerSpec('embed', ...) for tied
    embeddings."""

    def __init__(self, cfg):
        self.cfg = cfg

    def init(self, rng, x):
        return {"wte": _dense_init(rng, (self.cfg.vocab_size,
                                         self.cfg.hidden_size),
                                   self.cfg.param_dtype)}

    def apply(self, params, x, rng=None):
        return jnp.einsum("bsh,vh->bsv", x, params["wte"].astype(x.dtype),
                          preferred_element_type=jnp.float32)


def _tied_logits_helper(module, params, x):
    """forward_fn for the tied output site: the shared embedding table
    used as the LM head (GPT-NeoX's `_logits_helper` pattern — the tied
    module is the EmbeddingPipe, the computation is the projection)."""
    return jnp.einsum("bsh,vh->bsv", x, params["wte"].astype(x.dtype),
                      preferred_element_type=jnp.float32)


def to_layer_specs(cfg, use_pallas=True):
    """LayerSpec list for PipelineModule (reference: GPT-NeoX's pipelined
    model description)."""
    from ..runtime.pipe import LayerSpec, TiedLayerSpec
    if getattr(cfg, "moe_num_experts", 0):
        # block_forward returns (x, aux_loss) under MoE; the pipeline
        # stage functions carry a single hidden buffer between stages
        # and would silently drop (or trace-fail on) the aux loss
        raise NotImplementedError(
            "MoE layers cannot be pipelined yet: the expert aux loss is "
            "not threaded through the inter-stage buffers. Use MoE with "
            "data/tensor/expert parallelism, or pipeline a dense model")
    _require_neox_block(cfg, "the pipeline's layer specs")
    specs = []
    if cfg.tie_word_embeddings:
        specs.append(TiedLayerSpec("embed", EmbeddingPipe, cfg,
                                   tied_weight_attr="wte"))
    else:
        specs.append(LayerSpec(EmbeddingPipe, cfg))
    for _ in range(cfg.num_layers):
        specs.append(LayerSpec(TransformerBlockPipe, cfg, use_pallas))
    specs.append(LayerSpec(FinalNormPipe, cfg))
    if cfg.tie_word_embeddings:
        specs.append(TiedLayerSpec("embed", EmbeddingPipe, cfg,
                                   forward_fn=_tied_logits_helper,
                                   tied_weight_attr="wte"))
    else:
        specs.append(LayerSpec(OutputHeadPipe, cfg))
    return specs
