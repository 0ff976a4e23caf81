"""Benchmark: training throughput on the attached TPU chip(s).

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Headline metric is tokens/sec/chip for a bf16 GPT-NeoX-125M training step
(ZeRO-2); ``vs_baseline`` is MFU / 0.40 — the BASELINE.md north-star is
≥40% MFU, so ≥1.0 means target hit.

Process model: a chip belongs to one process at a time. The parent never
touches jax; the headline and every row run one after another, each in
its OWN child process (`--row NAME`), which holds the chip while it runs
and gives it back when it exits — so an OOM'd row cannot poison the next
one's HBM either. ``extra`` carries the rows' results; rows degrade
through a config ladder (smaller batch, more remat, offload tiers). A
row that ends in an error — its child died, timed out, or every rung of
its ladder failed — is recorded under a ``*_error`` key AND makes the
exit code non-zero. The persistent compile cache lives where
`deeperspeed_tpu.utils.compile_cache` puts it. DS_BENCH_ROWS selects a
comma list of row KEYS (default all):
  - zero3    (GPT-NeoX-125M, ZeRO-3)
  - bert128 / bert512  (BERT-Large: masked + fused in-kernel attention
             dropout — the reference's flagship single-device workload,
             docs/_tutorials/bert-pretraining.md)
  - gpt2xl   (gpt2_xl_1p5b: Megatron-GPT2 48L/1600H ladder rung, ZeRO-3
             + CPU-offload optimizer tier; reference
             tests/model/Megatron_GPT2)
  - longseq  (longseq_16k: 16k-token causal flash row)
  - moe      (moe_top2: GShard top-2 MoE row; walks the einsum and
             sort dispatch engines — DS_BENCH_MOE_DISPATCH narrows)
  - ckpt     (checkpoint-induced step stall, sync vs async
             snapshot-then-commit save; opt-in via DS_BENCH_CKPT=1 —
             disk-heavy)
  - sentinel (training-health sentinel detection overhead + injected-
             fault recovery latency; opt-in via DS_BENCH_SENTINEL=1)
  - telemetry (unified-telemetry scalars-on overhead + in-engine MFU
             vs analytic MFU cross-check; opt-in via DS_BENCH_TELEMETRY=1)
  - packed   (packed ragged-batch row: fixed-seed lognormal doc mixture
             packed into 16k rows, segment-aware kernels vs the same
             shapes without segments; opt-in via DS_BENCH_PACKED=1)
  - serve    (continuous-batching serving row: fixed-seed open-loop
             request stream through the InferenceEngine's paged KV
             cache; generated tokens/s/chip + p50/p99 per-token latency
             + zero-recompile check; opt-in via DS_BENCH_SERVE=1)
  - serve_chaos (serving-under-failure row: the serve stream run clean
             and again under a scripted fault storm — injected decode
             errors, a decode stall, page-pool pressure — against a
             bounded admission queue; success rate, shed fraction, p99
             TTFT degradation storm-vs-clean, and the chaos invariants
             (server up, zero leaked pages, zero post-warmup
             recompiles); opt-in via DS_BENCH_SERVE_CHAOS=1)
  - serve_prefix (prefix-cache + speculative-decode serving row: a
             bursty 80%-shared-prefix stream run cache-off, then with
             the prefix registry + a small draft model after a
             two-stream warmup; prefix hit rate, effective prefill
             tok/s vs cache-off, spec acceptance rate, p50 inter-token
             speedup, steady-state compile delta (must be 0); opt-in
             via DS_BENCH_SERVE_PREFIX=1)
  - serve_disagg (disaggregated prefill/decode serving row: the bursty
             80%-shared-prefix stream run unified vs a prefill-pool +
             decode-pool split over the in-memory handoff transport;
             tokens/s for both layouts, decode-side p50/p99 inter-token
             latency under the prefill bursts, handoff round-trip p50
             ms, post-warmup compile delta over both pools (must be 0);
             opt-in via DS_BENCH_SERVE_DISAGG=1)
  - elastic  (supervised-restart recovery: a hard mid-run kill under the
             elasticity supervisor — kill -> resumed-step wall clock
             (MTTR) and steps lost vs the committed checkpoint; opt-in
             via DS_BENCH_ELASTIC=1)
  - pipe     (config-driven 1F1B pipeline rows: NeoX-125M over 2/4
             stages x remaining-chips ZeRO-1 data parallel, classic and
             comm-overlap wire schedules, analytic bubble fraction +
             zero-recompile check; opt-in via DS_BENCH_PIPE=1)
  - offload  (tiered-offload rows: the explicit schedule on-chip vs
             host-DRAM rows vs NVMe rows (DS_BENCH_OFFLOAD_NVME=path)
             with step time / prefetch-stall fraction / h2d+d2h wire
             volume, plus a DS_BENCH_OFFLOAD_RATIO x-HBM synthetic rung
             trained on the host tier vs the flops-extrapolated on-chip
             time; opt-in via DS_BENCH_OFFLOAD=1)
  - quant    (low-precision rows: bf16 vs int8-weight decode tokens/s +
             p50 inter-token on a decode-heavy serve stream, int8-KV
             resident-session capacity at fixed pool bytes (scale pools
             included), compressed vs dense cross-host DP-grad step
             time on the explicit ZeRO-3 schedule; knobs in
             quant_knobs; opt-in via DS_BENCH_QUANT=1)
  - plan     (schedule-planner row: build_plan's planner-chosen config
             vs the hand-default explicit schedule on the 125M zero3
             ladder, plan fingerprint + chosen label in extra; opt-in
             via DS_BENCH_PLAN=1)
  - rl       (online-RL row: the co-located train+serve PPO loop on a
             CPU-proxy NeoX — rollout tokens/s under the
             continuous-batching scheduler, update-step ms, train->serve
             hot-swap latency, the zero-recompile pin (compile delta 0
             after warmup), and the co-residency tax: the same
             pretraining step timed alone vs with the RL pair resident
             (<=10% degradation target); opt-in via DS_BENCH_RL=1)
  - multislice (two-slice DCN drill on a CPU-drivable proxy: 1F1B split
             across a simulated slice boundary with dcn_delay charged
             per exposed crossing — classic vs comm-overlap wire
             throughput ratio vs single-slice, the overlap wire holding
             the <=10%-loss bar — plus a scripted slice_kill: detection
             -> emergency checkpoint -> in-process re-partition MTTR,
             zero survivor restarts, loss-trajectory alignment vs an
             unfaulted reference; opt-in via DS_BENCH_MULTISLICE=1)

The zero3 row additionally measures `zero3_explicit` — the explicit
shard_map collective schedule (layer-ahead bucketed all-gather prefetch,
reduce-scatter at layer-backward boundaries) vs the GSPMD path, with
prefetch depth / bucket MB / group size in extra
(DS_BENCH_ZERO3_PREFETCH / _BUCKET_MB / _GROUP).
"""

import gc
import json
import os
import resource
import signal
import subprocess
import sys
import time

import numpy as np

# the first child measures the headline; ROW_ORDER's rows follow it
HEADLINE = "headline"

ROW_ORDER = ["zero3", "bert128", "bert512", "gpt2xl", "longseq", "moe"]
ROW_TIMEOUT = {"gpt2xl": 1100, "longseq": 1100, "ckpt": 600,
               "sentinel": 600, "telemetry": 600, "packed": 800,
               "moe": 800, "serve": 800, "serve_chaos": 900,
               "serve_prefix": 900, "serve_disagg": 900,
               "zero3": 800, "pipe": 900, "offload": 1100,
               "elastic": 600, "fleet": 600,
               "quant": 1100,  # moe/longseq/quant walk both engines
               "plan": 1100,  # two full 125m variants (race both ways)
               "rl": 900,
               "multislice": 900}

ROW_TIMEOUT_DEFAULT = 420


def peak_flops_per_chip(device):
    """bf16 peak TFLOPS by TPU generation — the table lives in
    `deeperspeed_tpu.profiling.hardware` (shared with the in-engine
    telemetry MFU, so bench and live scalars can never disagree)."""
    from deeperspeed_tpu.profiling.hardware import \
        peak_flops_per_chip as _peak
    return _peak(device)


def force(tree):
    """Fence: wait until the device has finished computing `tree`."""
    import jax
    jax.block_until_ready(tree)


def timed_steps(engine, batch, steps, warmup):
    for _ in range(warmup):
        loss = engine.train_batch(batch=batch)
    force(engine.state.params)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch=batch)
    force(engine.state.params)
    return time.perf_counter() - t0, float(loss)


def _setup_jax():
    """Called by a row child only (the parent stays off jax). Re-runs
    and ladder retries skip the per-program XLA compile through the
    persistent cache."""
    import jax

    from deeperspeed_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    return jax


def _ladder(rungs, out, name):
    """Try configs in order until one produces numbers. Each rung is
    (tag, thunk) with thunk() -> dict of extra keys. Failures are
    recorded per-rung; the first success also records which rung ran."""
    errs = []
    for tag, thunk in rungs:
        try:
            res = thunk()
            out.update(res)
            out[f"{name}_config"] = tag
            if errs:
                out[f"{name}_degraded_from"] = "; ".join(errs)[:300]
            return out
        except Exception as e:  # noqa: BLE001 - degrade, don't die
            errs.append(f"{tag}: {type(e).__name__}: {e}"[:160])
            # drop the traceback before gc: its frames pin the dead
            # engine (and its HBM buffers) — the round-4 cascade
            e.__traceback__ = None
            del e
            gc.collect()
    out[f"{name}_error"] = " | ".join(errs)[:400]
    return out


# ---------------------------------------------------------------------------
# rows (each runs in its own subprocess)
# ---------------------------------------------------------------------------

def _neox_engine(model, params, batch, zero_cfg, extra_cfg=None):
    import deeperspeed_tpu
    config_params = {
        "train_batch_size": batch,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10_000,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "fp16": {"enabled": True, "type": "bfloat16"},
        "zero_optimization": zero_cfg,
    }
    if extra_cfg:
        config_params.update(extra_cfg)
    eng, *_ = deeperspeed_tpu.initialize(
        model=model,
        model_parameters=params,
        config_params=config_params)
    return eng


def _headline_setup(jax):
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
    cfg = GPTNeoXConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, max_seq_len=1024)
    model = GPTNeoX(cfg, use_pallas=True)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


def _flops_per_token(cfg, seq):
    return 6 * cfg.num_params() + 12 * cfg.num_layers * cfg.hidden_size * seq


def row_zero3():
    """ZeRO-3 row: the GSPMD path (XLA schedules the param gathers) AND
    the explicit shard_map schedule (zero_optimization.schedule.mode
    "explicit": bucketed all-gathers prefetched DS_BENCH_ZERO3_PREFETCH
    layers ahead, reduce-scatters at layer-backward boundaries) — the
    head-to-head on the zero3-vs-ddp gap (not measured in this round).
    Prefetch depth / bucket MB / remat-group size ride in extra."""
    jax = _setup_jax()
    n_chips = len(jax.devices())
    peak = peak_flops_per_chip(jax.devices()[0])
    cfg, model, params = _headline_setup(jax)
    seq = min(int(os.environ.get("DS_BENCH_SEQ", "1024")),
              cfg.max_seq_len)
    prefetch = int(os.environ.get("DS_BENCH_ZERO3_PREFETCH", "2"))
    bucket_mb = float(os.environ.get("DS_BENCH_ZERO3_BUCKET_MB", "32"))
    group = int(os.environ.get("DS_BENCH_ZERO3_GROUP", "4"))
    # remat off by default: the ddp/gspmd rows this one races do not
    # remat either — apples to apples (the 125M fits with the gathered
    # buffers resident; flip on for memory-bound shapes)
    remat = os.environ.get("DS_BENCH_ZERO3_REMAT", "0") not in (
        "0", "", "false")
    bs_ladder = [int(b) for b in os.environ.get(
        "DS_BENCH_ZERO3_BS", "48,32").split(",")]

    def run(bs, explicit):
        def thunk():
            batch = bs * n_chips
            rng = np.random.default_rng(0)
            tokens = rng.integers(0, cfg.vocab_size, size=(1, batch, seq),
                                  dtype=np.int32)
            zero_cfg = {"stage": 3}
            tag = "zero3"
            if explicit:
                tag = "zero3_explicit"
                zero_cfg["schedule"] = {
                    "mode": "explicit", "prefetch_depth": prefetch,
                    "bucket_mb": bucket_mb, "group_layers": group,
                    "remat": remat}
            eng = _neox_engine(model, params, batch, zero_cfg)
            steps = 12
            dt, _ = timed_steps(eng, (tokens, tokens), steps=steps,
                                warmup=4)
            tps = batch * seq * steps / dt / n_chips
            out = {f"{tag}_tokens_per_sec_chip": round(tps, 1),
                   f"{tag}_mfu": round(
                       tps * _flops_per_token(cfg, seq) / peak, 4)}
            if explicit:
                out["zero3_explicit_prefetch_depth"] = prefetch
                out["zero3_explicit_bucket_mb"] = bucket_mb
                out["zero3_explicit_group_layers"] = group
                out["zero3_explicit_remat"] = remat
            return out
        return thunk

    out = _ladder([(f"bs{b}", run(b, False)) for b in bs_ladder],
                  {}, "zero3")
    gc.collect()
    return _ladder([(f"bs{b}", run(b, True)) for b in bs_ladder],
                   out, "zero3_explicit")


# The hand-tuned explicit schedule the planner races against — the
# zero3 row's defaults (and the planner's own tie-break anchor).
HAND_DEFAULT_SCHEDULE = {"mode": "explicit", "prefetch_depth": 2,
                         "bucket_mb": 32.0, "group_layers": 4,
                         "remat": False}


def row_plan():
    """Schedule-planner row (opt-in via DS_BENCH_PLAN=1): `build_plan`
    resolves a schedule for the headline 125M shape (analytic cost
    model + memory screen; the measured probe ladder engages only where
    the kernel autotuners would probe too), then the planner-chosen
    config races the hand-default explicit schedule (prefetch 2 /
    bucket 32 MB / group 4 / no remat) on the zero3 bs ladder.
    Acceptance: plan_vs_hand_default >= 1.0."""
    jax = _setup_jax()
    n_chips = len(jax.devices())
    peak = peak_flops_per_chip(jax.devices()[0])
    cfg, model, params = _headline_setup(jax)
    seq = min(int(os.environ.get("DS_BENCH_SEQ", "1024")),
              cfg.max_seq_len)
    bs_ladder = [int(b) for b in os.environ.get(
        "DS_BENCH_ZERO3_BS", "48,32").split(",")]
    # CPU-proxy knob: 125M steps are seconds on TPU but ~30s each on a
    # 1-core host — shrink the timing window without changing the race
    steps = int(os.environ.get("DS_BENCH_PLAN_STEPS", "12"))
    warmup = max(1, min(4, steps // 3))

    from deeperspeed_tpu.planner import build_plan
    from deeperspeed_tpu.planner.cost_model import ModelShape
    shape = ModelShape(num_layers=cfg.num_layers,
                       hidden_size=cfg.hidden_size,
                       num_heads=cfg.num_heads, seq_len=seq,
                       vocab_size=cfg.vocab_size,
                       batch_per_chip=bs_ladder[0])
    # force=True, save=False: the bench must exercise a fresh plan of
    # THIS run's shape, not whatever a previous session cached
    plan = build_plan(shape, force=True, save=False)
    plan_cfg = plan.config

    def run(bs, planned):
        def thunk():
            batch = bs * n_chips
            rng = np.random.default_rng(0)
            tokens = rng.integers(0, cfg.vocab_size, size=(1, batch, seq),
                                  dtype=np.int32)
            if planned:
                tag = "plan_chosen"
                zero_cfg = dict(plan_cfg["zero_optimization"])
                extra_cfg = {k: v for k, v in plan_cfg.items()
                             if k != "zero_optimization"}
            else:
                tag = "plan_hand_default"
                zero_cfg = {"stage": 3,
                            "schedule": dict(HAND_DEFAULT_SCHEDULE)}
                extra_cfg = None
            eng = _neox_engine(model, params, batch, zero_cfg, extra_cfg)
            dt, _ = timed_steps(eng, (tokens, tokens), steps=steps,
                                warmup=warmup)
            tps = batch * seq * steps / dt / n_chips
            return {f"{tag}_tokens_per_sec_chip": round(tps, 1),
                    f"{tag}_mfu": round(
                        tps * _flops_per_token(cfg, seq) / peak, 4)}
        return thunk

    out = {"plan_fingerprint": plan.fingerprint,
           "plan_chosen_label": plan.payload["chosen"],
           "plan_probed": plan.payload["probed"]}
    out = _ladder([(f"bs{b}", run(b, True)) for b in bs_ladder],
                  out, "plan_chosen")
    # When analytic ties resolve to the hand-tuned defaults (world=1:
    # every collective term is zero), the two race legs are the same
    # program — report the identity instead of timing the same config
    # twice and publishing scheduler noise as a ratio.
    plan_zero = plan_cfg["zero_optimization"]
    matches_hand = (plan_zero.get("schedule") == HAND_DEFAULT_SCHEDULE
                    and "offload_optimizer" not in plan_zero
                    and "quantization" not in plan_cfg)
    out["plan_matches_hand_default"] = matches_hand
    if matches_hand:
        out["plan_vs_hand_default"] = 1.0
        return out
    gc.collect()
    out = _ladder([(f"bs{b}", run(b, False)) for b in bs_ladder],
                  out, "plan_hand_default")
    chosen_tps = out.get("plan_chosen_tokens_per_sec_chip")
    hand_tps = out.get("plan_hand_default_tokens_per_sec_chip")
    if chosen_tps and hand_tps:
        out["plan_vs_hand_default"] = round(chosen_tps / hand_tps, 3)
    return out


def row_pipe():
    """Config-driven 1F1B pipeline rows (opt-in via DS_BENCH_PIPE=1):
    NeoX-125M over 2/4 pipeline stages (DS_BENCH_PIPE_STAGES), the
    remaining chips data-parallel with ZeRO-1, micro_batches =
    DS_BENCH_PIPE_MICRO. Reports tokens/s/chip (all chips, stages
    included), the analytic bubble fraction for the schedule, and a
    zero-recompile check across the measured steps. DS_BENCH_PIPE_OVERLAP
    = 1 also measures the comm_overlap (wire-latency-2) schedule."""
    jax = _setup_jax()
    from deeperspeed_tpu.parallel.schedule import bubble_fraction
    n_chips = len(jax.devices())
    peak = peak_flops_per_chip(jax.devices()[0])
    cfg, model, params = _headline_setup(jax)
    seq = min(int(os.environ.get("DS_BENCH_SEQ", "1024")),
              cfg.max_seq_len)
    n_micro = int(os.environ.get("DS_BENCH_PIPE_MICRO", "8"))
    both_wires = os.environ.get("DS_BENCH_PIPE_OVERLAP", "1") not in (
        "0", "", "false")
    bs0 = int(os.environ.get("DS_BENCH_PIPE_BS", "48"))
    stages_sel = [int(s) for s in os.environ.get(
        "DS_BENCH_PIPE_STAGES", "2,4").split(",")]

    out = {}
    for stages in stages_sel:
        name = f"pipe{stages}"
        if n_chips % stages or cfg.num_layers % stages:
            out[f"{name}_error"] = (
                f"stages={stages} does not divide chips={n_chips} / "
                f"layers={cfg.num_layers}")
            continue
        dp = n_chips // stages
        for overlap in ([False, True] if both_wires else [False]):
            tag = f"{name}_overlap" if overlap else name

            def run(bs, stages=stages, overlap=overlap, dp=dp, tag=tag):
                def thunk():
                    bs_rank = max(n_micro, bs - bs % n_micro)
                    batch = bs_rank * dp
                    rng = np.random.default_rng(0)
                    tokens = rng.integers(0, cfg.vocab_size,
                                          size=(1, batch, seq),
                                          dtype=np.int32)
                    eng = _neox_engine(
                        model, params, batch, {"stage": 1},
                        {"pipeline": {"stages": stages,
                                      "micro_batches": n_micro,
                                      "comm_overlap": overlap}})
                    steps = 10
                    dt, _ = timed_steps(eng, (tokens, tokens),
                                        steps=steps, warmup=3)
                    compiled_before = len(eng._compiled_train)
                    eng.train_batch(batch=(tokens, tokens))
                    recompiles = len(eng._compiled_train) - \
                        compiled_before
                    tps = batch * seq * steps / dt / n_chips
                    w = 2 if overlap else 1
                    return {
                        f"{tag}_tokens_per_sec_chip": round(tps, 1),
                        f"{tag}_mfu": round(
                            tps * _flops_per_token(cfg, seq) / peak, 4),
                        f"{tag}_bubble_fraction": round(
                            bubble_fraction(stages, n_micro, w), 4),
                        f"{tag}_n_micro": n_micro,
                        f"{tag}_recompiles": recompiles,
                    }
                return thunk

            _ladder([("bs%d" % bs0, run(bs0)),
                     ("bs%d" % max(bs0 // 2, n_micro),
                      run(max(bs0 // 2, n_micro)))], out, tag)
            gc.collect()
    return out


def _bert_row(seq_len, bs_ladder):
    jax = _setup_jax()
    n_chips = len(jax.devices())
    peak = peak_flops_per_chip(jax.devices()[0])
    import deeperspeed_tpu
    from deeperspeed_tpu.models.bert import BertConfig, BertForPreTraining
    bcfg = BertConfig.large(max_position_embeddings=max(512, seq_len))
    bmodel = BertForPreTraining(bcfg)
    bparams = bmodel.init_params(jax.random.PRNGKey(1))
    name = f"bert_large_seq{seq_len}"

    def run(bs_per_chip):
        def thunk():
            bs = bs_per_chip * n_chips
            eng, *_ = deeperspeed_tpu.initialize(
                model=bmodel, model_parameters=bparams,
                config_params={
                    "train_batch_size": bs,
                    "steps_per_print": 10_000,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                    "fp16": {"enabled": True, "type": "bfloat16"},
                    "zero_optimization": {"stage": 2},
                })
            r = np.random.default_rng(2)
            ids = r.integers(0, bcfg.vocab_size, (1, bs, seq_len), np.int32)
            mask = np.ones((1, bs, seq_len), np.float32)
            labels = np.where(r.random((1, bs, seq_len)) < 0.15, ids,
                              -1).astype(np.int32)
            b = {"input_ids": ids,
                 "token_type_ids": np.zeros_like(ids),
                 "attention_mask": mask,
                 "masked_lm_labels": labels,
                 "next_sentence_label": r.integers(0, 2, (1, bs), np.int32)}
            steps = 10
            dt, _ = timed_steps(eng, b, steps=steps, warmup=3)
            tps = bs * seq_len * steps / dt / n_chips
            H, L, V = bcfg.hidden_size, bcfg.num_layers, bcfg.vocab_size
            # matmul params: 12H^2/layer (qkv+out+ffn@4H) + MLM transform
            # + tied decoder; attention term 12*L*H*S (qk+pv, fwd+bwd)
            ftok = 6 * (L * 12 * H * H + H * H + H * V) + \
                12 * L * H * seq_len
            return {f"{name}_tokens_per_sec_chip": round(tps, 1),
                    f"{name}_mfu": round(tps * ftok / peak, 4),
                    f"{name}_batch_per_chip": bs_per_chip}
        return thunk

    env_bs = os.environ.get(f"DS_BENCH_BERT_BS{seq_len}")
    if env_bs:
        bs_ladder = [int(env_bs)] + [b for b in bs_ladder
                                     if b < int(env_bs)]
    return _ladder([(f"bs{b}", run(b)) for b in bs_ladder], {}, name)


def row_bert128():
    return _bert_row(128, [64, 48, 32])


def row_bert512():
    return _bert_row(512, [20, 16, 12, 8])


def _xl_prescreen(jax, xcfg, policy, nckpt, bs):
    """(fits, stats) for one (remat policy × batch) rung: AOT-compile the
    bf16 grad program over abstract shapes (`memory_analysis()`, no HBM
    touched) and add the resident optimizer state the program doesn't
    see (lean state: params-as-masters + 2 bf16 Adam moments)."""
    import jax.numpy as jnp
    from deeperspeed_tpu.models.gpt2 import GPT2
    from deeperspeed_tpu.ops.autotune import memory_feasible
    model = GPT2(xcfg, use_pallas=True, scan_blocks=True,
                 remat_policy=policy, number_checkpoints=nckpt)
    pshapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    pshapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), pshapes)
    toks = jax.ShapeDtypeStruct((bs, 1024), jnp.int32)

    def grad_step(p, t):
        return jax.grad(lambda q: model.loss_fn(q, (t, t)))(p)

    moments = 2 * xcfg.num_params() * 2  # 2 bf16 moments rest in HBM
    return memory_feasible(grad_step, (pshapes, toks),
                           extra_bytes=moments)


def row_gpt2xl():
    jax = _setup_jax()
    n_chips = len(jax.devices())
    peak = peak_flops_per_chip(jax.devices()[0])
    import deeperspeed_tpu
    from deeperspeed_tpu.models.gpt2 import GPT2, GPT2Config
    xcfg = GPT2Config.megatron_1_5b()

    def run(bs_per_chip, zero_cfg, steps=2, warmup=1, lean_state=False,
            remat_policy=None, number_checkpoints=None):
        def thunk():
            # scan_blocks: one compiled block body instead of 48 —
            # the unrolled 48-layer remat program took ~20 min of XLA
            # compile; the scanned one compiles in normal time
            xmodel = GPT2(xcfg, use_pallas=True,
                          remat_blocks=remat_policy is None,
                          scan_blocks=True, remat_policy=remat_policy,
                          number_checkpoints=number_checkpoints)
            # init on the HOST cpu backend: the host-offload tier reads
            # fp32 masters host-side anyway — initializing on the chip
            # would round-trip 6.2 GB back over the host link and
            # transiently double fp32 HBM
            with jax.default_device(jax.local_devices(backend="cpu")[0]):
                xparams = xmodel.init_params(jax.random.PRNGKey(3))
            xparams = jax.tree_util.tree_map(np.asarray, xparams)
            bs = bs_per_chip * n_chips
            fp16_cfg = {"enabled": True, "type": "bfloat16"}
            opt_params = {"lr": 1e-4}
            if lean_state:
                # all-on-chip 1.5B: params-as-masters + bf16 moments
                # (~12.4 GB state vs 24.9 GB classic). The host link of
                # the machine builders have now has not been measured;
                # the ZeRO-Offload rung stays last in the ladder
                fp16_cfg["fp16_master_weights_and_grads"] = True
                opt_params["state_dtype"] = "bfloat16"
            eng, *_ = deeperspeed_tpu.initialize(
                model=xmodel, model_parameters=xparams,
                config_params={
                    "train_batch_size": bs,
                    "steps_per_print": 10_000,
                    "optimizer": {"type": "Adam", "params": opt_params},
                    "fp16": fp16_cfg,
                    "zero_optimization": zero_cfg,
                })
            del xparams
            gc.collect()
            r = np.random.default_rng(4)
            xtok = r.integers(0, xcfg.vocab_size, (1, bs, 1024), np.int32)
            dt, xl_loss = timed_steps(eng, (xtok, xtok), steps=steps,
                                      warmup=warmup)
            tps = bs * 1024 * steps / dt / n_chips
            xn = xcfg.num_params()
            xftok = 6 * xn + 12 * xcfg.num_layers * xcfg.hidden_size * 1024
            return {
                "gpt2_xl_1p5b_tokens_per_sec_chip": round(tps, 1),
                "gpt2_xl_1p5b_mfu": round(tps * xftok / peak, 4),
                "gpt2_xl_1p5b_params_b": round(xn / 1e9, 3),
                "gpt2_xl_1p5b_loss": xl_loss,
                "gpt2_xl_1p5b_batch_per_chip": bs_per_chip,
                # remat attribution: BENCH_*.json trajectories must say
                # WHICH policy/batch produced an MFU move
                "gpt2_xl_1p5b_remat_policy": remat_policy or "full",
                "gpt2_xl_1p5b_number_checkpoints": number_checkpoints,
                "gpt2_xl_1p5b_peak_rss_gb": round(
                    resource.getrusage(resource.RUSAGE_SELF).ru_maxrss /
                    1e6, 2),
            }
        return thunk

    # ------------------------------------------------------------------
    # (remat policy × batch) ladder, memory-screened: richer policies
    # (save more, recompute less) at the largest batch that FITS, walked
    # fattest-first; `memory_analysis()` on the AOT-compiled grad program
    # rejects infeasible rungs before any timed run. The legacy
    # full-remat bs4 rung stays as the floor, the ZeRO-Offload tier as
    # the final fallback.
    # ------------------------------------------------------------------
    bs0 = int(os.environ.get("DS_BENCH_XL_BS", "8"))
    # descending from bs0 (the env cap), never below the bs4 floor rung
    bs_ladder = [b for b in dict.fromkeys((bs0, 6, 4)) if b <= bs0]
    policies = [p.strip() for p in os.environ.get(
        "DS_BENCH_XL_POLICIES", "dots,attn_residuals,full").split(",")
        if p.strip()]
    nckpt_env = os.environ.get("DS_BENCH_XL_NCKPT")
    nckpt = int(nckpt_env) if nckpt_env and int(nckpt_env) > 0 else None

    ladder, screened_out, screen_errors = [], [], []
    # screening needs a known HBM budget; off-TPU the AOT compile would
    # burn minutes to learn nothing (hbm_bytes_limit() is None there)
    screen = os.environ.get("DS_BENCH_XL_SCREEN", "1") not in (
        "0", "false", "") and jax.devices()[0].platform == "tpu"
    for bs in bs_ladder:
        for pol in policies:
            if pol == "full" and bs == 4 and nckpt is None:
                continue  # that's exactly the floor rung below
            tag = f"onchip_{pol}_bs{bs}" + \
                (f"_k{nckpt}" if nckpt else "")
            if screen:
                try:
                    fits, stats = _xl_prescreen(jax, xcfg, pol, nckpt, bs)
                except Exception as e:  # noqa: BLE001 - screen, don't die
                    # the rung still RUNS (screening must never lose a
                    # viable config); record the screen failure apart
                    # from the genuinely excluded rungs
                    fits, stats = True, None
                    screen_errors.append(
                        f"{tag}: {type(e).__name__}")
                if not fits:
                    screened_out.append(
                        f"{tag}: peak {round(stats['peak'] / 2**30, 1)} "
                        "GiB over budget")
                    continue
            ladder.append((tag, run(bs, {"stage": 0}, steps=3, warmup=2,
                                    lean_state=True, remat_policy=pol,
                                    number_checkpoints=nckpt)))
    # floor: the pre-policy configuration (whole-block remat, bs4)
    ladder.append(("onchip_lean_bs4", run(4, {"stage": 0}, steps=3,
                                          warmup=2, lean_state=True)))
    # ZeRO-Offload rung last: the reference path (13B-on-one-GPU tier),
    # viable where the host link is fast (not measured in this round)
    host_opt = {"stage": 3, "offload_optimizer": {"device": "cpu"}}
    ladder.append(("z3_hostopt_bs2", run(2, host_opt)))
    out = {}
    if screened_out:
        out["gpt2_xl_1p5b_screened_out"] = "; ".join(screened_out)[:400]
    if screen_errors:
        out["gpt2_xl_1p5b_screen_errors"] = "; ".join(screen_errors)[:300]
    return _ladder(ladder, out, "gpt2_xl_1p5b")


def _flash_block_extra(tag):
    """Record the flash dispatch geometry the LAST trace actually chose
    (fwd and bwd blocks + grid variant) so a bench round documents WHICH
    kernel configuration produced its numbers — read through the public
    `ops.dispatch_report()` accessor (the same record the telemetry
    capture exports and fleet trace metadata embed)."""
    from deeperspeed_tpu.ops import dispatch_report
    flash = dispatch_report()["flash"]
    out = {}
    fwd, bwd = flash.get("fwd"), flash.get("dkv")
    if fwd:
        out[f"{tag}_fwd_blocks"] = f"{fwd[0]}x{fwd[1]}"
        out[f"{tag}_fwd_grid"] = flash.get("fwd_variant", "?")
    if bwd:
        out[f"{tag}_bwd_blocks"] = f"{bwd[0]}x{bwd[1]}"
        out[f"{tag}_bwd_grid"] = flash.get("bwd_variant", "?")
    return out


def row_longseq():
    jax = _setup_jax()
    n_chips = len(jax.devices())
    peak = peak_flops_per_chip(jax.devices()[0])
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    def run(seq, bs_per_chip, engine="dense"):
        def thunk():
            lcfg = GPTNeoXConfig(vocab_size=8192, hidden_size=768,
                                 num_layers=12, num_heads=12,
                                 max_seq_len=seq)
            lmodel = GPTNeoX(lcfg, use_pallas=True, remat_blocks=True)
            lparams = lmodel.init_params(jax.random.PRNGKey(5))
            lbs = bs_per_chip * n_chips
            extra_cfg = None
            if engine == "sparse":
                # local+global fixed pattern à la the reference's
                # SparseSelfAttention: 2k-token local window + one
                # global block per window, causal. Density ~10% at 16k,
                # ~5% at 32k — well under the sparse-kernel crossover.
                extra_cfg = {"sparse_attention": {
                    "mode": "fixed", "block": 128,
                    "num_local_blocks": 16, "num_global_blocks": 1,
                    "attention": "unidirectional"}}
            eng = _neox_engine(lmodel, lparams, lbs, {"stage": 2},
                               extra_cfg=extra_cfg)
            r = np.random.default_rng(6)
            ltok = r.integers(0, lcfg.vocab_size, (1, lbs, seq), np.int32)
            dt, _ = timed_steps(eng, (ltok, ltok), steps=3, warmup=2)
            tps = lbs * seq * 3 / dt / n_chips
            ln = lcfg.num_params()
            lftok = 6 * ln + 12 * lcfg.num_layers * lcfg.hidden_size * \
                seq // 2   # causal: half the score tiles are dead
            tag = f"longseq_{seq // 1024}k"
            if engine == "sparse":
                # dense-equivalent MFU: tokens/s × DENSE flops/token —
                # the comparable "how much dense work would this pace
                # amount to" scalar (the sparse kernels burn fewer)
                return {f"{tag}_sparse_tokens_per_sec_chip": round(tps, 1),
                        f"{tag}_sparse_mfu_dense_equiv":
                            round(tps * lftok / peak, 4),
                        f"{tag}_sparse_pattern": "fixed_l16g1"}
            out = {f"{tag}_tokens_per_sec_chip": round(tps, 1),
                   f"{tag}_mfu": round(tps * lftok / peak, 4),
                   f"{tag}_remat_policy": "full",
                   f"{tag}_batch_per_chip": bs_per_chip}
            out.update(_flash_block_extra(tag))
            return out
        return thunk

    lbs = int(os.environ.get("DS_BENCH_LONG_BS", "2"))
    want_sparse = os.environ.get("DS_BENCH_LONG_SPARSE", "1") not in (
        "0", "", "false")
    out = _ladder([(f"bs{lbs}", run(16384, lbs))] +
                  ([("bs1", run(16384, 1))] if lbs > 1 else []),
                  {}, "longseq_16k")
    if "longseq_16k_mfu" in out and want_sparse:
        # block-sparse engine comparison rung at the same shape
        out = _ladder([(f"sparse_bs{lbs}", run(16384, lbs, "sparse"))],
                      out, "longseq_16k_sparse")
    if "longseq_16k_mfu" in out and \
            os.environ.get("DS_BENCH_32K", "1") not in ("0", "false"):
        # stretch row: 32k tokens (the reference claims ~10× longer
        # sequences via sparse attention; dense-flash 32k beats it).
        # Tag matches what actually runs, with a true bs1 fallback rung.
        out = _ladder([(f"bs{lbs}", run(32768, lbs))] +
                      ([("bs1", run(32768, 1))] if lbs > 1 else []),
                      out, "longseq_32k")
        if "longseq_32k_mfu" in out and want_sparse:
            out = _ladder(
                [(f"sparse_bs{lbs}", run(32768, lbs, "sparse"))],
                out, "longseq_32k_sparse")
    return out


def row_packed():
    """Packed ragged-batch row (opt-in via DS_BENCH_PACKED=1): a fixed-
    seed lognormal document mixture (`runtime.packing.
    synthetic_doc_mixture` — the shape of web corpora) greedily packed
    into 16k rows, trained with segment-aware flash kernels. The same
    packed tokens run WITHOUT segment ids as the control: identical
    shapes and flop ceiling, so the delta isolates the block-level
    cross-document skip. Effective (non-pad, non-cross-doc) tokens/s
    quantify what the padded-baseline loader would have wasted."""
    jax = _setup_jax()
    n_chips = len(jax.devices())
    peak = peak_flops_per_chip(jax.devices()[0])
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
    from deeperspeed_tpu.runtime.packing import (
        count_effective_targets, pack_documents, synthetic_doc_mixture)

    seq = int(os.environ.get("DS_BENCH_PACKED_SEQ", str(16384)))

    def run(bs_per_chip, with_segments):
        def thunk():
            lcfg = GPTNeoXConfig(vocab_size=8192, hidden_size=768,
                                 num_layers=12, num_heads=12,
                                 max_seq_len=seq)
            lmodel = GPTNeoX(lcfg, use_pallas=True, remat_blocks=True)
            lparams = lmodel.init_params(jax.random.PRNGKey(5))
            lbs = bs_per_chip * n_chips
            extra_cfg = {"packing": {"enabled": True}} if with_segments \
                else None
            eng = _neox_engine(lmodel, lparams, lbs, {"stage": 2},
                               extra_cfg=extra_cfg)
            # fixed seed => identical mixture every round (per topology):
            # mean-2048 lognormal with a heavy tail, sized to fill lbs
            # rows of seq tokens with 75% margin (greedy packing leaves
            # partial tail rows; the guard below still backstops)
            mean_len = 2048.0
            n_docs = max(64, int(lbs * seq / mean_len * 1.75))
            docs = synthetic_doc_mixture(7, n_docs, lcfg.vocab_size,
                                         mean_len=mean_len, sigma=1.2,
                                         max_len=seq)
            tok, seg = pack_documents(docs, seq)
            if tok.shape[0] < lbs:
                raise RuntimeError(
                    f"mixture packed into {tok.shape[0]} rows < batch "
                    f"{lbs}; raise the doc count")
            tok, seg = tok[:lbs][None], seg[:lbs][None]  # [1, lbs, S]
            batch = (tok, tok, seg) if with_segments else (tok, tok)
            dt, _ = timed_steps(eng, batch, steps=3, warmup=2)
            tps = lbs * seq * 3 / dt / n_chips
            ln = lcfg.num_params()
            lftok = 6 * ln + 12 * lcfg.num_layers * lcfg.hidden_size * \
                seq // 2
            key = "packed_seg" if with_segments else "packed_noseg"
            out = {f"{key}_tokens_per_sec_chip": round(tps, 1),
                   f"{key}_mfu": round(tps * lftok / peak, 4)}
            if with_segments:
                eff = count_effective_targets(seg)
                total = int(np.prod(seg.shape[:-1])) * (seg.shape[-1] - 1)
                out["packed_occupancy"] = round(float((seg != 0).mean()), 4)
                out["packed_effective_token_fraction"] = round(
                    eff / total, 4)
                out["packed_effective_tokens_per_sec_chip"] = round(
                    tps * eff / total, 1)
                out.update(_flash_block_extra("packed"))
            return out
        return thunk

    bs0 = int(os.environ.get("DS_BENCH_PACKED_BS", "2"))
    out = _ladder([(f"bs{bs0}", run(bs0, True))] +
                  ([("bs1", run(1, True))] if bs0 > 1 else []),
                  {}, "packed")
    if "packed_seg_mfu" in out:
        bs_ran = int(out.get("packed_config", f"bs{bs0}")[2:] or bs0)
        out = _ladder([(f"bs{bs_ran}", run(bs_ran, False))], out,
                      "packed_ctl")
        if "packed_noseg_tokens_per_sec_chip" in out:
            out["packed_seg_speedup"] = round(
                out["packed_seg_tokens_per_sec_chip"] /
                out["packed_noseg_tokens_per_sec_chip"], 3)
    return out


def row_moe():
    """GShard top-2 MoE row, walked over both dispatch engines (einsum =
    reference one-hot, sort = argsort + Pallas grouped matmul). Headline
    `moe_top2_*` keys mirror the sort engine when it ran (the fast
    path), einsum otherwise; `extra` records dispatch, capacity factor
    and the configured a2a overlap depth. DS_BENCH_MOE_DISPATCH picks
    one engine ("einsum"/"sort", default both)."""
    jax = _setup_jax()
    n_chips = len(jax.devices())
    peak = peak_flops_per_chip(jax.devices()[0])
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    cap_factor = float(os.environ.get("DS_BENCH_MOE_CF", "1.25"))
    a2a_chunks = int(os.environ.get("DS_BENCH_MOE_A2A_CHUNKS", "1"))

    def run(bs_per_chip, dispatch):
        def thunk():
            mcfg = GPTNeoXConfig(vocab_size=50304, hidden_size=768,
                                 num_layers=12, num_heads=12,
                                 max_seq_len=1024, moe_num_experts=8,
                                 moe_top_k=2, moe_dispatch=dispatch,
                                 moe_capacity_factor=cap_factor,
                                 moe_a2a_overlap_chunks=a2a_chunks)
            mmodel = GPTNeoX(mcfg, use_pallas=True)
            mparams = mmodel.init_params(jax.random.PRNGKey(7))
            mbs = bs_per_chip * n_chips
            eng = _neox_engine(mmodel, mparams, mbs, {"stage": 2})
            r = np.random.default_rng(8)
            mtok = r.integers(0, mcfg.vocab_size, (1, mbs, 1024),
                              np.int32)
            dt, _ = timed_steps(eng, (mtok, mtok), steps=4, warmup=2)
            tps = mbs * 1024 * 4 / dt / n_chips
            # active params/token: top-2 of 8 experts → dense-equivalent
            # flops use 2 expert FFNs per token plus the shared trunk
            H, L = mcfg.hidden_size, mcfg.num_layers
            trunk = L * 4 * H * H + mcfg.vocab_size * H
            expert = L * mcfg.moe_top_k * 8 * H * H
            mftok = 6 * (trunk + expert) + 12 * L * H * 1024
            p = f"moe_top2_{dispatch}"
            return {f"{p}_tokens_per_sec_chip": round(tps, 1),
                    f"{p}_active_mfu": round(tps * mftok / peak, 4),
                    f"{p}_batch_per_chip": bs_per_chip}
        return thunk

    sel = os.environ.get("DS_BENCH_MOE_DISPATCH", "both")
    modes = ("einsum", "sort") if sel in ("both", "", "all") else (sel,)
    bs0 = int(os.environ.get("DS_BENCH_MOE_BS", "8"))
    out = {"moe_top2_capacity_factor": cap_factor,
           "moe_top2_a2a_overlap_chunks": a2a_chunks}
    for d in modes:
        out = _ladder([(f"{d}_bs{bs0}", run(bs0, d)),
                       (f"{d}_bs4", run(4, d))], out, f"moe_top2_{d}")
    head = next((d for d in ("sort", "einsum")
                 if f"moe_top2_{d}_active_mfu" in out), None)
    if head is not None:
        out["moe_top2_dispatch"] = head
        for k in ("tokens_per_sec_chip", "active_mfu", "batch_per_chip"):
            out[f"moe_top2_{k}"] = out[f"moe_top2_{head}_{k}"]
    return out


def row_ckpt():
    """Checkpoint-induced training stall, sync vs async: how long the
    step loop blocks for a full engine save (NeoX-125M, ZeRO-2 — fp32
    masters + both Adam moments on disk). The async row also counts how
    many train steps complete while the commit is in flight. Opt-in via
    DS_BENCH_CKPT (disk-heavy; writes ~1.5 GB per save)."""
    import shutil
    import tempfile

    jax = _setup_jax()
    n_chips = len(jax.devices())
    cfg, model, params = _headline_setup(jax)
    seq = 1024

    def run(bs_per_chip):
        def thunk():
            batch = bs_per_chip * n_chips
            rng = np.random.default_rng(0)
            tokens = rng.integers(0, cfg.vocab_size, size=(1, batch, seq),
                                  dtype=np.int32)
            stacked = (tokens, tokens)
            eng = _neox_engine(model, params, batch, {"stage": 2})
            steps = 6
            dt, _ = timed_steps(eng, stacked, steps=steps, warmup=3)
            step_ms = dt / steps * 1e3
            tmp = tempfile.mkdtemp(prefix="ds_ckpt_bench_")
            try:
                # sync: the whole snapshot+serialize+commit blocks the loop
                t0 = time.perf_counter()
                eng.save_checkpoint(tmp, tag="sync")
                sync_ms = (time.perf_counter() - t0) * 1e3
                # async: only the host snapshot blocks; commit overlaps
                t0 = time.perf_counter()
                eng.save_checkpoint_async(tmp, tag="async")
                async_ms = (time.perf_counter() - t0) * 1e3
                overlapped = 0
                while eng.checkpoint_manager.in_flight and overlapped < 64:
                    eng.train_batch(batch=stacked)
                    overlapped += 1
                force(eng.state.params)
                eng.checkpoint_manager.wait()
                mgr = eng.checkpoint_manager
                return {
                    "ckpt_step_ms": round(step_ms, 1),
                    "ckpt_sync_stall_ms": round(sync_ms, 1),
                    "ckpt_async_stall_ms": round(async_ms, 1),
                    "ckpt_async_overlap_steps": overlapped,
                    "ckpt_bytes_mb": round(mgr.total_bytes / 2**20, 1),
                    "ckpt_stall_ratio": round(
                        async_ms / sync_ms, 4) if sync_ms else None,
                }
            finally:
                shutil.rmtree(tmp, ignore_errors=True)
        return thunk

    bs0 = int(os.environ.get("DS_BENCH_CKPT_BS", "16"))
    return _ladder([(f"bs{bs0}", run(bs0)), ("bs8", run(8))], {}, "ckpt")


def row_sentinel():
    """Training-health sentinel cost + recovery latency (NeoX-125M,
    ZeRO-2): step time with the sentinel off vs on (the in-jit probe +
    the per-step flags read — the acceptance bar is < 1% overhead), then
    an injected NaN-grad step under policy `rollback` measuring the full
    detect -> restore-checkpoint -> continue wall time. Opt-in via
    DS_BENCH_SENTINEL=1."""
    import shutil
    import tempfile

    jax = _setup_jax()
    n_chips = len(jax.devices())
    cfg, model, params = _headline_setup(jax)
    seq = 1024

    def engine_with(batch, tmp=None, th=None):
        import deeperspeed_tpu
        config = {
            "train_batch_size": batch,
            "gradient_accumulation_steps": 1,
            "steps_per_print": 10_000,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "fp16": {"enabled": True, "type": "bfloat16"},
            "zero_optimization": {"stage": 2},
        }
        if tmp is not None:
            config["checkpoint"] = {"save_dir": tmp}
        if th is not None:
            config["training_health"] = th
        eng, *_ = deeperspeed_tpu.initialize(
            model=model, model_parameters=params, config_params=config)
        return eng

    def run(bs_per_chip):
        def thunk():
            batch = bs_per_chip * n_chips
            rng = np.random.default_rng(0)
            tokens = rng.integers(0, cfg.vocab_size, size=(1, batch, seq),
                                  dtype=np.int32)
            stacked = (tokens, tokens)
            steps = 8

            eng = engine_with(batch)
            dt_off, _ = timed_steps(eng, stacked, steps=steps, warmup=3)
            del eng
            gc.collect()

            th_on = {"enabled": True, "policy": "skip_batch",
                     "warmup_steps": 3}
            eng = engine_with(batch, th=th_on)
            dt_on, _ = timed_steps(eng, stacked, steps=steps, warmup=3)
            del eng
            gc.collect()
            overhead = (dt_on - dt_off) / dt_off

            # recovery latency: ckpt at step 3, NaN grads at step 4 ->
            # the faulted train_batch call detects, quarantines, and
            # restores the committed checkpoint before returning
            tmp = tempfile.mkdtemp(prefix="ds_sentinel_bench_")
            try:
                th_rb = {"enabled": True, "policy": "rollback",
                         "rollback_after": 1, "warmup_steps": 100,
                         "fault_injection": {"faults": [
                             {"kind": "nan_grads", "step": 4}]}}
                eng = engine_with(batch, tmp=tmp, th=th_rb)
                for _ in range(4):
                    eng.train_batch(batch=stacked)
                eng.save_checkpoint(tmp)
                force(eng.state.params)
                t0 = time.perf_counter()
                eng.train_batch(batch=stacked)   # fault -> rollback
                force(eng.state.params)
                recovery_ms = (time.perf_counter() - t0) * 1e3
                rollbacks = eng.sentinel.rollbacks
            finally:
                shutil.rmtree(tmp, ignore_errors=True)
            return {
                "sentinel_step_ms_off": round(dt_off / steps * 1e3, 2),
                "sentinel_step_ms_on": round(dt_on / steps * 1e3, 2),
                "sentinel_overhead_pct": round(overhead * 100, 2),
                "sentinel_recovery_ms": round(recovery_ms, 1),
                "sentinel_rollbacks": rollbacks,
            }
        return thunk

    bs0 = int(os.environ.get("DS_BENCH_SENTINEL_BS", "16"))
    return _ladder([(f"bs{bs0}", run(bs0)), ("bs8", run(8))], {},
                   "sentinel")


def row_telemetry():
    """Unified-telemetry cost + MFU cross-check (NeoX-125M, ZeRO-2):
    step time with the telemetry block off vs on (goodput + MFU + span
    scalars enabled, trace capture OFF — the acceptance bar is <= 1%
    overhead in that mode), plus the in-engine MFU scalar (per-variant
    `cost_analysis` flops / measured step time / peak) against this
    bench's analytic tokens/s MFU — the two methodologies must agree
    within ~2%. Opt-in via DS_BENCH_TELEMETRY=1."""
    import shutil
    import tempfile

    jax = _setup_jax()
    n_chips = len(jax.devices())
    peak = peak_flops_per_chip(jax.devices()[0])
    cfg, model, params = _headline_setup(jax)
    seq = 1024

    def engine_with(batch, tmp, telemetry=None):
        import deeperspeed_tpu
        config = {
            "train_batch_size": batch,
            "gradient_accumulation_steps": 1,
            "steps_per_print": 10_000,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "fp16": {"enabled": True, "type": "bfloat16"},
            "zero_optimization": {"stage": 2},
            # both engines log scalars: the row isolates the telemetry
            # layer's cost, not the monitor's
            "tensorboard": {"enabled": True, "output_path": tmp,
                            "job_name": "bench"},
        }
        if telemetry is not None:
            config["telemetry"] = telemetry
        eng, *_ = deeperspeed_tpu.initialize(
            model=model, model_parameters=params, config_params=config)
        return eng

    def run(bs_per_chip):
        def thunk():
            batch = bs_per_chip * n_chips
            rng = np.random.default_rng(0)
            tokens = rng.integers(0, cfg.vocab_size, size=(1, batch, seq),
                                  dtype=np.int32)
            stacked = (tokens, tokens)
            steps = 8
            tmp = tempfile.mkdtemp(prefix="ds_telemetry_bench_")
            try:
                eng = engine_with(batch, tmp)
                dt_off, _ = timed_steps(eng, stacked, steps=steps,
                                        warmup=3)
                del eng
                gc.collect()

                tel_on = {"enabled": True, "goodput": True, "mfu": True,
                          "spans": True}
                eng = engine_with(batch, tmp, telemetry=tel_on)
                dt_on, _ = timed_steps(eng, stacked, steps=steps,
                                       warmup=3)
                overhead = (dt_on - dt_off) / dt_off

                tps = batch * seq * steps / dt_on / n_chips
                mfu_analytic = tps * _flops_per_token(cfg, seq) / peak
                flops = eng.telemetry.compiled_flops.get(1)
                mfu_engine = (flops / (dt_on / steps) / peak
                              if flops else None)
                frac = eng.telemetry.goodput.fraction
                del eng
                gc.collect()
            finally:
                shutil.rmtree(tmp, ignore_errors=True)
            out = {
                "telemetry_step_ms_off": round(dt_off / steps * 1e3, 2),
                "telemetry_step_ms_on": round(dt_on / steps * 1e3, 2),
                "telemetry_overhead_pct": round(overhead * 100, 2),
                "telemetry_mfu_analytic": round(mfu_analytic, 4),
                "telemetry_goodput_fraction": round(frac, 4),
            }
            if mfu_engine is not None:
                out["telemetry_mfu_in_engine"] = round(mfu_engine, 4)
                out["telemetry_mfu_ratio"] = round(
                    mfu_engine / mfu_analytic, 4)
            return out
        return thunk

    bs0 = int(os.environ.get("DS_BENCH_TELEMETRY_BS", "16"))
    return _ladder([(f"bs{bs0}", run(bs0)), ("bs8", run(8))], {},
                   "telemetry")


def row_fleet():
    """Fleet observability row (opt-in via DS_BENCH_FLEET=1, NeoX-125M,
    ZeRO-2): (a) telemetry overhead with fleet scalars + the Prometheus
    exporter ON (capture off) vs the telemetry block absent — the
    acceptance bar is <= 1% step time; (b) straggler detection: an
    injected `slow_peer` fault (the PR 9 fault kind) must be NAMED by
    the collective-skew probe, recording the detection latency in steps
    and the named-host correctness; (c) a live scrape of the Prometheus
    endpoint counting the Train/* families served."""
    import shutil
    import tempfile
    import urllib.request

    jax = _setup_jax()
    n_chips = len(jax.devices())
    cfg, model, params = _headline_setup(jax)
    seq = 1024

    def engine_with(batch, tmp, fleet=False, fault_step=None):
        import deeperspeed_tpu
        config = {
            "train_batch_size": batch,
            "gradient_accumulation_steps": 1,
            "steps_per_print": 10_000,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "fp16": {"enabled": True, "type": "bfloat16"},
            "zero_optimization": {"stage": 2},
            "tensorboard": {"enabled": True, "output_path": tmp,
                            "job_name": "bench"},
        }
        if fleet:
            config["telemetry"] = {
                "enabled": True, "goodput": True, "mfu": False,
                "spans": True,
                "fleet": {"enabled": True, "window_steps": 4,
                          "skew_interval_steps": 2,
                          "skew_slow_threshold_ms": 100.0}}
            config["monitor"] = {"export": {"prometheus_port": 0}}
            config["elasticity"] = {"heartbeat": {
                "enabled": True, "interval_s": 0.2,
                "warn_after_s": 60.0, "fail_after_s": 600.0}}
        if fault_step is not None:
            config["training_health"] = {"fault_injection": {"faults": [
                {"kind": "slow_peer", "step": fault_step,
                 "seconds": 0.25}]}}
        eng, *_ = deeperspeed_tpu.initialize(
            model=model, model_parameters=params, config_params=config)
        return eng

    def run(bs_per_chip):
        def thunk():
            batch = bs_per_chip * n_chips
            rng = np.random.default_rng(0)
            tokens = rng.integers(0, cfg.vocab_size, size=(1, batch, seq),
                                  dtype=np.int32)
            stacked = (tokens, tokens)
            steps = 8
            tmp = tempfile.mkdtemp(prefix="ds_fleet_bench_")
            try:
                eng = engine_with(batch, tmp)
                dt_off, _ = timed_steps(eng, stacked, steps=steps,
                                        warmup=3)
                del eng
                gc.collect()

                eng = engine_with(batch, tmp, fleet=True)
                dt_on, _ = timed_steps(eng, stacked, steps=steps,
                                       warmup=3)
                overhead = (dt_on - dt_off) / dt_off
                prom = eng.monitor.prometheus
                eng.monitor.flush()
                families = 0
                if prom is not None:
                    body = urllib.request.urlopen(
                        f"http://127.0.0.1:{prom.port}/metrics",
                        timeout=5).read().decode()
                    families = sum(1 for line in body.splitlines()
                                   if line.startswith("# TYPE ds_train_"))
                if eng.peer_monitor is not None:
                    eng.peer_monitor.stop()
                eng.monitor.close()
                del eng
                gc.collect()

                # straggler detection: slow_peer fires at step 3; the
                # skew probe (every 2 steps) must NAME the simulated
                # host — detection latency = steps from fire to naming
                fault_step = 3
                eng = engine_with(batch, tmp, fleet=True,
                                  fault_step=fault_step)
                from deeperspeed_tpu.runtime.fault_injection import \
                    DEFAULT_SIM_PEER
                detected_at = None
                for i in range(10):
                    eng.train_batch(batch=stacked)
                    fleet = eng.telemetry.fleet
                    if detected_at is None and fleet is not None and \
                            fleet.last_slowest == DEFAULT_SIM_PEER:
                        detected_at = i + 1
                        break
                named_ok = detected_at is not None
                eng.peer_monitor.stop()
                eng.monitor.close()
                del eng
                gc.collect()
            finally:
                shutil.rmtree(tmp, ignore_errors=True)
            out = {
                "fleet_step_ms_off": round(dt_off / steps * 1e3, 2),
                "fleet_step_ms_on": round(dt_on / steps * 1e3, 2),
                "fleet_overhead_pct": round(overhead * 100, 2),
                "fleet_prom_train_families": families,
                "fleet_slow_peer_named": bool(named_ok),
            }
            if detected_at is not None:
                out["fleet_detect_latency_steps"] = \
                    detected_at - fault_step
            return out
        return thunk

    bs0 = int(os.environ.get("DS_BENCH_FLEET_BS", "16"))
    return _ladder([(f"bs{bs0}", run(bs0))], {}, "fleet")


def row_serve():
    """Continuous-batching serving row (opt-in via DS_BENCH_SERVE=1): a
    fixed-seed open-loop request stream (lognormal prompt lengths,
    arrivals every other scheduler step regardless of progress) through
    the InferenceEngine — NeoX-125M, greedy decode, paged KV cache,
    single-bucket prefill/decode batch shapes so warmup compiles
    exactly one program per prefill length plus one decode program.
    Reports generated tokens/s/chip, p50/p99 inter-token latency, p50
    time-to-first-token, and the compile-count delta over the measured
    stream (the zero-recompile discipline: must be 0)."""
    jax = _setup_jax()
    cfg, model, params = _headline_setup(jax)

    def run(n_req):
        def thunk():
            from deeperspeed_tpu.inference import InferenceEngine
            max_batch = int(os.environ.get("DS_BENCH_SERVE_BATCH", "16"))
            max_new = int(os.environ.get("DS_BENCH_SERVE_NEW", "64"))
            conf = {"inference": {
                "enabled": True, "page_size": 64,
                "num_pages": int(os.environ.get("DS_BENCH_SERVE_PAGES",
                                                "513")),
                "max_batch_size": max_batch, "token_budget": 2048,
                "prefill_batch_sizes": [4],
                "decode_batch_sizes": [max_batch]}}
            eng = InferenceEngine(model, config=conf, params=params)
            rng = np.random.default_rng(0)
            hi = min(768, eng.prefill_lengths[-1],
                     eng.max_seq_len - max_new)
            lens = np.clip(np.exp(rng.normal(5.0, 0.8, size=n_req)),
                           8, hi).astype(int)
            prompts = [list(rng.integers(1, cfg.vocab_size, size=int(n)))
                       for n in lens]

            # warm every prefill length bucket + the decode program so
            # the measured stream starts fully compiled (b - 2 so the
            # top bucket's prompt + 2 tokens still fits the window)
            eng.generate([list(rng.integers(1, cfg.vocab_size, size=b - 2))
                          for b in eng.prefill_lengths], max_new_tokens=2)
            compiled_warm = eng.compile_count()
            # measured-stream deltas only: the warmup pass's counters
            # include per-bucket compile time in its prefill span
            warm_stats = dict(eng.stats)

            t_start = time.perf_counter()
            submit_at, last, seen = {}, {}, {}
            itl, ttft = [], []
            submitted = 0
            step = 0
            while submitted < len(prompts) or eng.scheduler.has_work:
                while submitted < len(prompts) and submitted * 2 <= step:
                    rid = eng.submit(prompts[submitted],
                                     max_new_tokens=max_new)
                    submit_at[rid] = time.perf_counter()
                    submitted += 1
                if eng.scheduler.has_work:
                    eng.step()
                now = time.perf_counter()
                for r in list(eng.scheduler.running) + \
                        eng.scheduler.finished:
                    rid = r.request_id
                    if rid not in submit_at:
                        continue                      # warmup requests
                    k = len(r.generated)
                    if k > seen.get(rid, 0):
                        if rid in last:
                            itl.append(now - last[rid])
                        else:
                            ttft.append(now - submit_at[rid])
                        last[rid] = now
                        seen[rid] = k
                step += 1
            dt = time.perf_counter() - t_start
            stats = {k: v - warm_stats[k] for k, v in eng.stats.items()}
            gen = sum(len(r.generated) for r in eng.scheduler.finished
                      if r.request_id in submit_at)
            def pct(vals, q):
                # DS_BENCH_SERVE_NEW=1 yields no inter-token intervals
                # (every request finishes at prefill) — report null,
                # don't kill the row
                if not vals:
                    return None
                return round(float(np.percentile(np.asarray(vals), q))
                             * 1e3, 2)

            return {
                # serving runs on one chip unless a mesh is attached
                "serve_tokens_per_s_chip": round(gen / dt, 1),
                "serve_chips": 1,
                # precision identity: BENCH history needs to attribute
                # serving deltas to weight/compute/KV dtype changes
                # (docs/quantization.md)
                "serve_weight_dtype": eng.dtypes["weight"],
                "serve_compute_dtype": eng.dtypes["compute"],
                "serve_kv_dtype": eng.dtypes["kv_cache"],
                "serve_p50_token_ms": pct(itl, 50),
                "serve_p99_token_ms": pct(itl, 99),
                "serve_ttft_p50_ms": pct(ttft, 50),
                "serve_requests": n_req,
                "serve_gen_tokens": gen,
                "serve_steps": stats["steps"],
                "serve_evictions": stats["evictions"],
                "serve_prefill_s": round(stats["prefill_s"], 2),
                "serve_decode_s": round(stats["decode_s"], 2),
                "serve_compile_delta": eng.compile_count() - compiled_warm,
            }
        return thunk

    n0 = int(os.environ.get("DS_BENCH_SERVE_REQUESTS", "64"))
    return _ladder([(f"req{n0}", run(n0)), ("req16", run(16))], {},
                   "serve")


def row_serve_chaos():
    """Serving-under-failure row (opt-in DS_BENCH_SERVE_CHAOS=1): the
    fixed-seed open-loop serve stream run twice — CLEAN (robustness
    layer on, no faults firing) and under a scripted FAULT STORM
    (injected decode errors, a decode stall, page-pool pressure)
    against a bounded admission queue. Reports per-variant success
    rate, shed fraction, and p99 TTFT (plus the storm-vs-clean p99
    TTFT degradation), and pins the chaos invariants: the server never
    exits, every accepted request reaches exactly one terminal status,
    zero KV pages leak, zero post-warmup recompiles."""
    jax = _setup_jax()
    cfg, model, params = _headline_setup(jax)

    def run(n_req, faults, prefix):
        def thunk():
            from deeperspeed_tpu.inference import (InferenceEngine,
                                                   RequestRejected)
            max_batch = int(os.environ.get("DS_BENCH_SERVE_BATCH", "16"))
            max_new = int(os.environ.get("DS_BENCH_SERVE_NEW", "64"))
            block = {
                "enabled": True, "page_size": 64,
                "num_pages": int(os.environ.get("DS_BENCH_SERVE_PAGES",
                                                "513")),
                "max_batch_size": max_batch, "token_budget": 2048,
                "prefill_batch_sizes": [4],
                "decode_batch_sizes": [max_batch],
                "admission": {"max_queue_depth": int(os.environ.get(
                    "DS_BENCH_SERVE_CHAOS_QUEUE", "24"))},
                "retry": {"max_attempts": 3, "backoff_base_ms": 5,
                          "backoff_cap_ms": 50, "jitter": 0.25},
            }
            if faults:
                block["fault_injection"] = {"faults": faults}
            eng = InferenceEngine(model, config={"inference": block},
                                  params=params)
            rng = np.random.default_rng(0)
            hi = min(768, eng.prefill_lengths[-1],
                     eng.max_seq_len - max_new)
            lens = np.clip(np.exp(rng.normal(5.0, 0.8, size=n_req)),
                           8, hi).astype(int)
            prompts = [list(rng.integers(1, cfg.vocab_size, size=int(n)))
                       for n in lens]
            eng.generate([list(rng.integers(1, cfg.vocab_size, size=b - 2))
                          for b in eng.prefill_lengths], max_new_tokens=2)
            compiled_warm = eng.compile_count()
            base = {k: eng.stats[k] for k in
                    ("requests_ok", "requests_deadline_exceeded",
                     "requests_failed")}

            submit_at, first_tok = {}, {}
            shed = 0
            submitted = 0
            step = 0
            died = None
            t_start = time.perf_counter()
            while submitted < len(prompts) or eng.scheduler.has_work:
                while submitted < len(prompts) and submitted * 2 <= step:
                    try:
                        rid = eng.submit(prompts[submitted],
                                         max_new_tokens=max_new)
                        submit_at[rid] = time.perf_counter()
                    except RequestRejected:
                        shed += 1
                    submitted += 1
                if eng.scheduler.has_work:
                    try:
                        eng.step()
                    except BaseException as e:  # noqa: BLE001
                        died = f"{type(e).__name__}: {e}"
                        break
                now = time.perf_counter()
                for r in list(eng.scheduler.running) + \
                        eng.scheduler.finished:
                    rid = r.request_id
                    if rid in submit_at and rid not in first_tok and \
                            r.generated:
                        first_tok[rid] = now - submit_at[rid]
                step += 1
                if time.perf_counter() - t_start > 600:
                    died = "stream timed out"
                    break
            gen = sum(len(r.generated) for r in eng.scheduler.finished
                      if r.request_id in submit_at)
            dt = time.perf_counter() - t_start
            accepted = len(submit_at)
            terminal = sum(eng.stats[k] - base[k] for k in base)
            ttft = sorted(first_tok.values())

            def pct(vals, q):
                if not vals:
                    return None
                return round(float(np.percentile(np.asarray(vals), q))
                             * 1e3, 2)

            return {
                f"{prefix}requests": submitted,
                f"{prefix}success_rate": round(
                    (eng.stats["requests_ok"] - base["requests_ok"]) /
                    max(submitted, 1), 4),
                f"{prefix}shed_fraction": round(
                    shed / max(submitted, 1), 4),
                f"{prefix}ttft_p50_ms": pct(ttft, 50),
                f"{prefix}ttft_p99_ms": pct(ttft, 99),
                f"{prefix}tokens_per_s": round(gen / dt, 1),
                f"{prefix}quarantines": eng.stats["quarantines"],
                f"{prefix}evictions": eng.stats["evictions"],
                # invariants — all must hold for the row to mean anything
                f"{prefix}server_up": died is None,
                f"{prefix}died": died,
                f"{prefix}all_terminal": terminal == accepted,
                f"{prefix}pages_leaked":
                    (eng.cache.num_pages - 1) - eng.cache.num_free,
                f"{prefix}compile_delta":
                    eng.compile_count() - compiled_warm,
            }
        return thunk

    n0 = int(os.environ.get("DS_BENCH_SERVE_REQUESTS", "64"))
    # the storm script scales with the stream: errors early and late,
    # a stall mid-stream, pool pressure across a burst window
    storm = [
        {"kind": "decode_error", "step": 40, "times": 2},
        {"kind": "decode_error", "step": 120, "times": 1},
        {"kind": "decode_stall", "step": 80, "seconds": 0.05},
        {"kind": "page_pool_pressure", "step": 60, "times": 5,
         "factor": 0.7},
    ]
    out = {}
    _ladder([("clean", run(n0, None, "chaos_clean_"))], out,
            "serve_chaos_clean")
    _ladder([("storm", run(n0, storm, "chaos_storm_"))], out,
            "serve_chaos_storm")
    p99c = out.get("chaos_clean_ttft_p99_ms")
    p99s = out.get("chaos_storm_ttft_p99_ms")
    if p99c and p99s:
        # the headline number: how much tail TTFT the fault storm costs
        out["chaos_ttft_p99_degradation_pct"] = round(
            (p99s - p99c) / p99c * 100.0, 1)
    return out


def row_serve_prefix():
    """Prefix-cache + speculative-decode serving row (opt-in via
    DS_BENCH_SERVE_PREFIX=1): a bursty stream where 80% of the prompts
    share one long prefix — the archetypal system-prompt fleet — run
    through (1) a cache-off baseline engine and (2) an engine with the
    prefix registry AND a small draft model, measured on its third
    stream (two warmup streams: the first compiles the miss-path
    buckets, the second the registry-hit chunk buckets — steady state
    from there, pinned by serve_prefix_compile_delta == 0). Reports the
    prefix hit rate, effective prefill tokens/s for both engines (full
    context tokens per prefill-wall-second — shared pages make the
    cache-on number rise above the compute rate), the speculative
    acceptance rate, and the p50 inter-token speedup vs the
    non-speculative baseline."""
    jax = _setup_jax()
    cfg, model, params = _headline_setup(jax)

    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    # the draft: same vocab/window, a fraction of the depth/width — big
    # enough to agree with the target often, cheap enough that a k-step
    # propose costs less than the verified forward it saves
    draft_cfg = GPTNeoXConfig(vocab_size=cfg.vocab_size, hidden_size=256,
                              num_layers=4, num_heads=8,
                              max_seq_len=cfg.max_seq_len)
    draft = GPTNeoX(draft_cfg, use_pallas=True)
    draft_params = draft.init_params(jax.random.PRNGKey(3))

    max_new = int(os.environ.get("DS_BENCH_SERVE_NEW", "32"))
    n_req = int(os.environ.get("DS_BENCH_SERVE_REQUESTS", "32"))
    prefix_len = int(os.environ.get("DS_BENCH_SERVE_PREFIX_LEN", "256"))
    spec_k = int(os.environ.get("DS_BENCH_SERVE_SPEC_K", "4"))

    def make_prompts(rng, shared):
        out = []
        for i in range(n_req):
            tail = list(rng.integers(
                1, cfg.vocab_size, size=int(rng.integers(8, 48))))
            if i % 5 == 4:                   # 20% cold prompts
                out.append(list(rng.integers(
                    1, cfg.vocab_size, size=prefix_len)) + tail)
            else:
                out.append(shared + tail)
        return out

    def stream(eng, prompts):
        """One bursty stream: submit everything, drain, return wall
        inter-token p50 + the engine-stats deltas."""
        before = dict(eng.stats)
        last, itl = {}, []
        for p in prompts:
            eng.submit(p, max_new_tokens=max_new)
        while eng.scheduler.has_work:
            eng.step()
            now = time.perf_counter()
            for r in list(eng.scheduler.running):
                k = len(r.generated)
                if k and r.request_id in last and \
                        k > last[r.request_id][1]:
                    # spec appends several tokens per step: one step's
                    # gap amortizes over every token it appended
                    gap = (now - last[r.request_id][0]) / \
                        (k - last[r.request_id][1])
                    itl.extend([gap] * (k - last[r.request_id][1]))
                if k:
                    last[r.request_id] = (now, k)
        eng.scheduler.pop_finished()
        delta = {k: v - before[k] for k, v in eng.stats.items()
                 if isinstance(v, (int, float))}
        p50 = float(np.percentile(np.asarray(itl), 50)) if itl else None
        return delta, p50

    def thunk():
        from deeperspeed_tpu.inference import InferenceEngine
        base_block = {
            "enabled": True, "page_size": 64,
            "num_pages": int(os.environ.get("DS_BENCH_SERVE_PAGES",
                                            "513")),
            "max_batch_size": 8, "token_budget": 2048,
            "prefill_batch_sizes": [4], "decode_batch_sizes": [8]}
        rng = np.random.default_rng(0)
        # ONE shared prefix for the whole row — the registry warms on
        # stream one and every later shared prompt hits it
        shared = list(rng.integers(1, cfg.vocab_size, size=prefix_len))

        base = InferenceEngine(model, config={"inference": base_block},
                               params=params)
        stream(base, make_prompts(rng, shared))        # warmup
        base_delta, base_p50 = stream(base, make_prompts(rng, shared))

        both_block = dict(base_block)
        both_block["prefix_cache"] = {"enabled": True}
        both_block["speculative"] = {"enabled": True,
                                     "num_draft_tokens": spec_k}
        eng = InferenceEngine(model, config={"inference": both_block},
                              params=params, draft_model=draft,
                              draft_params=draft_params)
        stream(eng, make_prompts(rng, shared))         # warmup 1: misses
        stream(eng, make_prompts(rng, shared))         # warmup 2: hits
        warm = eng.compile_count()
        pcs_before = dict(eng.prefix_cache.stats)
        delta, p50 = stream(eng, make_prompts(rng, shared))

        pcs = {k: v - pcs_before[k]
               for k, v in eng.prefix_cache.stats.items()}
        out = {
            "serve_prefix_requests": n_req,
            "serve_prefix_shared_len": prefix_len,
            "serve_prefix_hit_rate": round(
                pcs["lookups"] and pcs["hits"] / pcs["lookups"], 3),
            "serve_prefix_saved_tokens": pcs["saved_prefill_tokens"],
            # effective prefill throughput: FULL context tokens per
            # prefill-wall-second (the cache-on engine only computes
            # the unshared suffixes, so its effective rate rises)
            "serve_prefix_base_prefill_tok_s": round(
                base_delta["prefill_tokens"] /
                max(base_delta["prefill_s"], 1e-9), 1),
            "serve_prefix_prefill_tok_s": round(
                delta["prefill_tokens"] /
                max(delta["prefill_s"], 1e-9), 1),
            "serve_prefix_spec_acceptance": round(
                delta["spec_proposed"] and
                delta["spec_accepted"] / delta["spec_proposed"], 3),
            "serve_prefix_base_p50_token_ms": round(base_p50 * 1e3, 2),
            "serve_prefix_p50_token_ms": round(p50 * 1e3, 2),
            "serve_prefix_p50_speedup": round(base_p50 / p50, 2),
            # steady-state pin: the measured stream compiled nothing
            "serve_prefix_compile_delta": eng.compile_count() - warm,
        }
        return out

    return _ladder([("neox125m", thunk)], {}, "serve_prefix")


def row_serve_disagg():
    """Disaggregated prefill/decode serving row (opt-in via
    DS_BENCH_SERVE_DISAGG=1): the bursty 80%-shared-prefix stream run
    through (1) a unified engine and (2) a prefill-pool + decode-pool
    split over the in-memory handoff transport, both with the prefix
    registry on. Two warmup streams per layout (the split needs both:
    the first warms the outbox-batched install buckets, the second the
    announcement-live staggered ones), then one measured stream.
    Reports generated tokens/s for both layouts, the decode-side
    p50/p99 inter-token latency while the prefill bursts land (the
    cadence isolation the split buys), the handoff round-trip p50 ms,
    and the post-warmup compile delta summed over BOTH pools (the
    steady-state pin — must be 0)."""
    jax = _setup_jax()
    cfg, model, params = _headline_setup(jax)

    max_new = int(os.environ.get("DS_BENCH_SERVE_NEW", "32"))
    n_req = int(os.environ.get("DS_BENCH_SERVE_REQUESTS", "32"))
    prefix_len = int(os.environ.get("DS_BENCH_SERVE_PREFIX_LEN", "256"))

    def make_prompts(rng, shared):
        out = []
        for i in range(n_req):
            tail = list(rng.integers(
                1, cfg.vocab_size, size=int(rng.integers(8, 48))))
            if i % 5 == 4:                   # 20% cold prompts
                out.append(list(rng.integers(
                    1, cfg.vocab_size, size=prefix_len)) + tail)
            else:
                out.append(shared + tail)
        return out

    def stream(front, decoder, engines, prompts):
        """One bursty stream: submit on ``front``, step every engine
        in lockstep, collect inter-token gaps on ``decoder``'s running
        set (for the split that is the decode pool only). Returns
        (wall_s, generated_tokens, itl_gaps)."""
        last, itl = {}, []
        t0 = time.perf_counter()
        for p in prompts:
            front.submit(p, max_new_tokens=max_new)
        while any(e.scheduler.has_work or
                  getattr(e, "_handoff_outbox", None) or
                  getattr(e, "_pending_handoff", None)
                  for e in engines):
            for e in engines:
                e.step()
            now = time.perf_counter()
            for r in list(decoder.scheduler.running):
                k = len(r.generated)
                if k and r.request_id in last and \
                        k > last[r.request_id][1]:
                    itl.append(now - last[r.request_id][0])
                if k:
                    last[r.request_id] = (now, k)
        wall = time.perf_counter() - t0
        finished = [r for e in engines
                    for r in e.scheduler.pop_finished()]
        assert len(finished) == n_req, (len(finished), n_req)
        tokens = sum(len(r.generated) for r in finished)
        return wall, tokens, itl

    def thunk():
        from deeperspeed_tpu.elasticity.heartbeat import \
            InMemoryTransport
        from deeperspeed_tpu.inference import InferenceEngine
        base_block = {
            "enabled": True, "page_size": 64,
            "num_pages": int(os.environ.get("DS_BENCH_SERVE_PAGES",
                                            "513")),
            "max_batch_size": 8, "token_budget": 2048,
            "prefill_batch_sizes": [4], "decode_batch_sizes": [8],
            "prefix_cache": {"enabled": True}}
        rng = np.random.default_rng(0)
        shared = list(rng.integers(1, cfg.vocab_size, size=prefix_len))

        uni = InferenceEngine(model, config={"inference": base_block},
                              params=params)
        for _ in range(2):                                   # warmup
            stream(uni, uni, [uni], make_prompts(rng, shared))
        uni_warm = uni.compile_count()
        uni_wall, uni_tokens, uni_itl = stream(
            uni, uni, [uni], make_prompts(rng, shared))

        t = InMemoryTransport()
        pools = {}
        for role in ("prefill", "decode"):
            block = dict(base_block)
            block["disaggregation"] = {"role": role,
                                       "pool_id": f"{role[:3]}0"}
            pools[role] = InferenceEngine(
                model, config={"inference": block}, params=params,
                handoff_transport=t)
        pre, dec = pools["prefill"], pools["decode"]
        for _ in range(2):                                   # warmup
            stream(pre, dec, [pre, dec], make_prompts(rng, shared))
        warm = pre.compile_count() + dec.compile_count()
        acked_before = pre.stats["handoff_acked"]
        wall, tokens, itl = stream(pre, dec, [pre, dec],
                                   make_prompts(rng, shared))

        itl_ms = np.asarray(itl) * 1e3
        uni_itl_ms = np.asarray(uni_itl) * 1e3
        return {
            "serve_disagg_requests": n_req,
            "serve_disagg_shared_len": prefix_len,
            "serve_disagg_unified_tok_s": round(uni_tokens /
                                                max(uni_wall, 1e-9), 1),
            "serve_disagg_tok_s": round(tokens / max(wall, 1e-9), 1),
            "serve_disagg_unified_p50_token_ms": round(
                float(np.percentile(uni_itl_ms, 50)), 2),
            "serve_disagg_unified_p99_token_ms": round(
                float(np.percentile(uni_itl_ms, 99)), 2),
            "serve_disagg_p50_token_ms": round(
                float(np.percentile(itl_ms, 50)), 2),
            "serve_disagg_p99_token_ms": round(
                float(np.percentile(itl_ms, 99)), 2),
            "serve_disagg_handoffs": pre.stats["handoff_acked"] -
                acked_before,
            "serve_disagg_handoff_p50_ms":
                pre.serve_stats().get("handoff_p50_ms"),
            # steady-state pin across BOTH pools
            "serve_disagg_compile_delta":
                pre.compile_count() + dec.compile_count() - warm,
        }

    return _ladder([("neox125m", thunk)], {}, "serve_disagg")


_ELASTIC_WORKER = '''
import json, os, sys, time
workdir, target, crash = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
restart = int(os.environ.get("DS_ELASTIC_RESTART_COUNT", "0") or 0)
import numpy as np
import jax, jax.numpy as jnp
import deeperspeed_tpu

D = 64
def loss_fn(params, batch, rng):
    x, y = batch
    h = jnp.tanh(x @ params["w1"])
    return jnp.mean((h @ params["w2"] - y) ** 2)

k1, k2 = jax.random.split(jax.random.PRNGKey(0))
params = {"w1": jax.random.normal(k1, (D, D)) * 0.1,
          "w2": jax.random.normal(k2, (D, D)) * 0.1}
ckpt = os.path.join(workdir, "ckpt")
engine, *_ = deeperspeed_tpu.initialize(
    model=loss_fn, model_parameters=params,
    config_params={"train_batch_size": 8, "steps_per_print": 100000,
                   "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                   "checkpoint": {"save_dir": ckpt, "async_save": False,
                                  "save_interval_steps": 2}})
resumed = None
if os.path.exists(os.path.join(ckpt, "latest")):
    path, _ = engine.load_checkpoint(ckpt)
    assert path is not None
    resumed = engine.global_steps
events = open(os.path.join(workdir, "events.jsonl"), "a")
while engine.global_steps < target:
    s = engine.global_steps
    r = np.random.default_rng(s)          # batch keyed by step: resume
    x = r.normal(size=(1, 8, D)).astype(np.float32)   # replays the
    y = r.normal(size=(1, 8, D)).astype(np.float32)   # exact stream
    loss = engine.train_batch(batch=(x, y))
    events.write(json.dumps({"restart": restart,
                             "step": engine.global_steps,
                             "t": time.time(), "resumed_from": resumed,
                             "loss": float(loss)}) + "\\n")
    events.flush()
    if restart == 0 and crash and engine.global_steps == crash:
        os._exit(3)                       # hard kill: no cleanup
'''


def row_elastic():
    """Supervised-restart recovery (opt-in via DS_BENCH_ELASTIC=1): a
    tiny training job under `elasticity.supervisor.Supervisor` is
    hard-killed (os._exit — the single-host stand-in for a preempted
    host) mid-run; the row reports the kill -> resumed-step wall clock
    (MTTR: crash detection + backoff + process relaunch + jax bring-up
    + checkpoint load + recompile) and the steps lost to the
    uncommitted window (save interval 2 -> at most 1)."""
    import shutil
    import tempfile

    from deeperspeed_tpu.elasticity import constants as ec
    from deeperspeed_tpu.elasticity.supervisor import Supervisor

    target = int(os.environ.get("DS_BENCH_ELASTIC_STEPS", "12"))
    crash = int(os.environ.get("DS_BENCH_ELASTIC_CRASH_STEP", "7"))
    workdir = tempfile.mkdtemp(prefix="ds_elastic_bench_")
    try:
        worker = os.path.join(workdir, "worker.py")
        with open(worker, "w") as f:
            f.write(_ELASTIC_WORKER)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = env.get("DS_BENCH_ELASTIC_PLATFORM",
                                       env.get("JAX_PLATFORMS", ""))
        # the worker runs from the temp dir: put this repo on its path,
        # and scrub any leaked rendezvous vars (the child is single-host)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.dirname(os.path.abspath(__file__))] +
            [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
        for var in ("RANK", "WORLD_SIZE", "LOCAL_RANK", "NODE_RANK",
                    "MASTER_ADDR", "MASTER_PORT", "DS_SLOTS"):
            env.pop(var, None)
        sup = Supervisor(
            [sys.executable, worker, workdir, str(target), str(crash)],
            os.path.join(workdir, "state"), env=env, max_restarts=2,
            backoff_base_s=float(os.environ.get(
                "DS_BENCH_ELASTIC_BACKOFF", "0.5")),
            backoff_max_s=4.0, backoff_jitter=0.0)
        t0 = time.perf_counter()
        stats = sup.run()
        total_s = time.perf_counter() - t0
        if stats["exit_code"] != 0 or stats["restarts"] != 1:
            return {"elastic_error": f"unexpected run: {stats}"}

        events = [json.loads(line) for line in
                  open(os.path.join(workdir, "events.jsonl"))]
        record = json.load(open(os.path.join(
            workdir, "state", ec.SUPERVISOR_FILE)))
        resumed = [e for e in events if e["restart"] == 1]
        first_resumed = resumed[0]
        recovery_s = first_resumed["t"] - record["crash_time"]
        steps_lost = crash - int(first_resumed["resumed_from"])
        # trajectory check: replayed steps match the first incarnation
        first_by_step = {e["step"]: e["loss"] for e in events
                         if e["restart"] == 0}
        aligned = all(
            abs(e["loss"] - first_by_step[e["step"]]) <= 1e-6
            for e in resumed if e["step"] in first_by_step)
        return {
            "elastic_recovery_s": round(recovery_s, 2),
            "elastic_steps_lost": steps_lost,
            "elastic_backoff_s": round(stats["total_backoff_s"], 2),
            "elastic_total_s": round(total_s, 2),
            "elastic_crash_step": crash,
            "elastic_resumed_from": int(first_resumed["resumed_from"]),
            "elastic_trajectory_aligned": bool(aligned),
        }
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def row_offload():
    """Tiered-offload row (opt-in via DS_BENCH_OFFLOAD=1): the explicit
    schedule run three ways — on-chip (the extrapolation baseline),
    host-DRAM rows (offload_param+offload_optimizer cpu, double-buffered
    prefetch), and NVMe rows when DS_BENCH_OFFLOAD_NVME names a path —
    with step time, prefetch-stall fraction and h2d/d2h wire volume per
    tier, plus a synthetic beyond-HBM rung: a model sized
    DS_BENCH_OFFLOAD_RATIO x device HBM (fallback
    DS_BENCH_OFFLOAD_SYNTH_GB when the backend reports no bytes_limit,
    e.g. the CPU lane) trains on the host-DRAM tier, and its measured
    step time is compared against the on-chip row extrapolated by the
    flops ratio (`offload_synth_overlap_fraction` — the >0.8 target)."""
    jax = _setup_jax()
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
    n_chips = len(jax.devices())
    cfg, model, params = _headline_setup(jax)
    seq = min(int(os.environ.get("DS_BENCH_SEQ", "1024")),
              cfg.max_seq_len)
    bs = int(os.environ.get("DS_BENCH_OFFLOAD_BS", "8"))
    batch = bs * n_chips
    prefetch = int(os.environ.get("DS_BENCH_OFFLOAD_PREFETCH", "2"))
    group = int(os.environ.get("DS_BENCH_OFFLOAD_GROUP", "4"))
    steps = int(os.environ.get("DS_BENCH_OFFLOAD_STEPS", "6"))
    sched = {"mode": "explicit", "prefetch_depth": prefetch,
             "group_layers": group}
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(1, batch, seq),
                          dtype=np.int32)
    out = {"offload_prefetch_depth": prefetch,
           "offload_group_layers": group,
           "offload_batch_per_chip": bs, "offload_seq": seq}

    def run(tag, zero_cfg, mdl=None, prm=None, toks=None, n_steps=steps,
            warmup=2, bsize=None):
        def thunk():
            eng = _neox_engine(mdl or model, prm if prm is not None
                               else params, bsize or batch, zero_cfg)
            t = toks if toks is not None else tokens
            # warmup OUTSIDE timed_steps, then snapshot the offload
            # counters: compile-time waits and cold first uploads would
            # otherwise inflate the stall fraction / wire volume of the
            # timed window
            for _ in range(warmup):
                eng.train_batch(batch=(t, t))
            base = dict(getattr(eng, "_offload_totals", {}))
            dt, loss = timed_steps(eng, (t, t), steps=n_steps, warmup=0)
            res = {f"offload_{tag}_step_ms": round(dt / n_steps * 1e3, 1),
                   f"offload_{tag}_loss": round(loss, 3)}
            tot = {k: v - base.get(k, 0)
                   for k, v in dict(getattr(eng,
                                            "_offload_totals",
                                            {})).items()}
            if tot.get("bytes_h2d"):
                res[f"offload_{tag}_stall_fraction"] = round(
                    tot.get("prefetch_stall_s", 0.0) / dt, 4)
                res[f"offload_{tag}_h2d_gb"] = round(
                    tot["bytes_h2d"] / 2**30, 3)
                res[f"offload_{tag}_d2h_gb"] = round(
                    tot["bytes_d2h"] / 2**30, 3)
            del eng
            gc.collect()
            return res
        return thunk

    onchip_zero = {"stage": 3, "schedule": dict(sched)}
    host_zero = {"stage": 3, "schedule": dict(sched),
                 "offload_optimizer": {"device": "cpu"},
                 "offload_param": {"device": "cpu"}}
    out = _ladder([("explicit", run("onchip", onchip_zero))], out,
                  "offload_onchip")
    out = _ladder([("host_dram", run("host", host_zero))], out,
                  "offload_host")
    nvme_path = os.environ.get("DS_BENCH_OFFLOAD_NVME")
    if nvme_path:
        nvme_zero = {"stage": 3, "schedule": dict(sched),
                     "offload_optimizer": {"device": "cpu"},
                     "offload_param": {"device": "nvme",
                                       "nvme_path": nvme_path}}
        out = _ladder([("nvme", run("nvme", nvme_zero))], out,
                      "offload_nvme")
    if "offload_onchip_step_ms" in out and "offload_host_step_ms" in out:
        out["offload_host_vs_onchip"] = round(
            out["offload_onchip_step_ms"] / out["offload_host_step_ms"],
            4)

    # --- synthetic beyond-HBM rung ------------------------------------
    try:
        hbm = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    except Exception:  # noqa: BLE001 - backends without memory_stats
        hbm = None
    ratio = float(os.environ.get("DS_BENCH_OFFLOAD_RATIO", "4"))
    if hbm:
        target = ratio * hbm
    else:
        target = float(os.environ.get(
            "DS_BENCH_OFFLOAD_SYNTH_GB", "0.5")) * 2**30
    H, V = 2048, cfg.vocab_size
    itemsize = 2   # bf16 compute rows are what rest in DRAM
    L = max(2, int(-(-(target / itemsize - V * H) // (12 * H * H))))
    synth_cfg = GPTNeoXConfig(vocab_size=V, hidden_size=H,
                              num_layers=L, num_heads=16,
                              max_seq_len=256)
    synth_seq = min(256, seq)
    sbs = max(n_chips, int(os.environ.get("DS_BENCH_OFFLOAD_SYNTH_BS",
                                          str(n_chips))))
    synth_model = GPTNeoX(synth_cfg, use_pallas=True)
    cpu0 = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu0):
        # init on HOST: the whole point is that this model does not fit
        # HBM — params flow host-init -> row store, never full on chip
        synth_params = synth_model.init_params(jax.random.PRNGKey(0))
    synth_bytes = synth_cfg.num_params() * itemsize
    out["offload_synth_params_m"] = round(synth_cfg.num_params() / 1e6, 1)
    out["offload_synth_hbm_ratio"] = (
        round(synth_bytes / hbm, 2) if hbm else None)
    stoks = rng.integers(0, V, size=(1, sbs, synth_seq), dtype=np.int32)

    def synth_done(res):
        # extrapolate the on-chip row to the synthetic shape by the
        # flops ratio (same schedule, same per-flop speed assumption)
        if "offload_onchip_step_ms" in out:
            base = out["offload_onchip_step_ms"]
            scale = ((_flops_per_token(synth_cfg, synth_seq)
                      * sbs * synth_seq)
                     / (_flops_per_token(cfg, seq) * batch * seq))
            extrapolated = base * scale
            res["offload_synth_extrapolated_onchip_ms"] = round(
                extrapolated, 1)
            res["offload_synth_overlap_fraction"] = round(
                extrapolated / res["offload_synth_step_ms"], 4)
        return res

    out = _ladder([("synth_host_dram", lambda: synth_done(run(
        "synth", host_zero, mdl=synth_model, prm=synth_params,
        toks=stoks, n_steps=2, warmup=1, bsize=sbs)()))], out,
        "offload_synth")
    return out


def row_quant():
    """Low-precision row (opt-in DS_BENCH_QUANT=1; docs/quantization.md).
    Three measurements on the headline 125M shape:

    (a) bf16 vs int8-WEIGHT decode: a fixed decode-heavy serve stream
        run at both weight precisions — decode tokens/s and p50
        inter-token. Decode is weight-bandwidth bound, so the ≥1.5×
        acceptance gate applies ON TPU (the Pallas dequant-in-kernel
        path); CPU hosts record the row through the XLA fallback, where
        the ratio is informational only.
    (b) int8-KV capacity: resident sessions at a FIXED pool byte budget
        (DS_BENCH_QUANT_POOL_MB) for bf16 vs int8 pools — the ≥1.9×
        gate is pure accounting (per-page scale pools included).
    (c) compressed vs dense cross-host DP gradients: explicit ZeRO-3
        step time with and without the error-feedback sign-compressed
        reduce-scatter (quantization.gradient_compression).

    Knobs ride in extra; DS_BENCH_QUANT_* envs override the defaults.
    """
    jax = _setup_jax()
    n_chips = len(jax.devices())
    cfg, model, params = _headline_setup(jax)
    out = {}

    max_new = int(os.environ.get("DS_BENCH_QUANT_NEW", "48"))
    n_req = int(os.environ.get("DS_BENCH_QUANT_REQUESTS", "16"))
    prompt_len = int(os.environ.get("DS_BENCH_QUANT_PROMPT", "62"))

    def serve(tag, weight_quant, kv_dtype=None):
        def thunk():
            from deeperspeed_tpu.inference import InferenceEngine
            conf = {"inference": {
                "enabled": True, "page_size": 64, "num_pages": 257,
                "max_batch_size": 16, "token_budget": 2048,
                "prefill_batch_sizes": [4],
                "prefill_lengths": [64],
                "decode_batch_sizes": [16]}}
            if kv_dtype:
                conf["inference"]["kv_cache_dtype"] = kv_dtype
            if weight_quant:
                conf["quantization"] = {"weights": weight_quant}
            eng = InferenceEngine(model, config=conf, params=params)
            rng = np.random.default_rng(0)
            prompts = [list(rng.integers(1, cfg.vocab_size,
                                         size=prompt_len))
                       for _ in range(n_req)]
            # warm both programs, then measure a decode-heavy stream
            eng.generate([prompts[0]], max_new_tokens=2)
            warm = dict(eng.stats)
            itl = []
            last = {}
            t0 = time.perf_counter()
            for p in prompts:
                eng.submit(p, max_new_tokens=max_new)
            while eng.scheduler.has_work:
                eng.step()
                now = time.perf_counter()
                for r in eng.scheduler.running:
                    k = len(r.generated)
                    if last.get(r.request_id, (0, 0))[0] < k:
                        prev = last.get(r.request_id)
                        if prev is not None:
                            itl.append(now - prev[1])
                        last[r.request_id] = (k, now)
            dt = time.perf_counter() - t0
            dtok = eng.stats["decode_tokens"] - warm["decode_tokens"]
            dsec = eng.stats["decode_s"] - warm["decode_s"]
            return {
                f"quant_decode_tok_s_{tag}": round(dtok / max(dsec,
                                                              1e-9), 1),
                f"quant_stream_tok_s_{tag}": round(dtok / dt, 1),
                f"quant_p50_token_ms_{tag}": (
                    round(float(np.percentile(itl, 50)) * 1e3, 2)
                    if itl else None),
                f"quant_weight_dtype_{tag}": eng.dtypes["weight"],
                f"quant_kv_dtype_{tag}": eng.dtypes["kv_cache"],
            }
        return thunk

    # three rungs, one axis at a time: the ≥1.5× weight gate must
    # measure WEIGHTS alone (int8 KV changes attention numerics and
    # adds quantize/dequantize work — conflating them makes the ratio
    # unattributable); the combined rung records the deployment config
    out = _ladder([("bf16", serve("bf16", None))], out, "quant_bf16")
    gc.collect()
    out = _ladder([("int8w", serve("int8w", "int8"))], out, "quant_int8w")
    gc.collect()
    out = _ladder([("int8w_int8kv", serve("int8w_int8kv", "int8",
                                          "int8"))],
                  out, "quant_int8w_int8kv")
    gc.collect()
    a, b = (out.get("quant_decode_tok_s_int8w"),
            out.get("quant_decode_tok_s_bf16"))
    if a and b:
        out["quant_int8_weight_decode_speedup"] = round(a / b, 3)

    # (b) int8-KV resident-session capacity at fixed pool bytes —
    # accounting over the real cache geometry (scale pools included)
    def kv_capacity():
        def thunk():
            from deeperspeed_tpu.inference.kv_cache import PagedKVCache
            import jax.numpy as jnp
            pool_mb = int(os.environ.get("DS_BENCH_QUANT_POOL_MB", "1024"))
            sess_tokens = int(os.environ.get("DS_BENCH_QUANT_SESSION_TOK",
                                             "1024"))
            res = {}
            for tag, dt_ in (("bf16", jnp.bfloat16), ("int8", jnp.int8)):
                c = PagedKVCache(num_layers=cfg.num_layers, num_pages=2,
                                 num_heads=cfg.num_heads, page_size=64,
                                 head_dim=cfg.head_dim, dtype=dt_)
                sessions = (pool_mb << 20) // (c.bytes_per_token()
                                               * sess_tokens)
                res[f"quant_kv_sessions_{tag}"] = int(sessions)
                res[f"quant_kv_bytes_per_token_{tag}"] = \
                    c.bytes_per_token()
            res["quant_kv_capacity_ratio"] = round(
                res["quant_kv_sessions_int8"] /
                max(res["quant_kv_sessions_bf16"], 1), 3)
            res["quant_kv_pool_mb"] = pool_mb
            res["quant_kv_session_tokens"] = sess_tokens
            return res
        return thunk

    out = _ladder([("acct", kv_capacity())], out, "quant_kv")

    # (c) compressed vs dense DP-grad step time on the explicit schedule
    seq = min(int(os.environ.get("DS_BENCH_QUANT_SEQ", "256")),
              cfg.max_seq_len)
    bs = int(os.environ.get("DS_BENCH_QUANT_BS", "4"))
    steps = int(os.environ.get("DS_BENCH_QUANT_STEPS", "6"))

    def grads(tag, compress):
        def thunk():
            batch = bs * n_chips
            rng = np.random.default_rng(0)
            tokens = rng.integers(0, cfg.vocab_size, size=(1, batch, seq),
                                  dtype=np.int32)
            zero_cfg = {"stage": 3,
                        "stage3_param_persistence_threshold": 0,
                        "schedule": {"mode": "explicit"}}
            extra_cfg = {}
            if compress:
                extra_cfg["quantization"] = {
                    "gradient_compression": {"enabled": True}}
            eng = _neox_engine(model, params, batch, zero_cfg, extra_cfg)
            dt, _ = timed_steps(eng, (tokens, tokens), steps=steps,
                                warmup=2)
            return {f"quant_grad_step_ms_{tag}": round(
                dt / steps * 1e3, 1)}
        return thunk

    out = _ladder([("dense", grads("dense", False))], out, "quant_gdense")
    gc.collect()
    if n_chips > 1:
        out = _ladder([("compressed", grads("compressed", True))], out,
                      "quant_gcomp")
    else:
        # a 1-chip dp world has no gather to compress (every leaf rests
        # replicated) — record the skip instead of a misleading error
        out["quant_gcomp_skipped"] = "single-chip dp world: no " \
            "cross-host gradient collective to compress"
    a, b = (out.get("quant_grad_step_ms_dense"),
            out.get("quant_grad_step_ms_compressed"))
    if a and b:
        out["quant_grad_compress_speedup"] = round(a / b, 3)
    out["quant_knobs"] = {
        "max_new": max_new, "requests": n_req, "prompt": prompt_len,
        "seq": seq, "bs": bs, "steps": steps}
    return out


def row_rl():
    """Online-RL row (docs/rl.md): the co-located train+serve loop on a
    CPU-proxy NeoX. Measures rollout throughput under the
    continuous-batching scheduler, the PPO update step, train->serve
    hot-swap latency, the zero-recompile pin (compile delta across the
    timed iterations must be 0), and the co-residency tax — the SAME
    pretraining step timed alone vs with the RL engine pair (train
    engine + serving engine + its KV pool) resident; the acceptance
    target is <=10% degradation. Scale with DS_BENCH_RL_{HIDDEN,
    LAYERS,BS,ITERS,...}; opt-in via DS_BENCH_RL=1."""
    jax = _setup_jax()
    import deeperspeed_tpu
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
    from deeperspeed_tpu.rl import RLDriver

    n_chips = len(jax.devices())
    hidden = int(os.environ.get("DS_BENCH_RL_HIDDEN", "256"))
    layers = int(os.environ.get("DS_BENCH_RL_LAYERS", "4"))
    heads = int(os.environ.get("DS_BENCH_RL_HEADS", "8"))
    vocab = int(os.environ.get("DS_BENCH_RL_VOCAB", "8192"))
    bs = int(os.environ.get("DS_BENCH_RL_BS", "8"))    # rollouts / chip
    iters = int(os.environ.get("DS_BENCH_RL_ITERS", "4"))
    steps = int(os.environ.get("DS_BENCH_RL_STEPS", "6"))
    max_new = int(os.environ.get("DS_BENCH_RL_MAX_NEW", "16"))
    prompt_len = int(os.environ.get("DS_BENCH_RL_PROMPT", "32"))

    bs += bs % 2                       # group_size-2 pairing
    rollouts = bs * n_chips
    seq = -(-(prompt_len + max_new) // 8) * 8

    cfg = GPTNeoXConfig(vocab_size=vocab, hidden_size=hidden,
                        num_layers=layers, num_heads=heads,
                        max_seq_len=max(seq, 128))
    model = GPTNeoX(cfg, use_pallas=False)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    lm_tokens = rng.integers(0, vocab, size=(1, rollouts, seq),
                             dtype=np.int32)
    out = {}

    def train_engine(extra_cfg=None):
        config = {"train_batch_size": rollouts,
                  "gradient_accumulation_steps": 1,
                  "steps_per_print": 10_000,
                  "optimizer": {"type": "Adam", "params": {"lr": 1e-4}}}
        config.update(extra_cfg or {})
        eng, *_ = deeperspeed_tpu.initialize(
            model=model, model_parameters=params, config_params=config)
        return eng

    def run():
        # (a) pure-pretraining baseline: the degradation denominator,
        # measured BEFORE the RL pair exists
        base = train_engine()
        dt, _ = timed_steps(base, (lm_tokens, lm_tokens), steps=steps,
                            warmup=2)
        pre_ms = dt / steps * 1e3

        # (b) the RL loop: warmup iteration compiles every path (serve
        # buckets, eval logits, PPO update), then the timed iterations
        # must hold the zero-recompile pin
        rl_engine = train_engine({"rl": {
            "enabled": True, "loss": "ppo_clip",
            "rollouts_per_iteration": rollouts, "group_size": 2,
            "max_new_tokens": max_new, "sequence_length": seq}})
        pages_per = -(-seq // 16)
        serve_config = {"inference": {
            "enabled": True, "page_size": 16,
            "num_pages": 2 * rollouts * pages_per,
            "max_batch_size": min(rollouts, 8),
            "token_budget": max(2 * rollouts * seq, 512),
            "prefill_lengths": [-(-prompt_len // 16) * 16],
            "prefill_batch_sizes": [1, 2, 4],
            "decode_batch_sizes": [1, 2, 4, 8],
            "temperature": 1.0, "seed": 7}}
        prompts = [list(map(int,
                            rng.integers(1, vocab, size=prompt_len)))
                   for _ in range(max(rollouts // 2, 4))]
        driver = RLDriver(rl_engine, prompts,
                          lambda pr, resp: float(len(set(resp))),
                          serve_config)
        driver.run_iteration()
        t0 = time.perf_counter()
        rows = [driver.run_iteration() for _ in range(iters)]
        wall = time.perf_counter() - t0

        roll_s = sum(r["rollout_s"] for r in rows)
        roll_tok = sum(r["rollout_tokens"] for r in rows)
        res = {
            "rl_rollout_tokens_per_s": round(
                roll_tok / max(roll_s, 1e-9), 1),
            # everything in the iteration that is not rollout: behavior/
            # reference logprobs, batch build, the PPO update, the swap
            "rl_update_step_ms": round((wall - roll_s) / iters * 1e3, 1),
            "rl_swap_ms": round(
                sum(r["swap_ms"] for r in rows) / iters, 2),
            "rl_compile_delta": sum(r["compile_delta"] for r in rows),
            "rl_mean_kl": round(rows[-1]["mean_kl"], 5),
        }

        # (c) co-residency tax: the SAME pretraining step, RL pair now
        # resident (no recompile — same engine, same shapes)
        dt2, _ = timed_steps(base, (lm_tokens, lm_tokens), steps=steps,
                             warmup=1)
        co_ms = dt2 / steps * 1e3
        res["rl_pretrain_step_ms"] = round(pre_ms, 1)
        res["rl_colocated_step_ms"] = round(co_ms, 1)
        res["rl_train_step_degradation"] = round(co_ms / pre_ms - 1, 4)
        return res

    out = _ladder([("ppo", run)], out, "rl")
    out["rl_knobs"] = {
        "hidden": hidden, "layers": layers, "rollouts": rollouts,
        "seq": seq, "max_new": max_new, "prompt": prompt_len,
        "iters": iters, "steps": steps}
    return out


def row_multislice():
    """Two-slice DCN drill (opt-in via DS_BENCH_MULTISLICE=1), on a
    CPU-drivable NeoX proxy so the row runs on a single host exactly
    like the fleet regime it models. Two measurements:

    (a) throughput under injected cross-slice latency: the 4-stage 1F1B
    pipeline split 2x2 across a simulated DCN boundary, with the
    `dcn_delay` fault charging DS_BENCH_MS_DELAY_MS per EXPOSED
    crossing every step, on the classic wire (2*n_micro exposed hops)
    and the comm-overlap wire (fill+drain only). Reported as the
    tokens/s ratio vs the same engine run single-slice — the overlap
    wire is the one expected to hold the <=10%-loss bar.

    (b) slice loss: a scripted slice_kill, heartbeat detection,
    emergency checkpoint, in-process `repartition_after_slice_loss` to
    the surviving 2-stage pipeline — MTTR seconds from detection to
    the first surviving optimizer step, with zero survivor restarts by
    construction, plus the loss-trajectory alignment bool vs an
    unfaulted reference engine resumed from the same checkpoint."""
    import copy
    import shutil
    import tempfile

    jax = _setup_jax()
    import deeperspeed_tpu
    from deeperspeed_tpu.elasticity import (SliceLostError,
                                            repartition_after_slice_loss)
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig

    n_chips = len(jax.devices())
    stages = int(os.environ.get("DS_BENCH_MS_STAGES", "4"))
    n_micro = int(os.environ.get("DS_BENCH_MS_MICRO", "8"))
    delay_s = float(os.environ.get("DS_BENCH_MS_DELAY_MS", "1.0")) / 1e3
    seq = int(os.environ.get("DS_BENCH_MS_SEQ", "256"))
    steps = int(os.environ.get("DS_BENCH_MS_STEPS", "8"))
    hidden = int(os.environ.get("DS_BENCH_MS_HIDDEN", "512"))
    if n_chips % stages:
        return {"multislice_error":
                f"stages={stages} does not divide chips={n_chips}"}
    dp = n_chips // stages
    bs = 2 * n_micro * dp
    cfg = GPTNeoXConfig(vocab_size=8192, hidden_size=hidden,
                        num_layers=2 * stages,
                        num_heads=max(hidden // 64, 2),
                        max_seq_len=seq)
    model = GPTNeoX(cfg, use_pallas=False)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(1, bs, seq),
                          dtype=np.int32)
    batch = (tokens, tokens)

    def conf(overlap=False, multislice=False, faults=None, ckpt=None):
        c = {"train_batch_size": bs,
             "steps_per_print": 10_000,
             "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
             "pipeline": {"stages": stages, "micro_batches": n_micro,
                          "comm_overlap": overlap}}
        if multislice:
            c["multislice"] = {"slices": 2, "names": ["s0", "s1"]}
        if faults is not None:
            c["multislice"]["slice_peers"] = {"s0": ["hostA"],
                                              "s1": ["hostB"]}
            c["elasticity"] = {"heartbeat": {
                "enabled": True, "interval_s": 0.05,
                "warn_after_s": 0.15, "fail_after_s": 0.3}}
            c["training_health"] = {"fault_injection": {"faults": faults}}
        if ckpt is not None:
            c["checkpoint"] = {"save_dir": ckpt, "async_save": False}
        return c

    def engine(c):
        eng, *_ = deeperspeed_tpu.initialize(
            model=model, model_parameters=params, config_params=c)
        return eng

    out = {"multislice_dcn_delay_ms": delay_s * 1e3,
           "multislice_n_micro": n_micro, "multislice_stages": stages}

    def wire_race():
        base = engine(conf())
        dt_base, _ = timed_steps(base, batch, steps=steps, warmup=3)
        out["multislice_single_slice_tokens_per_sec"] = round(
            bs * seq * steps / dt_base, 1)
        del base
        gc.collect()
        for overlap, tag in ((False, "classic"), (True, "overlap")):
            # a far-future dcn_delay entry arms the injector; the
            # per-step charge below drives the REAL stall path the
            # fault kind uses, at `delay_s` per exposed crossing
            eng = engine(conf(overlap=overlap, multislice=True,
                              faults=[{"kind": "dcn_delay",
                                       "step": 10 ** 9,
                                       "seconds": delay_s}]))
            exposed = eng._multislice.exposed_crossings(
                n_micro, 2 if overlap else 1)
            for _ in range(3):
                eng.train_batch(batch=batch)
            force(eng.state.params)
            t0 = time.perf_counter()
            for _ in range(steps):
                eng._apply_host_fault({"kind": "dcn_delay",
                                       "seconds": delay_s})
                eng.train_batch(batch=batch)
            force(eng.state.params)
            dt = time.perf_counter() - t0
            out[f"multislice_{tag}_exposed_crossings"] = exposed
            out[f"multislice_{tag}_tput_ratio"] = round(dt_base / dt, 4)
            del eng
            gc.collect()
        return {}

    def chaos():
        workdir = tempfile.mkdtemp(prefix="ds_bench_ms_")
        eng = None
        recovered = None
        reference = None
        try:
            eng = engine(conf(multislice=True, ckpt=workdir,
                              faults=[{"kind": "slice_kill", "step": 3,
                                       "slice": "s1"}]))
            err = None
            try:
                for _ in range(200):
                    eng.train_batch(batch=batch)
                    time.sleep(0.02)
            except SliceLostError as e:
                err = e
            if err is None:
                return {"multislice_chaos_error":
                        "slice_kill never escalated"}
            drill_conf = conf(multislice=True, ckpt=workdir,
                              faults=[{"kind": "slice_kill", "step": 3,
                                       "slice": "s1"}])
            recovered, surv = repartition_after_slice_loss(
                err, drill_conf,
                lambda c: GPTNeoX(cfg, use_pallas=False), workdir)
            recovered.train_batch(batch=batch)
            force(recovered.state.params)
            mttr = time.monotonic() - err.detected_at
            ref_model = GPTNeoX(cfg, use_pallas=False)
            reference, *_ = deeperspeed_tpu.initialize(
                model=ref_model, config_params=copy.deepcopy(surv))
            reference.load_checkpoint(workdir)
            reference.train_batch(batch=batch)
            rec_l = float(recovered.train_batch(batch=batch))
            ref_l = float(reference.train_batch(batch=batch))
            return {
                "multislice_slice_kill_mttr_s": round(mttr, 2),
                "multislice_survivor_stages": surv["pipeline"]["stages"],
                "multislice_survivor_restarts": 0,
                "multislice_trajectory_aligned": bool(
                    abs(rec_l - ref_l) <= 1e-5 * max(abs(ref_l), 1.0)),
            }
        finally:
            for e in (eng, recovered, reference):
                if e is not None and \
                        getattr(e, "peer_monitor", None) is not None:
                    e.peer_monitor.stop()
            shutil.rmtree(workdir, ignore_errors=True)
            gc.collect()

    _ladder([("wire", wire_race)], out, "multislice_wire")
    _ladder([("chaos", chaos)], out, "multislice_chaos")
    return out


ROW_FNS = {"zero3": row_zero3, "bert128": row_bert128,
           "bert512": row_bert512, "gpt2xl": row_gpt2xl,
           "longseq": row_longseq, "moe": row_moe, "ckpt": row_ckpt,
           "sentinel": row_sentinel, "telemetry": row_telemetry,
           "packed": row_packed, "serve": row_serve,
           "serve_chaos": row_serve_chaos,
           "serve_prefix": row_serve_prefix,
           "serve_disagg": row_serve_disagg,
           "elastic": row_elastic, "fleet": row_fleet,
           "pipe": row_pipe, "offload": row_offload,
           "quant": row_quant, "plan": row_plan, "rl": row_rl,
           "multislice": row_multislice}


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------

def rows_enabled():
    sel = os.environ.get("DS_BENCH_ROWS", "all")
    order = list(ROW_ORDER)
    # checkpoint-stall row is opt-in (DS_BENCH_CKPT=1 or an explicit
    # DS_BENCH_ROWS pick): each save writes ~1.5 GB to local disk
    if os.environ.get("DS_BENCH_CKPT", "0") not in ("0", "", "false"):
        order.append("ckpt")
    if os.environ.get("DS_BENCH_SENTINEL", "0") not in ("0", "", "false"):
        order.append("sentinel")
    if os.environ.get("DS_BENCH_TELEMETRY", "0") not in ("0", "", "false"):
        order.append("telemetry")
    if os.environ.get("DS_BENCH_PACKED", "0") not in ("0", "", "false"):
        order.append("packed")
    if os.environ.get("DS_BENCH_SERVE", "0") not in ("0", "", "false"):
        order.append("serve")
    if os.environ.get("DS_BENCH_SERVE_CHAOS", "0") not in \
            ("0", "", "false"):
        order.append("serve_chaos")
    if os.environ.get("DS_BENCH_SERVE_PREFIX", "0") not in \
            ("0", "", "false"):
        order.append("serve_prefix")
    if os.environ.get("DS_BENCH_SERVE_DISAGG", "0") not in \
            ("0", "", "false"):
        order.append("serve_disagg")
    if os.environ.get("DS_BENCH_ELASTIC", "0") not in ("0", "", "false"):
        order.append("elastic")
    if os.environ.get("DS_BENCH_FLEET", "0") not in ("0", "", "false"):
        order.append("fleet")
    if os.environ.get("DS_BENCH_PIPE", "0") not in ("0", "", "false"):
        order.append("pipe")
    if os.environ.get("DS_BENCH_OFFLOAD", "0") not in ("0", "", "false"):
        order.append("offload")
    if os.environ.get("DS_BENCH_QUANT", "0") not in ("0", "", "false"):
        order.append("quant")
    if os.environ.get("DS_BENCH_PLAN", "0") not in ("0", "", "false"):
        order.append("plan")
    if os.environ.get("DS_BENCH_RL", "0") not in ("0", "", "false"):
        order.append("rl")
    if os.environ.get("DS_BENCH_MULTISLICE", "0") not in \
            ("0", "", "false"):
        order.append("multislice")
    if sel in ("all", ""):
        return order
    if sel == "none":               # headline only (perf iteration)
        return []
    picked = {r.strip() for r in sel.split(",")}
    if "bert" in picked:            # back-compat alias
        picked |= {"bert128", "bert512"}
    for opt_in in ("ckpt", "sentinel", "telemetry", "packed", "serve",
                   "serve_chaos", "serve_prefix", "serve_disagg",
                   "elastic", "fleet",
                   "pipe", "offload", "quant", "plan", "rl",
                   "multislice"):
        if opt_in in picked and opt_in not in order:
            order.append(opt_in)
    return [r for r in order if r in picked]


def run_row_subprocess(name, extra):
    """One row in its own process, which holds the chip while it runs
    and has given it back by the time this returns: OOMs and compiler
    crashes stay contained and HBM is fully released. A child that dies,
    times out or prints no JSON is this row's error; nothing retries."""
    timeout = ROW_TIMEOUT.get(name, ROW_TIMEOUT_DEFAULT)
    cmd = [sys.executable, os.path.abspath(__file__), "--row", name]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout, env=os.environ.copy())
    except subprocess.TimeoutExpired:
        extra[f"{name}_row_error"] = f"row timed out after {timeout}s"
        return
    if proc.returncode == 0:
        for line in reversed(proc.stdout.splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    extra.update(json.loads(line))
                    return
                except json.JSONDecodeError:
                    break
    tail = (proc.stderr or proc.stdout or "").strip().splitlines()
    extra[f"{name}_row_error"] = (f"rc={proc.returncode}: " +
                                  " | ".join(tail[-3:]))[:300]


def row_headline():
    """GPT-NeoX-125M ZeRO-2, seq 1024: the number on the JSON line."""
    jax = _setup_jax()
    devices = jax.devices()
    n_chips = len(devices)
    peak = peak_flops_per_chip(devices[0])

    cfg, model, params = _headline_setup(jax)
    seq = 1024
    # bs48 fits the 16GB chip with the single-block attention kernels and
    # runs ~1.5% higher MFU than bs32 (bs64 OOMs); override via env.
    batch_per_chip = int(os.environ.get("DS_BENCH_BS", "48"))
    batch = batch_per_chip * n_chips

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(1, batch, seq),
                          dtype=np.int32)
    stacked = (tokens, tokens)

    engine = _neox_engine(model, params, batch, {"stage": 2})
    steps = int(os.environ.get("DS_BENCH_STEPS", "15"))
    elapsed, final_loss = timed_steps(engine, stacked, steps=steps,
                                      warmup=3)
    tokens_per_sec_chip = batch * seq * steps / elapsed / n_chips

    flops_per_token = _flops_per_token(cfg, seq)
    achieved = tokens_per_sec_chip * flops_per_token
    out = {
        "tokens_per_sec_chip": round(tokens_per_sec_chip, 1),
        "chips": n_chips,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "mfu": round(achieved / peak, 4),
        "achieved_tflops_per_chip": round(achieved / 1e12, 2),
        "params_m": round(cfg.num_params() / 1e6, 1),
        "final_loss": final_loss,
        "seq": seq,
        "batch_per_chip": batch_per_chip,
    }

    # Host-offload needs a fast local host link; opt in via env.
    if os.environ.get("DS_BENCH_OFFLOAD", "0") not in ("0", "", "false"):
        del engine
        gc.collect()
        eng = _neox_engine(model, params, batch,
                           {"stage": 2,
                            "offload_optimizer": {"device": "cpu"}})
        dt, _ = timed_steps(eng, stacked, steps=2, warmup=1)
        tps = batch * seq * 2 / dt / n_chips
        out["zero2_offload_tokens_per_sec_chip"] = round(tps, 1)
        out["zero2_offload_mfu"] = round(tps * flops_per_token / peak, 4)
    return out


ROW_FNS[HEADLINE] = row_headline


def main():
    if len(sys.argv) >= 3 and sys.argv[1] == "--row":
        print(json.dumps(ROW_FNS[sys.argv[2]]()))
        return 0

    # The parent stays off jax: the chip belongs to whichever child is
    # running. The headline is the first child.
    extra = {}

    def emit():
        value = extra.get("tokens_per_sec_chip")
        mfu = extra.get("mfu")
        print(json.dumps({
            "metric": "gpt_neox_125m_tokens_per_sec_per_chip",
            "value": value,
            "unit": "tokens/s/chip",
            "vs_baseline": None if mfu is None else round(mfu / 0.40, 4),
            "extra": extra,
        }), flush=True)

    # Never lose what was measured to a driver time budget: emit() runs
    # on every exit path, marking the row that was cut — and the exit
    # code says whether every row ran to an end.
    def _bail(signum, frame):  # noqa: ARG001
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _bail)
    try:
        for name in [HEADLINE] + rows_enabled():
            try:
                run_row_subprocess(name, extra)
            except KeyboardInterrupt:
                extra[f"{name}_row_error"] = "interrupted (time budget)"
                extra["rows_interrupted"] = name
                break
    finally:
        emit()
    failed = sorted(k for k in extra if k.endswith("_error"))
    if failed:
        print(f"bench: rows ended in error: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
