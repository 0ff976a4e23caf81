"""The planner's search driver: enumerate → analytic prune → probe.

Subsumes the per-kernel pickers' search discipline behind one driver:
the combinatorial schedule space (mode × prefetch_depth × bucket_mb ×
group_layers × remat × offload tier × quant recipe) is scored by the
analytic cost model and memory-screened down to a small ladder, then
the surviving rungs are ranked on real measured steps through the SAME
`ladder_pick` spine the kernel autotuners run on — so the planner
inherits the Autotuner's measure-once cache, the multi-host
deterministic degrade, and the interpret-mode / `DS_TPU_AUTOTUNE=0`
analytic-only fallbacks for free.
"""

import itertools

from ..ops.autotune import (Autotuner, autotune_enabled, hbm_bytes_limit,
                            ladder_pick)
from . import cost_model as cm
from .plan import PLAN_VERSION, Plan, cached_plan

# The knob grid the analytic model prunes. Small on purpose: the model
# is cheap (microseconds per candidate) but the grid must stay
# readable/loggable; axes with measured flat spots are thinned.
# DEFAULT-FIRST ordering on every axis: the analytic ladder's stable
# sort resolves exact ties (e.g. world=1, where all collective terms
# are zero) toward the hand-tuned defaults, so an
# analytic-only plan never regresses the known-good config on axes the
# model cannot separate — only a measured probe may move off them.
MODES = ("explicit", "gspmd")
PREFETCH_DEPTHS = (2, 1, 4)
BUCKET_MBS = (32.0, 8.0, 128.0)
GROUP_LAYERS = (4, 1, 2)
REMATS = (False, True)
OFFLOADS = ("none", "cpu")
# Quantized FFN recipes are OPT-IN at the build_plan level
# (allow_quant): analytically they always look faster, but they change
# training numerics — a plan should only flip them on when the caller
# asked to consider them (ds_plan --quant) and ideally probed them.
QUANT_FFNS = (None, "int8")

# How many analytic survivors graduate to the measured probe ladder.
DEFAULT_TOP_K = 4

# A dedicated tuner instance: plan probes are whole train steps, one
# timed iteration is plenty (the kernel tuners' 3 would triple an
# already-expensive probe phase).
_plan_tuner = Autotuner(warmup=1, iters=1)


def enumerate_candidates(allow_offload=True, allow_quant=True):
    """The full grid as `Candidate`s. GSPMD mode has no
    prefetch/bucket/group knobs — those collapse to one representative
    per (remat, offload, quant) so the grid carries no dead duplicates."""
    out = []
    offloads = OFFLOADS if allow_offload else ("none",)
    quants = QUANT_FFNS if allow_quant else (None,)
    for mode in MODES:
        knobs = (itertools.product(PREFETCH_DEPTHS, BUCKET_MBS,
                                   GROUP_LAYERS)
                 if mode == "explicit" else ((2, 32.0, 4),))
        for (pf, bmb, gl), remat, off, q in itertools.product(
                knobs, REMATS, offloads, quants):
            out.append(cm.Candidate(mode=mode, prefetch_depth=pf,
                                    bucket_mb=bmb, group_layers=gl,
                                    remat=remat, offload=off,
                                    quant_ffn=q))
    return out


def analytic_ladder(shape, hw, world, stage=3, top_k=DEFAULT_TOP_K,
                    candidates=None, aot_screen=None):
    """Score the grid, drop memory-infeasible points, return the
    `top_k` cheapest as (candidate, scores) rungs, fastest first.

    `aot_screen`, when given, is `candidate -> bool` running the
    caller's `memory_feasible` AOT compile over abstract shapes —
    the concrete screen on top of the analytic byte ledger."""
    rungs = []
    for cand in (candidates or enumerate_candidates()):
        if not cm.memory_feasible_analytic(cand, shape, world,
                                           hw["hbm_limit"], stage):
            continue
        scores = {
            "compute_s": cm.compute_time_s(cand, shape, hw),
            "collective_s": cm.collective_time_s(cand, shape, hw, world),
            "offload_s": cm.offload_time_s(cand, shape, hw, world),
            "memory_bytes": cm.memory_bytes(cand, shape, world, stage),
        }
        scores["step_s"] = (scores["compute_s"] + scores["collective_s"]
                            + scores["offload_s"])
        rungs.append((cand, scores))
    rungs.sort(key=lambda r: r[1]["step_s"])
    rungs = rungs[:max(1, int(top_k))]
    if aot_screen is not None:
        kept = [(c, s) for c, s in rungs if aot_screen(c)]
        rungs = kept or rungs[:1]
    if not rungs:
        raise ValueError(
            "planner: every candidate failed the memory screen "
            f"(shape {shape.key()}, hbm_limit {hw['hbm_limit']})")
    return rungs


def kernel_geometries(shape):
    """The per-kernel block geometries the plan pins, resolved through
    the kernel pickers' own screening tables (their deterministic
    static picks — never a probe: the plan must be emittable on a
    host with no accelerator). Unavailable kernels record None."""
    import jax.numpy as jnp
    out = {}
    head_dim = max(1, shape.hidden_size // max(1, shape.num_heads))
    attn_shape = (shape.batch_per_chip, shape.seq_len, shape.num_heads,
                  head_dim)
    try:
        from ..ops.autotune import _fitted_flash_candidates
        from ..ops.pallas.flash_attention import (
            _fit_block, flash_attention_supported)
        out["flash_blocks"] = list(_fitted_flash_candidates(
            attn_shape, _fit_block, flash_attention_supported)[0])
    except Exception:  # noqa: BLE001 - kernel unavailable on this host
        out["flash_blocks"] = None
    try:
        from ..ops.autotune import (GMM_BLOCK_CANDIDATES,
                                    _GMM_VMEM_BUDGET, _gmm_itemsize,
                                    gmm_vmem_bytes)
        itemsize = _gmm_itemsize(jnp.bfloat16)
        k_dim, n_dim = shape.hidden_size, 4 * shape.hidden_size
        fits = [c for c in GMM_BLOCK_CANDIDATES
                if max(gmm_vmem_bytes(c[0], c[1], k_dim, itemsize),
                       gmm_vmem_bytes(c[0], c[1], n_dim, itemsize))
                <= _GMM_VMEM_BUDGET]
        out["gmm_blocks"] = list(fits[0] if fits
                                 else GMM_BLOCK_CANDIDATES[-1])
    except Exception:  # noqa: BLE001
        out["gmm_blocks"] = None
    try:
        from ..ops.autotune import (_QMM_VMEM_BUDGET,
                                    QMM_BLOCK_CANDIDATES, _gmm_itemsize,
                                    qmm_vmem_bytes)
        itemsize = _gmm_itemsize(jnp.bfloat16)
        fits = [c for c in QMM_BLOCK_CANDIDATES
                if qmm_vmem_bytes(*c, itemsize=itemsize)
                <= _QMM_VMEM_BUDGET]
        out["qmm_blocks"] = list(fits[0] if fits
                                 else QMM_BLOCK_CANDIDATES[-1])
    except Exception:  # noqa: BLE001
        out["qmm_blocks"] = None
    return out


def candidate_config(cand, stage=3):
    """A candidate's resolved config overlay — what the engine's
    `"planner"` block merges under the user's explicit keys."""
    cfg = {
        "zero_optimization": {
            "stage": stage,
            "schedule": {
                "mode": cand.mode,
                "prefetch_depth": int(cand.prefetch_depth),
                "bucket_mb": float(cand.bucket_mb),
                "group_layers": int(cand.group_layers),
                "remat": bool(cand.remat),
            },
        },
        "activation_checkpointing": {
            "policy": "full" if cand.remat else "none",
        },
    }
    if cand.offload != "none":
        cfg["zero_optimization"]["offload_optimizer"] = {
            "device": cand.offload,
            "buffer_count": 1 + max(0, int(cand.prefetch_depth)),
        }
    if cand.quant_ffn:
        cfg["quantization"] = {"ffn": {"recipe": cand.quant_ffn}}
    return cfg


def probes_measurable(probe, measurable):
    """The planner's degrade verdict, mirroring the kernel pickers:
    no probe callable, `DS_TPU_AUTOTUNE=0`/unset, or interpret-mode
    Pallas (no real accelerator) → analytic-only. Multi-host degrade
    lives in `ladder_pick` itself."""
    if measurable is not None:
        return bool(measurable)
    if probe is None or not autotune_enabled():
        return False
    try:
        from ..ops.pallas.flash_attention import _interpret
        if _interpret():
            return False
    except Exception:  # noqa: BLE001 - kernel module unavailable
        pass
    return True


def build_plan(shape, device_kind=None, world=None, stage=3,
               top_k=DEFAULT_TOP_K, probe=None, measurable=None,
               tuner=None, cache_dir=None, force=False,
               allow_offload=True, allow_quant=False, aot_screen=None,
               hbm_limit=None, save=True):
    """The full planner pipeline; returns a `Plan`.

    1. warm cache: a persisted plan for (device kind, shape) short-
       circuits everything — ZERO probes, zero scoring (`force=True`
       replans);
    2. analytic ladder: enumerate → cost-model score → memory screen →
       `top_k` rungs;
    3. probe phase: `ladder_pick` over the rungs with
       `probe(candidate)` as the measure (timed by the Autotuner with
       `perf_counter` outside traced code); degrades to the analytic
       winner per `probes_measurable`;
    4. emit: resolved config + kernel geometries + analytic scores,
       persisted to the plan cache.
    """
    if device_kind is None:
        from ..ops.autotune import _device_kind
        device_kind = _device_kind()
    if world is None:
        try:
            import jax
            world = len(jax.devices())
        except Exception:  # noqa: BLE001 - backendless planning host
            world = 1
    if not force:
        hit = cached_plan(device_kind, shape.key(), cache_dir)
        if hit is not None:
            return hit

    if hbm_limit is None:
        try:
            hbm_limit = hbm_bytes_limit()
        except Exception:  # noqa: BLE001
            hbm_limit = None
    hw = cm.hardware_profile(device_kind, hbm_limit)
    rungs = analytic_ladder(
        shape, hw, world, stage, top_k,
        candidates=enumerate_candidates(allow_offload=allow_offload,
                                        allow_quant=allow_quant),
        aot_screen=aot_screen)
    scores = {c.label(): s for c, s in rungs}

    can_probe = probes_measurable(probe, measurable)
    chosen = ladder_pick(
        ("plan", device_kind, shape.key(), stage),
        [c for c, _ in rungs],
        probe if probe is not None else (lambda cand: None),
        tuner or _plan_tuner,
        measurable=can_probe)

    payload = {
        "version": PLAN_VERSION,
        "device_kind": device_kind,
        "shape_key": shape.key(),
        "world": int(world),
        "stage": int(stage),
        "model_shape": {
            "num_layers": shape.num_layers,
            "hidden_size": shape.hidden_size,
            "num_heads": shape.num_heads,
            "seq_len": shape.seq_len,
            "vocab_size": shape.vocab_size,
            "batch_per_chip": shape.batch_per_chip,
            "param_count": shape.params,
        },
        "chosen": chosen.label(),
        "config": candidate_config(chosen, stage),
        "kernels": kernel_geometries(shape),
        "analytic": {
            "ladder": scores,
            "hardware": {k: hw[k] for k in ("peak_flops",
                                            "ici_bandwidth",
                                            "hbm_limit")},
        },
        "probed": bool(can_probe and len(rungs) > 1),
    }
    plan = Plan(payload)
    if save:
        plan.save(cache_dir=cache_dir)
    return plan
