"""The planner's search driver: enumerate → analytic prune → probe.

The combinatorial schedule space (mode × prefetch_depth × bucket_mb ×
group_layers × remat × offload tier × quant recipe) is scored by the
analytic cost model and memory-screened down to a small ladder; with
`ds_plan --probe` the surviving rungs are then ranked on real measured
steps, offline and outside any trace (`Autotuner`, `ladder_pick`). This
is the only place in the package that times work to choose between
candidates; kernel block geometry never is (`ops/autotune.py`).
"""

import itertools
import time

import jax
import jax.numpy as jnp

from ..ops import autotune
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



class Autotuner:
    """Times callables on the live device, remembers the fastest.

    `pick(key, candidates, run)` → winning candidate. `run(candidate)`
    must execute the candidate end-to-end and return something blockable
    (`jax.block_until_ready` is applied). A candidate that raises (a
    config the engine refuses, an OOM) is disqualified rather than
    fatal."""

    def __init__(self, warmup=1, iters=3, timer=time.perf_counter):
        self.warmup = warmup
        self.iters = iters
        self.timer = timer
        self._cache = {}

    def cached(self, key):
        return self._cache.get((key, autotune._device_kind()))

    def store(self, key, value):
        """Record a decision without measuring."""
        self._cache[(key, autotune._device_kind())] = value
        return value

    def pick(self, key, candidates, run):
        hit = self.cached(key)
        if hit is not None:
            return hit
        best, best_t = None, float("inf")
        for cand in candidates:
            try:
                for _ in range(self.warmup):
                    jax.block_until_ready(run(cand))
                t0 = self.timer()
                for _ in range(self.iters):
                    out = run(cand)
                jax.block_until_ready(out)
                dt = self.timer() - t0
            except Exception:
                continue
            if dt < best_t:
                best, best_t = cand, dt
        if best is None:
            raise RuntimeError(
                f"autotune: every candidate failed for key {key!r}")
        return self.store(key, best)


# Plan probes are whole train steps: one timed iteration is plenty.
_plan_tuner = Autotuner(warmup=1, iters=1)


def ladder_pick(key, candidates, measure, tuner, measurable):
    """Cache hit for (key, device kind) → returned unmeasured. Not
    `measurable`, a multi-host run (per-host wall-clock picks can
    disagree → different programs per host → deadlock at the first
    collective) or a single rung → the first candidate, stored without
    touching the device. Otherwise each candidate is timed through
    `measure(candidate)` and the winner cached."""
    hit = tuner.cached(key)
    if hit is not None:
        return hit
    if not candidates:
        raise ValueError(f"planner: no candidates for key {key!r}")
    if len(candidates) == 1 or not measurable or jax.process_count() > 1:
        return tuner.store(key, candidates[0])
    return tuner.pick(key, candidates, measure)


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


def kernel_geometries(shape, device_kind=None):
    """The per-kernel block geometries the plan pins: what
    `ops.autotune`'s rules give for this shape on `device_kind` (pure
    functions: the plan is emittable on a host with no accelerator)."""
    head_dim = max(1, shape.hidden_size // max(1, shape.num_heads))
    attn_shape = (shape.batch_per_chip, shape.seq_len, shape.num_heads,
                  head_dim)
    try:
        fwd, bwd = autotune.flash_blocks(attn_shape, True, device_kind)
    except ValueError:          # no 128-multiple divides the sequence
        fwd = bwd = None
    return {
        "flash_blocks": fwd and list(fwd),
        "flash_bwd_blocks": bwd and list(bwd),
        "gmm_blocks": list(autotune.grouped_matmul_blocks(
            shape.hidden_size, 4 * shape.hidden_size, jnp.bfloat16)),
        "qmm_blocks": list(autotune.quant_matmul_blocks(jnp.bfloat16)),
    }


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
    """The planner's degrade verdict: measure when the caller handed a
    `probe` and an accelerator is here (off a TPU a timed step ranks the
    Pallas interpreter) → else analytic-only. An explicit `measurable`
    overrides. Multi-host degrade lives in `ladder_pick` itself."""
    if measurable is not None:
        return bool(measurable)
    if probe is None:
        return False
    from ..ops.pallas.flash_attention import _interpret
    return not _interpret()


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
       `probe(candidate)` as the measure (timed by the `Autotuner` with
       `perf_counter` outside traced code); degrades to the analytic
       winner per `probes_measurable`;
    4. emit: resolved config + kernel geometries + analytic scores,
       persisted to the plan cache.
    """
    if device_kind is None:
        device_kind = autotune._device_kind()
    if world is None:
        try:
            world = len(jax.devices())
        except Exception:  # noqa: BLE001 - backendless planning host
            world = 1
    if not force:
        hit = cached_plan(device_kind, shape.key(), cache_dir)
        if hit is not None:
            return hit

    if hbm_limit is None:
        try:
            hbm_limit = autotune.hbm_bytes_limit()
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
        tuner or _plan_tuner, can_probe)

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
        "kernels": kernel_geometries(shape, device_kind),
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
