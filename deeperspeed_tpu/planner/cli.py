"""`ds_plan` — emit, inspect, and refresh persisted schedule plans.

Mirrors `ds_lint`/`ds_report`: zero-argument friendly, `--json` for
machine consumers. Default run is ANALYTIC-ONLY (no device work, safe
on a backendless host); `--probe` opts into the measured ladder, which
builds a real engine per surviving rung and times actual train steps
(multi-host and off a TPU it degrades to the analytic winner).
"""

import argparse
import json
import sys

from .cost_model import ModelShape
from .plan import latest_plan, plan_cache_dir
from .search import build_plan, candidate_config

# Named model geometries.
PRESETS = {
    "125m": dict(num_layers=12, hidden_size=768, num_heads=12,
                 seq_len=1024, vocab_size=50304, batch_per_chip=48),
    "1.3b": dict(num_layers=24, hidden_size=2048, num_heads=16,
                 seq_len=1024, vocab_size=50304, batch_per_chip=16),
    "gpt2xl": dict(num_layers=48, hidden_size=1600, num_heads=25,
                   seq_len=1024, vocab_size=50304, batch_per_chip=8),
}


def _shape_from_args(args):
    if args.preset:
        base = dict(PRESETS[args.preset])
    else:
        base = {}
    for field, flag in (("num_layers", args.layers),
                        ("hidden_size", args.hidden),
                        ("num_heads", args.heads),
                        ("seq_len", args.seq),
                        ("vocab_size", args.vocab),
                        ("batch_per_chip", args.batch_per_chip)):
        if flag is not None:
            base[field] = int(flag)
    missing = [f for f in ("num_layers", "hidden_size", "num_heads",
                           "seq_len", "vocab_size", "batch_per_chip")
               if f not in base]
    if missing:
        raise SystemExit(
            f"ds_plan: missing model shape fields {missing}; pass "
            f"--preset {{{','.join(sorted(PRESETS))}}} or the explicit "
            f"flags")
    return ModelShape(**base)


def _make_probe(shape, stage):
    """candidate -> blockable: one real train step on the candidate's
    resolved config (the Autotuner times it outside traced code; its
    warmup call absorbs the XLA compile)."""
    import numpy as np

    import jax

    import deeperspeed_tpu
    from ..models.gpt_neox import GPTNeoX, GPTNeoXConfig

    cfg = GPTNeoXConfig(vocab_size=shape.vocab_size,
                        hidden_size=shape.hidden_size,
                        num_layers=shape.num_layers,
                        num_heads=shape.num_heads,
                        max_seq_len=shape.seq_len)
    model = GPTNeoX(cfg, use_pallas=True)
    params = model.init_params(jax.random.PRNGKey(0))
    n_chips = len(jax.devices())
    batch = shape.batch_per_chip * n_chips
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size,
                          size=(1, batch, shape.seq_len), dtype=np.int32)
    engines = {}

    def probe(cand):
        eng = engines.get(cand)
        if eng is None:
            config_params = {
                "train_batch_size": batch,
                "gradient_accumulation_steps": 1,
                "steps_per_print": 10_000,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                "fp16": {"enabled": True, "type": "bfloat16"},
            }
            config_params.update(candidate_config(cand, stage))
            eng, *_ = deeperspeed_tpu.initialize(
                model=model, model_parameters=params,
                config_params=config_params)
            engines[cand] = eng
        return eng.train_batch(batch=(tokens, tokens))

    return probe


def _print_plan(plan, out=sys.stdout):
    p = plan.payload
    print("-" * 64, file=out)
    print("DeeperSpeed-TPU schedule plan", file=out)
    print("-" * 64, file=out)
    rows = [("fingerprint", p["fingerprint"]),
            ("device kind", p["device_kind"]),
            ("model shape", p["shape_key"]),
            ("world", p["world"]),
            ("chosen", p["chosen"]),
            ("probed", p["probed"])]
    sched = p["config"]["zero_optimization"]["schedule"]
    rows += [(f"schedule.{k}", v) for k, v in sorted(sched.items())]
    rows += [("activation ckpt",
              p["config"]["activation_checkpointing"]["policy"]),
             ("kernels", {k: v for k, v in p["kernels"].items()
                          if v is not None} or "none resolved")]
    for name, value in rows:
        print(f"{name:.<24} {value}", file=out)
    ladder = p["analytic"]["ladder"]
    print("analytic ladder (fastest first):", file=out)
    for label, s in sorted(ladder.items(),
                           key=lambda kv: kv[1]["step_s"]):
        print(f"  {label:<28} step {s['step_s'] * 1e3:8.2f} ms  "
              f"(compute {s['compute_s'] * 1e3:.2f}, collective "
              f"{s['collective_s'] * 1e3:.2f}, mem "
              f"{s['memory_bytes'] / (1 << 30):.2f} GiB)", file=out)
    print("-" * 64, file=out)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="ds_plan",
        description="profile-guided schedule planner (docs/planner.md)")
    ap.add_argument("--preset", choices=sorted(PRESETS))
    ap.add_argument("--layers", type=int)
    ap.add_argument("--hidden", type=int)
    ap.add_argument("--heads", type=int)
    ap.add_argument("--seq", type=int)
    ap.add_argument("--vocab", type=int)
    ap.add_argument("--batch-per-chip", type=int)
    ap.add_argument("--stage", type=int, default=3)
    ap.add_argument("--top-k", type=int, default=None,
                    help="analytic survivors to probe (default 4)")
    ap.add_argument("--probe", action="store_true",
                    help="measure the surviving rungs on real steps "
                         "(needs a real accelerator; degrades to "
                         "analytic-only without one)")
    ap.add_argument("--quant", action="store_true",
                    help="let the plan consider quantized-FFN recipes "
                         "(changes training numerics; default off)")
    ap.add_argument("--no-offload", action="store_true",
                    help="exclude offload tiers from the search")
    ap.add_argument("--force", action="store_true",
                    help="replan even when a cached plan exists")
    ap.add_argument("--cache-dir", default=None,
                    help=f"plan cache (default {plan_cache_dir()})")
    ap.add_argument("--out", default=None,
                    help="also write the plan JSON to this path")
    ap.add_argument("--show", action="store_true",
                    help="print the newest cached plan and exit")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    if args.show:
        plan = latest_plan(args.cache_dir)
        if plan is None:
            print("ds_plan: no cached plans", file=sys.stderr)
            return 1
    else:
        shape = _shape_from_args(args)
        probe = None
        if args.probe:
            probe = _make_probe(shape, args.stage)
        kwargs = dict(stage=args.stage, probe=probe,
                      cache_dir=args.cache_dir, force=args.force,
                      allow_quant=args.quant,
                      allow_offload=not args.no_offload)
        if args.top_k is not None:
            kwargs["top_k"] = args.top_k
        plan = build_plan(shape, **kwargs)
        if args.out:
            plan.save(path=args.out)

    if args.json:
        print(json.dumps(plan.payload, indent=2, sort_keys=True,
                         default=str))
    else:
        _print_plan(plan)
    return 0


if __name__ == "__main__":
    sys.exit(main())
