#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, in this one process, on the TPU
this machine holds.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of the standard output is the result: one JSON object with
`correct`, `attempted`, `failed`, `metrics` and `device` (and `breakdown`
with `--trace 1`). `--trace 0` reports the cell's end-to-end metrics from
the host's clock with the profiler off; `--trace 1` records a short
stretch inside the window with the profiler on and reports the per-layer
metrics. Earlier lines are the run's record for a reader (checks, counts,
set-up account); the driver ignores them.

It needs a TPU with at least the chips the cell asks for: without, it
says so, prints no result and exits non-zero. Nothing here has a CPU
branch; `benchmarks/tests/` rehearses the same functions on the CPU at a
tiny size by steering them from outside.
"""

import time

T_START = time.perf_counter()       # before anything heavy is imported

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CACHE_DIR = os.path.join(ROOT, ".xla_cache")


def configure_jax():
    """The persistent compile cache at a fixed path inside the checkout
    (or where `JAX_COMPILATION_CACHE_DIR` places it), holding every
    program: the serving programs that compile in under a second too."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def note(record):
    print(json.dumps(record, default=str), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from benchmarks import harness
    from benchmarks.compile_log import CompileLog
    spec = harness.load_cell(ROOT, args.workload)
    # the program's own environment knobs, where the cell's file names
    # any: set before the program is imported
    os.environ.update(spec["cell"].get("env", {}))

    jax = configure_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec["chips"]:
        print(f"benchmarks/run.py: {args.workload} needs {spec['chips']} "
              f"TPU chip(s); jax reports {len(devices)} device(s) of "
              f"platform {devices[0].platform!r}", file=sys.stderr)
        return 1
    devices = devices[:spec["chips"]]
    harness.peaks_for(spec, devices[0].device_kind)
    log = CompileLog()

    rec = harness.run_cell(spec, args.seed, args.seconds, args.trace,
                           T_START, log, devices)
    note({"workload": args.workload, "seed": args.seed,
          "checks": rec["checks"], "check": rec["check"],
          "window_s": rec["window_s"], "setup_s": rec["setup_s"],
          "compile": log.snapshot(),
          "compiles_in_window": rec["compiles_in_window"],
          "dispatch": rec["dispatch"],
          "worst_device": (rec["trace"] or {}).get("worst_device"),
          "trace_path": rec.get("trace_path")})
    note(harness.result_line(spec, rec, args.trace, devices))
    return 0


if __name__ == "__main__":
    sys.exit(main())
