#!/usr/bin/env python3
"""Record the small qwen3next trace under `benchmarks/testdata/` on the chip.

    chiprun -- python3 benchmarks/tests/record_qwen3next_trace.py

Runs `qwen3-next-80b-a3b.serve_longchat64` through the harness at the
rehearsal's tiny size (`test_qwen3next_cell.tiny_qwen3next`) with a traced stretch of
two tenths of a second (prefills and decode steps), and writes into
`chiprun_out/testdata/` the trace packed with xz and
`tiny_qwen3next_serve.expected.json`: the scopes' reduction, the run's
counters, and what the new readers make of them.
`test_qwen3next_cell.py` holds the readers to those files.
"""

import json
import lzma
import os
import pathlib
import sys
import tempfile
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]
# not `*_scoped`: `test_scope_reduce.py` globs those and wants keys this
# file does not hold
NAME = "tiny_qwen3next_serve"


def main():
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"record_qwen3next_trace.py needs a TPU; jax reports "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    from benchmarks import harness, scope_reduce
    from benchmarks.compile_log import CompileLog
    from test_qwen3next_cell import CELL, NEW, tiny_qwen3next
    from test_rehearsal import checkout_with_links
    spec = tiny_qwen3next(harness.load_cell(ROOT, CELL))
    spec["cell"]["engine"]["inference"].pop("kernel")    # the chip's own
    spec["root"] = checkout_with_links(pathlib.Path(tempfile.mkdtemp()))
    spec["cell"].update(trace_after_s=0.1, traced_seconds=0.2)
    rec = harness.run_cell(spec, seed=3, seconds=1.0, trace=True,
                           t_start=T_START, log=CompileLog(),
                           devices=devices[:1])
    out_dir = os.path.join(ROOT, "chiprun_out", "testdata")
    os.makedirs(out_dir, exist_ok=True)
    packed = os.path.join(out_dir, NAME + ".xplane.pb.xz")
    with open(rec["trace_path"], "rb") as f, \
            lzma.open(packed, "wb", preset=9 | lzma.PRESET_EXTREME) as g:
        g.write(f.read())
    spec["root"] = ROOT
    expected = {
        "cell": CELL, "checks": rec["checks"], "stats": rec["stats"],
        "decode_steps": rec["decode_steps"],
        "traced_stats": rec["traced_stats"], "check": rec["check"],
        "scopes": scope_reduce.reduce_file(rec["trace_path"]),
        "metrics": {name: harness.load_module(ROOT, "metrics", name).read(rec)
                    for name in NEW}}
    with open(os.path.join(out_dir, NAME + ".expected.json"), "w") as f:
        json.dump(expected, f, indent=1)
    print(json.dumps(expected["metrics"], indent=1))
    print(packed, os.path.getsize(packed), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
