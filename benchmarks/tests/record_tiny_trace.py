#!/usr/bin/env python3
"""Record the small traces under `benchmarks/testdata/` on the chip.

    chiprun --chips 4 -- python3 benchmarks/tests/record_tiny_trace.py \
        pythia-1.4b.train_zero3_4c tiny_zero3_4c_scoped
    chiprun -- python3 benchmarks/tests/record_tiny_trace.py \
        pythia-1.4b.serve_closed32 tiny_serve_scoped

Runs the named cell through the harness at the rehearsal's tiny size
(`test_rehearsal.tiny`) with a traced stretch of one train step, or a few
hundredths of a second of serving, and writes into `chiprun_out/testdata/`
the trace packed with xz and `<name>.expected.json`: what
`trace_reduce.reduce_file` and `scope_reduce.reduce_file` make of it.
`test_scope_reduce.py` holds the readers to those files. One process, one
cell: a chip belongs to one process at a time.
"""

import json
import lzma
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.dirname(os.path.abspath(__file__))]


def record(cell, name, out_dir, devices):
    import pathlib
    import tempfile

    from benchmarks import harness, scope_reduce, trace_reduce
    from benchmarks.compile_log import CompileLog
    from test_rehearsal import checkout_with_links, tiny
    spec = tiny(harness.load_cell(ROOT, cell))
    # the raw trace lands under a root of its own, not in `out_dir`
    spec["root"] = checkout_with_links(pathlib.Path(tempfile.mkdtemp()))
    spec["cell"].update(traced_steps=1, trace_after_s=0.1,
                        traced_seconds=0.03)
    rec = harness.run_cell(spec, seed=3, seconds=1.0, trace=True,
                           t_start=T_START, log=CompileLog(),
                           devices=devices[:spec["chips"]])
    os.makedirs(os.path.join(out_dir, "testdata"), exist_ok=True)
    packed = os.path.join(out_dir, "testdata", name + ".xplane.pb.xz")
    with open(rec["trace_path"], "rb") as f, \
            lzma.open(packed, "wb", preset=9 | lzma.PRESET_EXTREME) as g:
        g.write(f.read())
    expected = {"cell": cell, "checks": rec["checks"],
                "trace": trace_reduce.reduce_file(packed),
                "scopes": scope_reduce.reduce_file(packed)}
    with open(os.path.join(out_dir, "testdata",
                           name + ".expected.json"), "w") as f:
        json.dump(expected, f, indent=1)
    return packed, expected


def main(argv):
    cell, name = argv
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"record_tiny_trace.py needs a TPU; jax reports "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    packed, expected = record(cell, name,
                              os.path.join(ROOT, "chiprun_out"), devices)
    print(json.dumps(expected["scopes"], indent=1))
    print(packed, os.path.getsize(packed), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
