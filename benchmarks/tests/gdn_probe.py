#!/usr/bin/env python3
"""`precision_probe.py` for a model of Gated DeltaNet layers (qwen3_next):
one cell run with a LOWER PRECISION or a DELIBERATE FAULT patched into the
program from outside, for the readings the cell's limits are set between
(PERF.md section 6; a builder's tool, never a benchmark run):

    python3 benchmarks/tests/gdn_probe.py <what> --workload <cell> --seed <n> --seconds <s> --trace 0

`what`: `bf16_state` (the delta rule's matrix states rounded to bfloat16
where they rest: after a prefill's walk and after every decode step),
`fp8_weights` (the engine's mixer projection weights, a gdn layer's `in_w`
and `out_w` and a full layer's `q_w`, `kv_w`, `gate_w` and `out_w`,
rounded to e4m3 with a scale a tensor; the reference reads them as
stated), `padding_moves_state` (a prefill that takes its bucket's padding
rows for real ones); `slot_not_zeroed` and `none` are
`precision_probe.py`'s own. Rounding is `jax.lax.reduce_precision`: XLA
removes an `astype` pair.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

import precision_probe  # noqa: E402

ROUNDED = ("in_w", "out_w", "q_w", "kv_w", "gate_w")


def bf16(x):
    import jax
    return jax.lax.reduce_precision(x, 8, 7)


def patch(what):
    import jax
    import deeperspeed_tpu.inference as inference
    from deeperspeed_tpu.inference import engine as engine_mod
    from deeperspeed_tpu.models import gpt_neox as neox
    from deeperspeed_tpu.ops.pallas import gdn as gdn_ops
    if what == "fp8_weights":
        class Rounded(inference.InferenceEngine):
            def __init__(self, model, config=None, params=None, **kw):
                stacks = {kind: dict(stack, attn={
                    k: jax.jit(jax.vmap(precision_probe.e4m3))(v)
                    if k in ROUNDED else v
                    for k, v in stack["attn"].items()})
                    for kind, stack in params["stacks"].items()}
                super().__init__(model, config=config,
                                 params=dict(params, stacks=stacks), **kw)
        inference.InferenceEngine = engine_mod.InferenceEngine = Rounded
    elif what == "bf16_state":
        chunk, step = gdn_ops.gdn_chunk, gdn_ops.gdn_step

        def rounded_chunk(*a):
            o, state = chunk(*a)
            return o, bf16(state)

        def rounded_step(pools, tail, slots, layer, *a):
            o, (conv, pool) = step(pools, tail, slots, layer, *a)
            return o, (conv, pool.at[layer].set(bf16(pool[layer])))
        gdn_ops.gdn_chunk, gdn_ops.gdn_step = rounded_chunk, rounded_step
    elif what == "padding_moves_state":
        mixer = neox.gdn_mixer
        neox.gdn_mixer = lambda cfg, p, a, real=None: mixer(cfg, p, a)
    else:
        precision_probe.patch(what)


if __name__ == "__main__":
    what = sys.argv.pop(1)
    from benchmarks import run
    run.configure_jax()
    patch(what)
    sys.exit(run.main())
