#!/usr/bin/env python3
"""Run one cell with a LOWER PRECISION or a DELIBERATE FAULT patched into
the program from outside, for the readings a cell's limits are set
between (PERF.md section 6; a builder's tool, never a benchmark run):

    python3 benchmarks/tests/precision_probe.py <what> --workload <cell> --seed <n> --seconds <s> --trace 0

`what`: `fp8_weights` (the engine's attention projection weights rounded
to e4m3, a scale a tensor; the reference reads them as stated),
`fp8_kv` (K and V rounded to e4m3 as they leave their projection),
`bf16_state` (the scan's recurrent state rounded to bfloat16 at every
step), `padding_moves_state` (a prefill that takes its bucket's padding
rows for real ones), `slot_not_zeroed` (a prefill whose state is added to
what its slot held), `none`. Rounding is `jax.lax.reduce_precision`: XLA
removes an `astype` pair.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT]


def e4m3(x):
    """x rounded to float8 e4m3 under one scale a tensor (to 240: the
    IEEE-like form `reduce_precision(4, 3)` overflows above it)."""
    import jax
    import jax.numpy as jnp
    scale = 240.0 / jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))),
                                1e-30)
    return (jax.lax.reduce_precision(x.astype(jnp.float32) * scale, 4, 3)
            / scale).astype(x.dtype)


def patch(what):
    import jax
    import jax.numpy as jnp
    import deeperspeed_tpu.inference as inference
    from deeperspeed_tpu.inference import engine as engine_mod
    from deeperspeed_tpu.models import gpt_neox as neox
    from deeperspeed_tpu.ops.pallas import ssm as ssm_ops
    if what == "fp8_weights":
        class Rounded(inference.InferenceEngine):
            def __init__(self, model, config=None, params=None, **kw):
                stacks = {}
                for kind, stack in params["stacks"].items():
                    attn = {k: jax.jit(jax.vmap(e4m3))(v)
                            if k in ("q_w", "kv_w", "out_w") and
                            "lam0" in stack["attn"] else v
                            for k, v in stack["attn"].items()}
                    stacks[kind] = dict(stack, attn=attn)
                super().__init__(model, config=config,
                                 params=dict(params, stacks=stacks), **kw)
        inference.InferenceEngine = engine_mod.InferenceEngine = Rounded
    elif what == "fp8_kv":
        qkv = neox._block_qkv

        def rounded(*a, **kw):
            q, k, v = qkv(*a, **kw)
            return q, None if k is None else e4m3(k), \
                None if v is None else e4m3(v)
        neox._block_qkv = rounded
    elif what == "bf16_state":
        step = ssm_ops._time_step

        def rounded(*a):
            h, s = step(*a)
            return [r.astype(jnp.bfloat16).astype(jnp.float32)
                    for r in h], s
        ssm_ops._time_step = rounded
    elif what == "padding_moves_state":
        mixer = neox.ssm_mixer
        neox.ssm_mixer = lambda cfg, p, a, real=None, use_pallas=True: \
            mixer(cfg, p, a, None, use_pallas)
    elif what == "slot_not_zeroed":
        engine_mod.InferenceEngine._write_state = staticmethod(
            lambda pool, new, slots: pool.at[:, slots].add(
                new.astype(pool.dtype)))
    elif what != "none":
        raise SystemExit(f"unknown probe {what!r}")


if __name__ == "__main__":
    what = sys.argv.pop(1)
    from benchmarks import run
    run.configure_jax()
    patch(what)
    sys.exit(run.main())
