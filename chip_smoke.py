#!/usr/bin/env python3
"""The standing proof that the trainer and the server start on the chip.

    python chip_smoke.py               # one TPU chip: train, then serve
    python chip_smoke.py --multichip   # four chips: ZeRO-3 over data=4
                                       # against the same steps on one

One process (a chip belongs to one process). It needs a TPU: without one
it says so and exits non-zero, and nothing here has a CPU branch. The
model is `GPTNeoXConfig()` as it stands, full width and full depth, bf16,
with weights and tokens made from `--seed`. Each phase goes through the
entry points a user calls (`deeperspeed_tpu.initialize` /
`engine.train_batch` / `save_checkpoint` / `load_checkpoint`,
`InferenceEngine.submit` / `step`), checks what comes out and prints one
JSON line. A failed check or an exception ends the run non-zero; nothing
is caught and passed over. The last line of the output is
`{"ok": true, "device": {...}}` with the device as jax reports it.

The timings printed are smoke timings, for orientation. They are not
measurements of anything and belong under no metric's name.

The phase functions take the model configuration and sizes as arguments
so that `tests/test_chip_smoke.py` can rehearse them on the CPU at a tiny
size; the script itself has no option for that.
"""

import argparse
import contextlib
import dataclasses
import gc
import json
import sys
import tempfile
import time

import numpy as np

# How far below the reference's best logit the served token's logit may
# lie. Random weights in bf16 give near-ties: the largest logits are a
# few units and bf16 keeps eight bits, so two paths that round at
# different places (flash and paged kernels against one XLA pass) may
# differ by a few hundredths in a logit and swap a near-tied pair.
SERVE_LOGIT_MARGIN = 0.05
# Four devices reduce in another order than one; bf16 keeps eight bits.
MULTICHIP_LOSS_RTOL = 2e-2

class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)


@contextlib.contextmanager
def compile_span():
    """Yields a dict filled, on exit, with what the program's own compile
    account (`telemetry.setup_report()`: programs lowered, persistent
    cache hits, the backend's compile seconds) gained over the span."""
    from deeperspeed_tpu.runtime.telemetry import setup_report
    before, t0 = setup_report()["totals"], time.perf_counter()
    out = {}
    yield out
    after = setup_report()["totals"]
    out["programs"] = after["programs"] - before["programs"]
    out["cache_hits"] = after["cache_hits"] - before["cache_hits"]
    out["compile_s"] = round(after["compile_s"] - before["compile_s"], 2)
    out["wall_s"] = round(time.perf_counter() - t0, 2)


def device_line(devices):
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def bytes_in_use(device):
    stats = device.memory_stats()
    check(stats is not None, f"{device} reports no memory statistics")
    return stats["bytes_in_use"]


def emit(record):
    print(json.dumps(record), flush=True)


def train_config(batch, zero_stage):
    """The README's configuration: Adam, bf16, ZeRO."""
    return {
        "train_batch_size": batch,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10_000,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "fp16": {"enabled": True, "type": "bfloat16"},
        "zero_optimization": {"stage": zero_stage},
    }


def make_batch(cfg, seed, batch, seq):
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=(1, batch, seq), dtype=np.int32)
    return tokens, tokens


def host_params(model, seed):
    """Initial weights on the host, so that no device holds a copy the
    engine does not own."""
    import jax
    return jax.device_get(model.init_params(jax.random.PRNGKey(seed)))


def run_steps(engine, batch, steps):
    """`steps` train steps on one repeated batch. Returns the losses and
    the compile spans of the first step and of the later ones."""
    import jax
    losses = []
    with compile_span() as first:
        losses.append(float(engine.train_batch(batch=batch)))
    with compile_span() as rest:
        for _ in range(steps - 1):
            losses.append(float(engine.train_batch(batch=batch)))
        jax.block_until_ready(engine.state.params)
    return losses, first, rest


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------

def phase_train(cfg, seed, batch, seq, steps, ckpt_dir):
    """Train `steps` steps, save, train one more; load the checkpoint
    into a fresh engine and take that step again. Returns the record."""
    import jax

    import deeperspeed_tpu
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX
    from deeperspeed_tpu.ops import dispatch_report

    model = GPTNeoX(cfg, use_pallas=True)
    data = make_batch(cfg, seed, batch, seq)

    def fresh_engine(param_seed):
        engine, *_ = deeperspeed_tpu.initialize(
            model=model, model_parameters=host_params(model, param_seed),
            config_params=train_config(batch, zero_stage=2))
        return engine

    with compile_span() as whole:
        engine = fresh_engine(seed)
        losses, first, rest = run_steps(engine, data, steps)
        engine.save_checkpoint(ckpt_dir, tag="smoke")
        after_save = float(engine.train_batch(batch=data))
        del engine
        gc.collect()
        # other weights than the run's: only the load can make it agree
        resumed = fresh_engine(seed + 1)
        path, _ = resumed.load_checkpoint(ckpt_dir, tag="smoke")
        check(path is not None, f"no checkpoint loaded from {ckpt_dir}")
        after_load = float(resumed.train_batch(batch=data))
        jax.block_until_ready(resumed.state.params)
        del resumed
        gc.collect()

    report = dispatch_report()
    record = {
        "phase": "train", "model": "gpt-neox", "params": cfg.num_params(),
        "layers": cfg.num_layers, "hidden": cfg.hidden_size,
        "heads": cfg.num_heads, "vocab": cfg.vocab_size,
        "batch": batch, "seq": seq, "steps": steps,
        "dtype": "bfloat16", "zero_stage": 2,
        "losses": [round(l, 4) for l in losses],
        "loss_after_save": round(after_save, 4),
        "loss_after_load": round(after_load, 4),
        "attention_backend": report["attention"].get("attention"),
        "flash": report["flash"],
        "xla_on_tpu": report["xla_on_tpu"],
        "programs_first_step": first["programs"],
        "programs_later_steps": rest["programs"],
        "programs": whole["programs"],
        "compile_cache_hits": whole["cache_hits"],
        "smoke_compile_s": whole["compile_s"],
        "smoke_first_step_s": first["wall_s"],
        "smoke_later_steps_s": rest["wall_s"],
        "smoke_wall_s": whole["wall_s"],
        "peak_bytes_in_use": peak_bytes(jax.devices()[0]),
    }
    emit(record)
    check(all(np.isfinite(losses + [after_save, after_load])),
          f"a loss is not finite: {losses} {after_save} {after_load}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    check(record["attention_backend"] == "pallas",
          f"attention ran on {record['attention_backend']!r}, not the "
          f"Pallas flash kernel")
    check("fwd" in report["flash"] and "dkv" in report["flash"],
          f"the flash kernels recorded no dispatch: {report['flash']}")
    check(not report["xla_on_tpu"],
          f"dispatchers took XLA in place of their kernel: "
          f"{report['xla_on_tpu']}")
    check(rest["programs"] == 0,
          f"{rest['programs']} programs were lowered after the first step")
    check(abs(after_load - after_save) <= 1e-3 * abs(after_save),
          f"the step after load_checkpoint gave {after_load}, the "
          f"uninterrupted run {after_save}")
    return record


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------

def reference_logits(cfg, params, rows, positions):
    """Float32 logits of a plain full forward pass (XLA attention, no
    cache, no kernel) at `positions` [B, T] of the token rows [B, S]."""
    import jax
    import jax.numpy as jnp

    from deeperspeed_tpu.models.gpt_neox import forward_hidden

    @jax.jit
    def run(params, rows, positions):
        hidden = forward_hidden(cfg, params, rows, use_pallas=False)
        picked = jnp.take_along_axis(hidden, positions[:, :, None], axis=1)
        head = params.get("embed_out", params["embed"])["wte"]
        return jnp.einsum("bth,vh->btv", picked, head.astype(picked.dtype),
                          preferred_element_type=jnp.float32)

    return np.asarray(run(params, jnp.asarray(rows), jnp.asarray(positions)))


def phase_serve(cfg, seed, ckpt_dir, prompt_lens, max_new, inference):
    """Serve prompts of `prompt_lens` tokens from the checkpoint the
    train phase wrote, and hold every served token against a plain greedy
    decode: full forward passes of the same weights in this process.

    The passes are teacher-forced along the served sequence, which by
    causality is one pass per request: position i's logits are those a
    full pass over the first i tokens gives, so where the served token is
    the argmax the two decodes are the same decode, and where it is not,
    its logit must lie within `SERVE_LOGIT_MARGIN` of the best."""
    import jax
    import jax.numpy as jnp

    from deeperspeed_tpu.inference import InferenceEngine
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX
    from deeperspeed_tpu.ops import dispatch_report

    serve_cfg = dataclasses.replace(cfg, param_dtype=jnp.bfloat16)
    model = GPTNeoX(serve_cfg, use_pallas=True)
    rng = np.random.default_rng(seed + 2)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in prompt_lens]

    with compile_span() as whole:
        engine = InferenceEngine(model, config={"inference": inference})
        path, _ = engine.load_checkpoint(ckpt_dir, tag="smoke")
        check(path is not None, f"no checkpoint loaded from {ckpt_dir}")
        ids = [engine.submit(p, max_new_tokens=max_new) for p in prompts]
        steps = 0
        while engine.scheduler.has_work:
            engine.step()
            steps += 1
        done = {r.request_id: r for r in engine.scheduler.pop_finished()}
    report = dispatch_report()     # before the reference's XLA pass
    check(sorted(done) == sorted(ids),
          f"{len(done)} of {len(ids)} requests finished")
    served = [done[i].generated for i in ids]
    for i, r in done.items():
        check(r.status == "ok" and len(r.generated) == max_new,
              f"request {i}: status {r.status!r}, "
              f"{len(r.generated)} of {max_new} tokens")

    # the plain decode, padded to one shape (causal: the tail is inert)
    width = -(-(max(prompt_lens) + max_new) // 128) * 128
    rows = np.zeros((len(prompts), width), np.int32)
    for b, (p, g) in enumerate(zip(prompts, served)):
        rows[b, :len(p) + len(g)] = p + g
    positions = np.asarray([[n - 1 + t for t in range(max_new)]
                            for n in prompt_lens], np.int32)
    # the weights the engine serves, in the natural layout the plain
    # forward pass reads: the layers sliced back out of their stack
    (stack,) = engine.params_stacked.values()
    served_params = dict(engine.params, blocks=[
        jax.tree_util.tree_map(lambda a, i=i: a[i], stack)
        for i in range(cfg.num_layers)])
    with compile_span() as ref:
        logits = reference_logits(serve_cfg, served_params, rows, positions)
    served = np.asarray(served)
    best = logits.max(axis=-1)
    got = np.take_along_axis(logits, served[:, :, None], axis=-1)[..., 0]
    shortfall = best - got
    exact = served == logits.argmax(axis=-1)

    record = {
        "phase": "serve", "model": "gpt-neox", "layers": cfg.num_layers,
        "hidden": cfg.hidden_size, "heads": cfg.num_heads,
        "requests": len(prompts), "prompt_lens": list(prompt_lens),
        "new_tokens": max_new, "engine_steps": steps,
        "weight_dtype": engine.dtypes["weight"],
        "kv_dtype": engine.dtypes["kv_cache"],
        "page_size": engine.page_size,
        "prefill_lengths": engine.prefill_lengths,
        "decode_backend": report["decode_attention"].get("decode"),
        "prefill_attention_backend": report["attention"].get("attention"),
        "xla_on_tpu": report["xla_on_tpu"],
        "exact_match_share": round(float(exact.mean()), 4),
        "max_logit_shortfall": round(float(shortfall.max()), 5),
        "logit_margin": SERVE_LOGIT_MARGIN,
        "engine_programs": engine.compile_count(),
        "programs": whole["programs"],
        "compile_cache_hits": whole["cache_hits"],
        "smoke_compile_s": whole["compile_s"],
        "smoke_wall_s": whole["wall_s"],
        "smoke_reference_s": ref["wall_s"],
        "peak_bytes_in_use": peak_bytes(jax.devices()[0]),
    }
    emit(record)
    check(np.isfinite(logits).all(), "the reference logits are not finite")
    check(record["decode_backend"] == "pallas",
          f"decode ran on {record['decode_backend']!r}, not the Pallas "
          f"paged kernel")
    check(record["prefill_attention_backend"] == "pallas",
          f"prefill attention ran on "
          f"{record['prefill_attention_backend']!r}, not the flash kernel")
    check(not report["xla_on_tpu"],
          f"dispatchers took XLA in place of their kernel: "
          f"{report['xla_on_tpu']}")
    check(float(shortfall.max()) <= SERVE_LOGIT_MARGIN,
          f"a served token lies {shortfall.max():.4f} below the plain "
          f"decode's best logit (margin {SERVE_LOGIT_MARGIN})")
    return record


# ---------------------------------------------------------------------------
# phase: multichip (--multichip only)
# ---------------------------------------------------------------------------

def sharded_state_report(state, n_devices):
    """How the engine state's leaves lie over the devices: every leaf
    that is sharded must span all of them at about 1/n each."""
    import jax
    total = sharded = 0
    bad = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(state):
        if not hasattr(leaf, "addressable_shards"):
            continue
        total += leaf.nbytes
        if leaf.sharding.is_fully_replicated:
            continue
        sharded += leaf.nbytes
        shards = leaf.addressable_shards
        devices = {s.device for s in shards}
        share = max(s.data.size for s in shards) / max(leaf.size, 1)
        if len(devices) != n_devices or \
                not 0.9 / n_devices <= share <= 1.25 / n_devices:
            bad.append(f"{jax.tree_util.keystr(path)}: {len(devices)} "
                       f"devices, largest shard {share:.3f} of the leaf")
    return total, sharded, bad


def phase_multichip(cfg, seed, batch, seq, steps, devices):
    """ZeRO stage 3 over every device (`data = n`) against the same steps
    on a one-device mesh, same weights, same global batch."""
    import jax

    import deeperspeed_tpu
    from deeperspeed_tpu.models.gpt_neox import GPTNeoX
    from deeperspeed_tpu.parallel.mesh import build_mesh

    n = len(devices)
    model = GPTNeoX(cfg, use_pallas=True)
    data = make_batch(cfg, seed, batch, seq)
    params = host_params(model, seed)

    def run(mesh):
        engine, *_ = deeperspeed_tpu.initialize(
            model=model, model_parameters=params, mesh=mesh,
            config_params=train_config(batch, zero_stage=3))
        losses, first, rest = run_steps(engine, data, steps)
        return engine, losses, first, rest

    with compile_span() as whole:
        engine, losses, first, rest = run(
            build_mesh(devices=devices, axes=["data"], dims=[n]))
        total, sharded, bad = sharded_state_report(engine.state, n)
        in_use = [bytes_in_use(d) for d in devices]
        peaks = [peak_bytes(d) for d in devices]
        del engine
        gc.collect()
        one, one_losses, _, _ = run(
            build_mesh(devices=devices[:1], axes=["data"], dims=[1]))
        del one
        gc.collect()

    worst = max(abs(a - b) / abs(b) for a, b in zip(losses, one_losses))
    record = {
        "phase": "multichip", "model": "gpt-neox", "layers": cfg.num_layers,
        "hidden": cfg.hidden_size, "batch": batch, "seq": seq,
        "steps": steps, "zero_stage": 3, "mesh": {"data": n},
        "losses": [round(l, 4) for l in losses],
        "one_device_losses": [round(l, 4) for l in one_losses],
        "max_rel_loss_diff": round(worst, 5),
        "loss_rtol": MULTICHIP_LOSS_RTOL,
        "state_bytes": total, "sharded_state_bytes": sharded,
        "bytes_in_use_per_device": in_use,
        "peak_bytes_in_use_per_device": peaks,
        "programs_later_steps": rest["programs"],
        "programs": whole["programs"],
        "compile_cache_hits": whole["cache_hits"],
        "smoke_compile_s": whole["compile_s"],
        "smoke_first_step_s": first["wall_s"],
        "smoke_later_steps_s": rest["wall_s"],
        "smoke_wall_s": whole["wall_s"],
    }
    emit(record)
    check(all(np.isfinite(losses + one_losses)), "a loss is not finite")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    check(worst <= MULTICHIP_LOSS_RTOL,
          f"ZeRO-3 over {n} devices and the one-device run differ by "
          f"{worst:.4f} (rtol {MULTICHIP_LOSS_RTOL})")
    check(not bad, "sharded leaves not spread evenly: " + "; ".join(bad))
    check(sharded >= 0.9 * total,
          f"only {sharded} of {total} state bytes are sharded")
    check(max(in_use) <= 1.5 * min(in_use),
          f"the devices do not hold about equal bytes: {in_use}")
    return record


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--multichip", action="store_true",
                    help="the four-chip phase and its comparison only")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (jax reports platform "
              f"{devices[0].platform!r}, {len(devices)} device(s)); this "
              f"script runs on the chip only", file=sys.stderr)
        return 1

    from deeperspeed_tpu.models.gpt_neox import GPTNeoXConfig
    from deeperspeed_tpu.utils.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    cfg = GPTNeoXConfig()
    emit({"phase": "start", "device": device_line(devices),
          "jax": jax.__version__, "compile_cache": cache_dir,
          "seed": args.seed})

    if args.multichip:
        if len(devices) < 4:
            print(f"chip_smoke: --multichip needs four chips, found "
                  f"{len(devices)}", file=sys.stderr)
            return 1
        phase_multichip(cfg, args.seed, batch=8, seq=cfg.max_seq_len,
                        steps=4, devices=devices)
    else:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as ckpt_dir:
            phase_train(cfg, args.seed, batch=8, seq=cfg.max_seq_len,
                        steps=8, ckpt_dir=ckpt_dir)
            phase_serve(
                cfg, args.seed, ckpt_dir,
                prompt_lens=(64, 150, 333, 512, 640, 777, 900, 1024),
                max_new=32,
                # prefill buckets from 128 up: the flash kernel takes
                # sequences that a 128-multiple block divides
                inference={"enabled": True, "page_size": 64,
                           "num_pages": 513, "max_batch_size": 8,
                           "token_budget": 2048,
                           "prefill_lengths": [128, 256, 512, 1024]})
    emit({"ok": True, "device": device_line(devices)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
