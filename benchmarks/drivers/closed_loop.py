"""Traffic kind `closed_loop`: `clients` callers of a server, each of
which submits its next request as soon as its last one finishes.
Evaluation harnesses, RL rollout workers and data-generation jobs call a
server this way.

Parameters (the traffic file): `clients`; `prompt_len` and `output_len`,
each a clipped lognormal (`median`, `sigma`, `min`, `max`); `max_total`,
the most prompt plus output may be; `population`, `order_seed`, `ramp_s`
and `check_requests`, below.

Lengths are not drawn one by one: the `population` mid-quantiles of each
distribution are the lengths, and `order_seed` (the traffic file's, not
the run's) orders them and pairs prompt with output; the cycle repeats.
Every run of the cell thus offers the same schedule of lengths, which is
what keeps two runs of one code comparable: with some 40 requests to a
window, drawn or reordered lengths change how many tokens the cache holds
and moved every serve metric by 1.5 to 2.3% between runs (PR 23). The
run's `--seed` makes the weights and the prompts' token ids, uniform over
the vocabulary. Decoding is greedy with no end token, so a request emits
exactly its output length. (The lognormal idea is `bench.py::row_serve`'s;
the arrivals are not: its load followed the server's own steps.)

The loop runs `ramp_s` seconds before the window opens, so that the batch
and the cache are at their steady occupancy when timing starts; that ramp
is set-up the traffic needs. The window is `--seconds` long on the host's
clock and closes at the end of the step that passes it. Requests in
flight at the close are drained without new submissions and counted;
their tokens after the close are not.

Token times are read as `bench.py::row_serve` reads them: after each
synchronous `engine.step()` (which ends in a read-back of the sampled
tokens) the generated length of every running or finished request is
compared with the last seen.

Correctness, after the window: for a seeded sample of `check_requests` of
the window's finished requests, the float32 reference runs one pass over
prompt plus served tokens (teacher-forced; by causality position i's
logits are those a plain decode would compute) and every served token's
logit must lie within the cell's `logit_margin` of the best logit there.
"""

import statistics

import numpy as np

from benchmarks import harness


def quantile_lengths(dist, n):
    """The `n` mid-quantiles of a clipped lognormal, as integers."""
    normal = statistics.NormalDist()
    z = np.array([normal.inv_cdf((i + 0.5) / n) for i in range(n)])
    lengths = np.exp(np.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(lengths), dist["min"], dist["max"]).astype(int)


class RequestSource:
    def __init__(self, traffic, vocab, seed):
        self.rng = np.random.default_rng(seed)
        order = np.random.default_rng(traffic["order_seed"])
        n = traffic["population"]
        self.prompts = order.permutation(
            quantile_lengths(traffic["prompt_len"], n))
        self.outputs = order.permutation(
            quantile_lengths(traffic["output_len"], n))
        self.max_total, self.vocab, self.i = traffic["max_total"], vocab, 0

    def next(self):
        k = self.i % len(self.prompts)
        self.i += 1
        n_prompt = int(self.prompts[k])
        n_out = int(min(self.outputs[k], self.max_total - n_prompt))
        return self.rng.integers(1, self.vocab, size=n_prompt).tolist(), n_out


def check_served(reference, conf, params, done, width, t_max, margin):
    """Worst shortfall of a served token's reference logit below the
    reference's best, and the share of served tokens that are the
    reference's argmax, over the requests `done`. `width` and `t_max`
    are the most tokens a request may hold and emit: one shape, so one
    program whatever the sample."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def one(params, row, positions, served, n):
        lg = reference.logits_at(conf, params, row[None], positions[None])[0]
        got = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
        live = jnp.arange(t_max) < n
        short = jnp.where(live, lg.max(-1) - got, 0.0)
        exact = jnp.where(live, lg.argmax(-1) == served, False)
        return short.max(), exact.sum(), jnp.isfinite(lg).all()

    worst, exact, total, finite = 0.0, 0, 0, True
    for r in done:
        n_p, n_g = len(r.prompt), len(r.generated)
        row = np.zeros(width, np.int32)
        row[:n_p + n_g] = list(r.prompt) + list(r.generated)
        positions = np.zeros(t_max, np.int32)
        positions[:n_g] = n_p - 1 + np.arange(n_g)
        served = np.zeros(t_max, np.int32)
        served[:n_g] = r.generated
        s, e, f = one(params, row, positions, served, n_g)
        worst, exact = max(worst, float(s)), exact + int(e)
        total, finite = total + n_g, finite and bool(f)
    return {"max_logit_shortfall": worst,
            "exact_match_share": exact / max(total, 1),
            "logit_margin": margin, "reference_finite": finite,
            "checked_requests": len(done), "checked_tokens": total}


def run(spec, family, reference, *, seed, seconds, trace, t_start, log,
        devices):
    from deeperspeed_tpu.inference import InferenceEngine
    from deeperspeed_tpu.ops import dispatch_report

    traffic, cell, conf = spec["traffic"], spec["cell"], spec["config"]
    model = family.build_model(conf, "bfloat16", cell["model_options"])
    params = family.init_params(model, seed)
    config = dict(cell["engine"])
    if trace:
        # the engine's own host spans (schedule / prefill / decode), as
        # trace annotations, for the attribution of idle gaps
        config["telemetry"] = {"enabled": True, "goodput": False,
                               "mfu": False, "spans": True}
    engine = InferenceEngine(model, config=config, params=params)
    source = RequestSource(traffic, conf["vocab_size"], seed)

    # warm the cell's shapes: every prefill length, and the decode batch
    warm = [source.rng.integers(1, conf["vocab_size"],
                                size=max(b - 2, 1)).tolist()
            for b in engine.prefill_lengths]
    engine.generate(warm, max_new_tokens=2)

    live = {}              # request id -> bookkeeping of a request in flight
    records = []           # the same, of every request submitted
    token_times, gaps, step_times, decode_steps = [], [], [], 0
    window = {"open": None, "close": None}

    def submit(now):
        prompt, n_out = source.next()
        rid = engine.submit(prompt, max_new_tokens=n_out)
        live[rid] = {"id": rid, "submit": now, "seen": 0, "last": None,
                     "first": None, "request": None,
                     "in_window": window["open"] is not None}
        records.append(live[rid])

    def observe(now):
        finished = engine.scheduler.pop_finished()
        for r in list(engine.scheduler.running) + finished:
            rec = live.get(r.request_id)
            if rec is None:
                continue
            k = len(r.generated)
            new = k - rec["seen"]
            if new > 0:
                if rec["last"] is None:
                    rec["first"] = now
                else:
                    gaps.extend([(now, (now - rec["last"]) / new)] * new)
                token_times.extend([now] * new)
                rec["seen"], rec["last"] = k, now
        for r in finished:
            rec = live.pop(r.request_id, None)
            if rec is not None:
                rec["request"] = r
        return len(finished)

    tracer = harness.Tracer(spec) if trace else None
    ramp_start = harness.now()
    for _ in range(traffic["clients"]):
        submit(ramp_start)
    stats0 = programs0 = None
    while engine.scheduler.has_work:
        t_step = harness.now()
        with harness.span("bench.engine_step"):
            summary = engine.step()
        now = harness.now()
        with harness.span("bench.observe"):
            n_finished = observe(now)
        if window["open"] is not None and window["close"] is None:
            step_times.append(now - t_step)
            decode_steps += 1 if summary["decoded"] else 0
            since = now - window["open"]
            if tracer and tracer.reduced is None:
                if not tracer.started and since >= cell["trace_after_s"]:
                    tracer.start()
                elif tracer.started and since >= \
                        cell["trace_after_s"] + cell["traced_seconds"]:
                    tracer.stop()
            if since >= seconds and not (tracer and tracer.reduced is None):
                window["close"] = now
                stats1, programs1 = dict(engine.stats), log.programs
        elif window["open"] is None and now - ramp_start >= traffic["ramp_s"]:
            window["open"] = now
            stats0, programs0 = dict(engine.stats), log.programs
        if window["close"] is None:
            with harness.span("bench.submit"):
                for _ in range(n_finished):
                    submit(now)
    if window["close"] is None:
        raise harness.BenchmarkError("the server ran out of work before "
                                     "the window closed")

    t0, t1 = window["open"], window["close"]
    setup_s = t0 - t_start
    mine = [r for r in records if r["in_window"]]
    failed = [r for r in mine
              if r["request"] is None or r["request"].status != "ok"
              or len(r["request"].generated) !=
              r["request"].max_new_tokens]
    ok = [r["request"] for r in mine if r not in failed]
    pick = np.random.default_rng(seed + 1).choice(
        len(ok), size=min(traffic["check_requests"], len(ok)), replace=False)
    check = check_served(reference, conf, params, [ok[i] for i in pick],
                         traffic["max_total"], traffic["output_len"]["max"],
                         cell["logit_margin"])
    checks = {
        "served_tokens_within_margin":
            check["max_logit_shortfall"] <= cell["logit_margin"],
        "reference_finite": check["reference_finite"],
        "requests_checked": check["checked_requests"] > 0,
        "all_requests_ok": not failed,
    }
    return {
        "correct": all(checks.values()), "checks": checks, "check": check,
        "attempted": len(mine), "failed": len(failed),
        "setup_s": setup_s, "window_s": t1 - t0,
        "out_tokens": sum(1 for t in token_times if t0 < t <= t1),
        "ttft_s": [r["first"] - r["submit"] for r in mine
                   if r["first"] is not None],
        "itl_s": [g for t, g in gaps if t0 < t <= t1],
        "step_times_s": step_times, "decode_steps": decode_steps,
        "stats": {k: stats1[k] - stats0[k] for k in stats0
                  if isinstance(stats0[k], (int, float))},
        "max_batch_size": engine.max_batch_size,
        "compiles_in_window": programs1 - programs0,
        "dispatch": dispatch_report(),
        "trace": tracer.reduced if tracer else None,
        "trace_path": getattr(tracer, "path", None),
    }
