"""Traffic kind `train_tokens`: a training job. One seeded batch of token
ids per step, made on the host while the device runs the step before.

Parameters (the traffic file): `global_batch`, `seq_len`,
`token_distribution` (`zipf` with an `exponent`, over the vocabulary, so
that the unigram distribution is something the loss can learn), and
`check`: the part of the check batch that carries loss (`rows` first
sequences, `positions` first positions), which is all the float32
reference has to compute.

The clock (the idea of `bench.py::timed_steps`, kept): the host's, closed
by reading a loss back. Each step's loss is fetched one step late, after
the next step is dispatched and the batch after it is made, so the device
always has a step queued. The window opens at a fetch and closes at the
first fetch at or past `--seconds`; it holds whole steps only, and the
rate is their tokens over its true length.

Correctness, outside the window (its cost is set-up):
- the engine's loss on the check batch at step 0 against the reference's
  on the same weights, within the cell's `loss_rtol`;
- the sign of the first Adam update (step one moves a weight by
  -lr * g / (|g| + eps), so by -lr * sign(g)) against the sign of the
  reference's gradient, on a fixed sample of weight leaves, over the
  elements whose reference gradient is in the leaf's top quarter by size
  and whose bf16 value moved at all; read through `engine.module`, the
  public view of the weights;
- every loss of the window is finite;
- the reference's loss on the check batch is lower after the window.
"""

import numpy as np

from benchmarks import harness

IGNORE_INDEX = -100
SAMPLE_LEAVES = 4
SAMPLE_MAX_ELEMENTS = 1 << 23
SIGN_AGREEMENT_MIN = 0.99
MOVED_SHARE_MIN = 0.25


class TokenSource:
    def __init__(self, traffic, vocab, seed):
        dist = traffic["token_distribution"]
        if dist["kind"] != "zipf":
            raise harness.BenchmarkError(
                f"token_distribution {dist['kind']!r} is not known")
        weights = 1.0 / np.arange(1, vocab + 1) ** dist["exponent"]
        self.cdf = np.cumsum(weights / weights.sum())
        self.rng = np.random.default_rng(seed)
        self.shape = (1, traffic["global_batch"], traffic["seq_len"])
        self.vocab = vocab

    def tokens(self):
        ranks = np.searchsorted(self.cdf, self.rng.random(self.shape))
        return np.minimum(ranks, self.vocab - 1).astype(np.int32)

    def batch(self):
        tokens = self.tokens()
        return tokens, tokens

    def check_batch(self, rows, positions):
        tokens = self.tokens()
        labels = np.full_like(tokens, IGNORE_INDEX)
        labels[0, :rows, :positions] = tokens[0, :rows, :positions]
        return tokens, labels


SAMPLE_SEED = 0


def _sample_paths(leaves_with_path):
    """A sample of the blocks' weight matrices small enough to copy: the
    first one in tree order, then others drawn with `SAMPLE_SEED`. The
    same leaves in every run: which leaves are differentiated is part of
    the reference's program, and a program that changed with `--seed`
    would compile anew in every run."""
    import jax
    eligible = [i for i, (_, leaf) in enumerate(leaves_with_path)
                if leaf.ndim >= 2 and leaf.size <= SAMPLE_MAX_ELEMENTS]
    blocks = [i for i in eligible
              if "blocks" in jax.tree_util.keystr(leaves_with_path[i][0])]
    rng = np.random.default_rng(SAMPLE_SEED)
    picked = [blocks[0]] + list(rng.choice(
        blocks[1:], size=min(SAMPLE_LEAVES - 1, len(blocks) - 1),
        replace=False))
    return sorted(int(i) for i in picked)


class Reference:
    """The float32 reference on the check batch's loss-carrying part, on
    one device, with the weights the engine computes with."""

    def __init__(self, reference, conf, device, treedef, sample):
        import jax
        self.device = device
        self.sample = sample

        def loss(sample_values, leaves, tokens, labels):
            leaves = list(leaves)
            for i, v in zip(sample, sample_values):
                leaves[i] = v
            params = jax.tree_util.tree_unflatten(treedef, leaves)
            return reference.loss(conf, params, tokens, labels)

        self._loss_and_grads = jax.jit(jax.value_and_grad(loss))
        self._loss = jax.jit(lambda leaves, t, l: loss((), leaves, t, l))

    def _place(self, params, tokens, labels):
        import jax
        leaves = jax.device_put(jax.tree_util.tree_leaves(params),
                                self.device)
        return leaves, jax.device_put(tokens, self.device), \
            jax.device_put(labels, self.device)

    def loss_and_grads(self, params, tokens, labels):
        import jax.numpy as jnp
        leaves, tokens, labels = self._place(params, tokens, labels)
        values = [leaves[i].astype(jnp.float32) for i in self.sample]
        loss, grads = self._loss_and_grads(values, leaves, tokens, labels)
        return float(loss), [np.asarray(g) for g in grads]

    def loss(self, params, tokens, labels):
        return float(self._loss(*self._place(params, tokens, labels)))


def _sign_check(before, after, grads):
    """Share of large-gradient elements that moved, and of those, the
    share that moved against the reference gradient's sign."""
    moved = agree = large = 0
    for b, a, g in zip(before, after, grads):
        delta = a.astype(np.float32) - b.astype(np.float32)
        big = np.abs(g) >= np.quantile(np.abs(g), 0.75)
        big &= g != 0
        went = big & (delta != 0)
        large += int(big.sum())
        moved += int(went.sum())
        agree += int((np.sign(delta[went]) == -np.sign(g[went])).sum())
    return moved / max(large, 1), agree / max(moved, 1)


def run(spec, family, reference, *, seed, seconds, trace, t_start, log,
        devices):
    import jax

    import deeperspeed_tpu
    from deeperspeed_tpu.ops import dispatch_report
    from deeperspeed_tpu.parallel.mesh import build_mesh

    traffic, cell, conf = spec["traffic"], spec["cell"], spec["config"]
    chips = spec["chips"]
    model = family.build_model(conf, "float32", cell["model_options"])
    # always the cell's own chips, whatever else the machine holds
    axes = cell.get("mesh", {"data": chips})
    mesh = build_mesh(devices=devices, axes=list(axes),
                      dims=list(axes.values()))
    params = family.init_params(model, seed, mesh)
    # `initialize_on: "cpu"` (a cell's file): the engine builds Adam's two
    # moments as whole unsharded zeros on the default device before it
    # shards them, 11.3 GB for 1.4B parameters beside that chip's share of
    # everything else; with the host as default device they never pass
    # through one chip's memory (PERF.md section 7)
    default = jax.devices(cell["initialize_on"])[0] \
        if cell.get("initialize_on") else None
    with jax.default_device(default):
        engine, *_ = deeperspeed_tpu.initialize(
            model=model, model_parameters=params, mesh=mesh,
            config_params=cell["engine"])
    del params

    source = TokenSource(traffic, conf["vocab_size"], seed)
    rows, positions = traffic["check"]["rows"], traffic["check"]["positions"]
    check = source.check_batch(rows, positions)
    check_part = (check[0][0, :rows, :positions],
                  check[1][0, :rows, :positions])

    # -- the reference on the step-0 weights, before the step donates them
    natural = engine.module
    leaves_with_path = jax.tree_util.tree_leaves_with_path(natural)
    sample = _sample_paths(leaves_with_path)
    ref = Reference(reference, conf, devices[-1],
                    jax.tree_util.tree_structure(natural), sample)
    ref_loss0, ref_grads = ref.loss_and_grads(natural, *check_part)
    before = [np.asarray(leaves_with_path[i][1]) for i in sample]
    del natural, leaves_with_path

    # -- step 0: the check batch (this compiles the step)
    loss0 = float(engine.train_batch(batch=check))
    after_leaves = jax.tree_util.tree_leaves(engine.module)
    after = [np.asarray(after_leaves[i]) for i in sample]
    del after_leaves
    moved_share, sign_agreement = _sign_check(before, after, ref_grads)
    loss_rel_diff = abs(loss0 - ref_loss0) / abs(ref_loss0)
    del before, after, ref_grads

    # -- warm-up steps in the window's own rhythm, then the window
    tokens_per_step = traffic["global_batch"] * traffic["seq_len"]
    losses, fetch_times = [], []
    batch = source.batch()
    pending = engine.train_batch(batch=batch)
    batch = source.batch()
    for _ in range(cell["warmup_steps"]):
        nxt = engine.train_batch(batch=batch)
        batch = source.batch()
        float(pending)
        pending = nxt

    tracer = harness.Tracer(spec) if trace else None
    traced_steps = cell["traced_steps"] if trace else 0
    programs0 = log.programs
    t0 = harness.now()
    setup_s = t0 - t_start
    while True:
        n = len(losses)
        if tracer and n == 1:
            tracer.start()
        with harness.span("bench.dispatch"):
            nxt = engine.train_batch(batch=batch)
        with harness.span("bench.make_batch"):
            batch = source.batch()
        with harness.span("bench.fetch_loss"):
            try:
                losses.append(float(pending))
            except Exception:       # noqa: BLE001 - a failed step is counted
                losses.append(float("nan"))
        fetch_times.append(harness.now())
        pending = nxt
        if tracer and n + 1 == 1 + traced_steps:
            tracer.stop()
        if fetch_times[-1] - t0 >= seconds and \
                not (tracer and tracer.reduced is None):
            break
    programs_in_window = log.programs - programs0
    float(pending)                  # the step in flight, not counted
    failed = sum(1 for v in losses if not np.isfinite(v))

    ref_loss_end = ref.loss(engine.module, *check_part)
    report = dispatch_report()
    checks = {
        "loss_agrees": loss_rel_diff <= cell["loss_rtol"],
        "update_moved": moved_share >= MOVED_SHARE_MIN,
        "gradient_signs_agree": sign_agreement >= SIGN_AGREEMENT_MIN,
        "losses_finite": failed == 0,
        "reference_loss_fell": ref_loss_end < ref_loss0,
    }
    return {
        "correct": all(checks.values()), "checks": checks,
        "attempted": len(losses), "failed": failed,
        "setup_s": setup_s, "window_s": fetch_times[-1] - t0,
        "step_times_s": list(np.diff([t0] + fetch_times)),
        "tokens_per_step": tokens_per_step, "chips": chips,
        "seq_len": traffic["seq_len"], "losses": losses,
        "check": {"engine_loss0": loss0, "reference_loss0": ref_loss0,
                  "loss_rel_diff": loss_rel_diff,
                  "loss_rtol": cell["loss_rtol"],
                  "sampled_leaves": sample, "moved_share": moved_share,
                  "sign_agreement": sign_agreement,
                  "reference_loss_end": ref_loss_end},
        "compiles_in_window": programs_in_window,
        "dispatch": report, "trace": tracer.reduced if tracer else None,
        "trace_path": getattr(tracer, "path", None),
    }
