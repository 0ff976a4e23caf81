import numpy as np

from benchmarks import harness


def read(rec):
    """Model flops a chip completes per second over its bf16 peak: the
    step's tokens per chip over the median step time, times the
    reference's model flops per token. From the median step, so that the
    profiler's own stalls in a traced run do not count; in a run without
    the profiler it is `train_tok_s_chip` times a constant."""
    if "tokens_per_step" not in rec:
        return None
    spec = rec["spec"]
    rate = rec["tokens_per_step"] / rec["chips"] / \
        float(np.median(rec["step_times_s"]))
    reference = harness.load_module(spec["root"], "reference",
                                    spec["config"]["family"])
    flops = reference.train_flops_per_token(spec["config"], rec["seq_len"])
    peak = harness.peaks_for(spec, rec["device_kind"])["bf16_flops_per_s"]
    return 100.0 * rate * flops / peak
