from benchmarks import qwen3next_costs


def read(rec):
    return qwen3next_costs.gdn_step_roofline(rec)
