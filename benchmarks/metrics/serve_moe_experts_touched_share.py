from benchmarks import qwen3next_costs


def read(rec):
    return qwen3next_costs.moe_experts_touched_share(rec)
