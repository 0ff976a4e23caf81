from benchmarks import qwen3next_costs


def read(rec):
    return qwen3next_costs.gdn_chunk_roofline(rec)
