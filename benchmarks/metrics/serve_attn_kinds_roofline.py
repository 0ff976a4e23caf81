from benchmarks import laguna_costs


def read(rec):
    return laguna_costs.attn_kinds_roofline(rec)
