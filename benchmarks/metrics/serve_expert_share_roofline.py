from benchmarks import laguna_costs


def read(rec):
    return laguna_costs.expert_share_roofline(rec)
