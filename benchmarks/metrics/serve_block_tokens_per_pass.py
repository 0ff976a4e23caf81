from benchmarks import sdar_costs


def read(rec):
    return sdar_costs.tokens_per_pass(rec)
