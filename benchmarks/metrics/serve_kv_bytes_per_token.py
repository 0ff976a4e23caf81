from benchmarks import laguna_costs


def read(rec):
    return laguna_costs.kv_bytes_per_token(rec)
