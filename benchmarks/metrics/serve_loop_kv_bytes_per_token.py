from benchmarks import ouro_costs


def read(rec):
    return ouro_costs.loop_kv_bytes_per_token(rec)
