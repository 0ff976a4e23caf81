from benchmarks import phi4flash_costs


def read(rec):
    return phi4flash_costs.cross_decode_time_share(rec)
