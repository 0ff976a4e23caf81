from benchmarks import phi4flash_costs


def read(rec):
    return phi4flash_costs.state_bytes_per_seq(rec)
