from benchmarks import phi4flash_costs


def read(rec):
    return phi4flash_costs.prefill_cross_row_share(rec)
