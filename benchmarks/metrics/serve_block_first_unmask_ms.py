from benchmarks import sdar_costs


def read(rec):
    return sdar_costs.first_unmask_ms(rec)
