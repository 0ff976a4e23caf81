from benchmarks import sdar_costs


def read(rec):
    return sdar_costs.grouped_matmul_roofline(rec)
