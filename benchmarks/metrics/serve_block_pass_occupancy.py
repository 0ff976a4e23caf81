from benchmarks import sdar_costs


def read(rec):
    return sdar_costs.pass_occupancy(rec)
