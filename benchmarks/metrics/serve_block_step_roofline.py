from benchmarks import sdar_costs


def read(rec):
    return sdar_costs.block_step_roofline(rec)
