from benchmarks import sdar_costs


def read(rec):
    return sdar_costs.block_decode_roofline(rec)
