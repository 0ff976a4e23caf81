from benchmarks import moe_costs


def read(rec):
    return moe_costs.roofline(rec)
