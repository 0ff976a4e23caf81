from benchmarks import sdar_costs


def read(rec):
    return sdar_costs.commit_share(rec)
