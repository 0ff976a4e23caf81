from benchmarks import evabyte_costs


def read(rec):
    return evabyte_costs.decode_time_share(rec)
