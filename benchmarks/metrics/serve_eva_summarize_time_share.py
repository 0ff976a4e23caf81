from benchmarks import evabyte_costs


def read(rec):
    return evabyte_costs.summarize_time_share(rec)
