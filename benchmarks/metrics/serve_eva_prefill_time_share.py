from benchmarks import evabyte_costs


def read(rec):
    return evabyte_costs.prefill_time_share(rec)
