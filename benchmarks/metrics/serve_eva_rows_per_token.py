from benchmarks import evabyte_costs


def read(rec):
    return evabyte_costs.rows_per_token(rec)
