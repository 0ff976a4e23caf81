from benchmarks import evabyte_costs


def read(rec):
    return evabyte_costs.rolls_in_window(rec)
