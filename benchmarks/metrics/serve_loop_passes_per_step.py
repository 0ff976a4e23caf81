from benchmarks import ouro_costs


def read(rec):
    return ouro_costs.loop_passes_per_step(rec)
