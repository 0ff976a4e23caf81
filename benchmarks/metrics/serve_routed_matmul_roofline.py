from benchmarks import glm_costs


def read(rec):
    return glm_costs.routed_matmul_roofline(rec)
