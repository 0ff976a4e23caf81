from benchmarks import glm_costs


def read(rec):
    return glm_costs.latent_prefill_roofline(rec)
