from benchmarks import glm_costs


def read(rec):
    return glm_costs.latent_kv_bytes_per_token(rec)
