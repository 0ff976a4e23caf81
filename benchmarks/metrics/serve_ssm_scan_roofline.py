from benchmarks import phi4flash_costs


def read(rec):
    return phi4flash_costs.ssm_scan_roofline(rec)
