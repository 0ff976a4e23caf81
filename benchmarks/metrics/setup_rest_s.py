from benchmarks.setup_account import read as _read


def read(rec):
    """`setup_s` less the six parts in seconds: warm-up and ramp steps that
    compiled nothing, and the caller's own set-up (weights, the
    reference, their compiles). Negative, or near `setup_s`: the
    account's fault. None where the program keeps no set-up account."""
    return _read(rec, "setup_rest_s")
