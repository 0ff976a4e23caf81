from benchmarks.setup_account import read as _read


def read(rec):
    """The engines' constructors (the build records' `wall_s`) less what
    jax traced, lowered, compiled and read inside them: placement, the
    stacking of layers, pools, optimizer state. None where the program keeps no set-up account."""
    return _read(rec, "setup_engine_build_s")
