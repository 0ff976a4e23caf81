from benchmarks.setup_account import read as _read


def read(rec):
    """The compile records' `first_call_s`, summed: what a program's first
    call cost the host beyond tracing, lowering and compiling or reading
    it (loading the executable, its transfers, the step itself). None where the program keeps no set-up account."""
    return _read(rec, "setup_first_call_s")
