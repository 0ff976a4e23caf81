from benchmarks.setup_account import read as _read


def read(rec):
    """`compile_s` of the engines' build and compile records: the backend's
    compiles; 0 where the persistent cache had every program. None where the program keeps no set-up account."""
    return _read(rec, "setup_backend_compile_s")
