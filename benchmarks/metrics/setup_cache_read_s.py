from benchmarks.setup_account import read as _read


def read(rec):
    """`cache_read_s` of the engines' build and compile records: the
    backend's calls that the persistent cache answered (key, retrieval,
    deserialization). None where the program keeps no set-up account."""
    return _read(rec, "setup_cache_read_s")
