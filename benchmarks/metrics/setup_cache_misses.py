from benchmarks.setup_account import read as _read


def read(rec):
    """`totals["cache_misses"]`: programs of the whole process, engines and
    caller, compiled and written because the persistent cache lacked
    them. A count, in no sum: two set-ups compare at equal misses. None
    where the program keeps no set-up account, or where its report says
    the totals at the window's opening fell short (`complete` False)."""
    return _read(rec, "setup_cache_misses")
