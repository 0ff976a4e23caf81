from benchmarks.setup_account import read as _read


def read(rec):
    """The package's own import (`setup_report()["import_s"]`: first line to
    last of the package's `__init__` files, jax's import not in it, a
    nested import once): grows with the package. None where the program keeps no set-up account."""
    return _read(rec, "setup_import_s")
