"""Where jax's persistent compilation cache lives.

The cache's directory is part of every entry's key, so a directory that
moves never hits. Two rules follow: whoever runs the program may place
the cache from outside with ``JAX_COMPILATION_CACHE_DIR`` (jax reads the
variable itself, and then no code sets another), and without it the
cache sits at one fixed path inside the checkout — never a temporary,
pid- or time-derived one. `chip_smoke.py` and `benchmarks/run.py` call
this; the tests keep the cache off.
"""

import os

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".xla_cache")


def configure_compile_cache():
    """Turn the persistent compile cache on; returns the directory in
    use. Call before the first compilation."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
