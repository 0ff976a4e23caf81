"""Test harness: run everything on an 8-device virtual CPU mesh.

Mirrors the reference's strategy of exercising multi-rank logic on one box
(`tests/unit/common.py` forks N processes over NCCL); with JAX we instead give
one process 8 XLA host devices and build real `jax.sharding.Mesh`es over
them, so every collective path compiles and runs.
"""

import os

# Tests run on the CPU whatever is attached: the multi-device sharding
# logic needs 8 virtual devices, and the chip is reached only through
# `chip_smoke.py`. Set before jax is imported, and again through
# jax.config in case a plugin imported jax first.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402  (import after env setup)

jax.config.update("jax_platforms", "cpu")

# The tests keep the persistent compile cache off and compile fresh every
# run: `deeperspeed_tpu.utils.compile_cache` is for `chip_smoke.py` and
# `benchmarks/run.py` only. (An earlier builder saw executables read back from the
# cache mis-execute on the CPU backend — diverging trajectories, glibc
# aborts; that was not re-tested at PR 22, so the rule stays.)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs
