"""Each train cell's model step compiled at its real size for a described
v5e 2x2 (`real_size.py`): the chip's compiler accepts it, the Mosaic
kernels are in it, the four-chip cell has its collectives, and the
program's temporaries plus the state fit a chip's 16 GB. Not a chip run;
PERF.md records the `memory_analysis()` numbers as compile-time bytes.

About a minute and a half a cell. The topology is described inside a
fixture, never while a module is imported (`on-chip-measurement` guide).
"""

import jax
import pytest

import real_size  # beside this file: pytest puts its directory on the path
from benchmarks import harness

TRAIN_CELLS = [w["name"] for w in
               harness.load_json(harness.ROOT, "BENCHMARK.json")["workloads"]
               if "train" in w["traffic"]]


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental.compilation_cache import compilation_cache
    try:
        devices = real_size.describe("v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield devices
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_train_step_compiles_and_fits(cell, v5e_2x2, monkeypatch):
    spec = harness.load_cell(harness.ROOT, cell)
    if spec["traffic"]["seq_len"] >= 8192:
        # at 8k and over the program picks flash blocks by timing them
        # on the chip, which a described chip cannot do: name one
        monkeypatch.setenv("DS_FLASH_BLOCKS", "512,512")
        monkeypatch.setenv("DS_FLASH_BWD_BLOCKS", "512,512")
    _, account = real_size.compile_train_step(spec, v5e_2x2)
    print(account)
    assert account["mosaic_calls"] >= 3 * spec["config"]["num_hidden_layers"]
    assert account["fits_16gb"], account
    if spec["chips"] > 1:
        assert account["all_gathers"] > 0 and account["reduce_scatters"] > 0
    else:
        assert account["all_gathers"] == account["all_reduces"] == 0
