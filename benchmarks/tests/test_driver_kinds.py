"""Every driver kind (a file of `benchmarks/drivers/`) run once at a tiny
size on the CPU through `harness.result_line`, and the record it ends in
held to the contract: it parses, and has `correct`, `attempted`, `failed`,
`metrics` and `device`, with at least one operation attempted and no more
failed than attempted. A kind whose `run` raises leaves a traceback as a
benchmark run's last line, which the driver of the PRs reads as
`output_malformed` (PR 58): this is where that shows before a chip is
asked for.

One case a kind, on the first cell of `BENCHMARK.json` whose traffic
names it, shrunk by that cell's own rehearsal (`test_<family>_cell.py`).
"""

import glob
import json
import os

import pytest

from benchmarks import harness
from test_rehearsal import (ROOT, checkout_with_links, log, on_cpu,  # noqa: F401
                            run, tiny)
from test_evabyte_cell import tiny_evabyte
from test_glm_cell import tiny_glm
from test_ouro_cell import tiny_ouro
from test_phi4flash_cell import tiny_phi4flash
from test_qwen3next_cell import tiny_qwen3next
from test_sdar_cell import tiny_sdar

# how a kind's cell is shrunk: its family's rehearsal
SHRINK = {"closed_loop": tiny, "train_tokens": tiny,
          "closed_loop_probed": tiny_glm, "closed_loop_kv_probed": tiny_ouro,
          "closed_loop_block_probed": tiny_sdar,
          "closed_loop_state_probed": tiny_phi4flash,
          "closed_loop_eva_probed": tiny_evabyte,
          "closed_loop_gdn_probed": tiny_qwen3next}
KINDS = sorted(os.path.basename(p)[:-3] for p in glob.glob(
    os.path.join(ROOT, "benchmarks", "drivers", "*.py")))


def cell_of(kind):
    """The first cell whose traffic is of driver kind `kind`."""
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    for w in bench["workloads"]:
        traffic = harness.load_json(ROOT, "benchmarks", "traffic",
                                    w["traffic"] + ".json")
        if traffic["kind"] == kind:
            return w["name"]
    raise AssertionError(f"no cell runs driver kind {kind!r}")


def test_every_driver_kind_has_a_case():
    assert KINDS == sorted(SHRINK)


@pytest.mark.parametrize("kind", KINDS)
def test_a_driver_kind_ends_in_a_well_formed_record(kind, on_cpu, log,  # noqa: F811
                                                    tmp_path):
    spec = on_cpu(SHRINK[kind](harness.load_cell(ROOT, cell_of(kind))))
    spec["root"] = checkout_with_links(tmp_path)
    _, line = run(spec, 0, log)
    line = json.loads(json.dumps(line))             # it parses
    assert {"correct", "attempted", "failed", "metrics", "device"} <= \
        set(line)
    assert isinstance(line["correct"], bool)
    assert line["attempted"] >= 1
    assert 0 <= line["failed"] <= line["attempted"]
    assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    assert line["device"]["count"] == spec["chips"]
