"""Every cell's code path rehearsed on the CPU at a tiny size.

`benchmarks/run.py` runs on the chip only and has no option that would
let it run here, so the tests steer the harness from outside, as
`tests/test_chip_smoke.py` does for the smoke: they load the cell as the
harness does, shrink the configuration and the traffic in the loaded
dicts, name the interpreted Pallas decode kernel through the serving
config, stand in for `memory_stats()` (the CPU backend has none) and
point the trace reducer at the CPU's own op threads. What a rehearsal
shows is control flow, checks, counts and the shape of the last line:
never a time.
"""

import copy
import json
import os
import re
import shutil
import subprocess
import sys

import jax
import pytest

from benchmarks import harness, trace_reduce
from benchmarks.compile_log import CompileLog

ROOT = harness.ROOT
BENCH = harness.load_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]

TINY = {"hidden_size": 256, "num_hidden_layers": 2,
        "num_attention_heads": 4, "intermediate_size": 1024,
        "vocab_size": 512, "max_position_embeddings": 256}


def tiny(spec):
    """The loaded cell, shrunk in place of the published sizes."""
    spec = copy.deepcopy(spec)
    spec["config"].update(TINY)
    spec["cell"].pop("env", None)         # knobs for the real shapes
    traffic, cell = spec["traffic"], spec["cell"]
    if traffic["kind"] == "train_tokens":
        traffic.update(global_batch=4, seq_len=256,
                       check={"rows": 1, "positions": 128})
        cell["engine"]["train_batch_size"] = 4
        cell["traced_steps"] = 2
        # float32 on the CPU against a float32 reference
        cell["loss_rtol"] = 1e-3
    else:
        traffic.update(
            clients=4, population=16, ramp_s=0.3, check_requests=3,
            max_total=256,
            prompt_len=dict(traffic["prompt_len"], median=40, min=8, max=100),
            output_len=dict(traffic["output_len"], median=6, min=2, max=12))
        cell["engine"]["inference"].update(
            page_size=16, num_pages=80, max_batch_size=4, token_budget=256,
            prefill_lengths=[128, 256], decode_batch_sizes=[4],
            kernel="pallas")
        cell.update(trace_after_s=0.1, traced_seconds=0.3)
    return spec


@pytest.fixture(scope="module")
def log():
    return CompileLog()


@pytest.fixture
def on_cpu(monkeypatch):
    """Stand-ins for what only a chip has; returns the function that
    gives a loaded cell the CPU's "peaks"."""
    monkeypatch.setattr(harness, "memory_peak_bytes",
                        lambda devices: 123456789)
    monkeypatch.setattr(trace_reduce, "DEVICE_PLANE",
                        re.compile(r"^/host:(CPU)$"))
    monkeypatch.setattr(trace_reduce, "OP_LINE",
                        re.compile(r"^tf_XLA(PjRtCpuClient|Eigen)/"))
    return lambda spec: dict(spec, peaks={"cpu": {"bf16_flops_per_s": 1e12}})


def checkout_with_links(tmp_path):
    """A root whose benchmarks/ links to the repo's code directories, so
    that a rehearsal's traces land under the temporary directory."""
    root = str(tmp_path / "root")
    os.makedirs(os.path.join(root, "benchmarks"))
    for kind in ("drivers", "families", "reference", "metrics"):
        os.symlink(os.path.join(ROOT, "benchmarks", kind),
                   os.path.join(root, "benchmarks", kind))
    return root


def run(spec, trace, log, seconds=2.0):
    import time
    devices = jax.devices()[:spec["chips"]]
    rec = harness.run_cell(spec, seed=3, seconds=seconds, trace=trace,
                           t_start=time.perf_counter(), log=log,
                           devices=devices)
    return rec, harness.result_line(spec, rec, trace, devices)


def check_line(line, spec, trace):
    """The contract's last line."""
    line = json.loads(json.dumps(line))
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == keys | ({"breakdown"} if trace else set())
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    want = spec["per_layer" if trace else "end_to_end"]
    units = {m["name"]: m["unit"] for m in want}
    assert set(line["metrics"]) <= set(units)
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == units[name]
        assert isinstance(m["value"], float)
    device = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(device)
    assert device["count"] == spec["chips"]
    if trace:
        assert device["busy_s"] > 0 and device["window_s"] > 0
        for key in ("device_ops", "idle_gaps"):
            assert len(line["breakdown"][key]) <= 10
    else:
        assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
        assert set(line["metrics"]) == set(units)
    return line


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal(cell, trace, on_cpu, log, tmp_path):
    spec = on_cpu(tiny(harness.load_cell(ROOT, cell)))
    spec["root"] = checkout_with_links(tmp_path)
    rec, line = run(spec, trace, log)
    assert rec["checks"] and all(rec["checks"].values()), rec["checks"]
    assert rec["compiles_in_window"] == 0
    line = check_line(line, spec, trace)
    if trace:
        assert len(line["metrics"]) >= 1


def test_a_cell_is_added_with_files_alone(on_cpu, log, tmp_path):
    """A new configuration, traffic mix, cell and per-layer metric: files
    added beside the benchmark's and entries in BENCHMARK.json, no file
    of the benchmark edited."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(os.path.join(dp, p), "rb").read()
              for dp, _, files in os.walk(os.path.join(root, "benchmarks"))
              for p in files}
    bench = copy.deepcopy(BENCH)
    base = harness.load_cell(ROOT, "pythia-410m.train_2k")

    def write(path, obj):
        with open(os.path.join(root, path), "w") as f:
            f.write(obj if isinstance(obj, str) else json.dumps(obj))

    write("benchmarks/configs/throwaway.json", dict(base["config"], **TINY))
    write("benchmarks/traffic/throwaway_512.json",
          dict(base["traffic"], global_batch=2, seq_len=512,
               check={"rows": 1, "positions": 128}))
    cell = copy.deepcopy(base["cell"])
    cell["engine"]["train_batch_size"] = 2
    cell["loss_rtol"] = 1e-3
    write("benchmarks/workloads/throwaway.train_512.json", cell)
    write("benchmarks/metrics/throwaway_last_loss.py",
          "def read(rec):\n    return rec['losses'][-1]\n")
    bench["configs"].append({
        "name": "throwaway", "source": "none", "reduced": [], "why": "test",
        "file": "benchmarks/configs/throwaway.json"})
    bench["workloads"].append({
        "name": "throwaway.train_512", "config": "throwaway",
        "traffic": "throwaway_512", "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("throwaway.train_512")
    bench["per_layer"].append({
        "name": "throwaway_last_loss", "unit": "nats", "better": "lower",
        "source": "program_counter", "layer": "model",
        "moves": "train_tok_s_chip", "workloads": ["throwaway.train_512"]})
    write("BENCHMARK.json", bench)

    spec = on_cpu(harness.load_cell(root, "throwaway.train_512"))
    rec, line = run(spec, 1, log)
    line = check_line(line, spec, 1)
    assert "throwaway_last_loss" in line["metrics"]
    after = {p: open(os.path.join(dp, p), "rb").read()
             for dp, _, files in os.walk(os.path.join(root, "benchmarks"))
             for p in files if p in before}
    assert after == before


def test_run_py_refuses_without_a_tpu():
    """No TPU: a message, no result line, a non-zero exit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELLS[0], "--seed", "0", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr
