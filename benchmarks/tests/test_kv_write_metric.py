"""`serve_kv_write_time_share` (PR 25): the device time under the
program's `ds.kv_write` scope (the decode step's row write and prefill's
whole-page scatter) over busy time, on the trace recorded on the chip
(`benchmarks/testdata/tiny_serve_scoped`) and where there is nothing to
read."""

import json
import os

import pytest

from benchmarks import harness, scope_reduce as sr

TESTDATA = os.path.join(harness.ROOT, "benchmarks", "testdata")
TRACE = os.path.join(TESTDATA, "tiny_serve_scoped.xplane.pb.xz")
NAME = "serve_kv_write_time_share"


def read(rec):
    return harness.load_module(harness.ROOT, "metrics", NAME).read(rec)


def test_on_the_recorded_serve_trace():
    with open(TRACE.replace(".xplane.pb.xz", ".expected.json")) as f:
        want = json.load(f)["scopes"]
    expected = 100.0 * want["scopes"]["ds.kv_write"] / want["busy_s"]
    assert 3.7 < expected < 3.9           # 5.6 us of 147.3 us
    assert read({"trace_path": TRACE}) == pytest.approx(expected, rel=1e-6)


def test_nothing_to_read_is_none(monkeypatch):
    """An untraced run, no record, a program from before the scopes, a
    traced program that never writes K/V: None, never an exception."""
    assert read({"trace_path": None}) is None
    assert read({}) is None
    assert read({"trace_path": os.path.join(
        TESTDATA, "tiny_zero3_4c.xplane.pb.xz")}) is None
    monkeypatch.setattr(sr, "reduce_file", lambda path: {
        "n_devices": 1, "busy_s": 1.0, "remat_s": 0.0, "calls": {},
        "scopes": {"ds.mlp": 0.9, sr.UNSCOPED: 0.1}})
    assert read({"trace_path": "a.xplane.pb"}) is None


def test_it_is_declared_after_the_paged_kernel_s_share():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    metric = bench["per_layer"][-1]
    assert metric == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "serve engine",
        "moves": "serve_out_tok_s",
        "workloads": ["pythia-1.4b.serve_closed32"]}
    spec = harness.load_cell(harness.ROOT, "pythia-1.4b.serve_closed32")
    assert NAME in [m["name"] for m in spec["per_layer"]]
    spec = harness.load_cell(harness.ROOT, "pythia-410m.train_2k")
    assert NAME not in [m["name"] for m in spec["per_layer"]]
