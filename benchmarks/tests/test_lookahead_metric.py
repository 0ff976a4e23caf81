"""`serve_lookahead_share` (PR 30): decode programs the engine enqueued
while the previous one was unread (`stats["lookahead_steps"]`) over the
window's decode steps, and None where there is nothing to read."""

import pytest

from benchmarks import harness

NAME = "serve_lookahead_share"


def read(rec):
    return harness.load_module(harness.ROOT, "metrics", NAME).read(rec)


def test_share_of_the_window_s_decode_steps():
    rec = {"stats": {"lookahead_steps": 1820, "steps": 1840},
           "decode_steps": 1830}
    assert read(rec) == pytest.approx(100.0 * 1820 / 1830)


def test_nothing_to_read_is_none():
    """A program from before the counter (the parent), a train cell, a
    window without a decode step: None, never an exception."""
    assert read({"stats": {"steps": 12}, "decode_steps": 12}) is None
    assert read({}) is None
    assert read({"stats": None, "decode_steps": 3}) is None
    assert read({"stats": {"lookahead_steps": 0}, "decode_steps": 0}) is None


def test_it_is_declared_for_both_serve_cells_only():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    metric = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert metric == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "serve engine",
        "moves": "serve_out_tok_s",
        "workloads": ["pythia-1.4b.serve_closed32",
                      "olmoe-1b-7b.serve_fewshot32"]}
    for cell in metric["workloads"]:
        spec = harness.load_cell(harness.ROOT, cell)
        assert NAME in [m["name"] for m in spec["per_layer"]]
    spec = harness.load_cell(harness.ROOT, "pythia-410m.train_2k")
    assert NAME not in [m["name"] for m in spec["per_layer"]]
