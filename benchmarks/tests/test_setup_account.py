"""The set-up account's eight metrics (PR 52; `benchmarks/setup_account.py`
and `benchmarks/metrics/setup_*.py`) on a recorded
`telemetry.setup_report()` (`testdata/setup_report.json`: a tiny server
and a tiny trainer on the CPU, cut where a window would open, `setup_s`
beside it)."""

import json
import os
import sys
import types

import pytest

from benchmarks import harness, setup_account

NAMES = ("setup_import_s", "setup_engine_build_s", "setup_trace_lower_s",
         "setup_backend_compile_s", "setup_cache_read_s",
         "setup_first_call_s", "setup_cache_misses", "setup_rest_s")
CELLS = {
    "pythia-410m.train_2k", "pythia-410m.train_16k",
    "pythia-1.4b.serve_closed32", "pythia-1.4b.train_zero3_4c",
    "olmoe-1b-7b.serve_fewshot32", "laguna-s-2.1.serve_mixed32",
    "glm-4.7-flash.serve_longdoc32", "ouro-2.6b.serve_reason16",
    "sdar-30b-a3b.serve_blockgen32", "phi-4-mini-flash.serve_reason96",
    "evabyte.serve_bytes24"}


def read(name, rec):
    return harness.load_module(harness.ROOT, "metrics", name).read(rec)


@pytest.fixture
def recorded():
    with open(os.path.join(harness.ROOT, "benchmarks", "testdata",
                           "setup_report.json")) as f:
        report = json.load(f)
    # as `account()` leaves it on the run's record after its first call
    return {"setup_s": report.pop("setup_s"), "_setup_account": report}


def test_each_reader_reads_its_part(recorded):
    report = recorded["_setup_account"]
    train, serve = report["engines"]
    assert (train["engine"], serve["engine"]) == ("train", "serve")
    builds = [train["build"], serve["build"]]
    programs = train["programs"] + serve["programs"]
    records = builds + programs

    def jax_s(e):
        return e["trace_s"] + e["lower_s"] + e["compile_s"] \
            + e["cache_read_s"]
    assert read("setup_import_s", recorded) == report["import_s"] > 0.0
    assert read("setup_engine_build_s", recorded) == pytest.approx(
        sum(b["wall_s"] - jax_s(b) for b in builds))
    assert read("setup_trace_lower_s", recorded) == pytest.approx(
        sum(r["trace_s"] + r["lower_s"] for r in records))
    assert read("setup_backend_compile_s", recorded) == pytest.approx(
        sum(r["compile_s"] for r in records))
    assert read("setup_cache_read_s", recorded) == 0.0   # no cache there
    assert read("setup_first_call_s", recorded) == pytest.approx(
        sum(p["first_call_s"] for p in programs))
    assert read("setup_cache_misses", recorded) == \
        report["totals"]["cache_misses"] == 0
    # a build is more than what jax did inside it, a call more than its
    # compile
    assert read("setup_engine_build_s", recorded) > 0.0
    assert all(p["first_call_s"] > 0.0 for p in programs)


def test_the_parts_add_up_to_setup_s(recorded):
    seconds = [read(name, recorded) for name in NAMES
               if name != "setup_cache_misses"]
    assert sum(seconds) == pytest.approx(recorded["setup_s"], rel=1e-9)
    rest = read("setup_rest_s", recorded)
    assert 0.0 <= rest < recorded["setup_s"]
    assert set(setup_account.SECONDS) | {"setup_rest_s",
                                         "setup_cache_misses"} == set(NAMES)


def test_totals_that_fell_short_are_not_published(recorded):
    """`complete` False: the report could not add up the whole process's
    sums at the cut. The engines' records are exact all the same."""
    whole = {name: read(name, recorded) for name in NAMES}
    recorded["_setup_account"]["complete"] = False
    assert read("setup_cache_misses", recorded) is None
    assert {name: read(name, recorded) for name in NAMES
            if name != "setup_cache_misses"} == \
        {name: value for name, value in whole.items()
         if name != "setup_cache_misses"}


@pytest.mark.parametrize("name", NAMES)
def test_none_at_a_program_without_the_accessor(name, monkeypatch):
    """The parent of PR 52: `runtime/telemetry.py` has no `setup_report`.
    Every reader returns None and raises nothing."""
    monkeypatch.setitem(sys.modules, "deeperspeed_tpu.runtime.telemetry",
                        types.ModuleType("telemetry"))
    monkeypatch.setattr(sys.modules["__main__"], "T_START", 1.0,
                        raising=False)
    assert read(name, {"setup_s": 40.0}) is None


def test_the_account_is_cut_where_the_window_opened(monkeypatch):
    from deeperspeed_tpu.runtime import telemetry
    seen = []

    def setup_report(until=None):
        seen.append(until)
        return {"import_s": 1.0, "engines": [],
                "totals": {"cache_misses": 3}}
    monkeypatch.setattr(telemetry, "setup_report", setup_report)
    # `T_START` of the module that runs `run.main`: the script itself ...
    monkeypatch.setattr(sys.modules["__main__"], "T_START", 100.0,
                        raising=False)
    rec = {"setup_s": 40.0}
    assert read("setup_cache_misses", rec) == 3
    assert read("setup_rest_s", rec) == pytest.approx(39.0)
    assert seen == [140.0]              # one report a run, shared
    # ... or `benchmarks.run` under a wrapper that has none
    monkeypatch.delattr(sys.modules["__main__"], "T_START")
    monkeypatch.setitem(sys.modules, "benchmarks.run",
                        types.SimpleNamespace(T_START=7.0))
    assert read("setup_import_s", {"setup_s": 1.0}) == 1.0
    assert seen[-1] == 8.0
    # ... and nothing to cut at is nothing to read
    monkeypatch.setitem(sys.modules, "benchmarks.run",
                        types.SimpleNamespace())
    assert read("setup_import_s", {"setup_s": 1.0}) is None


@pytest.mark.parametrize("name", NAMES)
def test_every_cell_reports_the_metric(name):
    """Membership, not the list's ends: a later PR appends cells and
    metrics."""
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert CELLS <= set(entry["workloads"])
    assert entry["moves"] == "setup_s" and entry["better"] == "lower"
    assert entry["layer"] == "engine set-up"
    assert entry["source"] == "program_counter"
    assert entry["unit"] == ("programs" if name == "setup_cache_misses"
                             else "s")
    for cell in CELLS:
        spec = harness.load_cell(harness.ROOT, cell)
        assert name in {m["name"] for m in spec["per_layer"]}
