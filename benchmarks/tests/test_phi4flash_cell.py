"""The phi4flash cell (`phi-4-mini-flash.serve_reason96`) rehearsed on the
CPU at a tiny size, its deliberate faults held to fail, and its own
per-layer readers held to arithmetic and to a small trace recorded on the
chip.

What a rehearsal shows is control flow, checks, counts and the shape of
the last line: never a time.
"""

import copy
import json
import lzma
import os

import pytest

from benchmarks import harness, phi4flash_costs
from test_rehearsal import (ROOT, check_line, checkout_with_links, log,  # noqa: F401
                            on_cpu, run)

CELL = "phi-4-mini-flash.serve_reason96"
TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
NEW = ("serve_ssm_time_share", "serve_ssm_step_roofline",
       "serve_ssm_scan_roofline", "serve_cross_decode_time_share",
       "serve_cross_decode_roofline", "serve_gmu_time_share",
       "serve_attn_diff_time_share", "serve_state_bytes_per_seq",
       "serve_prefill_cross_row_share")
COUNTERS = NEW[-2:]
GENERAL = ("serve_out_tok_s", "serve_ttft_p50_ms", "serve_ttft_p95_ms",
           "serve_step_ms_p50", "serve_device_idle_share",
           "serve_peak_hbm_gb", "serve_batch_occupancy",
           "serve_prefill_share", "serve_window_attn_time_share",
           "serve_kv_write_time_share", "serve_unscoped_time_share",
           "serve_xla_fallbacks", "serve_compiles_in_window",
           "serve_slow_step_share", "serve_itl_p50_ms", "serve_itl_p99_ms",
           "serve_paged_decode_time_share", "serve_lookahead_share")


def tiny_phi4flash(spec):
    """The loaded cell at hidden 512, 8 heads of 64 (4 pairs of 128, the
    least the chip's kernels take) over 4 K/V heads, inner 1,024 (whole
    registers for the scan kernels), MLP width 768, window 32, 8 layers:
    ssm, window, ssm, window, ssm, full, gmu, cross."""
    spec = copy.deepcopy(spec)
    conf, traffic, cell = spec["config"], spec["traffic"], spec["cell"]
    conf.update(hidden_size=512, num_attention_heads=8,
                num_key_value_heads=4, intermediate_size=768,
                vocab_size=512, num_hidden_layers=8, sliding_window=32,
                max_position_embeddings=256)
    traffic.update(
        clients=4, population=16, ramp_s=0.3, check_requests=3,
        max_total=256,
        prompt_len=dict(traffic["prompt_len"], median=40, min=8, max=100),
        output_len=dict(traffic["output_len"], median=24, min=8, max=60))
    cell["model_options"]["max_seq_len"] = 256
    cell["engine"]["inference"].update(
        page_size=16, num_pages=4 * 16 + 3, max_seq_len=256,
        max_batch_size=4, token_budget=260, prefill_lengths=[64, 128],
        decode_batch_sizes=[4], kernel="pallas")
    cell.update(trace_after_s=0.1, traced_seconds=0.3)
    return spec


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(trace, on_cpu, log, tmp_path):  # noqa: F811
    spec = on_cpu(tiny_phi4flash(harness.load_cell(ROOT, CELL)))
    spec["root"] = checkout_with_links(tmp_path)
    rec, line = run(spec, trace, log)
    assert rec["checks"] and all(rec["checks"].values()), rec["checks"]
    assert rec["compiles_in_window"] == 0
    assert {"served_tokens_match_reference", "cached_rows_within_limit",
            "recurrent_state_within_limit",
            "served_tokens_within_margin"} <= set(rec["checks"])
    check = rec["check"]
    # both readings of the probe request, every layer of each kind (bf16
    # weights, activations and pages against the float32 reference)
    assert set(check["readings"]) == {"after_prefill", "at_end"}
    for reading in check["readings"].values():
        assert len(reading["state_error_by_layer"]) == 3
        assert len(reading["conv_rows_error_by_layer"]) == 3
        assert len(reading["full_row_error_by_layer"]) == 1
        assert len(reading["window_row_error_by_layer"]) == 2
    first, last = (check["readings"][k] for k in ("after_prefill", "at_end"))
    assert first["fed"] in (check["probed_prompt"],
                            check["probed_prompt"] + 1)
    assert last["fed"] == check["probed_tokens"] > first["fed"] + 6
    assert 0 < check["cache_row_error"] < 0.03, check
    assert 0 < check["state_error"] < 0.03, check
    assert 0 < check["conv_rows_error"] < 0.03, check
    assert 0 < check["state_error_first_layer"] <= check["state_error"]
    assert bool(trace) == ("traced_stats" in rec)
    line = check_line(line, spec, trace)
    stats = rec["stats"]
    # counted at the dispatch, the tokens at the read-back a step later
    assert abs(stats["state_slot_steps"] - stats["decode_tokens"]) <= 4 \
        < stats["state_slot_steps"]
    assert abs(stats["prefill_rows_cross"] - stats["prefill_requests"]) <= 1 \
        and stats["prefill_rows"] >= 64 * stats["prefill_rows_cross"] > 0
    assert stats["kv_page_steps_window"] > 0
    if trace:
        # counters alone: what the CPU's trace holds no kernel for is left
        # out of the line, not raised
        metrics = line["metrics"]
        assert metrics["serve_state_bytes_per_seq"]["value"] == \
            3 * (3 * 1024 * 2 + 16 * 1024 * 4)
        assert 0 < metrics["serve_prefill_cross_row_share"]["value"] <= 1 / 64
        assert "serve_ssm_step_roofline" not in metrics
    else:
        assert set(line["metrics"]) == {"serve_out_tok_s",
                                        "serve_ttft_p50_ms", "setup_s"}


FAULTS = {
    # a padded prefill that lets the bucket's tail move the state: the
    # scan and the convolution take the padding rows as real
    "padding moves the state": "real",
    # a slot that is not started from zero: the prefill's scan goes on
    # from what the slot held
    "a slot not zeroed": "stale",
}


@pytest.mark.parametrize("fault", FAULTS.values(), ids=FAULTS.keys())
def test_a_deliberate_fault_fails_the_state_check(fault, on_cpu, log,  # noqa: F811
                                                  tmp_path, monkeypatch):
    import jax.numpy as jnp
    from deeperspeed_tpu.models import gpt_neox as neox
    from deeperspeed_tpu.ops.pallas import ssm as ssm_ops
    if fault == "real":
        mixer = neox.ssm_mixer
        monkeypatch.setattr(
            neox, "ssm_mixer",
            lambda cfg, p, a, real=None, use_pallas=True: mixer(
                cfg, p, a, None, use_pallas))
    else:
        scan = ssm_ops.ssm_scan

        def stale(dt, x, Bm, Cm, A, D, backend=None):
            s, h = scan(dt, x, Bm, Cm, A, D, backend=backend)
            return s, h + 0.05 * jnp.ones_like(h)   # what a slot held
        monkeypatch.setattr(ssm_ops, "ssm_scan", stale)
    spec = on_cpu(tiny_phi4flash(harness.load_cell(ROOT, CELL)))
    spec["root"] = checkout_with_links(tmp_path)
    rec, _ = run(spec, 0, log)
    assert not rec["checks"]["recurrent_state_within_limit"], rec["check"]
    assert not rec["correct"]


def test_the_cell_is_files_and_entries_alone():
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve_reason96" and \
        cell["config"] == "phi-4-mini-flash"
    lists = {m["name"]: m.get("workloads")
             for m in bench["per_layer"] + bench["end_to_end"]}
    # membership, never a list's end: a later cell is appended behind
    for name in GENERAL:
        assert CELL in lists[name], name
    # experts, latent pages, the loop, blocks, a plain flash forward (the
    # full layer's prefill attention is the last row's paged decode) and a
    # head size read off hidden / heads: not this cell's
    for name in ("serve_moe_time_share", "serve_latent_decode_time_share",
                 "serve_loop_step_roofline", "serve_block_decode_roofline",
                 "serve_prefill_kernel_time_share",
                 "serve_paged_decode_roofline", "serve_attn_kinds_roofline",
                 "serve_kv_bytes_per_token"):
        assert CELL not in lists[name], name
    for name in NEW:
        assert lists[name] == [CELL]
        assert os.path.exists(os.path.join(ROOT, "benchmarks", "metrics",
                                           name + ".py"))
    traffic = harness.load_json(ROOT, "benchmarks", "traffic",
                                cell["traffic"] + ".json")
    assert traffic["kind"] == "closed_loop_state_probed"
    assert (traffic["clients"], traffic["population"],
            traffic["check_requests"], traffic["order_seed"],
            traffic["max_total"]) == (96, 192, 8, 0, 3072)
    assert [traffic["prompt_len"][k] for k in
            ("median", "sigma", "min", "max")] == [256, 0.5, 64, 1024]
    assert [traffic["output_len"][k] for k in
            ("median", "sigma", "min", "max")] == [1024, 0.5, 256, 2048]
    spec = harness.load_cell(ROOT, CELL)
    inference = spec["cell"]["engine"]["inference"]
    assert inference["prefill_lengths"] == [128, 256, 512, 1024]
    assert (inference["page_size"], inference["num_pages"],
            inference["max_batch_size"], inference["max_seq_len"],
            inference["decode_batch_sizes"],
            inference["prefill_batch_sizes"]) == \
        (64, 96 * 48 + 16 + 1, 96, 3072, [96], [1])
    for limit in ("logit_margin", "exact_match_floor",
                  "cache_row_error_limit", "state_error_limit",
                  "conv_rows_error_limit", "state_error_first_layer_limit"):
        assert spec["cell"][limit] > 0 and \
            len(spec["cell"][limit + "_why"]) > 200, limit
    entry = next(c for c in bench["configs"]
                 if c["name"] == "phi-4-mini-flash")
    assert entry["reduced"] == []
    conf = harness.load_json(ROOT, entry["file"])
    assert conf["source"] == entry["source"] and entry["source"].endswith(
        "microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json")
    assert conf["reduced"] == [] and conf["family"] == "phi4flash"
    assert len(conf["assumed"]) >= 12 and \
        conf["assumed"]["num_parameters"] == 3852562944


def test_the_published_keys_are_the_catalogs():
    """Every key of the catalog's `config` under the same key: nothing
    is reduced."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the model-configs catalog is not here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    conf = harness.load_json(ROOT, "benchmarks", "configs",
                             "phi-4-mini-flash.json")
    assert {k for k, v in row["config"].items()
            if conf.get(k, "?") != v} == set()
    assert conf["source"] == row["source_url"]


def test_costs_by_hand():
    conf = harness.load_cell(ROOT, CELL)["config"]
    assert phi4flash_costs.inner(conf) == 5120
    assert phi4flash_costs.layer_counts(conf) == (9, 7)
    # one decode step's nine layers of 96 live rows: each state 327,680 B
    # read and written, 3 vectors of 5,120 float32, B and C; A and D a call
    flops, bytes_ = phi4flash_costs.ssm_step(96 * 9, conf, 9)
    assert bytes_ == 864 * (2 * 327680 + 3 * 20480 + 128) + \
        9 * (327680 + 20480)
    assert 0.62e9 < bytes_ < 0.63e9 and flops / 197e12 < bytes_ / 819e9
    # a prompt of 290 tokens through nine scans
    flops, bytes_ = phi4flash_costs.ssm_scan(290 * 9, conf, 9)
    assert bytes_ == 2610 * (3 * 20480 + 128) + 9 * (2 * 327680 + 20480)
    # ONE layer's decode over 96,000 attended rows: 5,120 B a row
    flops, bytes_ = phi4flash_costs.cross_decode(96, 96000, conf)
    assert bytes_ == 96000 * 5120 + 2 * 96 * 2 * 2560 * 2
    assert flops == 4 * 96000 * 2560


def _rec(stats, **more):
    spec = harness.load_cell(ROOT, CELL)
    return dict({"spec": spec, "stats": stats, "decode_steps": 10,
                 "device_kind": "TPU v5 lite"}, **more)


def test_the_counter_readers():
    rec = _rec({"state_slot_steps": 960, "state_byte_steps": 960 * 3225600,
                "prefill_rows": 5 * 256, "prefill_rows_cross": 5})
    assert phi4flash_costs.state_bytes_per_seq(rec) == 3225600 == \
        9 * (327680 + 30720)
    assert phi4flash_costs.prefill_cross_row_share(rec) == 1 / 256


def test_a_run_without_the_scopes_or_counters_reads_nothing():
    """Another cell, or a commit from before this configuration: every
    new reader returns None and raises nothing."""
    other = harness.load_cell(ROOT, "pythia-1.4b.serve_closed32")
    bare = {"spec": other, "stats": {"decode_kv_tokens": 5, "decode_tokens":
                                     5, "kv_page_steps_full": 1},
            "decode_steps": 5, "device_kind": "TPU v5 lite",
            "trace_path": None}
    mine = dict(bare, spec=harness.load_cell(ROOT, CELL))   # no counters
    for name in NEW:
        read = harness.load_module(ROOT, "metrics", name).read
        assert read(bare) is None and read(dict(bare, stats=None)) is None
        assert read(mine) is None, name
    packed = os.path.join(TESTDATA, "tiny_serve_scoped.xplane.pb.xz")
    if os.path.exists(packed):
        # a recorded trace of another cell: scopes, but none of these
        import tempfile
        with tempfile.NamedTemporaryFile(suffix=".xplane.pb") as f, \
                lzma.open(packed) as g:
            f.write(g.read())
            f.flush()
            for rec in (dict(bare, trace_path=f.name),
                        dict(mine, trace_path=f.name)):
                for name in NEW:
                    assert harness.load_module(
                        ROOT, "metrics", name).read(rec) is None, name


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The tiny trace recorded on the chip
    (`benchmarks/tests/record_phi4flash_trace.py`), unpacked."""
    packed = os.path.join(TESTDATA, "tiny_phi4flash_serve.xplane.pb.xz")
    if not os.path.exists(packed):
        pytest.skip("no recorded phi4flash trace under benchmarks/testdata")
    path = tmp_path_factory.mktemp("trace") / "tiny_phi4flash.xplane.pb"
    with lzma.open(packed) as f:
        path.write_bytes(f.read())
    with open(os.path.join(TESTDATA,
                           "tiny_phi4flash_serve.expected.json")) as f:
        return str(path), json.load(f)


def test_the_readers_on_a_trace_recorded_on_the_chip(recorded):
    from benchmarks import scope_reduce
    path, expected = recorded
    reduced = scope_reduce.reduce_file(path)
    for name in ("ds.ssm_in", "ds.ssm_scan", "ds.ssm_step", "ds.ssm_out",
                 "ds.gmu", "ds.attn_diff", "ds.paged_decode_cross",
                 "ds.paged_decode", "ds.paged_decode_window",
                 "ds.flash_fwd_window", "ds.kv_write"):
        assert reduced["scopes"].get(name, 0.0) > 0.0, name
        assert reduced["scopes"][name] == pytest.approx(
            expected["scopes"]["scopes"][name])
    for kernel in ("ds.ssm_scan", "ds.ssm_step", "ds.paged_decode_cross"):
        assert reduced["calls"][kernel][0] > 0, kernel
    spec = tiny_phi4flash(harness.load_cell(ROOT, CELL))
    rec = {"spec": spec, "trace_path": path, "stats": expected["stats"],
           "decode_steps": expected["decode_steps"],
           "traced_stats": expected["traced_stats"],
           "device_kind": "TPU v5 lite"}
    assert all(expected["checks"].values())
    for reader in NEW:
        value = harness.load_module(ROOT, "metrics", reader).read(rec)
        want = expected["metrics"][reader]
        assert want is not None and value == pytest.approx(want), reader
    for reader in NEW[:7]:
        assert 0 < expected["metrics"][reader] < 100, reader
    # no window mean stands in for the traced stretch's own counters
    assert harness.load_module(ROOT, "metrics", "serve_ssm_step_roofline"
                               ).read(dict(rec, traced_stats=None)) is None
