"""The Ouro cell (`ouro-2.6b.serve_reason16`) rehearsed on the CPU at a
tiny size, and its own per-layer readers held to arithmetic and to a
small trace recorded on the chip.

What a rehearsal shows is control flow, checks, counts and the shape of
the last line: never a time.
"""

import copy
import json
import lzma
import os

import pytest

from benchmarks import harness, ouro_costs
from test_rehearsal import (ROOT, check_line, checkout_with_links, log,  # noqa: F401
                            on_cpu, run)

CELL = "ouro-2.6b.serve_reason16"
TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
NEW = ("serve_loop_passes_per_step", "serve_loop_overhead_time_share",
       "serve_loop_exit_time_share", "serve_loop_step_roofline",
       "serve_loop_kv_bytes_per_token")


def tiny_ouro(spec):
    """The loaded cell at hidden 256, 2 heads of 128 (the least the
    chip's kernels take), MLP width 384, 3 layers run 3 times: 9 cache
    layers a token."""
    spec = copy.deepcopy(spec)
    conf, traffic, cell = spec["config"], spec["traffic"], spec["cell"]
    conf.update(hidden_size=256, num_attention_heads=2,
                num_key_value_heads=2, head_dim=128, intermediate_size=384,
                vocab_size=512, num_hidden_layers=3, total_ut_steps=3,
                layer_types=["full_attention"] * 3,
                max_position_embeddings=256)
    traffic.update(
        clients=4, population=16, ramp_s=0.3, check_requests=3,
        max_total=256,
        prompt_len=dict(traffic["prompt_len"], median=40, min=8, max=100),
        output_len=dict(traffic["output_len"], median=8, min=3, max=14))
    cell["model_options"]["max_seq_len"] = 256
    cell["engine"]["inference"].update(
        page_size=16, num_pages=80, max_seq_len=256, max_batch_size=4,
        token_budget=260, prefill_lengths=[64, 128, 256],
        decode_batch_sizes=[4], kernel="pallas")
    cell.update(trace_after_s=0.1, traced_seconds=0.3,
                cache_row_error_limit_by_pass=cell[
                    "cache_row_error_limit_by_pass"][:3])
    return spec


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(trace, on_cpu, log, tmp_path):  # noqa: F811
    spec = on_cpu(tiny_ouro(harness.load_cell(ROOT, CELL)))
    spec["root"] = checkout_with_links(tmp_path)
    rec, line = run(spec, trace, log)
    assert rec["checks"] and all(rec["checks"].values()), rec["checks"]
    assert rec["compiles_in_window"] == 0
    assert {"served_tokens_match_reference", "cached_rows_within_limit",
            "cached_rows_within_limit_every_pass",
            "served_tokens_within_margin"} <= set(rec["checks"])
    check = rec["check"]
    # every pass's cache layers, read while the probe request is live
    # (bf16 weights, activations and pools against the float32 reference:
    # 0.3% at the first cache layer, 1.3% at the ninth)
    assert len(check["cache_row_error_by_layer"]) == 9
    assert 0 < check["cache_row_error"] < 0.03, check
    by_pass = check["cache_row_error_by_pass"]
    assert len(by_pass) == 3 and max(by_pass) == check["cache_row_error"]
    assert check["cache_row_error_limit_by_pass"] == \
        spec["cell"]["cache_row_error_limit_by_pass"]
    assert check["probed_tokens"] > check["probed_prompt"]    # decoded rows too
    assert bool(trace) == ("traced_stats" in rec)
    line = check_line(line, spec, trace)
    stats = rec["stats"]
    assert stats["loop_passes"] >= 3 * stats["decode_steps"] > 0
    assert stats["kv_page_steps_full"] > 0 == stats["kv_page_steps_latent"]
    if trace:
        # counters alone: what the CPU's trace holds no kernel for is left
        # out of the line, not raised
        value = line["metrics"]["serve_loop_kv_bytes_per_token"]["value"]
        assert 9 * 2 * 2 * 128 * 2 <= value < 2 * 9 * 2 * 2 * 128 * 2
        assert "serve_loop_step_roofline" not in line["metrics"]
        assert "serve_loop_passes_per_step" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"serve_out_tok_s",
                                        "serve_ttft_p50_ms", "setup_s"}


def test_the_cell_is_files_and_entries_alone():
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve_reason16"
    lists = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    # a head-less pool, experts, a window, Laguna's own layer counts: not
    # this cell's (`serve_kv_bytes_per_token` counts the configuration's
    # 48 layers, not the 192 cache layers)
    for name in ("serve_latent_decode_time_share", "serve_moe_time_share",
                 "serve_window_attn_time_share", "serve_kv_bytes_per_token",
                 "serve_grouped_matmul_roofline"):
        assert CELL not in lists[name], name
    for name in ("serve_paged_decode_time_share",
                 "serve_paged_decode_roofline", "serve_kv_write_time_share",
                 "serve_prefill_kernel_time_share", "serve_peak_hbm_gb",
                 "serve_xla_fallbacks", "serve_compiles_in_window"):
        assert CELL in lists[name], name
    traffic = harness.load_json(ROOT, "benchmarks", "traffic",
                                cell["traffic"] + ".json")
    assert traffic["kind"] == "closed_loop_kv_probed"
    # the engine ISSUE 39 names, and a limit a pass that ends in the
    # whole loop's
    spec = harness.load_cell(ROOT, CELL)
    inference = spec["cell"]["engine"]["inference"]
    assert inference["prefill_lengths"] == [64, 128, 256]
    assert (inference["page_size"], inference["num_pages"],
            inference["max_batch_size"]) == (64, 81, 16)
    by_pass = spec["cell"]["cache_row_error_limit_by_pass"]
    assert len(by_pass) == spec["config"]["total_ut_steps"]
    assert by_pass == sorted(by_pass)
    assert by_pass[-1] == spec["cell"]["cache_row_error_limit"]
    assert (traffic["clients"], traffic["population"],
            traffic["check_requests"], traffic["order_seed"],
            traffic["max_total"]) == (16, 32, 8, 0, 640)
    for name in NEW:
        assert lists[name] == [CELL]
        assert os.path.exists(os.path.join(ROOT, "benchmarks", "metrics",
                                           name + ".py"))
    entry = next(c for c in bench["configs"] if c["name"] == "ouro-2.6b")
    assert entry["reduced"] == []
    conf = harness.load_json(ROOT, entry["file"])
    assert conf["source"] == entry["source"] and \
        entry["source"].endswith("ByteDance/Ouro-2.6B/blob/main/config.json")
    assert conf["reduced"] == [] and conf["family"] == "ouro"
    assert len(conf["assumed"]) >= 9 and "deployment" in conf["assumed"] \
        and "bytes" in conf["assumed"]
    inference = harness.load_json(
        ROOT, "benchmarks", "workloads", CELL + ".json")["engine"]["inference"]
    assert (inference["num_pages"], inference["page_size"],
            inference["max_batch_size"]) == (81, 64, 16)


def test_the_published_keys_are_the_catalogs():
    """Every key of the catalog's `config` under the same key: nothing
    is reduced."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the model-configs catalog is not here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    conf = harness.load_json(ROOT, "benchmarks", "configs", "ouro-2.6b.json")
    assert {k for k, v in row["config"].items()
            if conf.get(k, "?") != v} == set()
    assert conf["source"] == row["source_url"]


def test_costs_of_a_decode_step_by_hand():
    conf = harness.load_cell(ROOT, CELL)["config"]
    assert ouro_costs.cache_layers(conf) == 192
    assert ouro_costs.kv_token_bytes(conf) == 1572864
    # a layer's matmuls: q and o 2 x 2048 x 2048, k and v the same, the
    # gated MLP 3 x 2048 x 5632
    assert ouro_costs.block_matmul_params(conf) == \
        48 * (4 * 2048 * 2048 + 3 * 2048 * 5632) == 2466250752
    flops, bytes_ = ouro_costs.decode_step(16, 4000, conf)
    # the weights four times, the head once, 4,000 tokens of K and V
    assert bytes_ == (4 * 2466250752 + 49152 * 2048) * 2 + 4000 * 1572864
    assert flops == 2 * 16 * (4 * 2466250752 + 49152 * 2048) + \
        4 * 4000 * 16 * 128 * 192
    # memory-bound: 26.2 GB at 819 GB/s is 32 ms, the matmuls' 0.33 TFLOP
    # at 197 TFLOP/s under 2
    assert 31e-3 < bytes_ / 819e9 < 33e-3 and flops / 197e12 < 2e-3


def _rec(stats, **more):
    spec = harness.load_cell(ROOT, CELL)
    return dict({"spec": spec, "stats": stats, "decode_steps": 10,
                 "device_kind": "TPU v5 lite"}, **more)


def test_kv_bytes_per_token_is_the_pool_in_use_over_the_context():
    # 10 steps of 16 rows at 256 tokens: 4 full pages a row
    rec = _rec({"decode_kv_tokens": 10 * 16 * 256, "loop_passes": 40,
                "kv_page_steps_full": 10 * 16 * 4})
    assert ouro_costs.loop_kv_bytes_per_token(rec) == 1572864
    # a last page three quarters used: its unused slots count
    part = _rec({"decode_kv_tokens": 10 * 16 * 240, "loop_passes": 40,
                 "kv_page_steps_full": 10 * 16 * 4})
    assert ouro_costs.loop_kv_bytes_per_token(part) == \
        pytest.approx(1572864 * 256 / 240)


def test_a_run_without_the_scopes_or_counters_reads_nothing():
    """Another cell, or a commit from before the loop: every new reader
    returns None and raises nothing."""
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
    (`benchmarks/tests/record_ouro_trace.py`), unpacked."""
    packed = os.path.join(TESTDATA, "tiny_ouro_serve_loop.xplane.pb.xz")
    if not os.path.exists(packed):
        pytest.skip("no recorded Ouro trace under benchmarks/testdata")
    path = tmp_path_factory.mktemp("trace") / "tiny_ouro.xplane.pb"
    with lzma.open(packed) as f:
        path.write_bytes(f.read())
    with open(os.path.join(TESTDATA,
                           "tiny_ouro_serve_loop.expected.json")) as f:
        return str(path), json.load(f)


def test_the_readers_on_a_trace_recorded_on_the_chip(recorded):
    from benchmarks import scope_reduce
    path, expected = recorded
    reduced = scope_reduce.reduce_file(path)
    for name in ("ds.loop", "ds.loop_exit", "ds.paged_decode",
                 "ds.kv_write"):
        assert reduced["scopes"].get(name, 0.0) > 0.0, name
        assert reduced["scopes"][name] == pytest.approx(
            expected["scopes"]["scopes"][name])
    spec = tiny_ouro(harness.load_cell(ROOT, CELL))
    rec = {"spec": spec, "trace_path": path, "stats": expected["stats"],
           "decode_steps": expected["decode_steps"],
           "traced_stats": expected["traced_stats"],
           "device_kind": "TPU v5 lite"}
    steps = ouro_costs.decode_steps(rec)
    # whole decode programs inside the stretch, 3 passes x 3 layers of
    # the paged kernel in each
    assert len(steps) >= 3 and {n for _, n in steps} == {9}
    assert 0 < expected["check"]["cache_row_error"] < 0.03
    assert harness.load_module(
        ROOT, "metrics", "serve_loop_step_roofline").read(
            dict(rec, traced_stats=None)) is None   # no window mean instead
    for reader in NEW:
        value = harness.load_module(ROOT, "metrics", reader).read(rec)
        want = expected["metrics"][reader]
        assert want is not None and value == pytest.approx(want), reader
    assert expected["metrics"]["serve_loop_passes_per_step"] == 3.0
    for reader in NEW[1:4]:
        assert 0 < expected["metrics"][reader] < 100, reader
