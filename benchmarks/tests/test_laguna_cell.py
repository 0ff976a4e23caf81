"""The Laguna cell (`laguna-s-2.1.serve_mixed32`) rehearsed on the CPU at
a tiny size, and its own per-layer readers held to arithmetic and to a
small trace recorded on the chip.

`test_rehearsal.tiny` shrinks a configuration by the keys every family
before this one had; a planned model has more of them (a head dim of its
own, per-layer lists, experts scored and held), so this file brings its
own shrink, and the generic rehearsal of this one cell does not apply.
What a rehearsal shows is control flow, checks, counts and the shape of
the last line: never a time.
"""

import copy
import json
import lzma
import os

import pytest

from benchmarks import harness, laguna_costs
from test_rehearsal import (ROOT, check_line, checkout_with_links, log,  # noqa: F401
                            on_cpu, run)

CELL = "laguna-s-2.1.serve_mixed32"
TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")


def tiny_laguna(spec):
    """The loaded cell at hidden 128, head dim 64, 2 KV heads under 4 / 6
    query heads, window 32, 16 experts scored of which 8 held, 4 a token,
    the five layers in their published order."""
    spec = copy.deepcopy(spec)
    conf, traffic, cell = spec["config"], spec["traffic"], spec["cell"]
    conf.update(hidden_size=128, head_dim=64, num_attention_heads=4,
                num_key_value_heads=2,
                num_attention_heads_per_layer=[4, 6, 6, 6, 4],
                intermediate_size=256, moe_intermediate_size=64,
                shared_expert_intermediate_size=64, vocab_size=512,
                num_experts_published=16, num_experts=8, held_experts="0-7",
                num_experts_per_tok=4, sliding_window=32,
                max_position_embeddings=256)
    traffic.update(
        clients=4, population=16, ramp_s=0.3, check_requests=3,
        max_total=256,
        prompt_len=dict(traffic["prompt_len"], median=40, min=8, max=100),
        output_len=dict(traffic["output_len"], median=6, min=2, max=12))
    cell["model_options"]["max_seq_len"] = 256
    cell["engine"]["inference"].update(
        page_size=16, num_pages=80, max_seq_len=256, max_batch_size=4,
        token_budget=260, prefill_lengths=[128, 256],
        decode_batch_sizes=[4], kernel="pallas")
    cell.update(trace_after_s=0.1, traced_seconds=0.3)
    return spec


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(trace, on_cpu, log, tmp_path):  # noqa: F811
    spec = on_cpu(tiny_laguna(harness.load_cell(ROOT, CELL)))
    spec["root"] = checkout_with_links(tmp_path)
    rec, line = run(spec, trace, log)
    assert rec["checks"] and all(rec["checks"].values()), rec["checks"]
    assert rec["compiles_in_window"] == 0
    line = check_line(line, spec, trace)
    stats = rec["stats"]
    assert 0 < stats["moe_rows_held"] < stats["moe_rows_routed"]
    assert stats["kv_page_steps_window"] <= stats["kv_page_steps_full"]
    if trace:
        # counters alone: what the CPU's trace holds no kernel for is left
        # out of the line, not raised
        assert "serve_kv_bytes_per_token" in line["metrics"]
        assert "serve_paged_decode_roofline" not in line["metrics"]
        assert "serve_grouped_matmul_roofline" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"serve_out_tok_s",
                                        "serve_ttft_p50_ms", "setup_s"}


def test_the_cell_is_files_and_entries_alone():
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve_mixed32"
    lists = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    for name in ("serve_paged_decode_roofline",
                 "serve_grouped_matmul_roofline"):
        assert CELL not in lists[name]
    for name in ("serve_window_attn_time_share", "serve_attn_kinds_roofline",
                 "serve_expert_share_roofline", "serve_moe_shared_time_share",
                 "serve_kv_bytes_per_token"):
        assert lists[name] == [CELL]
        assert os.path.exists(os.path.join(ROOT, "benchmarks", "metrics",
                                           name + ".py"))
    conf = harness.load_json(ROOT, "benchmarks", "configs",
                             "laguna-s-2.1.json")
    assert conf["num_hidden_layers"] == 5 and conf["num_experts"] == 128
    assert conf["num_experts_published"] == 256
    assert len(conf["assumed"]) >= 9 and "deployment" in conf["assumed"]


def _rec(stats, **more):
    spec = harness.load_cell(ROOT, CELL)
    return dict({"spec": spec, "stats": stats, "decode_steps": 10,
                 "device_kind": "TPU v5 lite"}, **more)


def test_kv_bytes_per_token_is_the_pools_in_use_over_the_context():
    # 10 steps of 32 rows at 1,024 tokens: 16 full pages a row; a window
    # layer keeps 9. Every layer keeping everything would be 20,480 bytes
    # a token; here 2 full layers keep 16 pages and 3 window layers 9
    tokens = 10 * 32 * 1024
    rec = _rec({"decode_kv_tokens": tokens, "decode_tokens": 320,
                "kv_page_steps_full": 10 * 32 * 16,
                "kv_page_steps_window": 10 * 32 * 9})
    page = 2 * 8 * 64 * 128 * 2
    assert laguna_costs.kv_bytes_per_token(rec) == \
        (16 * 2 + 9 * 3) * page / 1024
    everything = _rec({"decode_kv_tokens": tokens,
                       "kv_page_steps_full": 10 * 32 * 16,
                       "kv_page_steps_window": 10 * 32 * 16})
    assert laguna_costs.kv_bytes_per_token(everything) == 20480.0
    # a program from before the counters: nothing to read, nothing raised
    assert laguna_costs.kv_bytes_per_token(
        _rec({"decode_kv_tokens": tokens})) is None
    assert laguna_costs.attn_kinds_roofline(
        _rec({"decode_kv_tokens": tokens})) is None
    assert laguna_costs.expert_share_roofline(_rec({})) is None


def test_costs_of_the_two_kernels():
    flops, bytes_ = laguna_costs.paged_decode(32, 32 * 512, 72, 8, 128)
    assert flops == 4 * 32 * 512 * 72 * 128
    assert bytes_ == 2 * 32 * 512 * 8 * 128 * 2 + 2 * 32 * 72 * 128 * 2
    # 160 useful rows over 128 held experts reach 91.5 of them (71%)
    assert 91 < laguna_costs.touched_experts(160, 128) < 92
    flops, bytes_ = laguna_costs.expert_share(160, 3072, 2048, 128)
    assert flops == 2 * 160 * 3072 * 2048
    assert bytes_ < 128 * 3072 * 2048 * 2 + 160 * (3072 + 2048) * 2


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The tiny trace recorded on the chip
    (`benchmarks/tests/record_laguna_trace.py`), unpacked."""
    packed = os.path.join(TESTDATA, "tiny_laguna_serve_scoped.xplane.pb.xz")
    if not os.path.exists(packed):
        pytest.skip("no recorded Laguna trace under benchmarks/testdata")
    path = tmp_path_factory.mktemp("trace") / "tiny_laguna.xplane.pb"
    with lzma.open(packed) as f:
        path.write_bytes(f.read())
    with open(os.path.join(
            TESTDATA, "tiny_laguna_serve_scoped.expected.json")) as f:
        return str(path), json.load(f)


def test_the_readers_on_a_trace_recorded_on_the_chip(recorded):
    from benchmarks import scope_reduce
    path, expected = recorded
    reduced = scope_reduce.reduce_file(path)
    # the stretch of three hundredths of a second holds one decode step (a
    # prefill's `ds.flash_fwd_window` fell outside it)
    for name in ("ds.paged_decode_window", "ds.paged_decode",
                 "ds.moe_shared", "ds.attn_gate"):
        assert reduced["scopes"].get(name, 0.0) > 0.0, name
        assert reduced["scopes"][name] == pytest.approx(
            expected["scopes"]["scopes"][name])
    for name in ("ds.paged_decode", "ds.paged_decode_window"):
        assert reduced["calls"][name][0] >= 1
    spec = tiny_laguna(harness.load_cell(ROOT, CELL))
    rec = {"spec": spec, "trace_path": path, "stats": expected["stats"],
           "decode_steps": expected["decode_steps"],
           "device_kind": "TPU v5 lite"}
    for reader in ("serve_window_attn_time_share",
                   "serve_moe_shared_time_share",
                   "serve_attn_kinds_roofline",
                   "serve_kv_bytes_per_token"):
        value = harness.load_module(ROOT, "metrics", reader).read(rec)
        assert value == pytest.approx(expected["metrics"][reader]), reader
    assert 0 < expected["metrics"]["serve_attn_kinds_roofline"] < 100
