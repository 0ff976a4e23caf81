"""The GLM cell (`glm-4.7-flash.serve_longdoc32`) rehearsed on the CPU at
a tiny size, and its own per-layer readers held to arithmetic and to a
small trace recorded on the chip.

`test_rehearsal.tiny` shrinks a configuration by the keys every family
before the planned ones had; this one has more (two ranks, three head
dims, experts scored), so this file brings its own shrink, as
`test_laguna_cell.py` does. What a rehearsal shows is control flow,
checks, counts and the shape of the last line: never a time.
"""

import copy
import json
import lzma
import os

import pytest

from benchmarks import glm_costs, harness
from test_rehearsal import (ROOT, check_line, checkout_with_links, log,  # noqa: F401
                            on_cpu, run)

CELL = "glm-4.7-flash.serve_longdoc32"
TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
NEW = ("serve_latent_decode_time_share", "serve_latent_decode_roofline",
       "serve_latent_prefill_roofline", "serve_mla_proj_time_share",
       "serve_latent_kv_bytes_per_token", "serve_routed_matmul_roofline")


def tiny_glm(spec):
    """The loaded cell at hidden 128, 4 heads of nope 64 + rope 64 and v
    128 (the least the chip's kernels take), ranks 64 and 128, so a cache
    row of 192 features in a pool row of 256; 16 experts of width 64, 4 a
    token; layer 0 dense and one expert layer."""
    spec = copy.deepcopy(spec)
    conf, traffic, cell = spec["config"], spec["traffic"], spec["cell"]
    conf.update(hidden_size=128, num_attention_heads=4,
                num_key_value_heads=4, q_lora_rank=64, kv_lora_rank=128,
                qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=128,
                intermediate_size=256, moe_intermediate_size=64,
                n_routed_experts=16, vocab_size=512, num_hidden_layers=2,
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
    spec = on_cpu(tiny_glm(harness.load_cell(ROOT, CELL)))
    spec["root"] = checkout_with_links(tmp_path)
    rec, line = run(spec, trace, log)
    assert rec["checks"] and all(rec["checks"].values()), rec["checks"]
    assert rec["compiles_in_window"] == 0
    # what `closed_loop_probed` adds to `closed_loop`'s checks: the pool's
    # rows against the reference's, read while the probe request is live
    # (bf16 against float32: under half a percent), and the floor
    assert {"served_tokens_match_reference", "cached_rows_within_limit",
            "served_tokens_within_margin"} <= set(rec["checks"])
    check = rec["check"]
    assert len(check["cache_row_error_by_layer"]) == 2
    assert 0 < check["cache_row_error"] < 0.01, check
    assert check["probed_tokens"] > check["probed_prompt"]    # decoded rows too
    assert bool(trace) == ("traced_stats" in rec)
    line = check_line(line, spec, trace)
    stats = rec["stats"]
    assert stats["decode_kv_tokens_latent"] == stats["decode_kv_tokens"] > 0
    assert stats["kv_page_steps_latent"] > 0 == stats["kv_page_steps_full"]
    assert stats["moe_rows_held"] == stats["moe_rows_routed"] > 0
    if trace:
        # counters alone: what the CPU's trace holds no kernel for is left
        # out of the line, not raised
        assert "serve_latent_kv_bytes_per_token" in line["metrics"]
        assert "serve_latent_decode_roofline" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"serve_out_tok_s",
                                        "serve_ttft_p50_ms", "setup_s"}


def test_the_cell_is_files_and_entries_alone():
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve_longdoc32"
    assert bench["workloads"][-1] is cell             # entries at the end
    lists = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    # another scope, a head dim taken as hidden / heads, a config that
    # spells num_experts otherwise, Laguna's own: not this cell's
    for name in ("serve_paged_decode_time_share",
                 "serve_paged_decode_roofline",
                 "serve_grouped_matmul_roofline",
                 "serve_window_attn_time_share", "serve_attn_kinds_roofline",
                 "serve_expert_share_roofline", "serve_kv_bytes_per_token"):
        assert CELL not in lists[name], name
    for name in ("serve_moe_time_share", "serve_grouped_matmul_time_share",
                 "serve_moe_dispatch_time_share", "serve_kv_write_time_share",
                 "serve_prefill_kernel_time_share"):
        assert lists[name][-1] == CELL, name
    # `test_laguna_cell.py` pins this list to Laguna's cell: left as it is
    assert CELL not in lists["serve_moe_shared_time_share"]
    assert [m["name"] for m in bench["per_layer"][-6:]] == list(NEW)
    traffic = harness.load_json(ROOT, "benchmarks", "traffic",
                                cell["traffic"] + ".json")
    assert traffic["kind"] == "closed_loop_probed"
    for name in NEW:
        assert lists[name] == [CELL]
        assert os.path.exists(os.path.join(ROOT, "benchmarks", "metrics",
                                           name + ".py"))
    entry = bench["configs"][-1]
    assert entry["reduced"] == ["num_hidden_layers",
                                "num_nextn_predict_layers"]
    conf = harness.load_json(ROOT, entry["file"])
    assert conf["source"] == entry["source"] and \
        entry["source"].endswith("zai-org/GLM-4.7-Flash/blob/main/config.json")
    assert conf["num_hidden_layers"] == 6 and conf["n_routed_experts"] == 64
    assert len(conf["assumed"]) >= 9 and "deployment" in conf["assumed"] \
        and "bytes" in conf["assumed"]


def test_the_published_keys_are_the_catalogs():
    """Every number of the catalog's `config` under the same key, but for
    the two keys in `reduced`."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the model-configs catalog is not here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "GLM-4.7-Flash")
    conf = harness.load_json(ROOT, "benchmarks", "configs",
                             "glm-4.7-flash.json")
    differ = {k for k, v in row["config"].items() if conf.get(k, "?") != v}
    assert differ == {"num_hidden_layers", "num_nextn_predict_layers"}
    assert conf["source"] == row["source_url"]


def _rec(stats, **more):
    spec = harness.load_cell(ROOT, CELL)
    return dict({"spec": spec, "stats": stats, "decode_steps": 10,
                 "device_kind": "TPU v5 lite"}, **more)


def test_costs_of_the_latent_kernel_by_hand():
    # 32 rows over 300,000 attended latent rows, 20 heads: every head
    # meets every row over 576 features for the score and 512 for the
    # value; a row's 1,152 bytes are read once whatever the heads
    flops, bytes_ = glm_costs.latent_decode(32, 300000, 20, 576, 512)
    assert flops == 2 * 300000 * 20 * (576 + 512) == 13056000000
    assert bytes_ == 300000 * 1152 + 32 * 20 * (576 + 512) * 2
    conf = harness.load_cell(ROOT, CELL)["config"]
    assert glm_costs.widths(conf) == (576, 512)


def test_kv_bytes_per_token_is_the_pool_in_use_over_the_context():
    # 10 steps of 32 rows at 1,024 tokens: 16 full pages a row, of 64
    # slots x 640 features x 2 bytes in each of 6 layers
    tokens = 10 * 32 * 1024
    rec = _rec({"decode_kv_tokens_latent": tokens, "decode_tokens": 320,
                "kv_page_steps_latent": 10 * 32 * 16})
    assert glm_costs.latent_kv_bytes_per_token(rec) == 6 * 640 * 2 == 7680
    # a last page half used: its unused slots count
    half = _rec({"decode_kv_tokens_latent": 10 * 32 * 992,
                 "kv_page_steps_latent": 10 * 32 * 16})
    assert glm_costs.latent_kv_bytes_per_token(half) == \
        pytest.approx(7680 * 1024 / 992)


def test_prompt_tokens_by_bucket_are_the_populations():
    tokens = glm_costs.prompt_tokens_by_bucket(harness.load_cell(ROOT, CELL))
    assert sorted(tokens) == [2048, 3072, 4096, 6144, 8192, 12288, 16384]
    assert tokens[16384] == (12653 + 13483 + 14458 + 15637 + 4 * 16384) / 8
    assert tokens[2048] == 2048           # no prompt lands in it
    assert all(below < tokens[b] <= b for below, b in
               zip([2048, 3072, 4096, 6144, 8192, 12288],
                   [3072, 4096, 6144, 8192, 12288, 16384]))


def test_a_run_without_the_scopes_or_counters_reads_nothing():
    """A cell (or a commit) without latent layers: every new reader
    returns None and raises nothing."""
    other = harness.load_cell(ROOT, "olmoe-1b-7b.serve_fewshot32")
    bare = {"spec": other, "stats": {"decode_kv_tokens": 5, "decode_tokens":
                                     5}, "decode_steps": 5,
            "device_kind": "TPU v5 lite", "trace_path": None}
    for name in NEW:
        assert harness.load_module(ROOT, "metrics", name).read(bare) is None
        assert harness.load_module(ROOT, "metrics", name).read(
            dict(bare, stats=None)) is None
    packed = os.path.join(TESTDATA, "tiny_moe_serve_scoped.xplane.pb.xz")
    if os.path.exists(packed):
        # a recorded trace of another cell: scopes, but none of these
        import tempfile
        with tempfile.NamedTemporaryFile(suffix=".xplane.pb") as f, \
                lzma.open(packed) as g:
            f.write(g.read())
            f.flush()
            traced = dict(bare, trace_path=f.name)
            for name in NEW:
                assert harness.load_module(ROOT, "metrics",
                                           name).read(traced) is None, name


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The tiny trace recorded on the chip
    (`benchmarks/tests/record_glm_trace.py`), unpacked."""
    packed = os.path.join(TESTDATA, "tiny_glm_serve_scoped.xplane.pb.xz")
    if not os.path.exists(packed):
        pytest.skip("no recorded GLM trace under benchmarks/testdata")
    path = tmp_path_factory.mktemp("trace") / "tiny_glm.xplane.pb"
    with lzma.open(packed) as f:
        path.write_bytes(f.read())
    with open(os.path.join(TESTDATA,
                           "tiny_glm_serve_scoped.expected.json")) as f:
        return str(path), json.load(f)


def test_the_readers_on_a_trace_recorded_on_the_chip(recorded):
    from benchmarks import scope_reduce
    path, expected = recorded
    reduced = scope_reduce.reduce_file(path)
    for name in ("ds.paged_decode_latent", "ds.mla_q", "ds.mla_kv",
                 "ds.mla_absorb", "ds.moe_shared", "ds.kv_write"):
        assert reduced["scopes"].get(name, 0.0) > 0.0, name
        assert reduced["scopes"][name] == pytest.approx(
            expected["scopes"]["scopes"][name])
    assert reduced["calls"]["ds.paged_decode_latent"][0] >= 1
    spec = tiny_glm(harness.load_cell(ROOT, CELL))
    rec = {"spec": spec, "trace_path": path, "stats": expected["stats"],
           "decode_steps": expected["decode_steps"],
           "traced_stats": expected["traced_stats"],
           "device_kind": "TPU v5 lite"}
    # the pool held what the reference computes, to bf16's rounding
    assert 0 < expected["check"]["cache_row_error"] < 0.01
    assert harness.load_module(
        ROOT, "metrics", "serve_latent_decode_roofline").read(
            dict(rec, traced_stats=None)) is None   # no window mean instead
    for reader in NEW:
        value = harness.load_module(ROOT, "metrics", reader).read(rec)
        want = expected["metrics"][reader]
        assert (value is None and want is None) or \
            value == pytest.approx(want), reader
    for reader in ("serve_latent_decode_time_share",
                   "serve_latent_decode_roofline",
                   "serve_mla_proj_time_share",
                   "serve_latent_kv_bytes_per_token"):
        assert expected["metrics"][reader] is not None, reader
    for reader in NEW[:4] + NEW[5:]:
        if expected["metrics"][reader] is not None:
            assert 0 < expected["metrics"][reader] < 100, reader
