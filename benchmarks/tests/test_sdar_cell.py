"""The SDAR cell (`sdar-30b-a3b.serve_blockgen32`) rehearsed on the CPU at
a tiny size, and its own per-layer readers held to arithmetic and to a
small trace recorded on the chip.

What a rehearsal shows is control flow, checks, counts and the shape of
the last line: never a time.
"""

import copy
import json
import lzma
import os

import pytest

from benchmarks import harness, sdar_costs
from test_rehearsal import (ROOT, check_line, checkout_with_links, log,  # noqa: F401
                            on_cpu, run)

CELL = "sdar-30b-a3b.serve_blockgen32"
TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
NEW = ("serve_block_decode_time_share", "serve_block_decode_roofline",
       "serve_block_step_roofline", "serve_unmask_time_share",
       "serve_block_grouped_matmul_roofline",
       "serve_block_tokens_per_pass", "serve_block_commit_share",
       "serve_block_pass_occupancy", "serve_block_first_unmask_ms")


def tiny_sdar(spec):
    """The loaded cell at hidden 256, 4 query heads over 2 KV heads of 128
    (the least the chip's kernels take), 8 experts of width 128 (2 a
    token), 2 layers, a vocabulary of 512 whose last id is the mask
    token."""
    spec = copy.deepcopy(spec)
    conf, traffic, cell = spec["config"], spec["traffic"], spec["cell"]
    conf.update(hidden_size=256, num_attention_heads=4,
                num_key_value_heads=2, head_dim=128, num_experts=8,
                num_experts_per_tok=2, moe_intermediate_size=128,
                vocab_size=512, mask_token_id=511, num_hidden_layers=2,
                max_position_embeddings=256)
    traffic.update(
        clients=4, population=16, ramp_s=0.3, check_requests=3,
        max_total=256,
        prompt_len=dict(traffic["prompt_len"], median=40, min=8, max=100),
        output_len=dict(traffic["output_len"], median=10, min=4, max=18))
    cell["model_options"]["max_seq_len"] = 256
    cell["engine"]["inference"].update(
        page_size=16, num_pages=80, max_seq_len=256, max_batch_size=4,
        token_budget=272, prefill_lengths=[64, 128, 256],
        decode_batch_sizes=[4], kernel="pallas")
    cell.update(trace_after_s=0.1, traced_seconds=0.3)
    return spec


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(trace, on_cpu, log, tmp_path):  # noqa: F811
    spec = on_cpu(tiny_sdar(harness.load_cell(ROOT, CELL)))
    spec["root"] = checkout_with_links(tmp_path)
    rec, line = run(spec, trace, log)
    assert rec["checks"] and all(rec["checks"].values()), rec["checks"]
    assert rec["compiles_in_window"] == 0
    assert {"served_tokens_match_reference", "cached_rows_within_limit",
            "served_tokens_within_margin", "requests_checked"} <= \
        set(rec["checks"])
    check = rec["check"]
    # 3 of the WINDOW's requests and 3 of 4 that ran together after it,
    # their recorded passes replayed, commits among them; of the second
    # set the pool rows of every committed block of both layers (bf16
    # weights, activations and pools against the float32 reference)
    for which in ("", "probe_"):
        assert check[which + "checked_requests"] == 3
        assert check[which + "checked_tokens"] > 10
        assert 0 < check[which + "checked_commit_passes"] < \
            check[which + "checked_passes"]
        assert 0 <= check[which + "logit_shortfall"] <= \
            check[which + "max_logit_shortfall"] < spec["cell"]["logit_margin"]
        assert 0.5 < check[which + "exact_match_share"] <= 1
    assert "cache_row_error" not in \
        {k[len("probe_"):] for k in check if k.startswith("probe_")}
    assert len(check["cache_row_error_by_layer"]) == 2
    assert 0 < check["cache_row_error"] < 0.02, check
    assert check["probed_tokens"] > 0
    assert bool(trace) == ("traced_stats" in rec)
    line = check_line(line, spec, trace)
    stats = rec["stats"]
    assert stats["block_passes"] > stats["block_commit_passes"] > 0
    assert stats["decode_kv_tokens_block"] == stats["decode_kv_tokens"] > 0
    assert stats["moe_rows_decode"] == stats["block_passes"] * 4 * 2 * 2
    if trace:
        # counters alone: what the CPU's trace holds no kernel for is left
        # out of the line, not raised
        per_pass = line["metrics"]["serve_block_tokens_per_pass"]["value"]
        assert 0.5 < per_pass < 0.9
        assert 0.1 < line["metrics"]["serve_block_commit_share"]["value"] \
            <= 0.25
        # 4 clients over a batch of 4: nearly every row of a pass is live
        assert 50 < line["metrics"]["serve_block_pass_occupancy"]["value"] \
            <= 100
        assert line["metrics"]["serve_block_first_unmask_ms"]["value"] > 0
        assert "serve_batch_occupancy" not in line["metrics"]
        assert "serve_block_grouped_matmul_roofline" not in line["metrics"]
        assert "serve_block_step_roofline" not in line["metrics"]
        assert "serve_block_decode_roofline" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"serve_out_tok_s", "setup_s"}


def test_the_cell_is_files_and_entries_alone():
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve_blockgen32"
    assert len(bench["workloads"]) == 9
    assert sum(1 for w in bench["workloads"] if w["chips"] == 4) == 1
    lists = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    # the token step's kernel name, a loop, a window, latent rows, a held
    # share: not this cell's (`serve_grouped_matmul_roofline` stays
    # OLMoE's: its useful rows are a token step's)
    for name in ("serve_paged_decode_time_share",
                 "serve_paged_decode_roofline", "serve_loop_step_roofline",
                 "serve_window_attn_time_share",
                 "serve_latent_decode_time_share",
                 "serve_grouped_matmul_roofline",
                 "serve_expert_share_roofline",
                 # it divides DELIVERED tokens: occupancy x tokens a
                 # row-pass here (`serve_block_pass_occupancy` instead)
                 "serve_batch_occupancy"):
        assert CELL not in lists[name], name
    for name in ("serve_moe_time_share", "serve_grouped_matmul_time_share",
                 "serve_moe_dispatch_time_share",
                 "serve_kv_write_time_share",
                 "serve_prefill_kernel_time_share", "serve_peak_hbm_gb",
                 "serve_xla_fallbacks", "serve_compiles_in_window",
                 "serve_lookahead_share"):
        assert CELL in lists[name], name
    # no `serve_ttft_p50_ms`: a block model's first token is its first
    # block's LEFTMOST row, unmasked 1 to 4 passes after the prefill as
    # the seed's weights draw it, and the median over a window's 170
    # requests spread by 6.9% over six seeds where half the bound is 1%
    # (PERF.md section 6, PR 43); `serve_ttft_p95_ms` moves it
    for metric in bench["end_to_end"]:
        assert (CELL in metric.get("workloads", [CELL])) == \
            (metric["name"] in ("serve_out_tok_s", "setup_s"))
    assert CELL not in lists["serve_ttft_p95_ms"]
    traffic = harness.load_json(ROOT, "benchmarks", "traffic",
                                cell["traffic"] + ".json")
    assert traffic["kind"] == "closed_loop_block_probed"
    assert (traffic["clients"], traffic["population"],
            traffic["check_requests"], traffic["order_seed"],
            traffic["max_total"]) == (32, 32, 8, 0, 3072)
    assert (traffic["prompt_len"]["median"], traffic["prompt_len"]["sigma"],
            traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]) == \
        (512, 0.7, 128, 2048)
    assert (traffic["output_len"]["median"], traffic["output_len"]["sigma"],
            traffic["output_len"]["min"], traffic["output_len"]["max"]) == \
        (256, 0.6, 64, 1024)
    spec = harness.load_cell(ROOT, CELL)
    inference = spec["cell"]["engine"]["inference"]
    assert inference["prefill_lengths"] == [256, 512, 1024, 2048]
    assert (inference["page_size"], inference["num_pages"],
            inference["max_batch_size"], inference["token_budget"]) == \
        (64, 1601, 32, 2048 + 32 * 4)
    family = harness.load_module(ROOT, "families", "sdar_moe")
    assert "block_generation" not in inference
    built = family.model_config(spec["config"], "bfloat16")
    assert (built.generation_block, built.generation_steps,
            built.generation_threshold, built.mask_token_id) == \
        (4, 4, 0.9, 151669)
    for name in NEW:
        assert lists[name] == [CELL]
        assert os.path.exists(os.path.join(ROOT, "benchmarks", "metrics",
                                           name + ".py"))
    entry = next(c for c in bench["configs"] if c["name"] == "sdar-30b-a3b")
    assert entry["reduced"] == ["num_hidden_layers"]
    conf = harness.load_json(ROOT, entry["file"])
    assert conf["source"] == entry["source"] and entry["source"].endswith(
        "JetLM/SDAR-30B-A3B-Chat/blob/main/config.json")
    assert conf["reduced"] == ["num_hidden_layers"] and \
        conf["family"] == "sdar_moe"
    assert len(conf["assumed"]) >= 9 and "deployment" in conf["assumed"] \
        and "bytes" in conf["assumed"]


def test_the_published_keys_are_the_catalogs():
    """Every key of the catalog's `config` under the same key, but the
    depth."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the model-configs catalog is not here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    conf = harness.load_json(ROOT, "benchmarks", "configs",
                             "sdar-30b-a3b.json")
    assert {k for k, v in row["config"].items()
            if conf.get(k, "?") != v} == {"num_hidden_layers"}
    assert conf["source"] == row["source_url"]


def test_the_parameter_count_is_the_built_models():
    """4,361,055,744: the configuration file's arithmetic against
    `num_params` of the model the family builds (no weight is made)."""
    spec = harness.load_cell(ROOT, CELL)
    family = harness.load_module(ROOT, "families", "sdar_moe")
    model = family.build_model(spec["config"], "bfloat16",
                               spec["cell"]["model_options"])
    cfg = model.config
    assert cfg.num_params() == spec["config"]["assumed"]["num_parameters"] \
        == 4361055744
    assert (cfg.generation_block, cfg.mask_token_id, cfg.qk_norm) == \
        (4, 151669, "head")
    assert set(cfg.plan_kinds()) == {"full32.experts"}
    layer = 2048 * 4096 * 2 + 2 * 2048 * 512 + 256 + 4096 + 2048 * 128 + \
        128 * 3 * 2048 * 768
    assert layer == 623120640
    assert cfg.num_params() == 6 * layer + 2 * 151936 * 2048 + 2048


def test_costs_of_a_block_pass_by_hand():
    conf = harness.load_cell(ROOT, CELL)["config"]
    assert sdar_costs.kv_token_bytes(conf) == 2048
    assert sdar_costs.layer_fixed_params(conf) == \
        2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128 == 19136512
    assert sdar_costs.expert_params(conf) == 3 * 2048 * 768 == 4718592
    # 128 rows, 8 distinct experts each: all but 0.03 of the 128 touched
    assert sdar_costs.experts_touched(128, conf) == pytest.approx(
        128 * (1 - (15 / 16) ** 128))
    assert 127.9 < sdar_costs.experts_touched(128, conf) < 128
    assert sdar_costs.experts_touched(1, conf) == pytest.approx(8)
    # the paged kernel, one layer: 32 row-passes over 30,000 positions
    flops, bytes_ = sdar_costs.block_decode(32, 30000, 4, conf)
    assert flops == 4 * 30000 * 4 * 32 * 128
    assert bytes_ == 30000 * 2048 + 2 * 32 * 4 * 32 * 128 * 2
    # the whole program
    flops, bytes_ = sdar_costs.block_step(32, 30000, 4, conf)
    touched = sdar_costs.experts_touched(128, conf)
    assert bytes_ == pytest.approx(
        (6 * (19136512 + touched * 4718592) + 151936 * 2048) * 2 +
        6 * (30000 + 128) * 2048)
    assert flops == 2 * 128 * (6 * (19136512 + 8 * 4718592) +
                               151936 * 2048) + 6 * 4 * 30000 * 4 * 32 * 128
    # memory-bound: 8.4 GB at 819 GB/s is 10 ms, the matmuls' 0.2 TFLOP
    # at 197 TFLOP/s 1 ms
    assert 9.9e-3 < bytes_ / 819e9 < 10.6e-3 and flops / 197e12 < 1.5e-3


def _rec(stats, **more):
    spec = harness.load_cell(ROOT, CELL)
    return dict({"spec": spec, "stats": stats, "decode_steps": 10,
                 "device_kind": "TPU v5 lite"}, **more)


def test_the_pass_counters_invariants():
    # 10 blocks of 4 masked rows under the floor: 40 denoising passes
    # and 10 commits
    rec = _rec({"block_passes": 50, "block_commit_passes": 10,
                "block_tokens_final": 40, "decode_steps": 2,
                "block_first_unmasks": 4, "block_first_unmask_s": 0.18},
               max_batch_size=32)
    assert sdar_costs.tokens_per_pass(rec) == 0.8
    assert sdar_costs.commit_share(rec) == 0.2
    # 50 row-passes in 2 programs of 32 rows; 4 requests' first rows in
    # 180 ms together
    assert sdar_costs.pass_occupancy(rec) == 100 * 50 / 64
    assert sdar_costs.first_unmask_ms(rec) == pytest.approx(45.0)


def test_the_grouped_matmul_at_a_pass_rows_by_hand(monkeypatch):
    """A pass's useful rows are row-passes x block x experts a token, not
    a token step's batch x experts a token."""
    from benchmarks import moe_costs
    spec = harness.load_cell(ROOT, CELL)
    # 128 token rows a pass: 1,024 routed rows in a buffer of 2,944
    assert moe_costs.buffer_rows(32 * 4, 8, 128) == 2944
    traced = [(2944, 2048, 1536, 128, 1.2e-3), (2944, 768, 2048, 128, 6e-4),
              (2176, 2048, 1536, 128, 1.0)]       # a token step's: no such
    monkeypatch.setattr(moe_costs, "calls", lambda rec: traced)
    rec = {"spec": spec, "device_kind": "TPU v5 lite", "trace_path": "x",
           "traced_stats": {"decode_steps": 10, "block_passes": 310,
                            "decode_kv_tokens_block": 250000}}
    rows = 31 * 4 * 8
    peaks = harness.peaks_for(spec, "TPU v5 lite")
    least = sum(max(2 * rows * k * n / peaks["bf16_flops_per_s"],
                    (128 * k * n + rows * (k + n)) * 2 /
                    peaks["hbm_bytes_per_s"])
                for _, k, n, _, _ in traced[:2])
    assert sdar_costs.grouped_matmul_roofline(rec) == pytest.approx(
        100 * least / 1.8e-3)
    assert 60 < sdar_costs.grouped_matmul_roofline(rec) < 100
    assert sdar_costs.grouped_matmul_roofline(
        dict(rec, traced_stats=None)) is None


def test_a_run_without_the_scopes_or_counters_reads_nothing():
    """Another cell, or a commit from before block generation: every new
    reader returns None and raises nothing."""
    other = harness.load_cell(ROOT, "olmoe-1b-7b.serve_fewshot32")
    bare = {"spec": other, "stats": {"decode_kv_tokens": 5, "decode_tokens":
                                     5, "block_passes": 5,
                                     "block_commit_passes": 1,
                                     "block_tokens_final": 4},
            "decode_steps": 5, "device_kind": "TPU v5 lite",
            "trace_path": None}
    mine = dict(bare, spec=harness.load_cell(ROOT, CELL),
                stats={"decode_kv_tokens": 5})        # no counters
    for name in NEW:
        read = harness.load_module(ROOT, "metrics", name).read
        assert read(bare) is None and read(dict(bare, stats=None)) is None
        assert read(mine) is None, name
    packed = os.path.join(TESTDATA, "tiny_moe_serve_scoped.xplane.pb.xz")
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
    (`benchmarks/tests/record_sdar_trace.py`), unpacked."""
    packed = os.path.join(TESTDATA, "tiny_sdar_serve_block.xplane.pb.xz")
    if not os.path.exists(packed):
        pytest.skip("no recorded SDAR trace under benchmarks/testdata")
    path = tmp_path_factory.mktemp("trace") / "tiny_sdar.xplane.pb"
    with lzma.open(packed) as f:
        path.write_bytes(f.read())
    with open(os.path.join(TESTDATA,
                           "tiny_sdar_serve_block.expected.json")) as f:
        return str(path), json.load(f)


def test_the_readers_on_a_trace_recorded_on_the_chip(recorded):
    from benchmarks import scope_reduce
    path, expected = recorded
    reduced = scope_reduce.reduce_file(path)
    for name in ("ds.paged_decode_block", "ds.unmask", "ds.kv_write",
                 "ds.grouped_matmul"):
        assert reduced["scopes"].get(name, 0.0) > 0.0, name
        assert reduced["scopes"][name] == pytest.approx(
            expected["scopes"]["scopes"][name])
    assert "ds.paged_decode" not in reduced["scopes"]
    spec = tiny_sdar(harness.load_cell(ROOT, CELL))
    rec = {"spec": spec, "trace_path": path, "stats": expected["stats"],
           "decode_steps": expected["decode_steps"],
           "traced_stats": expected["traced_stats"],
           "max_batch_size": expected["max_batch_size"],
           "device_kind": "TPU v5 lite"}
    # whole block-pass programs inside the stretch
    assert len(sdar_costs.block_programs(rec)) >= 3
    calls = reduced["calls"]["ds.paged_decode_block"][0]
    assert calls >= 2 * len(sdar_costs.block_programs(rec))   # 2 layers
    for name in ("serve_block_step_roofline", "serve_block_decode_roofline"):
        assert harness.load_module(ROOT, "metrics", name).read(
            dict(rec, traced_stats=None)) is None   # no window mean instead
    for reader in NEW:
        value = harness.load_module(ROOT, "metrics", reader).read(rec)
        want = expected["metrics"][reader]
        assert want is not None and value == pytest.approx(want), reader
    for reader in NEW[:5] + ("serve_block_pass_occupancy",):
        assert 0 < expected["metrics"][reader] <= 100, reader
    assert expected["metrics"]["serve_block_first_unmask_ms"] > 0
    assert 0.5 < expected["metrics"]["serve_block_tokens_per_pass"] < 0.9
    assert 0.1 < expected["metrics"]["serve_block_commit_share"] <= 0.25
