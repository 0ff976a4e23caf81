"""The qwen3_next cell (`qwen3-next-80b-a3b.serve_longchat64`) rehearsed
on the CPU at a tiny size, its deliberate faults held to fail, and its own
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

from benchmarks import harness, qwen3next_costs
from test_rehearsal import (ROOT, check_line, checkout_with_links, log,  # noqa: F401
                            on_cpu, run)

CELL = "qwen3-next-80b-a3b.serve_longchat64"
TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
NEW = ("serve_gdn_time_share", "serve_gdn_step_roofline",
       "serve_gdn_chunk_roofline", "serve_moe_experts_touched_share")
GENERAL = ("serve_out_tok_s", "serve_ttft_p50_ms", "serve_ttft_p95_ms",
           "serve_step_ms_p50", "serve_device_idle_share",
           "serve_peak_hbm_gb", "serve_batch_occupancy",
           "serve_prefill_share", "serve_kv_write_time_share",
           "serve_unscoped_time_share", "serve_xla_fallbacks",
           "serve_compiles_in_window", "serve_slow_step_share",
           "serve_itl_p50_ms", "serve_itl_p99_ms",
           "serve_paged_decode_time_share", "serve_lookahead_share",
           "serve_prefill_kernel_time_share", "serve_moe_time_share",
           "serve_grouped_matmul_time_share",
           "serve_moe_dispatch_time_share", "serve_moe_shared_time_share",
           "serve_state_bytes_per_seq", "setup_cache_misses")


def tiny_qwen3next(spec):
    """The loaded cell at hidden 512, 4 query heads over 2 KV heads of 128,
    2 key and 4 value heads of 128 (the least the chip's kernels take;
    1,024 convolution channels, whole registers), 8 of 16 experts of width
    128, top 4, 6 layers: gdn, gdn, gdn, full, gdn, gdn."""
    spec = copy.deepcopy(spec)
    conf, traffic, cell = spec["config"], spec["traffic"], spec["cell"]
    conf.update(hidden_size=512, num_attention_heads=4,
                num_key_value_heads=2, head_dim=128,
                linear_num_key_heads=2, linear_num_value_heads=4,
                num_experts=8, num_experts_published=16, held_experts="0-7",
                num_experts_per_tok=4, moe_intermediate_size=128,
                shared_expert_intermediate_size=128, intermediate_size=768,
                vocab_size=512, num_hidden_layers=6,
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
    spec = on_cpu(tiny_qwen3next(harness.load_cell(ROOT, CELL)))
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
        assert len(reading["state_error_by_layer"]) == 5
        assert len(reading["conv_rows_error_by_layer"]) == 5
        assert len(reading["full_row_error_by_layer"]) == 1
        assert reading["window_row_error_by_layer"] == []
    first, last = (check["readings"][k] for k in ("after_prefill", "at_end"))
    assert first["fed"] in (check["probed_prompt"],
                            check["probed_prompt"] + 1)
    assert last["fed"] == check["probed_tokens"] > first["fed"] + 4
    assert 0 < check["cache_row_error"] < 0.03, check
    assert 0 < check["state_error"] < 0.05, check
    assert 0 < check["conv_rows_error"] < 0.03, check
    assert 0 < check["state_error_first_layer"] <= check["state_error"]
    assert bool(trace) == ("traced_stats" in rec)
    line = check_line(line, spec, trace)
    stats = rec["stats"]
    # counted at the dispatch, the tokens at the read-back a step later
    assert abs(stats["state_slot_steps"] - stats["decode_tokens"]) <= 4 \
        < stats["state_slot_steps"]
    assert stats["gdn_state_updates"] == 5 * stats["state_slot_steps"]
    assert stats["gdn_prefill_tokens"] == 5 * stats["prefill_tokens"] > 0
    assert 0 < stats["moe_experts_touched"] <= 8 * 6 * stats["decode_steps"]
    if trace:
        # counters alone: what the CPU's trace holds no kernel for is left
        # out of the line, not raised
        metrics = line["metrics"]
        assert metrics["serve_state_bytes_per_seq"]["value"] == \
            5 * (3 * 1024 * 2 + 4 * 128 * 128 * 4)
        assert 0 < metrics["serve_moe_experts_touched_share"]["value"] <= 100
        assert "serve_gdn_step_roofline" not in metrics
    else:
        assert set(line["metrics"]) == {"serve_out_tok_s",
                                        "serve_ttft_p50_ms", "setup_s"}


FAULTS = {
    # a padded prefill that lets the bucket's tail move the state: the
    # delta rule and the convolution take the padding rows as real
    "padding moves the state": "real",
    # a slot that is not started from zero: the prefill's walk goes on
    # from what the slot held
    "a slot not zeroed": "stale",
}


@pytest.mark.parametrize("fault", FAULTS.values(), ids=FAULTS.keys())
def test_a_deliberate_fault_fails_the_state_check(fault, on_cpu, log,  # noqa: F811
                                                  tmp_path, monkeypatch):
    import jax.numpy as jnp
    from deeperspeed_tpu.models import gpt_neox as neox
    from deeperspeed_tpu.ops.pallas import gdn as gdn_ops
    if fault == "real":
        mixer = neox.gdn_mixer
        monkeypatch.setattr(neox, "gdn_mixer",
                            lambda cfg, p, a, real=None: mixer(cfg, p, a))
    else:
        chunk = gdn_ops.gdn_chunk

        def stale(*a):
            o, state = chunk(*a)
            return o, state + 0.05 * jnp.ones_like(state)  # what a slot held
        monkeypatch.setattr(gdn_ops, "gdn_chunk", stale)
    spec = on_cpu(tiny_qwen3next(harness.load_cell(ROOT, CELL)))
    spec["root"] = checkout_with_links(tmp_path)
    rec, _ = run(spec, 0, log)
    assert not rec["checks"]["recurrent_state_within_limit"], rec["check"]
    assert not rec["correct"]


def test_the_cell_is_files_and_entries_alone():
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve_longchat64" and \
        cell["config"] == "qwen3-next-80b-a3b"
    lists = {m["name"]: m.get("workloads")
             for m in bench["per_layer"] + bench["end_to_end"]}
    # membership, never a list's end: a later cell is appended behind
    for name in GENERAL:
        assert CELL in lists[name], name
    # window and latent pages, the loop, blocks, Mamba's scan, and the
    # readers that read another family's file: not this cell's
    for name in ("serve_window_attn_time_share",
                 "serve_latent_decode_time_share",
                 "serve_loop_step_roofline", "serve_block_decode_roofline",
                 "serve_ssm_step_roofline", "serve_paged_decode_roofline",
                 "serve_attn_kinds_roofline", "serve_kv_bytes_per_token",
                 "serve_expert_share_roofline"):
        assert CELL not in lists[name], name
    for name in NEW:
        assert lists[name] == [CELL]
        assert os.path.exists(os.path.join(ROOT, "benchmarks", "metrics",
                                           name + ".py"))
    traffic = harness.load_json(ROOT, "benchmarks", "traffic",
                                cell["traffic"] + ".json")
    assert traffic["kind"] == "closed_loop_gdn_probed"
    assert (traffic["clients"], traffic["population"],
            traffic["check_requests"], traffic["order_seed"],
            traffic["max_total"], traffic["ramp_s"]) == \
        (64, 128, 8, 0, 9216, 10)
    assert [traffic["prompt_len"][k] for k in
            ("median", "sigma", "min", "max")] == [4096, 0.5, 1024, 8192]
    assert [traffic["output_len"][k] for k in
            ("median", "sigma", "min", "max")] == [256, 0.6, 64, 1024]
    spec = harness.load_cell(ROOT, CELL)
    inference = spec["cell"]["engine"]["inference"]
    assert inference["prefill_lengths"] == [1024, 2048, 3072, 4096, 6144,
                                            8192]
    assert (inference["page_size"], inference["num_pages"],
            inference["max_batch_size"], inference["max_seq_len"],
            inference["token_budget"], inference["decode_batch_sizes"],
            inference["prefill_batch_sizes"]) == \
        (64, 64 * 144 + 16 + 1, 64, 9216, 8256, [64], [1])
    for limit in ("logit_margin", "exact_match_floor",
                  "cache_row_error_limit", "state_error_limit",
                  "conv_rows_error_limit", "state_error_first_layer_limit"):
        assert spec["cell"][limit] > 0 and \
            len(spec["cell"][limit + "_why"]) > 200, limit
    entry = next(c for c in bench["configs"]
                 if c["name"] == "qwen3-next-80b-a3b")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts"]
    conf = harness.load_json(ROOT, entry["file"])
    assert conf["source"] == entry["source"] and entry["source"].endswith(
        "Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json")
    assert conf["reduced"] == entry["reduced"] and \
        conf["family"] == "qwen3_next"
    assert len(conf["assumed"]) >= 12 and \
        conf["assumed"]["num_parameters"] == 5675228608


def test_the_published_keys_are_the_catalogs():
    """Every key of the catalog's `config` under the same key, but for
    the two in `reduced`."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the model-configs catalog is not here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    conf = harness.load_json(ROOT, "benchmarks", "configs",
                             "qwen3-next-80b-a3b.json")
    assert {k for k, v in row["config"].items()
            if conf.get(k, "?") != v} == set(conf["reduced"])
    assert conf["source"] == row["source_url"]
    assert conf["num_experts_published"] == row["config"]["num_experts"]


def test_costs_by_hand():
    conf = harness.load_cell(ROOT, CELL)["config"]
    assert qwen3next_costs.dims(conf) == (16, 32, 128, 128)
    assert qwen3next_costs.gdn_layers(conf) == 5
    # one decode step's five layers of 64 live rows: each state 2,097,152 B
    # read and written, q and k 16 x 128, v and o 32 x 128, g and beta 32
    flops, bytes_ = qwen3next_costs.gdn_step(64 * 5, conf)
    assert bytes_ == 320 * (2 * 2097152 + 4 * (2 * 2048 + 2 * 4096 + 64))
    assert 1.35e9 < bytes_ < 1.36e9 and flops == 320 * 7 * 524288
    assert flops / 197e12 < bytes_ / 819e9          # the memory floor
    # a prompt of 4,600 tokens through five chunk walks: a token and head
    # 4 x 64 x 128 + 64 x 256 + 6 x 128 x 128 + 2 x 64 x 128 operations
    flops, bytes_ = qwen3next_costs.gdn_chunk(4600 * 5, conf, 5)
    assert flops == 23000 * 32 * (32768 + 16384 + 98304 + 16384)
    assert bytes_ == 23000 * 4 * (4096 + 8192 + 64) + 5 * 2 * 2097152


def _rec(stats, **more):
    spec = harness.load_cell(ROOT, CELL)
    return dict({"spec": spec, "stats": stats, "decode_steps": 10,
                 "device_kind": "TPU v5 lite"}, **more)


def test_the_counter_reader():
    rec = _rec({"decode_steps": 100, "moe_experts_touched": 100 * 6 * 182})
    assert qwen3next_costs.moe_experts_touched_share(rec) == \
        pytest.approx(100 * 182 / 256)


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
    (`benchmarks/tests/record_qwen3next_trace.py`), unpacked."""
    packed = os.path.join(TESTDATA, "tiny_qwen3next_serve.xplane.pb.xz")
    if not os.path.exists(packed):
        pytest.skip("no recorded qwen3next trace under benchmarks/testdata")
    path = tmp_path_factory.mktemp("trace") / "tiny_qwen3next.xplane.pb"
    with lzma.open(packed) as f:
        path.write_bytes(f.read())
    with open(os.path.join(TESTDATA,
                           "tiny_qwen3next_serve.expected.json")) as f:
        return str(path), json.load(f)


def test_the_readers_on_a_trace_recorded_on_the_chip(recorded):
    from benchmarks import scope_reduce
    path, expected = recorded
    reduced = scope_reduce.reduce_file(path)
    for name in ("ds.gdn_in", "ds.gdn_chunk", "ds.gdn_step", "ds.gdn_out",
                 "ds.moe_shared", "ds.paged_decode",
                 "ds.flash_fwd", "ds.grouped_matmul", "ds.kv_write"):
        assert reduced["scopes"].get(name, 0.0) > 0.0, name
        assert reduced["scopes"][name] == pytest.approx(
            expected["scopes"]["scopes"][name])
    for kernel in ("ds.gdn_chunk", "ds.gdn_step"):
        assert reduced["calls"][kernel][0] > 0, kernel
    spec = tiny_qwen3next(harness.load_cell(ROOT, CELL))
    rec = {"spec": spec, "trace_path": path, "stats": expected["stats"],
           "decode_steps": expected["decode_steps"],
           "traced_stats": expected["traced_stats"],
           "device_kind": "TPU v5 lite"}
    assert all(expected["checks"].values())
    for reader in NEW:
        value = harness.load_module(ROOT, "metrics", reader).read(rec)
        want = expected["metrics"][reader]
        assert want is not None and value == pytest.approx(want), reader
        # a share; the step's at this toy size (4 rows of 4 heads a call,
        # 2 us) reads a little over its memory floor, 109: the cell's
        # calls of 64 rows read 79 (PERF.md section 6, PR 62)
        assert 0 < want <= (110 if reader == "serve_gdn_step_roofline"
                            else 100), reader
    # no window mean stands in for the traced stretch's own counters
    assert harness.load_module(ROOT, "metrics", "serve_gdn_step_roofline"
                               ).read(dict(rec, traced_stats=None)) is None
