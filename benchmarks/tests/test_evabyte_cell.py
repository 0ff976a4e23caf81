"""The evabyte cell (`evabyte.serve_bytes24`) rehearsed on the CPU at a
tiny size, a deliberate fault held to fail, and its own per-layer readers
held to arithmetic.

What a rehearsal shows is control flow, checks, counts and the shape of
the last line: never a time.
"""

import copy

import pytest

from benchmarks import evabyte_costs, harness
from test_rehearsal import (ROOT, check_line, checkout_with_links, log,  # noqa: F401
                            on_cpu, run)

CELL = "evabyte.serve_bytes24"
NEW = ("serve_eva_decode_roofline", "serve_eva_decode_time_share",
       "serve_eva_summarize_time_share", "serve_eva_summarize_roofline",
       "serve_eva_prefill_time_share", "serve_eva_prefill_roofline",
       "serve_eva_rows_per_token", "serve_eva_rolls_in_window")


def tiny_evabyte(spec):
    """The loaded cell at hidden 256, 4 heads of 64 (the least the chip's
    kernels take), MLP width 384, window 64, chunk 4, page 16, 2 layers."""
    spec = copy.deepcopy(spec)
    conf, traffic, cell = spec["config"], spec["traffic"], spec["cell"]
    conf.update(hidden_size=256, num_attention_heads=4,
                num_key_value_heads=4, intermediate_size=384,
                num_hidden_layers=2, window_size=64, chunk_size=4,
                max_position_embeddings=512)
    traffic.update(
        clients=4, population=16, ramp_s=0.3, check_requests=3,
        max_total=512,
        prompt_len=dict(traffic["prompt_len"], median=150, min=64, max=300),
        output_len=dict(traffic["output_len"], median=40, min=16, max=100))
    cell["model_options"]["max_seq_len"] = 512
    cell["engine"]["inference"].update(
        page_size=16, num_pages=4 * 12 + 33, max_seq_len=512,
        max_batch_size=4, token_budget=400, prefill_lengths=[128, 320],
        decode_batch_sizes=[4])
    cell.update(trace_after_s=0.1, traced_seconds=0.3, logit_margin=0.5,
                exact_match_floor=0.5, cache_row_error_limit=0.05,
                pooling_error_limit=0.004, head_logit_error_limit=0.5)
    return spec


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(trace, on_cpu, log, tmp_path):  # noqa: F811
    spec = on_cpu(tiny_evabyte(harness.load_cell(ROOT, CELL)))
    spec["root"] = checkout_with_links(tmp_path)
    rec, line = run(spec, trace, log)
    assert rec["checks"] and all(rec["checks"].values()), \
        (rec["checks"], rec["check"])
    assert rec["compiles_in_window"] == 0
    assert {"served_tokens_match_reference", "cached_rows_within_limit",
            "pooled_rows_are_the_pool_s_own_rows_pooled",
            "every_head_within_limit",
            "served_tokens_within_margin"} <= set(rec["checks"])
    check = rec["check"]
    assert set(check["readings"]) == {"after_prefill", "after_roll"}
    first, last = (check["readings"][k]
                   for k in ("after_prefill", "after_roll"))
    assert last["windows"] > first["windows"] >= 1
    for reading in (first, last):
        for name in ("visible", "pending", "exact"):
            assert len(reading[f"{name}_row_error_by_layer"]) == 2
    assert 0 < check["cache_row_error"] < 0.05, check
    # bf16 pages: a pooled row is a float32 sum rounded once
    assert 0 < check["pooling_error"] < 0.004, check
    assert len(check["head_logit_error_by_head"]) == 8
    assert bool(trace) == ("traced_stats" in rec)
    line = check_line(line, spec, trace)
    stats = rec["stats"]
    assert stats["eva_windows_rolled"] > 0
    assert stats["decode_kv_tokens"] == \
        stats["decode_kv_tokens_eva_window"] + \
        stats["decode_kv_tokens_eva_summary"]
    if trace:
        metrics = line["metrics"]
        assert 0 < metrics["serve_eva_rows_per_token"]["value"] < 1
        assert metrics["serve_eva_rolls_in_window"]["value"] == \
            stats["eva_windows_rolled"]
        # what the CPU's trace holds no kernel for is left out, not raised
        assert "serve_eva_decode_roofline" not in metrics
    else:
        assert set(line["metrics"]) == {"serve_out_tok_s", "setup_s",
                                        "serve_ttft_p50_ms"}


def test_a_pooled_row_left_out_fails_the_pool_check(on_cpu, log, tmp_path,  # noqa: F811
                                                    monkeypatch):
    """A decode step that pools nothing (the pending pages keep what they
    held) is served, and the probe's pooling check refuses it."""
    from deeperspeed_tpu.inference import engine as engine_mod
    monkeypatch.setattr(engine_mod, "eva_summarize",
                        lambda pools, *a, **kw: tuple(pools))
    spec = on_cpu(tiny_evabyte(harness.load_cell(ROOT, CELL)))
    spec["root"] = checkout_with_links(tmp_path)
    rec, _ = run(spec, 0, log)
    assert not rec["checks"]["pooled_rows_are_the_pool_s_own_rows_pooled"]
    assert rec["check"]["pooling_error"] > 0.5
    assert not rec["correct"]


def test_costs_are_counted_from_the_counters():
    conf = {"hidden_size": 4096, "chunk_size": 16}
    assert evabyte_costs.row_bytes(conf) == 16384
    flops, bytes_ = evabyte_costs.decode_read(1000, 24, conf)
    assert bytes_ == 1000 * 16384 + 2 * 24 * 4096 * 2
    assert flops == 4 * 1000 * 4096
    flops, bytes_ = evabyte_costs.summarize(3, conf)
    assert bytes_ == 3 * 17 * 16384
    for name in NEW:
        reader = harness.load_module(ROOT, "metrics", name)
        # a run of a program without the scopes or counters: nothing read,
        # nothing raised
        assert reader.read({"stats": {}, "spec": {"config": conf}}) is None
