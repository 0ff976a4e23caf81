"""The MoE layer's per-layer metrics (PR 26): `serve_moe_time_share`,
`serve_grouped_matmul_time_share`, `serve_moe_dispatch_time_share` and
`serve_grouped_matmul_roofline`, on the trace recorded on the chip
(`benchmarks/testdata/tiny_moe_serve_scoped`: the new cell at the
rehearsal's tiny size, `record_tiny_trace.py`), by hand, and where there
is nothing to read."""

import json
import os

import pytest

from benchmarks import harness, kernel_costs, moe_costs, scope_reduce as sr
from test_rehearsal import tiny

CELL = "olmoe-1b-7b.serve_fewshot32"
TESTDATA = os.path.join(harness.ROOT, "benchmarks", "testdata")
TRACE = os.path.join(TESTDATA, "tiny_moe_serve_scoped.xplane.pb.xz")
PEAKS = harness.load_json(harness.ROOT, "benchmarks",
                          "peaks.json")["TPU v5 lite"]
SHARES = {
    "serve_moe_time_share": ["ds.moe_route", "ds.moe_dispatch",
                             "ds.grouped_matmul", "ds.moe_combine"],
    "serve_grouped_matmul_time_share": ["ds.grouped_matmul"],
    "serve_moe_dispatch_time_share": ["ds.moe_route", "ds.moe_dispatch",
                                      "ds.moe_combine"],
}


def read(name, rec):
    return harness.load_module(harness.ROOT, "metrics", name).read(rec)


@pytest.fixture(scope="module")
def expected():
    with open(TRACE.replace(".xplane.pb.xz", ".expected.json")) as f:
        return json.load(f)["scopes"]


@pytest.mark.parametrize("name", sorted(SHARES))
def test_share_on_the_recorded_trace(name, expected):
    want = 100.0 * sum(expected["scopes"][s] for s in SHARES[name]) / \
        expected["busy_s"]
    assert 0.0 < want < 100.0
    assert read(name, {"trace_path": TRACE}) == pytest.approx(want, rel=1e-6)


def test_the_scopes_account_for_the_moe_layer(expected):
    """Route + dispatch + matmuls + combine are what an MoE layer costs:
    beside them `ds.mlp` itself holds ln2 and the activation product."""
    scopes = expected["scopes"]
    moe = sum(scopes[s] for s in SHARES["serve_moe_time_share"])
    assert scopes["ds.mlp"] < 0.1 * moe
    # two kernel calls a layer a step, counted and timed
    calls, seconds = expected["calls"]["ds.grouped_matmul"]
    assert calls >= 2 and calls % 2 == 0
    assert seconds == pytest.approx(scopes["ds.grouped_matmul"], rel=1e-6)


def test_costs_by_hand():
    # a decode step's call at the published widths: 256 useful rows
    flops, bytes_ = moe_costs.grouped_matmul(256, 2048, 2048, 64)
    assert flops == 2 * 256 * 2048 * 2048
    assert bytes_ == 64 * 2048 * 2048 * 2 + 256 * (2048 + 2048) * 2
    # fewer rows than experts: at most one expert a row is read
    assert moe_costs.grouped_matmul(32, 256, 2048, 64)[1] == \
        32 * 256 * 2048 * 2 + 32 * (256 + 2048) * 2
    least, bound = kernel_costs.least_seconds(flops, bytes_, PEAKS)
    assert bound == "memory" and 0.65e-3 < least < 0.67e-3   # 537 MB
    # a 1,024-token prefill: 128 rows an expert, still under the ridge
    flops, bytes_ = moe_costs.grouped_matmul(8192, 2048, 2048, 64)
    assert kernel_costs.least_seconds(flops, bytes_, PEAKS)[1] == "memory"
    # the buffers the cell's programs compile, as the traces show them
    rows = {t: moe_costs.buffer_rows(t, 8, 64)
            for t in (32, 256, 512, 1024, 1536)}
    assert rows == {32: 1216, 256: 4032, 512: 8128, 1024: 16384,
                    1536: 20480}


def test_the_buffer_formula_is_the_program_s():
    from deeperspeed_tpu.moe.layer import dropless_geometry
    for tokens in (1, 4, 32, 100, 256, 512, 1024, 1536, 2048):
        for top_k, experts in ((8, 64), (2, 16), (1, 8)):
            assert moe_costs.buffer_rows(tokens, top_k, experts) == \
                dropless_geometry(tokens, top_k, experts)[0]


def test_call_shapes_from_the_hlo_text():
    text = ("%ds.grouped_matmul.3 = bf16[1216,2048]{1,0:T(8,128)(2,1)} "
            "custom-call(s32[76]{0:T(128)} %a, s32[76]{0:T(128)} %b, "
            "s32[1]{0:T(128)} %c, bf16[1216,1024]{1,0:T(8,128)(2,1)} %x, "
            "bf16[6,64,1024,2048]{3,2,1,0:T(8,128)(2,1)} %w), "
            "custom_call_target=\"tpu_custom_call\", operand_layout_"
            "constraints={s32[76]{0}, bf16[6,64,1024,2048]{3,2,1,0}}")
    assert moe_costs.call_shapes(text) == (1216, 1024, 2048, 64)
    plain = text.replace("bf16[6,64,1024,2048]", "bf16[64,1024,2048]")
    assert moe_costs.call_shapes(plain) == (1216, 1024, 2048, 64)
    # the backward's dx reads w [E, K, N] against its other dimension
    dx = plain.replace("bf16[1216,2048]{1,0:T(8,128)(2,1)} custom",
                       "bf16[1216,1024]{1,0:T(8,128)(2,1)} custom")
    assert moe_costs.call_shapes(dx) == (1216, 2048, 1024, 64)
    assert moe_costs.call_shapes("%fusion.1 = f32[8]{0} fusion(%p)") is None


def test_useful_rows_of_the_cell_s_programs():
    spec = harness.load_cell(harness.ROOT, CELL)
    useful = moe_costs.useful_rows_by_buffer(spec)
    assert set(useful) == {1216, 4032, 8128, 16384, 20480}
    assert useful[1216] == 32 * 8            # a decode step: the batch
    # a prefill bucket: the mean of the population's prompts that land
    # in it (median 1024, sigma 0.35: none under 256, half above 1024)
    assert useful[4032] == 256 * 8
    assert 512 * 8 < useful[16384] <= 1024 * 8
    assert 1024 * 8 < useful[20480] <= 1536 * 8


def test_roofline_on_the_recorded_trace(expected):
    spec = tiny(harness.load_cell(harness.ROOT, CELL))
    rec = {"trace_path": TRACE, "spec": spec, "device_kind": "TPU v5 lite"}
    calls = moe_costs.calls(rec)
    n, seconds = expected["calls"]["ds.grouped_matmul"]
    assert len(calls) == n
    assert sum(c[-1] for c in calls) == pytest.approx(seconds, rel=1e-6)
    # the tiny cell: hidden 256, experts of 1024, 64 experts, 8 a token.
    # A decode step's calls (batch 4: 32 rows in a buffer of 992) and a
    # prefill's (bucket 128: 1,024 rows in a buffer of 1,984), told
    # apart by their buffers
    assert {c[:4] for c in calls} == {
        (992, 256, 2048, 64), (992, 1024, 256, 64),
        (1984, 256, 2048, 64), (1984, 1024, 256, 64)}
    # every prompt of the tiny population (8 to 100 tokens) lands in the
    # bucket of 128: a prefill call's useful rows are their mean x 8
    closed_loop = harness.load_module(harness.ROOT, "drivers", "closed_loop")
    prompts = closed_loop.quantile_lengths(spec["traffic"]["prompt_len"],
                                           spec["traffic"]["population"])
    assert prompts.max() <= 128
    useful = {992: 4 * 8, 1984: float(prompts.mean()) * 8}
    least = 0.0
    for rows, k, n_out, experts, _ in calls:
        # 32 rows reach at most 32 of the 64 experts
        bytes_ = min(experts, useful[rows]) * k * n_out * 2 + \
            useful[rows] * (k + n_out) * 2
        least += max(2 * useful[rows] * k * n_out / PEAKS["bf16_flops_per_s"],
                     bytes_ / PEAKS["hbm_bytes_per_s"])
    want = 100.0 * least / seconds
    got = read("serve_grouped_matmul_roofline", rec)
    assert got == pytest.approx(want, rel=1e-6)
    assert 0.0 < got <= 100.0


def test_nothing_to_read_is_none(monkeypatch):
    """An untraced run, no record, a trace of a program without the
    kernel (the parent's, another cell's): None, never an exception."""
    spec = harness.load_cell(harness.ROOT, CELL)
    other = os.path.join(TESTDATA, "tiny_serve_scoped.xplane.pb.xz")
    for name in (*SHARES, "serve_grouped_matmul_roofline"):
        assert read(name, {"trace_path": None, "spec": spec}) is None
        assert read(name, {}) is None
        assert read(name, {"trace_path": other, "spec": spec,
                           "device_kind": "TPU v5 lite"}) is None
    monkeypatch.setattr(sr, "reduce_file", lambda path: {
        "n_devices": 1, "busy_s": 1.0, "remat_s": 0.0, "calls": {},
        "scopes": {"ds.mlp": 0.9, sr.UNSCOPED: 0.1}})
    for name in SHARES:
        assert read(name, {"trace_path": "a.xplane.pb"}) is None


def test_they_are_declared_for_the_new_cell_alone():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    mine = [m for m in bench["per_layer"]
            if m["name"] in (*SHARES, "serve_grouped_matmul_roofline")]
    assert len(mine) == 4 and mine == bench["per_layer"][-4:]
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_out_tok_s"
        assert m["source"] == "device_trace" and m["unit"] == "%"
    names = {m["name"] for m in harness.load_cell(harness.ROOT,
                                                  CELL)["per_layer"]}
    assert {m["name"] for m in mine} <= names
    names = {m["name"] for m in harness.load_cell(
        harness.ROOT, "pythia-1.4b.serve_closed32")["per_layer"]}
    assert not {m["name"] for m in mine} & names
