"""The readers of the program's own names: the wire reader of a trace's
operation metadata (`xplane_meta`), the reduction by `ds.*` scope
(`scope_reduce`) on planes written by hand and on traces recorded on the
chip (`benchmarks/testdata/`), and each metric reader that rests on them
on a record made by hand."""

import glob
import json
import os

import pytest

from benchmarks import harness, scope_reduce as sr, trace_reduce as tr
from benchmarks import xplane_meta

TESTDATA = os.path.join(harness.ROOT, "benchmarks", "testdata")
UNSCOPED_TRACE = os.path.join(TESTDATA, "tiny_zero3_4c.xplane.pb.xz")
SCOPED_TRACES = sorted(glob.glob(os.path.join(TESTDATA,
                                              "*_scoped.xplane.pb.xz")))
BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")


# -- the wire reader ---------------------------------------------------------

def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, payload):
    if isinstance(payload, int):
        return varint(number << 3) + varint(payload)
    return varint(number << 3 | 2) + varint(len(payload)) + payload


def test_wire_reader_on_a_space_written_by_hand():
    """One plane, two stat names, two operations: one with a string
    `tf_op`, a referenced `source` and a stat that is not wanted, one
    with no stats; a line, a double and a fixed32 to step over."""
    stat_meta = b"".join(
        field(5, field(1, i) + field(2, field(1, i) + field(2, name)))
        for i, name in ((1, b"tf_op"), (2, b"source"), (3, b"flops"),
                        (4, b"models/gpt_neox.py:356")))
    op = field(1, 7) + field(2, b"%fusion.1 = f32[4] fusion()") + \
        field(5, field(1, 1) + field(5, b"jit(f)/ds.block/ds.mlp/dot")) + \
        field(5, field(1, 2) + field(7, 4)) + \
        field(5, field(1, 3) + field(3, 12345)) + \
        field(5, field(1, 3) + varint(2 << 3 | 1) + b"\0" * 8)
    bare = field(1, 8) + field(2, b"%copy.2 = f32[4] copy()")
    plane = field(1, 1) + field(2, b"/device:TPU:0") + \
        field(3, field(2, b"XLA Ops") + varint(9 << 3 | 5) + b"\0" * 4) + \
        field(4, field(1, 7) + field(2, op)) + \
        field(4, field(1, 8) + field(2, bare)) + stat_meta
    (name, table), = xplane_meta.planes(field(1, plane))
    assert name == "/device:TPU:0"
    assert table == {
        "%fusion.1 = f32[4] fusion()": {
            "tf_op": "jit(f)/ds.block/ds.mlp/dot",
            "source": "models/gpt_neox.py:356"},
        "%copy.2 = f32[4] copy()": {}}
    with pytest.raises(ValueError):
        list(xplane_meta.fields(varint(1 << 3 | 3)))    # a group: not ours


def test_metadata_of_the_trace_recorded_by_pr_23():
    """Every Mosaic custom call of the recorded four-chip trace carries
    the jax name stack of its `pallas_call` and the line that called it;
    the names are those `trace_reduce.load` returns."""
    meta = dict(xplane_meta.load(UNSCOPED_TRACE))
    seen = 0
    for pname, lines in tr.load(UNSCOPED_TRACE):
        if not tr.DEVICE_PLANE.match(pname):
            continue
        names = {n for lname, evs in lines if tr.OP_LINE.match(lname)
                 for n, _, _ in evs}
        assert names <= set(meta[pname])
        for n in names:
            if tr.MOSAIC in n:
                seen += 1
                stats = meta[pname][n]
                assert stats["tf_op"].endswith("pallas_call:"), stats
                assert "models/gpt_neox.py" in stats["source"], stats
    assert seen >= 4            # forward and backward on four chips


# -- the reduction on planes written by hand ---------------------------------

def hlo(inst, op, attrs=""):
    return f"%{inst} = bf16[8,128]{{1,0}} {op}(bf16[8,128]{{1,0}} %p.1){attrs}"


MOSAIC = ', custom_call_target="tpu_custom_call"'
OPS = {
    # name: (start, end, tf_op or None for an operation without metadata)
    hlo("while.1", "while"): (0.0, 6.0, "jit(step)/ds.layers/while"),
    hlo("dynamic-slice.1", "dynamic-slice"):
        (0.0, 1.0, "jit(step)/ds.layers/while/body/dynamic_slice"),
    hlo("fusion.1", "fusion"):
        (1.0, 2.0, "jit(step)/ds.layers/while/body/checkpoint/ds.block/"
                   "ds.attn/dot_general"),
    hlo("ds.flash_fwd.3", "custom-call", MOSAIC):
        (2.0, 4.0, "jit(step)/ds.layers/while/body/checkpoint/ds.block/"
                   "ds.attn/ds.flash_fwd/ds.flash_fwd/pallas_call:"),
    hlo("fusion.2", "fusion"):
        (4.0, 5.0, "jit(step)/transpose(jvp())/ds.layers/while/body/"
                   "checkpoint/rematted_computation/ds.block/ds.mlp/dot"),
    hlo("copy.9", "copy"): (6.0, 7.0, None),
    hlo("fusion.3", "fusion"): (8.0, 10.0, "jit(step)/vmap(ds.kv_write)/s"),
    # starts inside the window and ends outside: clipped for the shares,
    # not counted as a call
    hlo("ds.flash_fwd.4", "custom-call", MOSAIC):
        (11.0, 13.0, "jit(step)/ds.attn/ds.flash_fwd/pallas_call:"),
}


def planes_and_meta(window=(0.0, 12.0)):
    ops = [(n, s, e) for n, (s, e, _) in OPS.items()]
    host = [("decode", 3.0, 4.0)]
    if window:
        host.append((tr.WINDOW_SPAN, *window))
    planes = [("/device:TPU:0", [("XLA Ops", ops), ("Steps", [("1", 0, 9)])]),
              ("/host:CPU", [("main", host)])]
    meta = [("/device:TPU:0", {n: ({"tf_op": t} if t else {})
                               for n, (_, _, t) in OPS.items()})]
    return planes, meta


def test_reduction_of_planes_written_by_hand():
    out = sr.reduce_planes(*planes_and_meta())
    assert out["n_devices"] == 1
    assert out["busy_s"] == pytest.approx(10.0)
    assert out["scopes"] == {
        # the while's own second (5 to 6) and the scan's slicing
        "ds.layers": pytest.approx(2.0),
        "ds.attn": pytest.approx(1.0),
        "ds.flash_fwd": pytest.approx(3.0),     # 2 + the clipped 1
        "ds.mlp": pytest.approx(1.0),
        "ds.kv_write": pytest.approx(2.0),
        sr.UNSCOPED: pytest.approx(1.0)}
    assert sum(out["scopes"].values()) == pytest.approx(out["busy_s"])
    assert out["remat_s"] == pytest.approx(1.0)
    assert out["calls"] == {"ds.flash_fwd": [1.0, pytest.approx(2.0)]}


def test_without_a_window_span_every_event_counts_whole():
    out = sr.reduce_planes(*planes_and_meta(window=None))
    assert out["busy_s"] == pytest.approx(11.0)
    assert out["scopes"]["ds.flash_fwd"] == pytest.approx(4.0)
    assert out["calls"]["ds.flash_fwd"] == [2.0, pytest.approx(4.0)]


def test_devices_are_averaged():
    planes, meta = planes_and_meta()
    second = [(n, s, e) for n, s, e in planes[0][1][0][1] if "copy" in n]
    planes.insert(1, ("/device:TPU:1", [("XLA Ops", second)]))
    meta.append(("/device:TPU:1", meta[0][1]))
    out = sr.reduce_planes(planes, meta)
    assert out["n_devices"] == 2
    assert out["busy_s"] == pytest.approx((10.0 + 1.0) / 2)
    assert out["scopes"][sr.UNSCOPED] == pytest.approx(1.0)
    assert out["scopes"]["ds.mlp"] == pytest.approx(0.5)
    assert sum(out["scopes"].values()) == pytest.approx(out["busy_s"])
    assert out["calls"]["ds.flash_fwd"] == [0.5, pytest.approx(1.0)]


def test_innermost_scope():
    assert sr.innermost("jit(f)/ds.block/ds.attn/ds.flash_fwd/x") == \
        "ds.flash_fwd"
    assert sr.innermost("jit(f)/vmap(ds.kv_write)/scatter") == "ds.kv_write"
    assert sr.innermost("jit(f)/jvp()/pallas_call:") == sr.UNSCOPED
    assert sr.innermost(None) == sr.UNSCOPED


# -- the traces recorded on the chip -----------------------------------------

def test_a_trace_from_before_the_scopes_has_nothing_for_a_scope_reader():
    reduced = sr.reduce_file(UNSCOPED_TRACE)
    assert set(reduced["scopes"]) == {sr.UNSCOPED}
    assert reduced["scopes"][sr.UNSCOPED] == pytest.approx(
        reduced["busy_s"], rel=1e-9)
    assert reduced["busy_s"] == pytest.approx(
        tr.reduce_file(UNSCOPED_TRACE)["busy_s"], rel=1e-9)
    rec = {"trace_path": UNSCOPED_TRACE}
    assert sr.of_run(rec) is None
    assert sr.share(rec, ["ds.flash_fwd"]) is None
    assert sr.unscoped_share(rec) is None
    assert sr.seconds_per_call(rec, ["ds.flash_fwd"], ["ds.flash_fwd"]) \
        is None
    # recomputed work is marked by jax.checkpoint itself: the CE head's
    assert 0 < sr.remat_share(rec) < 100


def test_scoped_traces_are_there():
    assert len(SCOPED_TRACES) >= 2, SCOPED_TRACES
    for path in SCOPED_TRACES:
        assert os.path.getsize(path) < 300_000, path


@pytest.mark.parametrize("path", SCOPED_TRACES, ids=os.path.basename)
def test_recorded_trace_with_scopes(path):
    with open(path.replace(".xplane.pb.xz", ".expected.json")) as f:
        expected = json.load(f)
    out = sr.reduce_file(path)
    want = expected["scopes"]
    assert out["n_devices"] == want["n_devices"]
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert out["remat_s"] == pytest.approx(want["remat_s"], rel=1e-6)
    assert set(out["scopes"]) == set(want["scopes"])
    for name, seconds in want["scopes"].items():
        assert out["scopes"][name] == pytest.approx(seconds, rel=1e-6), name
    assert {k: [pytest.approx(v[0]), pytest.approx(v[1], rel=1e-6)]
            for k, v in want["calls"].items()} == out["calls"]
    # the scopes and (unscoped) are the busy time, which is the reducer's
    assert sum(out["scopes"].values()) == pytest.approx(out["busy_s"],
                                                       rel=1e-3)
    assert out["busy_s"] == pytest.approx(tr.reduce_file(path)["busy_s"],
                                          rel=1e-9)
    assert out["busy_s"] == pytest.approx(expected["trace"]["busy_s"],
                                          rel=1e-6)
    # every Mosaic call of a program with scopes is inside a kernel scope
    assert sum(t for _, t in out["calls"].values()) <= \
        tr.reduce_file(path)["mosaic_s"] * (1 + 1e-6)
    meta = dict(xplane_meta.load(path))
    for pname, lines in tr.load(path):
        if tr.DEVICE_PLANE.match(pname):
            for n in {n for lname, evs in lines if tr.OP_LINE.match(lname)
                      for n, _, _ in evs if tr.MOSAIC in n}:
                assert sr.innermost(meta[pname][n]["tf_op"]) in \
                    ("ds.flash_fwd", *sr.FLASH_BACKWARD, "ds.paged_decode",
                     "ds.adam"), meta[pname][n]


# -- the metric readers on a record made by hand -----------------------------

V5E = {"TPU v5 lite": {"bf16_flops_per_s": 197e12,
                       "hbm_bytes_per_s": 819e9}}
CONFIG = {"num_attention_heads": 16, "hidden_size": 1024}


def read(metric, rec):
    return harness.load_module(harness.ROOT, "metrics", metric).read(rec)


@pytest.fixture
def reduction(monkeypatch):
    """A hand-made reduction in place of a trace file's."""
    reduced = {
        "n_devices": 1, "busy_s": 1.0, "remat_s": 0.18,
        "scopes": {"ds.flash_fwd": 0.09, "ds.flash_bwd_dq": 0.08,
                   "ds.flash_bwd_dkv": 0.12, "ds.ce_head": 0.15,
                   "ds.optimizer": 0.03, "ds.adam": 0.01, "ds.mlp": 0.3,
                   "ds.attn": 0.1, "ds.layers": 0.05, "ds.kv_write": 0.02,
                   "ds.paged_decode": 0.03, sr.UNSCOPED: 0.02},
        # 24 layers: forward 3.5 ms a call, backward 4 + 5 ms a layer
        "calls": {"ds.flash_fwd": [24.0, 24 * 3.5e-3],
                  "ds.flash_bwd_dq": [24.0, 24 * 4e-3],
                  "ds.flash_bwd_dkv": [24.0, 24 * 5e-3],
                  "ds.paged_decode": [48.0, 48 * 2.5e-3]}}
    monkeypatch.setattr(sr, "reduce_file", lambda path: reduced)
    return reduced


def train_rec():
    return {"trace_path": "a.xplane.pb", "device_kind": "TPU v5 lite",
            "spec": {"config": CONFIG, "peaks": V5E},
            "tokens_per_step": 16 * 2048, "seq_len": 2048, "chips": 1}


def serve_rec():
    return {"trace_path": "a.xplane.pb", "device_kind": "TPU v5 lite",
            "spec": {"config": dict(CONFIG, hidden_size=2048),
                     "peaks": V5E},
            "window_s": 30.0, "decode_steps": 200,
            "stats": {"decode_tokens": 200 * 32,
                      "decode_kv_tokens": 200 * 32 * 470,
                      "readback_s": 27.0, "build_inputs_s": 0.6}}


@pytest.mark.parametrize("metric,rec,expected", [
    ("train_flash_fwd_time_share", train_rec, 9.0),
    ("train_flash_bwd_time_share", train_rec, 20.0),
    # 4 * 16 * 16 * 2048^2 * 64 / 2 flops at 197 TFLOP/s over 3.5 ms
    ("train_flash_fwd_roofline", train_rec,
     100 * (137438953472 / 197e12) / 3.5e-3),
    ("train_flash_bwd_roofline", train_rec,
     100 * (2.5 * 137438953472 / 197e12) / 9e-3),
    ("train_ce_head_time_share", train_rec, 15.0),
    ("train_optimizer_time_share", train_rec, 4.0),
    ("train_remat_time_share", train_rec, 18.0),
    ("train_unscoped_time_share", train_rec, 7.0),
    ("serve_unscoped_time_share", serve_rec, 7.0),
    ("serve_paged_decode_time_share", serve_rec, 3.0),
    ("serve_prefill_kernel_time_share", serve_rec, 9.0),
    # 32 x 470 tokens x 16 heads x 128 x (K and V) x 2 bytes, and q and
    # out, at 819 GB/s over 2.5 ms
    ("serve_paged_decode_roofline", serve_rec,
     100 * ((2 * 32 * 470 * 16 * 128 * 2 + 2 * 32 * 16 * 128 * 2)
            / 819e9) / 2.5e-3),
    ("serve_readback_share", serve_rec, 90.0),
    ("serve_build_inputs_share", serve_rec, 2.0),
])
def test_reader_on_a_record_made_by_hand(reduction, metric, rec, expected):
    assert read(metric, rec()) == pytest.approx(expected, rel=1e-9)


NEW = ["train_flash_fwd_time_share", "train_flash_bwd_time_share",
       "train_flash_fwd_roofline", "train_flash_bwd_roofline",
       "train_ce_head_time_share", "train_optimizer_time_share",
       "train_remat_time_share", "train_unscoped_time_share",
       "serve_unscoped_time_share", "serve_paged_decode_time_share",
       "serve_prefill_kernel_time_share", "serve_paged_decode_roofline",
       "serve_readback_share", "serve_build_inputs_share"]


def test_the_fourteen_are_declared():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    reported = {m["name"] for m in BENCH["end_to_end"]}
    for name in NEW:
        m = declared[name]
        assert m["workloads"] and m["moves"] in reported
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert name.startswith(m["moves"].split("_")[0])


@pytest.mark.parametrize("metric", NEW)
def test_reader_has_nothing_to_read(metric, monkeypatch):
    """An untraced run, a program from before the scopes and counters, a
    trace that names nothing: None, never an exception."""
    assert read(metric, {"trace_path": None, "stats": {},
                         "window_s": 1.0}) is None
    assert read(metric, {}) is None
    monkeypatch.setattr(sr, "reduce_file", lambda path: {
        "n_devices": 1, "busy_s": 1.0, "remat_s": 0.0, "calls": {},
        "scopes": {sr.UNSCOPED: 1.0}})
    rec = dict(train_rec(), stats={"decode_tokens": 5}, decode_steps=3,
               window_s=1.0)
    if metric == "train_remat_time_share":
        assert read(metric, rec) == 0.0
    else:
        assert read(metric, rec) is None


def test_a_scope_that_is_absent_is_not_a_zero(reduction):
    del reduction["scopes"]["ds.ce_head"]
    assert read("train_ce_head_time_share", train_rec()) is None
    # a sum over several scopes reads those that are there
    del reduction["scopes"]["ds.adam"]
    assert read("train_optimizer_time_share", train_rec()) == \
        pytest.approx(3.0)


def test_roofline_needs_both_peaks(reduction):
    rec = train_rec()
    rec["spec"]["peaks"] = {"TPU v5 lite": {"bf16_flops_per_s": 1e12}}
    assert read("train_flash_fwd_roofline", rec) is None
