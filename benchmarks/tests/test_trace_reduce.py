"""The trace reducer: its interval arithmetic on planes written by hand,
and the whole path on a small trace recorded on the chip
(`benchmarks/testdata/`, a few steps of a tiny model under ZeRO-3 on
four v5e chips, recorded by PR 23)."""

import glob
import os

import pytest

from benchmarks import harness, trace_reduce as tr

TESTDATA = os.path.join(harness.ROOT, "benchmarks", "testdata")


def planes():
    """One device, window 0..10 s:
    0-4   while.1, holding fusion.1 (0-1), all-gather-start.1 (1-1.5),
          a Mosaic custom call (1.5-3), all-gather-done.1 (3-4)
    4-5   idle, the host inside `decode`
    5-6   all-reduce.3
    6-8   idle, the host inside `schedule` inside `bench.engine_step`
    8-10  add_fusion.2
    """
    def hlo(inst, op, shape="bf16[8,128]", attrs=""):
        # a device event's name is the instruction's whole text
        return (f"%{inst} = {shape}{{1,0:T(8,128)(2,1)}} {op}("
                f"{shape}{{1,0:T(8,128)(2,1)S(1)}} %p.1){attrs}")
    ops = [(hlo("while.1", "while"), 0.0, 4.0),
           (hlo("fusion.1", "fusion"), 0.0, 1.0),
           (hlo("all-gather-start.1", "all-gather-start"), 1.0, 1.5),
           (hlo("closed_call.7", "custom-call",
                attrs=', custom_call_target="tpu_custom_call"'), 1.5, 3.0),
           (hlo("all-gather-done.1", "all-gather-done"), 3.0, 4.0),
           (hlo("all-reduce.3", "all-reduce", "f32[4]"), 5.0, 6.0),
           (hlo("add_fusion.2", "fusion", "f32[4]"), 8.0, 10.0)]
    host = [(tr.WINDOW_SPAN, 0.0, 10.0), ("bench.engine_step", 0.0, 9.0),
            ("decode", 3.5, 4.5), ("schedule", 5.5, 7.0),
            ("$python frame", 0.0, 10.0)]
    return [("/device:TPU:0", [("XLA Ops", ops), ("Steps", [("1", 0, 10)])]),
            ("/host:CPU", [("main", host), ("other", [("x", 0.0, 10.0)])])]


def test_interval_arithmetic():
    assert tr.union([[3, 4], [0, 1], [0.5, 2]]) == [[0, 2], [3, 4]]
    assert tr.subtract([[0, 10]], [[1, 2], [4, 12]]) == [[0, 1], [2, 4]]
    selfs = {n: t for n, _, _, t in tr.self_times(
        [("outer", 0, 10), ("a", 1, 3), ("b", 3, 4), ("c", 11, 12)])}
    assert selfs == {"outer": 7, "a": 2, "b": 1, "c": 1}


def test_reduction_of_planes_written_by_hand():
    out = tr.summarize(tr.reduce_planes(planes()))
    assert out["window_found"] and out["n_devices"] == 1
    assert out["window_s"] == pytest.approx(10.0)
    assert out["busy_s"] == pytest.approx(7.0)
    assert out["idle_share"] == pytest.approx(0.3)
    assert out["mosaic_s"] == pytest.approx(1.5)
    # in collective operations, with nothing else running: the start
    # (0.5), the done (1.0) and the all-reduce (1.0)
    assert out["collective_exposed_s"] == pytest.approx(2.5)
    # the gather was in flight from its start to its done (1 to 4)
    assert out["collective_s"] == pytest.approx(4.0)
    ops = dict(out["device_ops"])
    assert ops["fusion bf16[8,128]"] == pytest.approx(1.0)
    assert ops["fusion add_fusion f32[4]"] == pytest.approx(2.0)
    assert ops["custom-call closed_call bf16[8,128]"] == pytest.approx(1.5)
    assert "while bf16[8,128]" not in ops   # all of it is its children
    gaps = dict(out["idle_gaps"])
    assert gaps == {"decode": pytest.approx(1.0),
                    "schedule": pytest.approx(2.0)}


def test_without_a_window_span_the_device_extent_is_the_window():
    ps = planes()
    ps[1] = ("/host:CPU", [("main", [("decode", 3.5, 4.5)])])
    out = tr.summarize(tr.reduce_planes(ps))
    assert not out["window_found"]
    assert out["window_s"] == pytest.approx(10.0)
    assert dict(out["idle_gaps"]) == {
        "(no span open)": pytest.approx(3.0)}


def test_recorded_trace_from_the_chip():
    files = glob.glob(os.path.join(TESTDATA, "*.xplane.pb.xz"))
    assert files, "benchmarks/testdata holds no recorded trace"
    expected = harness.load_json(TESTDATA, "expected.json")
    out = tr.reduce_file(files[0])
    assert out["n_devices"] == expected["n_devices"]
    assert out["window_found"]
    for key in ("window_s", "busy_s", "idle_share", "collective_s",
                "collective_exposed_s", "mosaic_s"):
        assert out[key] == pytest.approx(expected[key], rel=1e-6), key
    # read by hand from the trace (PR 23): the only Mosaic call of the
    # tiny step is flash attention under the engine's shard_map, and the
    # chips sat idle mostly while the host was inside the jitted call
    ops = dict(out["device_ops"])
    assert ops["custom-call shard_map bf16[2,4,256,64]"] == \
        pytest.approx(out["mosaic_s"])
    assert 0 < out["collective_exposed_s"] <= out["collective_s"] \
        < out["busy_s"] < out["window_s"]
    assert out["idle_gaps"][0][0] == "PjitFunction(train_step)"
    assert sum(t for _, t in out["idle_gaps"]) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=0.01)
    assert [n for n, _ in out["device_ops"]] == \
        [n for n, _ in expected["device_ops"]]
    assert [n for n, _ in out["idle_gaps"]] == \
        [n for n, _ in expected["idle_gaps"]]
