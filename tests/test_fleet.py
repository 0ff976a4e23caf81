"""Fleet observability (runtime/fleet.py + runtime/exporters.py): the
cross-host aggregation windows, collective-skew straggler probe, merged
Perfetto capture, Prometheus/JSONL metrics export, MoE routing
observability, and the ds_report/ops dispatch satellites.

Everything runs single-host: multiple simulated hosts share in-memory
transports, the skew probe's gather is either injected or derived from
the heartbeat monitor's `slow_peer` fault state, and the acceptance
pins (slow host NAMED within the configured window; the Prometheus
scrape serving Train/* + Serve/* families incl. histogram buckets) are
fast-lane tests."""

import json
import os
import urllib.request

import numpy as np
import pytest

import jax

import deeperspeed_tpu
from deeperspeed_tpu.elasticity.heartbeat import (InMemoryTransport,
                                                  PeerHealthMonitor)
from deeperspeed_tpu.runtime import telemetry as tm
from deeperspeed_tpu.runtime.config import DeepSpeedConfig
from deeperspeed_tpu.runtime.config_utils import DeepSpeedConfigError
from deeperspeed_tpu.runtime.exporters import (Histogram, JSONLBackend,
                                               PrometheusBackend,
                                               RotatingFile,
                                               prometheus_name)
from deeperspeed_tpu.runtime.fleet import FleetAggregator, build_fleet
from tests.simple_model import SimpleModel

pytestmark = [pytest.mark.fleet]

HIDDEN = 8
BATCH = 8


def fleet_params(**overrides):
    base = {"enabled": True, "window_steps": 3, "skew_interval_steps": 2,
            "skew_ema_beta": 0.5, "skew_slow_threshold_ms": 50.0,
            "max_trace_events": 2000}
    base.update(overrides)
    return base


def make_host(idx, n, summary, trace, gather=None, **overrides):
    return FleetAggregator(fleet_params(**overrides), process_index=idx,
                          process_count=n, summary_transport=summary,
                          trace_transport=trace, gather=gather)


@pytest.fixture
def ds_logs(caplog):
    """The DeeperSpeedTPU logger has propagate=False; attach caplog's
    handler directly so log-content assertions work."""
    from deeperspeed_tpu.utils.logging import logger as ds_logger
    ds_logger.addHandler(caplog.handler)
    try:
        with caplog.at_level("INFO", logger=ds_logger.name):
            yield caplog
    finally:
        ds_logger.removeHandler(caplog.handler)


class Recorder:
    def __init__(self):
        self.records = []

    def record(self, sample, scalars):
        self.records.append((int(sample), dict(scalars)))

    def series(self, key):
        return [s[key] for _, s in self.records if key in s]


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def _conf(d):
    base = {"train_batch_size": 8}
    base.update(d)
    return DeepSpeedConfig(None, param_dict=base)


class TestFleetConfig:
    def test_defaults(self):
        cfg = _conf({"telemetry": {"enabled": True,
                                   "fleet": {"enabled": True}}})
        fl = cfg.telemetry_config["fleet"]
        assert fl["window_steps"] == 50
        assert fl["skew_interval_steps"] == 10
        assert fl["skew_ema_beta"] == 0.9
        assert fl["max_trace_events"] == 2000

    def test_absent_or_disabled_is_none(self):
        cfg = _conf({"telemetry": {"enabled": True}})
        assert cfg.telemetry_config["fleet"] is None
        cfg = _conf({"telemetry": {"enabled": True,
                                   "fleet": {"enabled": False,
                                             "window_steps": 7}}})
        assert cfg.telemetry_config["fleet"] is None

    @pytest.mark.parametrize("block,match", [
        ({"fleet": {"enabled": True, "bogus": 1}}, "Unknown"),
        ({"fleet": {"enabled": 1}}, "boolean"),
        ({"fleet": {"enabled": True, "window_steps": 0}}, ">= 1"),
        ({"fleet": {"enabled": True, "skew_interval_steps": -1}}, ">= 0"),
        ({"fleet": {"enabled": True, "skew_ema_beta": 1.0}}, r"\[0, 1\)"),
        ({"fleet": {"enabled": True, "skew_ema_beta": "x"}}, "number"),
        ({"fleet": {"enabled": True,
                    "skew_slow_threshold_ms": -2}}, ">= 0"),
        ({"fleet": {"enabled": True, "max_trace_events": 0}}, ">= 1"),
        ({"fleet": []}, "object"),
    ])
    def test_rejects(self, block, match):
        tel = {"enabled": True}
        tel.update(block)
        with pytest.raises(DeepSpeedConfigError, match=match):
            _conf({"telemetry": tel})


class TestMonitorExportConfig:
    def test_defaults(self):
        cfg = _conf({})
        assert cfg.monitor_export_config == {
            "prometheus_port": None, "prometheus_host": "127.0.0.1",
            "jsonl": False, "rotate_max_mb": 64.0, "rotate_keep": 5}
        assert cfg.monitor_export_active is False

    def test_parse(self):
        cfg = _conf({"monitor": {"export": {
            "prometheus_port": 0, "prometheus_host": "0.0.0.0",
            "jsonl": True, "rotate_max_mb": 1, "rotate_keep": 2}}})
        assert cfg.monitor_export_config["prometheus_port"] == 0
        assert cfg.monitor_export_config["prometheus_host"] == "0.0.0.0"
        assert cfg.monitor_export_config["jsonl"] is True
        assert cfg.monitor_export_active is True

    @pytest.mark.parametrize("block,match", [
        ({"bogus": {}}, "Unknown 'monitor'"),
        ({"export": {"bogus": 1}}, "Unknown monitor.export"),
        ({"export": {"prometheus_port": -1}}, r"\[0, 65535\]"),
        ({"export": {"prometheus_port": "x"}}, "int"),
        ({"export": {"jsonl": "yes"}}, "boolean"),
        ({"export": {"prometheus_host": ""}}, "bind address"),
        ({"export": {"prometheus_host": 7}}, "bind address"),
        ({"export": {"rotate_max_mb": -1}}, ">= 0"),
        ({"export": {"rotate_keep": 0}}, ">= 1"),
    ])
    def test_rejects(self, block, match):
        with pytest.raises(DeepSpeedConfigError, match=match):
            _conf({"monitor": block})


class TestMoeObservabilityConfig:
    def test_sort_accepted(self):
        cfg = _conf({"moe": {"num_experts": 4, "dispatch": "sort",
                             "observability": True}})
        assert cfg.moe_params["observability"] is True

    def test_einsum_rejected(self):
        with pytest.raises(DeepSpeedConfigError, match="sort"):
            _conf({"moe": {"num_experts": 4, "observability": True}})

    def test_non_bool_rejected(self):
        with pytest.raises(DeepSpeedConfigError, match="boolean"):
            _conf({"moe": {"num_experts": 4, "dispatch": "sort",
                           "observability": 1}})


# ---------------------------------------------------------------------------
# exporters: histogram / prometheus / jsonl / rotation
# ---------------------------------------------------------------------------

class TestHistogram:
    def test_buckets_and_percentiles(self):
        h = Histogram(edges=(1.0, 10.0, 100.0))
        for v in (0.5, 5.0, 5.0, 50.0, 5000.0):
            h.observe(v)
        cum = dict(h.cumulative())
        assert cum[1.0] == 1 and cum[10.0] == 3 and cum[100.0] == 4
        assert cum[float("inf")] == 5
        assert h.count == 5 and h.total == pytest.approx(5060.5)
        assert h.percentile(0.5) == 10.0
        # +Inf bucket quantiles report the last finite edge
        assert h.percentile(0.99) == 100.0
        assert Histogram().percentile(0.5) is None

    def test_unsorted_edges_rejected(self):
        with pytest.raises(ValueError, match="sorted"):
            Histogram(edges=(10.0, 1.0))


class TestPrometheusBackend:
    def test_name_sanitization(self):
        assert prometheus_name("Train/Fleet/step_skew_ms") == \
            "ds_train_fleet_step_skew_ms"
        assert prometheus_name("Serve/p50 latency (ms)") == \
            "ds_serve_p50_latency_ms"

    def test_render_gauges_and_histograms(self):
        b = PrometheusBackend()
        b.observe_scalar("Train/Samples/train_loss", 1.25, 10)
        b.observe_scalar("Train/Samples/train_loss", 1.5, 20)  # latest wins
        b.observe_histogram("Serve/ttft_ms", 3.0, edges=(1.0, 10.0))
        b.observe_histogram("Serve/ttft_ms", 30.0, edges=(1.0, 10.0))
        text = b.render()
        assert "# TYPE ds_train_samples_train_loss gauge" in text
        assert "ds_train_samples_train_loss 1.5" in text
        assert '# TYPE ds_serve_ttft_ms histogram' in text
        assert 'ds_serve_ttft_ms_bucket{le="10.0"} 1' in text
        assert 'ds_serve_ttft_ms_bucket{le="+Inf"} 2' in text
        assert "ds_serve_ttft_ms_sum 33.0" in text
        assert "ds_serve_ttft_ms_count 2" in text

    def test_http_scrape(self):
        b = PrometheusBackend(port=0)
        try:
            b.observe_scalar("Train/Fleet/step_skew_ms", 12.5)
            url = f"http://127.0.0.1:{b.port}"
            body = urllib.request.urlopen(f"{url}/metrics",
                                          timeout=5).read().decode()
            assert "ds_train_fleet_step_skew_ms 12.5" in body
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"{url}/nope", timeout=5)
        finally:
            b.close()


class TestRotation:
    def test_rotating_file_keeps_last_n(self, tmp_path):
        path = str(tmp_path / "events.tsv")
        f = RotatingFile(path, max_bytes=100, keep=2, header="h\n")
        for i in range(300):
            f.write(f"row{i:04d}\n")
        f.close()
        assert os.path.exists(path)
        assert os.path.exists(path + ".1")
        assert os.path.exists(path + ".2")
        assert not os.path.exists(path + ".3")
        # rotated generations start with the header (fresh opens)
        assert open(path + ".1").readline() == "h\n"

    def test_jsonl_backend(self, tmp_path):
        b = JSONLBackend(str(tmp_path))
        b.observe_scalar("Train/Samples/train_loss", 1.5, 10)
        b.observe_scalar("Train/Goodput/fraction", 0.9, 10)
        b.flush()
        b.observe_histogram("Serve/ttft_ms", 4.0)
        b.close()
        lines = [json.loads(line) for line in
                 open(tmp_path / "events.jsonl")]
        assert lines[0]["sample"] == 10
        assert lines[0]["scalars"]["Train/Samples/train_loss"] == 1.5
        assert lines[1] == {"ts": lines[1]["ts"], "kind": "observation",
                            "tag": "Serve/ttft_ms", "value": 4.0}


class TestMonitorFanOut:
    def test_one_drain_feeds_all_backends(self, tmp_path):
        from deeperspeed_tpu.runtime.monitor import TensorBoardMonitor
        mon = TensorBoardMonitor(
            output_path=str(tmp_path), job_name="t", flush_interval=100,
            export={"prometheus_port": 0, "jsonl": True})
        try:
            mon.record(8, {"Train/Samples/train_loss": 2.0,
                           "Serve/queue_depth": 3.0})
            mon.observe_histogram("Serve/inter_token_ms", 7.0)
            mon.flush()
            prom = mon.prometheus
            assert prom is not None
            text = prom.render()
            assert "ds_train_samples_train_loss 2.0" in text
            assert "ds_serve_queue_depth 3.0" in text
            assert 'ds_serve_inter_token_ms_bucket{le="10.0"} 1' in text
            jsonl = tmp_path / "t" / "events.jsonl"
            assert jsonl.exists()
        finally:
            mon.close()
        # closed: endpoint gone, record drops with one warning
        with pytest.raises(Exception):
            urllib.request.urlopen(
                f"http://127.0.0.1:{prom.port}/metrics", timeout=1)


# ---------------------------------------------------------------------------
# FleetAggregator: window aggregation
# ---------------------------------------------------------------------------

class TestFleetWindows:
    def test_rank0_aggregates_across_hosts(self):
        summary, trace = InMemoryTransport(), InMemoryTransport()
        hosts = [make_host(i, 3, summary, trace, window_steps=3,
                           skew_interval_steps=0) for i in range(3)]
        # hosts 1/2 close their windows first (publish), then rank 0
        scalars = {}
        for idx in (1, 2, 0):
            agg = hosts[idx]
            out = {}
            for _ in range(3):
                out = agg.on_step_end(0.010 * (idx + 1),
                                      data_wait_s=0.001 * idx)
            if idx != 0:
                assert out == {}       # only the collector emits
            else:
                scalars = out
        assert scalars["Train/Fleet/hosts"] == 3.0
        assert scalars["Train/Fleet/step_time_ms_min"] == \
            pytest.approx(10.0)
        assert scalars["Train/Fleet/step_time_ms_median"] == \
            pytest.approx(20.0)
        assert scalars["Train/Fleet/step_time_ms_max"] == \
            pytest.approx(30.0)
        assert scalars["Train/Fleet/step_time_ms_skew"] == \
            pytest.approx(20.0)
        # slowest host named (host 2: 30ms mean step)
        assert scalars["Train/Fleet/slowest_host_step_time"] == 2.0
        assert scalars["Train/Fleet/data_wait_ms_max"] == \
            pytest.approx(2.0)

    def test_window_resets_accumulators(self):
        summary, trace = InMemoryTransport(), InMemoryTransport()
        agg = make_host(0, 1, summary, trace, window_steps=2,
                        skew_interval_steps=0)
        for _ in range(2):
            out = agg.on_step_end(0.010)
        assert out["Train/Fleet/step_time_ms_median"] == \
            pytest.approx(10.0)
        for _ in range(2):
            out = agg.on_step_end(0.030)
        assert out["Train/Fleet/step_time_ms_median"] == \
            pytest.approx(30.0)

    def test_transport_error_degrades_with_one_warning(self, ds_logs):
        class Broken:
            def publish(self, *a):
                raise RuntimeError("kv down")

            def read_all(self):
                raise RuntimeError("kv down")

        agg = make_host(0, 1, Broken(), Broken(), window_steps=1,
                        skew_interval_steps=0)
        out = agg.on_step_end(0.01)
        agg.on_step_end(0.01)
        # degraded to this host only: own summary still aggregates
        assert out["Train/Fleet/hosts"] == 1.0
        warns = [r for r in ds_logs.records
                 if "fleet: summary" in r.getMessage()]
        assert len(warns) == 1         # warned once, not per window


# ---------------------------------------------------------------------------
# collective-skew probe
# ---------------------------------------------------------------------------

class TestSkewProbe:
    def test_names_straggler_and_tracks_ema(self, ds_logs):
        caplog = ds_logs
        lateness = {"0": 0.0, "1": 180.0, "2": 10.0}
        agg = make_host(0, 3, InMemoryTransport(), InMemoryTransport(),
                        gather=lambda: lateness, skew_interval_steps=2,
                        window_steps=1000, skew_ema_beta=0.5)
        out = {}
        for _ in range(2):
            out = agg.on_step_end(0.01)
        assert out["Train/Fleet/step_skew_ms"] == pytest.approx(180.0)
        assert out["Train/Fleet/slowest_host"] == 1.0
        assert agg.last_slowest == "1"
        # behind-median: median is host 2 at 10ms -> host 1 is 170 behind
        assert agg.skew_ema_ms["1"] == pytest.approx(170.0)
        assert agg.behind_steps["1"] == 2
        assert agg.behind_steps["0"] == 0
        assert any("host 1 is 170ms/step behind" in r.getMessage()
                   for r in caplog.records)
        # second probe: EMA converges, consecutive count grows
        for _ in range(2):
            agg.on_step_end(0.01)
        assert agg.behind_steps["1"] == 4
        # host recovers: counter resets, re-naming re-arms
        lateness["1"] = 0.0
        for _ in range(2):
            out = agg.on_step_end(0.01)
        assert agg.behind_steps["1"] == 0

    def test_below_threshold_names_nobody(self):
        agg = make_host(0, 2, InMemoryTransport(), InMemoryTransport(),
                        gather=lambda: {"0": 0.0, "1": 20.0},
                        skew_interval_steps=1, window_steps=1000,
                        skew_slow_threshold_ms=50.0)
        out = agg.on_step_end(0.01)
        assert out["Train/Fleet/step_skew_ms"] == pytest.approx(20.0)
        # always emitted: -1 clears the gauge for latest-value scrapes
        assert out["Train/Fleet/slowest_host"] == -1.0
        assert agg.last_slowest is None

    def test_simulated_gather_reads_slow_peer_fault(self):
        monitor = PeerHealthMonitor("0", interval_s=100.0,
                                    warn_after_s=1e6, fail_after_s=1e7)
        monitor.ensure_simulated_peer("sim_peer_0")
        monitor.inject_slow_peer("sim_peer_0", 0.18)   # 180 ms lateness
        agg = make_host(0, 1, InMemoryTransport(), InMemoryTransport(),
                        skew_interval_steps=1, window_steps=1000)
        agg.bind_peer_monitor(monitor)
        out = agg.on_step_end(0.01)
        assert out["Train/Fleet/step_skew_ms"] == pytest.approx(180.0)
        assert agg.last_slowest == "sim_peer_0"

    def test_probe_feeds_heartbeat_note_skew(self):
        monitor = PeerHealthMonitor("0", interval_s=100.0,
                                    warn_after_s=1e6, fail_after_s=1e7)
        agg = make_host(0, 2, InMemoryTransport(), InMemoryTransport(),
                        gather=lambda: {"0": 0.0, "3": 180.0},
                        skew_interval_steps=1, window_steps=1000)
        agg.bind_peer_monitor(monitor)
        agg.on_step_end(0.01)
        ctx = monitor.skew_context("3")
        assert ctx is not None
        assert "behind the median" in ctx and "host 3" in ctx
        assert monitor.skew_context("0") is None   # ahead of median


class TestHeartbeatSkewCitation:
    def test_slow_escalation_cites_skew(self, ds_logs):
        caplog = ds_logs
        """The heartbeat `slow` log must carry the quantitative verdict
        — 'host X is Nms/step behind the median for K consecutive
        steps' — when the fleet probe has one."""
        clock = [0.0]
        monitor = PeerHealthMonitor(
            "0", peers=["0", "1"], interval_s=1.0, warn_after_s=5.0,
            fail_after_s=1e6, clock=lambda: clock[0])
        transport = monitor.transport
        transport.publish("1", {"serial": 1, "step": 0})
        monitor.poll_once()            # sees peer 1 fresh
        monitor.note_skew({"1": 180.0}, {"1": 50})
        clock[0] = 10.0                # past warn_after_s, no new beat
        monitor.poll_once()
        msgs = [r.getMessage() for r in caplog.records
                if "peer 1 heartbeat stale" in r.getMessage()]
        assert msgs, caplog.records
        assert "fleet skew probe: host 1 is 180ms/step behind the " \
            "median for 50 consecutive steps" in msgs[0]


# ---------------------------------------------------------------------------
# merged Perfetto capture
# ---------------------------------------------------------------------------

class TestMergedTrace:
    def test_one_lane_per_host_with_metadata(self, tmp_path):
        summary, trace = InMemoryTransport(), InMemoryTransport()
        hosts = [make_host(i, 3, summary, trace) for i in range(3)]
        for i, agg in enumerate(hosts):
            events = [("train_dispatch", 100.0 + i, 0.010, 0),
                      ("data_fetch", 100.5 + i, 0.002, 1)]
            agg.ship_capture("step5", events)
        path = hosts[0].merged_trace("step5", str(tmp_path))
        assert path and os.path.exists(path)
        doc = json.load(open(path))
        events = doc["traceEvents"]
        pids = {e["pid"] for e in events}
        assert pids == {0, 1, 2}       # one lane per host
        names = {e["args"]["name"] for e in events
                 if e.get("ph") == "M" and e["name"] == "process_name"}
        assert names == {"host0", "host1", "host2"}
        spans = [e for e in events if e.get("ph") == "X"]
        assert len(spans) == 6
        # per-host metadata: env fingerprint + kernel dispatch report
        meta = doc["otherData"]["hosts"]
        assert set(meta) == {"0", "1", "2"}
        assert meta["0"]["env"]["jax"] == jax.__version__
        assert "flash" in meta["0"]["dispatch"]
        # timestamps are host-relative (lanes align at window start)
        assert min(e["ts"] for e in spans) == 0.0

    def test_event_bound_drops_and_counts(self, tmp_path):
        summary, trace = InMemoryTransport(), InMemoryTransport()
        agg = make_host(0, 1, summary, trace, max_trace_events=5)
        events = [(f"s{i}", float(i), 0.001, 0) for i in range(20)]
        agg.ship_capture("t", events)
        path = agg.merged_trace("t", str(tmp_path))
        doc = json.load(open(path))
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert len(spans) == 5
        assert doc["otherData"]["hosts"]["0"]["dropped_events"] == 15

    def test_non_collector_does_not_merge(self, tmp_path):
        summary, trace = InMemoryTransport(), InMemoryTransport()
        agg = make_host(1, 2, summary, trace)
        agg.ship_capture("t", [("a", 0.0, 0.001, 0)])
        assert agg.merged_trace("t", str(tmp_path)) is None

    def test_stale_tags_ignored(self, tmp_path):
        summary, trace = InMemoryTransport(), InMemoryTransport()
        agg = make_host(0, 1, summary, trace)
        agg.ship_capture("old", [("a", 0.0, 0.001, 0)])
        assert agg.merged_trace("new", str(tmp_path),
                                timeout_s=0) is None

    def test_merge_waits_for_late_peers(self, tmp_path):
        """Rank 0 must not merge instantly: a peer shipping a few
        moments after the collector's own close still gets its lane."""
        summary, trace = InMemoryTransport(), InMemoryTransport()
        h0 = make_host(0, 2, summary, trace)
        h1 = make_host(1, 2, summary, trace)
        h0.ship_capture("t", [("a", 0.0, 0.001, 0)])
        import threading
        timer = threading.Timer(
            0.2, lambda: h1.ship_capture("t", [("b", 0.0, 0.001, 0)]))
        timer.start()
        try:
            path = h0.merged_trace("t", str(tmp_path))
        finally:
            timer.cancel()
        doc = json.load(open(path))
        assert {e["pid"] for e in doc["traceEvents"]} == {0, 1}

    def test_incomplete_merge_warns_with_lane_count(self, tmp_path,
                                                    ds_logs):
        summary, trace = InMemoryTransport(), InMemoryTransport()
        agg = make_host(0, 3, summary, trace)   # 2 peers never ship
        agg.ship_capture("t", [("a", 0.0, 0.001, 0)])
        path = agg.merged_trace("t", str(tmp_path), timeout_s=0.1)
        assert path is not None
        assert any("1/3 host lane" in r.getMessage()
                   for r in ds_logs.records)


class TestTelemetryFleetIntegration:
    def test_capture_close_exports_merged_trace(self, tmp_path):
        """A telemetry capture window close ships this host's spans and
        (on rank 0) writes the merged fleet trace next to the per-host
        export — whose metadata carries the dispatch report."""
        import types
        rec = Recorder()
        tel = tm.Telemetry(
            monitor=rec, devices=[], goodput=True, mfu=False, spans=True,
            trace_dir=str(tmp_path), capture={"start_step": 0,
                                              "num_steps": 1},
            fleet=fleet_params(window_steps=1000, skew_interval_steps=0))
        engine = types.SimpleNamespace(global_samples=0,
                                       checkpoint_manager=None,
                                       global_steps=0)
        tel.on_step_start(0)
        with tel.span("train_dispatch"):
            pass
        tel.on_step_end(engine)
        tel.close()
        per_host = tmp_path / "spans_step0.json"
        merged = tmp_path / "fleet_spans_step0.json"
        assert per_host.exists() and merged.exists()
        doc = json.load(open(per_host))
        assert "dispatch" in doc["otherData"]
        mdoc = json.load(open(merged))
        lanes = {e["pid"] for e in mdoc["traceEvents"]}
        assert lanes == {0}            # single real host on this box
        assert str(tmp_path / "fleet_spans_step0.json") in \
            tel.exported_traces

    def test_build_fleet_disabled(self):
        assert build_fleet(None) is None
        assert build_fleet({"enabled": False}) is None


# ---------------------------------------------------------------------------
# engine-level acceptance pin: slow_peer fault -> named within the window
# ---------------------------------------------------------------------------

def make_engine(extra_config):
    config = {
        "train_batch_size": BATCH,
        "steps_per_print": 1000,
        "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
    }
    config.update(extra_config)
    model = SimpleModel(hidden_dim=HIDDEN)
    params = model.init_params(jax.random.PRNGKey(0))
    engine, *_ = deeperspeed_tpu.initialize(
        model=model, model_parameters=params, config_params=config)
    return engine


class TestEngineFleet:
    def test_slow_peer_named_within_window(self):
        """THE acceptance pin: an injected `slow_peer` fault is named by
        `Train/Fleet/step_skew_ms`'s probe within the configured
        interval, the scalars flow to the monitor, and the heartbeat
        monitor receives the quantitative skew."""
        engine = make_engine({
            "telemetry": {"enabled": True, "goodput": True, "mfu": False,
                          "spans": True,
                          "fleet": {"enabled": True, "window_steps": 3,
                                    "skew_interval_steps": 2,
                                    "skew_slow_threshold_ms": 100.0}},
            "elasticity": {"heartbeat": {
                "enabled": True, "interval_s": 60.0,
                "warn_after_s": 3600.0, "fail_after_s": 86400.0}},
            "training_health": {"fault_injection": {"faults": [
                {"kind": "slow_peer", "step": 2, "seconds": 0.25}]}},
        })
        rec = Recorder()
        engine.telemetry.monitor = rec
        try:
            x = np.random.default_rng(0).standard_normal(
                (1, BATCH, HIDDEN)).astype(np.float32)
            y = np.random.default_rng(1).standard_normal(
                (1, BATCH, 1)).astype(np.float32)
            fleet = engine.telemetry.fleet
            assert fleet is not None
            named_at = None
            for i in range(6):
                engine.train_batch(batch=(x, y))
                if named_at is None and \
                        fleet.last_slowest == "sim_peer_0":
                    named_at = i + 1
            # fault fires at step 2; the probe runs every 2 steps —
            # naming must land within one probe interval of the fault
            assert named_at is not None and named_at <= 4
            skews = rec.series("Train/Fleet/step_skew_ms")
            assert skews and max(skews) == pytest.approx(250.0)
            assert rec.series("Train/Fleet/step_time_ms_median")
            ctx = engine.peer_monitor.skew_context("sim_peer_0")
            assert ctx and "behind the median" in ctx
        finally:
            engine.peer_monitor.stop()

    def test_fleet_off_by_default(self):
        engine = make_engine({"telemetry": {"enabled": True}})
        assert engine.telemetry.fleet is None

    def test_export_alone_builds_monitor(self, tmp_path):
        """An armed monitor.export block must serve without a
        tensorboard block — a validated exporter that silently never
        scrapes is the failure the parser exists to prevent."""
        import urllib.request
        engine = make_engine({
            "tensorboard": {"enabled": False,
                            "output_path": str(tmp_path)},
            "monitor": {"export": {"prometheus_port": 0}}})
        assert engine.monitor is not None
        prom = engine.monitor.prometheus
        assert prom is not None
        try:
            x = np.zeros((1, BATCH, HIDDEN), np.float32)
            y = np.zeros((1, BATCH, 1), np.float32)
            engine.train_batch(batch=(x, y))
            engine.monitor.flush()
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{prom.port}/metrics",
                timeout=5).read().decode()
            assert "ds_train_samples_train_loss" in body
        finally:
            engine.monitor.close()


# ---------------------------------------------------------------------------
# MoE routing observability
# ---------------------------------------------------------------------------

class TestMoeObservability:
    def _params(self, rng, E=4, H=16, I=32):
        import jax.numpy as jnp
        k1, k2, k3 = jax.random.split(rng, 3)
        return {"gate": jax.random.normal(k1, (H, E)) * 0.02,
                "w_in": jax.random.normal(k2, (E, H, I)) * 0.02,
                "b_in": jnp.zeros((E, I)),
                "w_out": jax.random.normal(k3, (E, I, H)) * 0.02,
                "b_out": jnp.zeros((E, H))}

    def test_sort_dispatch_emits_stats(self):
        from deeperspeed_tpu.moe.layer import (ROUTING_STATS,
                                               moe_ffn_dense)
        rng = jax.random.PRNGKey(0)
        params = self._params(rng)
        x = jax.random.normal(rng, (64, 16))
        ROUTING_STATS.drain()          # isolate from other tests
        y_obs, _ = moe_ffn_dense(params, x, dispatch="sort",
                                 capacity_factor=1.0, observe=True)
        jax.block_until_ready(y_obs)
        stats = ROUTING_STATS.drain()
        assert stats is not None
        load_min = stats["Train/MoE/expert_load_min"]
        load_max = stats["Train/MoE/expert_load_max"]
        assert 0.0 <= load_min <= 0.25 <= load_max <= 1.0
        assert 0.0 <= stats["Train/MoE/capacity_drop_fraction"] <= 1.0
        assert stats["Train/MoE/expert_load_cv"] >= 0.0
        # observe must not perturb the numerics
        y_plain, _ = moe_ffn_dense(params, x, dispatch="sort",
                                   capacity_factor=1.0)
        np.testing.assert_array_equal(np.asarray(y_obs),
                                      np.asarray(y_plain))
        ROUTING_STATS.drain()

    def test_einsum_observe_rejected(self):
        from deeperspeed_tpu.moe.layer import moe_ffn_dense
        rng = jax.random.PRNGKey(0)
        with pytest.raises(ValueError, match="sort"):
            moe_ffn_dense(self._params(rng),
                          jax.random.normal(rng, (64, 16)),
                          dispatch="einsum", observe=True)

    def test_drain_empty_returns_none(self):
        from deeperspeed_tpu.moe.layer import _RoutingStatsCollector
        assert _RoutingStatsCollector().drain() is None

    def test_engine_records_moe_scalars(self):
        """JSON-config-driven: moe.observability routes the sort
        engine's stats into Train/MoE/* monitor scalars."""
        from deeperspeed_tpu.models.gpt_neox import GPTNeoX, GPTNeoXConfig
        from deeperspeed_tpu.moe.layer import ROUTING_STATS
        ROUTING_STATS.drain()
        cfg = GPTNeoXConfig(vocab_size=64, hidden_size=16, num_layers=2,
                            num_heads=2, max_seq_len=16)
        model = GPTNeoX(config=cfg, use_pallas=False)
        config = {
            "train_batch_size": 8,
            "steps_per_print": 1000,
            "optimizer": {"type": "Adam", "params": {"lr": 0.01}},
            "moe": {"num_experts": 4, "dispatch": "sort",
                    "observability": True},
            "tensorboard": {"enabled": False},
        }
        engine, *_ = deeperspeed_tpu.initialize(
            model=model, config_params=config)
        assert engine._moe_observe
        rec = Recorder()
        engine.monitor = rec
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, 64, size=(1, 8, 16), dtype=np.int32)
        for _ in range(3):
            engine.train_batch(batch=(tokens, tokens))
        keys = set()
        for _, sc in rec.records:
            keys |= set(sc)
        assert "Train/MoE/expert_load_max" in keys
        assert "Train/MoE/capacity_drop_fraction" in keys
        ROUTING_STATS.drain()


# ---------------------------------------------------------------------------
# ops.dispatch_report / ds_report --json satellites
# ---------------------------------------------------------------------------

class TestDispatchReport:
    def test_accessor_shape(self):
        from deeperspeed_tpu.ops import dispatch_report
        report = dispatch_report()
        assert set(report) == {"flash", "attention", "decode_attention",
                               "quant_matmul", "grouped_matmul", "moe",
                               "ssm", "ce_head", "xla_on_tpu"}
        # the CE head's calls traced so far, by the rule that ran
        assert set(report["ce_head"]) == {"loss_and_grads", "loss_only"}
        # a state-space layer's scan and step, by the backend that ran
        assert set(report["ssm"]) <= {"scan", "step"}
        # the dropless MoE layers traced so far, by the form of their plan
        assert set(report["moe"]) == {"plan"}
        # and by how the layer numbered the pairs it planned
        assert set(report["moe"]["plan"]) <= {"counted", "choice_major"}
        assert isinstance(report["flash"], dict)
        # the attention projections traced so far, by their form
        assert set(report["attention"]["head_projection"]) == \
            {"plain", "folded", "split"}

    @pytest.mark.parametrize("rows,form", [(4, "plain"), (64, "folded")])
    def test_head_projection_counts_the_form_of_each_trace(self, rows,
                                                           form):
        """A projection to heads is counted when it is traced, under
        the form its shapes gave it (rows of the activation against the
        weight's contracting dim, `autotune.head_projection_plain`); a
        copy of the report does not move with the record."""
        import jax
        import jax.numpy as jnp

        from deeperspeed_tpu.models.gpt_neox import _heads_dot
        from deeperspeed_tpu.ops import dispatch_report
        before = dispatch_report()["attention"]["head_projection"]
        x = jax.ShapeDtypeStruct((rows, 1, 16), jnp.float32)
        w = jax.ShapeDtypeStruct((16, 48), jnp.float32)
        assert jax.eval_shape(_heads_dot, x, w).shape == (rows, 1, 48)
        after = dispatch_report()["attention"]["head_projection"]
        other = {"plain": "folded", "folded": "plain"}[form]
        assert after[form] == before[form] + 1
        assert after[other] == before[other]

    @pytest.mark.parametrize("head_dim,want", [(64, "pallas"), (96, "xla")])
    def test_attention_records_backend_and_names_xla_on_a_tpu(
            self, ds_logs, monkeypatch, head_dim, want):
        """Every dispatch records kernel-or-XLA; an unsupported shape
        (head dim 96, the NeoX-20B preset) takes XLA, and on a TPU that
        is logged once, by name — never silently. The XLA branch runs
        anywhere, so the test only steers what `note_xla_on_tpu` sees."""
        import importlib

        import jax.numpy as jnp

        from deeperspeed_tpu.models.gpt_neox import causal_attention
        from deeperspeed_tpu.ops import dispatch_report
        fa = importlib.import_module(
            "deeperspeed_tpu.ops.pallas.flash_attention")
        monkeypatch.setattr(fa, "_XLA_NOTED", set())
        if want == "xla":
            monkeypatch.setattr(fa, "_interpret", lambda: False)
        q = jnp.ones((1, 128, 2, head_dim), jnp.float32)
        for _ in range(2):
            out = causal_attention(q, q, q)
        assert out.shape == q.shape
        report = dispatch_report()
        assert report["attention"]["attention"] == want
        named = [r for r in ds_logs.records
                 if "causal_attention: running on XLA" in r.getMessage()]
        if want == "xla":
            assert report["xla_on_tpu"] == ["causal_attention"]
            assert len(named) == 1 and "96" in named[0].getMessage()
        else:
            assert report["xla_on_tpu"] == [] and not named

    def test_decode_records_backend_and_logs_once(self, ds_logs):
        caplog = ds_logs
        import jax.numpy as jnp

        from deeperspeed_tpu.ops import dispatch_report
        from deeperspeed_tpu.ops.pallas import decode_attention as da
        B, H, D, ps, NP = 1, 2, 4, 4, 4
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((NP, H, ps, D)), jnp.float32)
        vp = jnp.asarray(rng.standard_normal((NP, H, ps, D)), jnp.float32)
        pt = jnp.zeros((B, NP), jnp.int32)
        lens = jnp.asarray([3], jnp.int32)
        da._DISPATCH_LOGGED = False
        da.paged_decode_attention(q, kp, vp, pt, lens)
        da.paged_decode_attention(q, kp, vp, pt, lens)
        logs = [r for r in caplog.records
                if "decode_attention first dispatch" in r.getMessage()]
        assert len(logs) == 1          # one structured line, first only
        assert dispatch_report()["decode_attention"]["decode"] in \
            ("xla", "pallas")


class TestEnvReportJson:
    def test_fingerprint_fields(self):
        from deeperspeed_tpu.env_report import env_fingerprint
        info = env_fingerprint()
        assert info["jax"] == jax.__version__
        assert info["process_count"] == jax.process_count()
        assert info["device_kind"]
        assert "devices_per_process" in info["topology"]

    def test_json_mode_stdout(self, capsys):
        from deeperspeed_tpu.env_report import main
        main(["--json"])
        out = capsys.readouterr().out
        doc = json.loads(out)
        assert doc["env"]["jax"] == jax.__version__
        assert isinstance(doc["ops"], dict) and doc["ops"]
