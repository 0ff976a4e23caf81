"""The harness: finds a cell's files by the names in `BENCHMARK.json`,
runs the cell once, and builds the contract's last line.

Everything that belongs to one thing is one file, found by name, so a
later PR adds files and entries and edits none:

    configs/<config>.json     a model configuration as published, with
                              `family` naming families/<family>.py and
                              reference/<family>.py
    traffic/<traffic>.json    a traffic mix: `kind` names the general
                              generator drivers/<kind>.py, the rest are
                              its parameters
    workloads/<cell>.json     what the cell gives the program: the
                              engine's JSON config verbatim, the model's
                              options, the mesh, the tolerances
    metrics/<metric>.py       `read(rec)` -> a number, or None where the
                              run has nothing for it to read

`root` is the directory that holds `BENCHMARK.json`; the repo's own by
default. A rehearsal hands in a copy with files added.
"""

import glob
import importlib.util
import json
import os
import shutil
import time

from benchmarks import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchmarkError(Exception):
    """The cell cannot be run as specified."""


def load_json(root, *parts):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def load_module(root, kind, name):
    """The module benchmarks/<kind>/<name>.py of `root`."""
    path = os.path.join(root, "benchmarks", kind, name + ".py")
    if not os.path.exists(path):
        raise BenchmarkError(f"no {kind} named {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name.replace('-', '_').replace('.', '_')}",
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(root, workload):
    """Everything one cell is made of, as one dict."""
    bench = load_json(root, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise BenchmarkError(
            f"BENCHMARK.json has no workload {workload!r}; it has "
            f"{[w['name'] for w in bench['workloads']]}")
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == entry["config"])
    config = load_json(root, config_entry["file"])
    traffic = load_json(root, "benchmarks", "traffic",
                        entry["traffic"] + ".json")
    cell = load_json(root, "benchmarks", "workloads", workload + ".json")
    peaks = load_json(root, "benchmarks", "peaks.json")

    def applies(metric):
        return workload in metric.get("workloads", [workload])
    end_to_end = [m for m in bench["end_to_end"] if applies(m)]
    reported = {m["name"] for m in end_to_end}
    return {
        "root": root, "name": workload, "chips": entry["chips"],
        "config_name": entry["config"], "config": config,
        "traffic_name": entry["traffic"], "traffic": traffic,
        "cell": cell, "peaks": peaks, "end_to_end": end_to_end,
        # a per-layer metric is reported only where the metric it moves is
        "per_layer": [m for m in bench["per_layer"]
                      if applies(m) and m["moves"] in reported],
    }


def peaks_for(spec, device_kind):
    if device_kind not in spec["peaks"]:
        raise BenchmarkError(
            f"benchmarks/peaks.json has no entry for device kind "
            f"{device_kind!r}")
    return spec["peaks"][device_kind]


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest device."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            raise BenchmarkError(f"{d} reports no peak_bytes_in_use")
        peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks)


def read_metrics(spec, rec, trace):
    """The cell's end-to-end metrics (`trace` 0) or per-layer metrics
    (`trace` 1), each from its own reader."""
    out = {}
    for metric in spec["per_layer" if trace else "end_to_end"]:
        value = load_module(spec["root"], "metrics", metric["name"]).read(rec)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def result_line(spec, rec, trace, devices):
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    line = {"correct": bool(rec["correct"]),
            "attempted": int(rec["attempted"]), "failed": int(rec["failed"]),
            "metrics": read_metrics(spec, rec, trace), "device": device}
    if trace:
        t = rec["trace"]
        if t is None or t["busy_s"] <= 0:
            raise BenchmarkError(
                "the traced stretch shows no operation on the device")
        device["busy_s"] = t["busy_s"]
        device["window_s"] = t["window_s"]
        line["breakdown"] = {"device_ops": t["device_ops"],
                             "idle_gaps": t["idle_gaps"]}
    return line


def run_cell(spec, seed, seconds, trace, t_start, log, devices):
    """Run the cell once. Returns the run's record: what the drivers
    measured and counted, for the metric readers."""
    driver = load_module(spec["root"], "drivers", spec["traffic"]["kind"])
    family = load_module(spec["root"], "families", spec["config"]["family"])
    reference = load_module(spec["root"], "reference",
                            spec["config"]["family"])
    rec = driver.run(spec, family, reference, seed=seed, seconds=seconds,
                     trace=bool(trace), t_start=t_start, log=log,
                     devices=devices)
    rec["spec"] = spec
    rec["device_kind"] = devices[0].device_kind
    rec["memory_peak_bytes"] = memory_peak_bytes(devices)
    return rec


class Tracer:
    """Records a profiler trace of a short stretch and reduces it in this
    process. The stretch is marked on the host thread as
    `trace_reduce.WINDOW_SPAN`."""

    def __init__(self, spec):
        self.out_dir = os.path.join(spec["root"], ".bench_traces",
                                    spec["name"])
        shutil.rmtree(self.out_dir, ignore_errors=True)
        self.reduced = self.path = self._span = None

    @property
    def started(self):
        return self._span is not None

    def start(self):
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host spans, not every frame
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        self._span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self._span.__enter__()

    def stop(self):
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        files = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        if not files:
            raise BenchmarkError(f"the profiler wrote no trace under "
                                 f"{self.out_dir}")
        self.path = files[-1]
        self.reduced = trace_reduce.reduce_file(self.path)


def span(name):
    """A host span of the benchmark's own, around a call into a layer."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def now():
    return time.perf_counter()
