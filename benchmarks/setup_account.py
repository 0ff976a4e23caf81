"""Where a run's `setup_s` went, from the program's own set-up account
(`deeperspeed_tpu.runtime.telemetry.setup_report`; docs/observability.md,
"Set-up"), cut where the window opened. The eight `setup_*` metric
readers share it.

The parts in seconds and the remainder add up to `setup_s` by
construction: the package's own import, the engines' constructors less
what jax did inside them, tracing + lowering, the backend's compiles,
the reads of the persistent cache, what the compiling calls cost beyond
that, and the rest (warm-up and ramp steps that compiled nothing, and
the caller's own set-up: weights, the reference, their compiles). A
negative remainder, or one near `setup_s` itself, is the account's
fault. `setup_cache_misses` is a count and in no sum: two set-ups
compare at equal misses."""

import sys

SECONDS = ("setup_import_s", "setup_engine_build_s", "setup_trace_lower_s",
           "setup_backend_compile_s", "setup_cache_read_s",
           "setup_first_call_s")


def t_start():
    """`T_START` of whichever module is running `run.main`: the script
    itself, or `benchmarks.run` under a wrapper (`rec` does not carry
    it)."""
    for name in ("__main__", "benchmarks.run"):
        stamp = getattr(sys.modules.get(name), "T_START", None)
        if stamp is not None:
            return stamp
    return None


def account(rec):
    """`setup_report(until=the window's opening)`, or None where the
    program has no such report (a parent before PR 52) or no `T_START`
    is to be found."""
    if "_setup_account" not in rec:
        try:
            from deeperspeed_tpu.runtime.telemetry import setup_report
        except ImportError:
            setup_report = None
        start = t_start()
        rec["_setup_account"] = None if setup_report is None or \
            start is None else setup_report(until=start + rec["setup_s"])
    return rec["_setup_account"]


def _jax_s(entry):
    return entry["trace_s"] + entry["lower_s"] + entry["compile_s"] \
        + entry["cache_read_s"]


def parts(rec):
    """The eight metrics by name, or None without an account."""
    report = account(rec)
    if report is None:
        return None
    builds = [e["build"] for e in report["engines"] if e["build"]]
    programs = [p for e in report["engines"] for p in e["programs"]]
    records = builds + programs
    out = {
        "setup_import_s": report["import_s"],
        "setup_engine_build_s": sum(b["wall_s"] - _jax_s(b)
                                    for b in builds),
        "setup_trace_lower_s": sum(r["trace_s"] + r["lower_s"]
                                   for r in records),
        "setup_backend_compile_s": sum(r["compile_s"] for r in records),
        "setup_cache_read_s": sum(r["cache_read_s"] for r in records),
        "setup_first_call_s": sum(p["first_call_s"] for p in programs),
        # the one part read from the totals, which the report adds up
        # from its kept entries: not published where it says they fell
        # short (`complete` False); the rest are the engines' own records
        "setup_cache_misses": report["totals"]["cache_misses"]
        if report.get("complete", True) else None,
    }
    out["setup_rest_s"] = rec["setup_s"] - sum(out[name]
                                               for name in SECONDS)
    return out


def read(rec, name):
    found = parts(rec)
    return None if found is None else found[name]
