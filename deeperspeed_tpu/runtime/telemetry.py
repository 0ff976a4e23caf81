"""Unified telemetry: step/phase tracing, goodput + MFU accounting, and
trigger-driven profiler capture.

The reference instruments training piecemeal (`wall_clock_breakdown`
CUDA timers, a standalone flops profiler, tensorboard scalars); here the
pieces fuse into one config-driven layer the engine consults every step:

- **Span tracer** (`telemetry.span("data_fetch")`): host-side phase
  timing around every boundary the engine already owns — data fetch,
  host→device batch upload, train-step dispatch, checkpoint snapshot
  stall, sentinel escalation, rollback restore. Each span also enters a
  `jax.profiler.TraceAnnotation`, so a device trace captured over the
  same steps carries the same phase names, and the host spans export as
  Chrome-trace/Perfetto JSON per capture window.
- **Goodput accounting**: cumulative wall time inside step windows is
  classified into productive / data_wait / ckpt_stall / quarantined /
  rollback buckets, emitted as `Train/Goodput/*` scalars plus a running
  `Train/Goodput/fraction` (productive over everything).
- **In-engine MFU**: the engine AOT-compiles its train step when MFU is
  on, the per-variant flops are harvested ONCE from
  `compiled.cost_analysis()` (`profiling.flops_profiler._cost_analysis`)
  and every step emits `Train/Samples/mfu` and achieved-FLOPS/s against
  the per-device-kind peak table (`profiling.hardware`).
- **Trigger-driven capture**: the validated ``"telemetry"`` JSON block
  arms programmatic `jax.profiler` trace windows
  (``capture: {start_step, num_steps}``), periodic HBM
  `memory_stats` watermark scalars, and an on-anomaly hook — the
  sentinel's warn/quarantine/rollback path and the hang watchdog grab a
  memory snapshot immediately and a trace of the following step(s)
  automatically, at most once per anomaly episode.

- **Step timeline** (`StepTimeline`, one per engine, kept with or
  without the block): every step one record (its key, its phases' self
  seconds, the caller's time, thread CPU time, collector time, whether
  something was lowered), a slow step judged by one rule against its
  key's own typical step and its excess split over what held it.
  `step_report()` is the process-wide accessor; the spans above write
  into the open record and carry its serial (docs/observability.md,
  "Slow steps").
- **Set-up account** (always kept, like the timeline): ONE
  `jax.monitoring` listener stamps every trace, lowering, backend compile
  and persistent-cache read or miss of the process by name, and charges
  it to the engine call it fired in or to the caller; each engine's
  constructor is one `build` record of named phases; a step that
  compiled says what compiled. `setup_report(until)` is the process-wide
  accessor (docs/observability.md, "Set-up").

Zero-overhead path: when the block is absent the engine holds
`NULL_TELEMETRY`, whose hooks are empty methods and whose `span()`
returns a shared no-op context manager — the compiled programs are
unchanged, and the host loop keeps only its step timeline.
"""

import bisect
import contextlib
import dataclasses
import gc
import json
import os
import statistics
import threading
import time
import typing
import weakref
from collections import deque

from ..utils.logging import log_dist, logger

# one process-wide flag: jax.profiler supports a single active trace;
# overlapping windows (scheduled + anomaly) must coalesce, not crash
_TRACE_LOCK = threading.Lock()
_TRACE_ACTIVE = False


def _release_orphaned_trace(wstate):
    """weakref.finalize target: a Telemetry collected mid-capture-window
    must stop the jax trace it started and release the process-wide
    flag, or every later window in the process silently skips tracing
    (and the profiler keeps buffering forever). Shares only the mutable
    `wstate` dict with the owner — no reference cycle."""
    global _TRACE_ACTIVE
    if not wstate.get("started_jax"):
        return
    with _TRACE_LOCK:
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001 - interpreter may be tearing down
            pass
        _TRACE_ACTIVE = False
        wstate["started_jax"] = False


def _cost_analysis_flops(compiled):
    """Per-device program flops from an AOT-compiled executable (None
    when the backend reports no cost model)."""
    from ..profiling.flops_profiler.profiler import _cost_analysis
    flops = float(_cost_analysis(compiled).get("flops", 0.0))
    return flops if flops > 0 else None


class _AOTStep:
    """AOT executable with a one-time jit fallback.

    The executable is compiled against the FIRST call's input shardings
    and layouts. GSPMD may settle the donated state onto different
    output shardings (the jit path silently retraces once for exactly
    this; `_build_train_window`'s docstring records the same effect for
    layouts) — and a checkpoint restore re-places state the same way.
    The AOT call then raises a sharding/layout mismatch BEFORE executing
    (inputs intact), so we degrade to the plain jit wrapper, which
    re-specializes per input just like the telemetry-off path. Total
    compile count matches the jit path's own worst case (two)."""

    def __init__(self, compiled, rebuild):
        self._fn = compiled
        self._rebuild = rebuild
        self._fell_back = False

    def __call__(self, *args):
        if not self._fell_back:
            try:
                return self._fn(*args)
            # ValueError: sharding/layout mismatch; TypeError: aval
            # mismatch ("Argument types differ...") — both raised by the
            # Compiled input checks BEFORE execution, so inputs (incl.
            # donated buffers) are intact and the jit retry is safe.
            # Anything raised mid-execution propagates.
            except (ValueError, TypeError) as e:
                logger.warning(
                    "telemetry: inputs settled away from the first-call "
                    f"AOT compile ({e}); this step variant "
                    "re-specializes under jit from here on")
                self._fell_back = True
                self._fn = self._rebuild()
        return self._fn(*args)


def aot_compile_with_flops(jitted, args, rebuild=None):
    """Lower+compile `jitted` against concrete `args` (one trace, one
    compile — the AOT executable IS the step the engine runs, so
    `cost_analysis` costs nothing extra). Returns (callable, flops);
    falls back to the plain jit wrapper on any failure. `rebuild`
    (() -> fresh jit wrapper) arms the one-time sharding-settle fallback
    — see `_AOTStep`."""
    try:
        compiled = jitted.lower(*args).compile()
        flops = _cost_analysis_flops(compiled)
    except Exception as e:  # noqa: BLE001 - telemetry must not kill training
        logger.warning(f"telemetry: AOT flops harvest failed "
                       f"({type(e).__name__}: {e}); MFU scalars disabled "
                       f"for this step variant")
        return jitted, None
    if rebuild is not None:
        return _AOTStep(compiled, rebuild), flops
    return compiled, flops


class _NullSpan:
    """Shared no-op context manager (the zero-overhead span)."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """One live span of a `SpanTracer` or a `StepTimeline`: its seconds
    go to the owner's `_on_span` with the serial of the step record that
    was open when it began (None outside an engine), and it mirrors a
    `jax.profiler.TraceAnnotation` (the serial as its `step` argument)
    so device timelines show the same names."""
    __slots__ = ("tel", "name", "t0", "ann", "step")

    def __init__(self, tel, name):
        self.tel = tel
        self.name = name
        self.ann = None

    def __enter__(self):
        tel = self.tel
        self.t0 = time.perf_counter()
        self.step = tel.serial
        tel._stack.append(0.0)      # seconds of the spans opened inside
        if tel.mirror_annotations:
            import jax
            self.ann = jax.profiler.TraceAnnotation(self.name) \
                if self.step is None else \
                jax.profiler.TraceAnnotation(self.name, step=self.step)
            self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.tel._on_span(self.name, self.t0, t1 - self.t0, self.step)
        return False


class SpanEvent(tuple):
    """A buffered span, `(name, t0, dur, depth)` as every consumer
    unpacks it, with the serial of the step record it belongs to beside
    it (`step`; None outside an engine) and further Chrome `args`."""
    step = None
    args = None


def clock_pair():
    """One reading of both clocks, `(perf_counter, time_ns)`: spans and
    step records are stamped on the first, a profiler's xplane file on
    the second; the pair lines them up offline."""
    return time.perf_counter(), time.time_ns()


class SpanTracer:
    """Host-side span recorder. Always accumulates per-step phase
    durations (goodput inputs); buffers full (name, ts, dur, depth)
    events only while a capture window is open, and exports them as a
    Chrome-trace JSON (`{"traceEvents": [...]}`, "X" complete events,
    microsecond timestamps) loadable in Perfetto/chrome://tracing."""

    serial = None       # a bare tracer's spans belong to no step record

    def __init__(self, mirror_annotations=True):
        self.mirror_annotations = mirror_annotations
        self._stack = []            # open spans (`_Span` keeps it)
        self._phase_acc = {}        # name -> seconds, this step window
        self._buffer = []           # capture-window events
        self.capturing = False

    def span(self, name):
        return _Span(self, name)

    def _on_span(self, name, t0, dur, step):
        self._stack.pop()
        self.record(name, t0, dur, len(self._stack), step)

    def record(self, name, t0, dur, depth, step=None):
        """One closed span: its seconds to the phase account and, inside
        a capture window, to the buffer. An engine's `StepTimeline`
        hands its spans on through here."""
        self._phase_acc[name] = self._phase_acc.get(name, 0.0) + dur
        if self.capturing:
            self.record_event(name, t0, dur, depth, step=step)

    def record_event(self, name, t0, dur, depth=0, step=None, **args):
        """Append one pre-timed event to an open capture window (the
        serving engine's per-request lifecycle records ride this — they
        are not live spans, the request's wall time was measured by the
        scheduler). `step` is the serial of the step record the event
        belongs to; further keywords land in the Chrome event's `args`.
        No-op outside a window."""
        if self.capturing:
            event = SpanEvent((str(name), float(t0), float(dur),
                               int(depth)))
            event.step, event.args = step, args or None
            self._buffer.append(event)

    def drain_phases(self):
        phases, self._phase_acc = self._phase_acc, {}
        return phases

    def start_capture(self):
        self._buffer = []
        self.capturing = True

    def stop_capture(self):
        self.capturing = False
        events, self._buffer = self._buffer, []
        return events

    @staticmethod
    def chrome_trace(events, pid=0, metadata=None):
        """Chrome-trace dict for a list of (name, t0, dur, depth), each
        with its step serial and further arguments as the event's
        ``args`` where it carries any (`SpanEvent`). `metadata` (kernel
        dispatch report, env fingerprint) lands in the trace's
        ``otherData``, beside one `clock_pair()`: ``ts`` is
        `perf_counter` microseconds."""
        trace_events = []
        for event in events:
            name, t0, dur, depth = event
            out = {"name": name, "ph": "X", "pid": pid, "tid": depth,
                   "ts": t0 * 1e6, "dur": dur * 1e6,
                   "cat": "deeperspeed_tpu"}
            step = getattr(event, "step", None)
            args = dict(getattr(event, "args", None) or {})
            if step is not None:
                args["step"] = step
            if args:
                out["args"] = args
            trace_events.append(out)
        perf, epoch_ns = clock_pair()
        return {"traceEvents": trace_events, "displayTimeUnit": "ms",
                "otherData": dict(metadata or {}, clock={
                    "perf_counter": perf, "time_ns": epoch_ns})}

    @classmethod
    def export_chrome_trace(cls, events, path, pid=0, metadata=None):
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(cls.chrome_trace(events, pid=pid,
                                       metadata=metadata), f)
        return path


# goodput bucket names, in emission order
GOODPUT_BUCKETS = ("productive", "data_wait", "param_wait", "ckpt_stall",
                   "quarantined", "rollback")


class GoodputMeter:
    """Cumulative wall-time classifier over step windows.

    Every `account()` call covers one step window of `dt` seconds and
    splits it: data-fetch span time is always charged to `data_wait`;
    host-visible parameter-fetch stalls (`param_gather` spans — the
    offload tiers waiting on a segment upload; the in-jit explicit
    ZeRO-3 gathers are scheduled/overlapped inside the program and show
    up in device traces, not here) to `param_wait`; checkpoint snapshot
    stall (the delta of the async manager's cumulative stall inside
    this window) to `ckpt_stall`; the rest goes to `productive` for
    taken steps, `quarantined` for in-jit skipped updates (sentinel
    quarantine or fp16 overflow — either way the step burned time
    without advancing), and `rollback` for windows that ended in a
    checkpoint restore."""

    def __init__(self):
        self.buckets = {name: 0.0 for name in GOODPUT_BUCKETS}

    def account(self, dt, verdict, data_wait=0.0, param_wait=0.0,
                ckpt_stall=0.0):
        data_wait = min(max(data_wait, 0.0), dt)
        param_wait = min(max(param_wait, 0.0), dt - data_wait)
        ckpt_stall = min(max(ckpt_stall, 0.0),
                         dt - data_wait - param_wait)
        rest = dt - data_wait - param_wait - ckpt_stall
        self.buckets["data_wait"] += data_wait
        self.buckets["param_wait"] += param_wait
        self.buckets["ckpt_stall"] += ckpt_stall
        if verdict == "rollback":
            self.buckets["rollback"] += rest
        elif verdict in ("quarantined", "overflow"):
            self.buckets["quarantined"] += rest
        else:
            self.buckets["productive"] += rest

    @property
    def total(self):
        return sum(self.buckets.values())

    @property
    def fraction(self):
        total = self.total
        return self.buckets["productive"] / total if total > 0 else 1.0

    def scalars(self):
        out = {f"Train/Goodput/{name}_s": secs
               for name, secs in self.buckets.items()}
        out["Train/Goodput/fraction"] = self.fraction
        return out


# ---------------------------------------------------------------------------
# step timeline: one record a step, a slow step named by what held it
# ---------------------------------------------------------------------------

STEP_RING = 4096            # records a timeline keeps
SLOW_KEPT = 256             # slow records it keeps whole
TYPICAL_STEPS = 64          # a key's last steps not judged slow
MIN_STEPS = 8               # no verdict before this many steps of a key
# a step is slow where wall - typical > max(SLOW_FLOOR_S, SLOW_DEVIATIONS
# x the key's median absolute deviation). False positives (a slow verdict
# in a run whose end-to-end metric is within its spread of the median) in
# the seven cells at these values: PERF.md section 6, PR 37
SLOW_FLOOR_S = 5e-3
SLOW_DEVIATIONS = 8
# this many slow verdicts of a key in a row are its new level, not a
# stall: the key learns its typical step anew
RELEVEL_AFTER = 8
SLOW_LOG_INTERVAL_S = 10.0

COMPILES_KEPT = 256         # compile records (and the build) kept whole
COMPILE_LOG_AFTER = 64      # steady steps before a compile is worth a line
# the newest timelines, held past their engines' lives: `step_report()`
# is read after a run, when the engine that made the records may be gone
_TIMELINES = deque(maxlen=16)
# process-wide: [seconds inside collections, start of the one running];
# one gc.callbacks hook, installed with the listener below
_GC = [0.0, 0.0]


def _on_gc(phase, info):  # noqa: ARG001
    if phase == "start":
        _GC[1] = time.perf_counter()
    else:
        _GC[0] += time.perf_counter() - _GC[1]


# ---------------------------------------------------------------------------
# the compile account: what jax traced, lowered, compiled or read, and whose
# ---------------------------------------------------------------------------

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWERED_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# jax.monitoring's events (jax 0.9.0) -> the account's kinds. The first
# three carry `fun_name`; the backend's covers `compile_or_get_cached`,
# so at a cache hit it IS the read (key, retrieval, deserialization), and
# jax's own `cache_retrieval_time_sec` and `compile_time_saved_sec` would
# have no reader here
COMPILE_EVENTS = {
    TRACE_EVENT: "trace", LOWERED_EVENT: "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}
EVENTS_KEPT = 4096          # entries kept whole, at either end
NAMES_KEPT = 8              # program names a record's delta carries
_SECONDS_OF = {"trace": "trace_s", "lower": "lower_s",
               "compile": "compile_s", "cache_read": "cache_read_s"}


@dataclasses.dataclass(slots=True)
class CompileDelta:
    """What jax did on a record's account (or the caller's, or the whole
    process's): sums that `add` grows an event at a time. Seconds are
    SELF seconds: an event's duration less the timed events that fired
    inside it (a jitted helper traced inside an outer trace, a constant's
    program run while tracing), so the four never count a second twice
    and fit inside the wall they fell in."""
    programs: int = 0           # modules lowered: each then read or compiled
    trace_s: float = 0.0        # Python to jaxpr: the package's own code
    lower_s: float = 0.0        # jaxpr to StableHLO
    compile_s: float = 0.0      # the backend's compiles
    cache_read_s: float = 0.0   # ... and its reads of the persistent cache
    cache_hits: int = 0
    cache_misses: int = 0       # compiled and written: the cache lacked it
    fun_names: tuple = ()       # the lowered programs, the first few

    def add(self, kind, name, seconds):
        field = _SECONDS_OF.get(kind)
        if field is not None:
            setattr(self, field, getattr(self, field) + seconds)
            if kind == "lower":
                self.programs += 1
                if name and len(self.fun_names) < NAMES_KEPT:
                    self.fun_names += (name,)
        elif kind == "cache_hit":
            self.cache_hits += 1
        else:
            self.cache_misses += 1


NO_COMPILE = CompileDelta()     # shared by every record that compiled nothing

# process-wide, one of each: the entries `(perf_counter stamp, kind,
# fun_name or None, self seconds or 1, the owner's engine or None)`, the
# process's first EVENTS_KEPT (a set-up lives there) and, once those are
# full, its newest EVENTS_KEPT, with the stamp of the first entry that fell
# between the two; the caller's sums (whatever fired outside every engine
# call) and everyone's; the timeline inside an engine call right now;
# timed events that a later, longer one may turn out to hold `(start,
# seconds)`; whether the cache answered the compile request in flight; the
# package's import intervals
_FIRST = []
_EVENTS = deque(maxlen=EVENTS_KEPT)
_DROPPED = [None]
_CALLER = CompileDelta()
_TOTALS = CompileDelta()
_OWNER = [None]
_NESTED = deque(maxlen=EVENTS_KEPT)
_HIT = [False]
_IMPORTS = []


def _program_name(fun_name):
    """`jit(planned_prefill)` (a module, as lowering and the backend name
    it) -> `planned_prefill` (the function, as tracing names it)."""
    if fun_name and fun_name[-1] == ")" and "(" in fun_name:
        return fun_name[fun_name.index("(") + 1:-1]
    return fun_name


def _on_lowered(event, duration=1, fun_name=None, **_):
    """THE `jax.monitoring` listener of this package, for plain events
    and durations both: one entry an event, charged to the engine call it
    fired in (`StepTimeline.begin` to `leave` / `end`, or a constructor's
    build record) or else to the caller."""
    kind = COMPILE_EVENTS.get(event)
    if kind is None:
        return
    now = time.perf_counter()
    name = _program_name(fun_name)
    seconds = duration
    if kind in _SECONDS_OF:
        # self seconds: what fired inside this event arrived before it
        start = now - duration
        nested = _NESTED
        while nested and nested[-1][0] >= start and \
                nested[-1][0] + nested[-1][1] <= now + 1e-3:
            seconds -= nested.pop()[1]
        nested.append((start, duration))
        seconds = max(seconds, 0.0)
        if kind == "compile":
            if _HIT[0]:
                kind = "cache_read"
            _HIT[0] = False
        elif kind == "lower":
            _HIT[0] = False     # a new program: its request is yet to come
    elif kind == "cache_hit":
        _HIT[0] = True
    owner = _OWNER[0]
    _TOTALS.add(kind, name, seconds)
    (_CALLER if owner is None else owner._owned()).add(kind, name, seconds)
    whose = None if owner is None else owner.engine
    newest = _EVENTS or _FIRST
    last = newest[-1] if newest else None
    if kind == "trace" and last is not None and last[1] == kind \
            and last[4] == whose:
        # jax reports every jitted helper traced inside an outer trace
        # (`add`, `_where`: nine events in ten) as an event of its own,
        # ending first: a run of traces is one entry, the last's
        newest[-1] = (now, kind, name or last[2], last[3] + seconds, whose)
    elif len(_FIRST) < EVENTS_KEPT:
        _FIRST.append((now, kind, name, seconds, whose))
    else:
        if _DROPPED[0] is None and len(_EVENTS) == EVENTS_KEPT:
            _DROPPED[0] = _EVENTS[0][0]
        _EVENTS.append((now, kind, name, seconds, whose))


def _entries():
    """The entries kept, oldest first."""
    return _FIRST + list(_EVENTS)


def note_import(t0):
    """One of the package's `__init__` files ran, from `t0` to now (jax's
    own import before `t0`). One that ran inside another counts once."""
    t1 = time.perf_counter()
    _IMPORTS[:] = [(a, b) for a, b in _IMPORTS if not (t0 <= a and b <= t1)]
    if not any(a <= t0 and t1 <= b for a, b in _IMPORTS):
        _IMPORTS.append((t0, t1))


def _listen():
    """Install the process's one collector hook and its one compile
    listener: the package's only site that listens to jax's monitoring,
    run at this module's import, which the package's own import ends
    with: before anything a user can compile through the package."""
    if _on_gc in gc.callbacks:
        return
    gc.callbacks.append(_on_gc)
    import jax
    # jax keeps plain events and durations on two lists: the one
    # listener goes on both
    jax.monitoring.register_event_listener(_on_lowered)
    jax.monitoring.register_event_duration_secs_listener(_on_lowered)


_PROFILE_STATE = []         # jax's profiler state, looked up once


def _profiler_on():
    """Is a `jax.profiler` trace being recorded? Starting one and, far
    more, stopping one (the trace is written out) holds the loop for
    seconds, and that is the profiler's doing, not a stall. jax keeps the
    session in a private place; where it moves, nothing is profiled as far
    as the timeline knows."""
    if not _PROFILE_STATE:
        try:
            from jax._src.profiler import _profile_state
        except Exception:  # noqa: BLE001
            _profile_state = None
        _PROFILE_STATE.append(_profile_state)
    return getattr(_PROFILE_STATE[0], "profile_session", None) is not None


class StepRecord(typing.NamedTuple):
    """One step of an engine, as its `StepTimeline` keeps it."""
    serial: int         # the timeline's count of steps, from 1 (build: 0)
    key: str            # the programs the step enqueued
    t_start: float      # perf_counter: the engine was entered
    t_end: float        # ... and the record closed
    wall: float         # what the rule judges: `outside` + the step
    outside: float      # the caller's seconds charged to the step
    phases: dict        # self seconds by span name, and "other"
    cpu_s: float        # thread CPU seconds over `wall`
    gc_s: float         # seconds inside garbage collections over `wall`
    compiled: bool      # jax traced, lowered or compiled on its account
    profiler: bool      # a profiler trace started or stopped during it
    rows: int           # rows or tokens the step accounted for
    starved: bool       # train: the device's queue was empty at its end
    verdict: str        # None, "slow", "compile", "profiler" or "build"
    excess: float       # a slow step's seconds over its key's typical
    compile: CompileDelta = NO_COMPILE  # what `compiled` stands for


class _KeySteps:
    """What the rule knows of one key: its last steps not judged slow,
    and their lengths sorted."""
    __slots__ = ("records", "walls", "slow_run")

    def __init__(self):
        self.records = deque()
        self.walls = []
        self.slow_run = 0


class StepTimeline:
    """The always-kept record of an engine's host loop (module docstring;
    docs/observability.md, "Slow steps").

    The engine calls `begin()` when a step enters it, names the programs
    it enqueues (`enqueued`), opens its phases as `span(name)`, and
    closes the record with `end()`. A serve step's record closes when
    `step()` returns and is charged the caller's time BEFORE it
    (`outside`: the previous record's end to this one's start). A train
    step's record stays open past the call's return (`leave()`) until
    the next entry, so it runs dispatch to dispatch and is charged the
    caller's time AFTER the call. Either way the records of a busy
    engine tile the timeline with no hole.

    The engine's CONSTRUCTOR is one record more, `with timeline.build():`
    around its whole body: key and verdict `build`, serial 0, its phases
    the spans opened inside, kept as `built` and at the head of
    `compiles` and nowhere else: never judged, no step of the ring.
    Whatever jax traces, lowers, compiles or reads between `begin()` and
    `leave()` / `end()`, or inside the build, is on the record's account
    (`StepRecord.compile`); anything else is the caller's.

    `counters` takes every span's seconds (`<name>_s`) and the slow-step
    sums; the serving engine hands in its `stats` dict. `tracer` (a
    `SpanTracer`, attached by `Telemetry.attach`) receives every span
    for the goodput account and the capture buffer."""

    def __init__(self, engine, counters=None):
        self.engine = engine        # "train" or "serve"
        self.counters = {} if counters is None else counters
        for name in ("slow_steps", "compile_steps", "profiler_steps",
                     "starved_steps"):     # the last: train records only
            self.counters.setdefault(name, 0)
        for name in ("slow_step_excess_s", "slow_excess_device_wait_s",
                     "slow_excess_gc_s", "slow_excess_host_s",
                     "slow_excess_outside_s", "gc_s"):
            self.counters.setdefault(name, 0.0)
        self.tracer = None
        self.mirror_annotations = False
        self.serial = 0
        self.open = False
        self.ring = deque(maxlen=STEP_RING)
        self.slow = deque(maxlen=SLOW_KEPT)
        # the build record and every record of verdict `compile`, whole:
        # the ring turns over inside one serving window
        self.compiles = deque(maxlen=COMPILES_KEPT)
        self.built = None
        # the open record's `CompileDelta`, made by its first event; the
        # timeline this one's call runs inside (an engine built or stepped
        # inside another's call), which gets the account back
        self._own = self._outer = None
        self._keys = {}
        self._stack = []
        self._phases = {}
        self._key = ""
        self._t_start = self._t_leave = self._t_end = None
        self._cover = self._cpu0 = self._gc0 = None
        self._profiler0 = False
        self._logged = {}       # kind of line -> [when, lines held back]
        _TIMELINES.append(self)

    # -- the engine's calls -------------------------------------------------

    def begin(self, busy=True):
        """A step enters the engine. `busy` False: the engine had nothing
        to do since the last record closed (an idle server), so the time
        since is nobody's stall and the record covers the step alone."""
        now = time.perf_counter()
        if not busy or self._t_end is None:
            self._cover, self._cpu0 = now, time.thread_time()
            self._gc0, self._profiler0 = _GC[0], _profiler_on()
        self._t_start, self._t_leave = now, None
        self._phases, self._key = {}, ""
        self.serial += 1
        self.open = True
        if _OWNER[0] is not self:       # compile events are this call's
            self._outer, _OWNER[0] = _OWNER[0], self

    def enqueued(self, program):
        """Name a program the open step enqueued, by the key the engine's
        own program cache uses."""
        self._key = f"{self._key} + {program}" if self._key else program

    def span(self, name):
        return _Span(self, name)

    def leave(self):
        """The train engine's call returns (or dies); the record stays
        open, and what compiles from here on is the caller's."""
        self._t_leave = time.perf_counter()
        if _OWNER[0] is self:
            _OWNER[0], self._outer = self._outer, None

    @contextlib.contextmanager
    def build(self):
        """The engine's constructor as one record (the class docstring)."""
        self.begin(busy=False)
        self.serial, self._key = 0, "build"     # serial 0: no step
        try:
            yield self
        finally:
            self.built = self._close()._replace(verdict="build")
            self.compiles.append(self.built)
            self._t_end = None      # the first step covers itself alone

    def _owned(self):
        """The open record's compile account (the listener's call)."""
        own = self._own
        if own is None:
            own = self._own = CompileDelta()
        return own

    def _close(self, rows=0, starved=False):
        now, cpu = time.perf_counter(), time.thread_time()
        gc_s, profiler = _GC[0], _profiler_on()
        if _OWNER[0] is self:
            _OWNER[0], self._outer = self._outer, None
        wall = now - self._cover
        inside = (self._t_leave or now) - self._t_start
        phases = self._phases
        phases["other"] = inside - sum(phases.values())
        own, self._own = self._own, None
        record = StepRecord(
            self.serial, self._key or "none", self._t_start, now, wall,
            wall - inside, phases, cpu - self._cpu0, gc_s - self._gc0,
            own is not None, profiler != self._profiler0, rows, starved,
            None, 0.0, NO_COMPILE if own is None else own)
        self._t_end = self._cover = now
        self._cpu0, self._gc0, self._profiler0 = cpu, gc_s, profiler
        self.open = False
        if record.gc_s and self.serial:     # the counters: steps only
            self.counters["gc_s"] += record.gc_s
        return record

    def end(self, rows=0, starved=False):
        """Close the open record and judge it. Returns the slow step's
        whole record (a dict, also kept in `slow`) or None."""
        record, slow = self._judge(self._close(rows, starved))
        if record.starved:
            self.counters["starved_steps"] += 1
        self.ring.append(record)
        return slow

    # -- spans (`_Span` calls these) -----------------------------------------

    def _on_span(self, name, t0, dur, step):
        stack = self._stack
        inner = stack.pop()
        if stack:
            stack[-1] += dur
        phases = self._phases
        phases[name] = phases.get(name, 0.0) + dur - inner
        if self.serial:             # a build's phases are no step's sums
            counters = self.counters
            name_s = name + "_s"
            counters[name_s] = counters.get(name_s, 0.0) + dur
        if self.tracer is not None:
            self.tracer.record(name, t0, dur, len(stack), step)

    # -- the rule -------------------------------------------------------------

    def _judge(self, record):
        steps = self._keys.get(record.key)
        if steps is None:
            steps = self._keys[record.key] = _KeySteps()
        if record.compiled or record.profiler:
            # the runtime being set up or observed, not a steady step:
            # never slow, never part of the key's typical step, and the
            # device's queue running dry meanwhile is no one's starving
            verdict = "compile" if record.compiled else "profiler"
            self.counters[verdict + "_steps"] += 1
            record = record._replace(verdict=verdict, starved=False)
            if record.compiled:
                self.compiles.append(record)
                self._log_compile(record)
            return record, None
        walls = steps.walls
        n = len(walls)
        if n >= MIN_STEPS:
            typical = (walls[(n - 1) >> 1] + walls[n >> 1]) / 2
            excess = record.wall - typical
            if excess > SLOW_FLOOR_S:
                deviation = statistics.median(
                    abs(w - typical) for w in walls)
                if excess > SLOW_DEVIATIONS * deviation:
                    record = record._replace(verdict="slow", excess=excess)
                    slow = self._slow(record, steps, typical, deviation)
                    steps.slow_run += 1
                    if steps.slow_run >= RELEVEL_AFTER:
                        self._keys[record.key] = _KeySteps()
                    return record, slow
        steps.slow_run = 0
        steps.records.append(record)
        bisect.insort(walls, record.wall)
        if n >= TYPICAL_STEPS:
            del walls[bisect.bisect_left(walls,
                                         steps.records.popleft().wall)]
        return record, None

    def _slow(self, record, steps, typical, deviation):
        """Split a slow step's excess over what held it. Each part is
        the excess of one quantity over its own typical for the key, and
        the parts are paid out of the step's excess in this order:
        `device_wait` (the thread slept in the runtime: no Python ran),
        `gc` (collections), `host` (thread CPU time, less `gc`: the
        program's own Python), then the other phases, `other` (the
        step's time under no span) and `outside` (the caller), largest
        first: what is left of a phase's excess there is time its thread
        spent off the CPU. `unattributed` is the rest."""
        usual = steps.records

        def over(value, get):
            return max(value - statistics.median(map(get, usual)), 0.0)

        raw = {name: over(seconds, lambda r, n=name: r.phases.get(n, 0.0))
               for name, seconds in record.phases.items()}
        raw["outside"] = over(record.outside, lambda r: r.outside)
        gc_s = over(record.gc_s, lambda r: r.gc_s)
        first = {"device_wait": raw.pop("device_wait", 0.0), "gc": gc_s,
                 "host": max(over(record.cpu_s, lambda r: r.cpu_s) - gc_s,
                             0.0)}
        held, left = {}, record.excess
        for name, seconds in list(first.items()) + sorted(
                raw.items(), key=lambda kv: -kv[1]):
            seconds = min(seconds, left)
            if seconds > 0.0:
                held[name] = seconds
                left -= seconds
        if left > 0.0:
            held["unattributed"] = left

        counters = self.counters
        counters["slow_steps"] += 1
        counters["slow_step_excess_s"] += record.excess
        for name in ("device_wait", "gc", "host", "outside"):
            counters[f"slow_excess_{name}_s"] += held.get(name, 0.0)
        slow = {"engine": self.engine, **record._asdict(),
                "compile": dataclasses.asdict(record.compile),
                "typical_s": typical, "deviation_s": deviation,
                "held_by": held, "clock": clock_pair()}
        self.slow.append(slow)
        self._log(slow)
        return slow

    def _may_log(self, kind, now):
        """At most one line of a kind every SLOW_LOG_INTERVAL_S. Returns
        None to hold this one back, else what the line ends with: how
        many were held back since the last."""
        at = self._logged.setdefault(kind, [None, 0])
        if at[0] is not None and now - at[0] < SLOW_LOG_INTERVAL_S:
            at[1] += 1
            return None
        more = f" ({at[1]} more {kind} steps since the last line)" \
            if at[1] else ""
        at[:] = now, 0
        return more

    def _log(self, slow):
        """One line a slow step; the next line says how many were held
        back."""
        more = self._may_log("slow", slow["t_end"])
        if more is None:
            return
        held = ", ".join(f"{s * 1e3:,.1f} in {name}"
                         for name, s in slow["held_by"].items()
                         if s >= 5e-5)
        gc_ms = slow["gc_s"] * 1e3
        logger.warning(
            f"{self.engine} step {slow['serial']} ({slow['key']}) "
            f"{slow['wall'] * 1e3:,.1f} ms, typical "
            f"{slow['typical_s'] * 1e3:,.1f}: {held}, CPU "
            f"{slow['cpu_s'] * 1e3:,.1f} ms, "
            + (f"collections {gc_ms:,.1f} ms" if gc_ms
               else "no collection") + ", no compile"
            + (", the device's queue ran dry" if slow["starved"]
               else "") + more)

    def _log_compile(self, record):
        """One line a step that compiled once the engine is steady (its
        first COMPILE_LOG_AFTER steps that were neither compile, profiler
        nor slow behind it): which step, which program, and whether the
        persistent cache had it. A set-up's compiles are not news."""
        counters = self.counters
        steady = self.serial - counters["compile_steps"] - \
            counters["profiler_steps"] - counters["slow_steps"]
        if steady < COMPILE_LOG_AFTER:
            return
        more = self._may_log("compile", record.t_end)
        if more is None:
            return
        c = record.compile
        cache = "no persistent cache"
        if c.cache_hits or c.cache_misses:
            cache = "cache miss" if not c.cache_hits else "cache hit" \
                if not c.cache_misses else \
                f"{c.cache_hits} cache hits, {c.cache_misses} misses"
        logger.warning(
            f"{self.engine} step {record.serial:,} ({record.key}) "
            f"compiled: {', '.join(c.fun_names) or 'no new program'} "
            f"traced {c.trace_s:.2f} s, lowered {c.lower_s:.2f} s, "
            f"compiled {c.compile_s:.2f} s"
            + (f", read {c.cache_read_s:.2f} s" if c.cache_hits else "")
            + f" ({cache})" + more)

    # -- the report -----------------------------------------------------------

    def report(self, last=None):
        """Counters over the newest `last` closed records (all the ring
        holds by default), with the slow ones whole."""
        records = list(self.ring)
        if last is not None:
            records = records[-last:] if last > 0 else []
        first = records[0].serial if records else self.serial + 1
        slow = [s for s in self.slow if s["serial"] >= first]
        held = {}
        for s in slow:
            for name, seconds in s["held_by"].items():
                held[name] = held.get(name, 0.0) + seconds
        return {"engine": self.engine, "serial": self.serial,
                "steps": len(records),
                "wall_s": sum(r.wall for r in records),
                "slow_steps": sum(r.verdict == "slow" for r in records),
                "slow_step_excess_s": sum(r.excess for r in records),
                "slow_excess_s": held,
                "compile_steps": sum(r.verdict == "compile"
                                     for r in records),
                "profiler_steps": sum(r.verdict == "profiler"
                                      for r in records),
                "starved_steps": sum(r.starved for r in records),
                "gc_s": sum(r.gc_s for r in records),
                "slow": slow}

    def setup(self, until=None):
        """This engine's part of `setup_report`: its build record and
        every record that compiled, of those that closed (train: whose
        call returned) at or before `until`. None for a timeline that
        holds neither."""
        build, programs = None, []
        for r in self.compiles:
            inside = r.wall - r.outside
            # a serve record's `outside` lies before its start, a train
            # record's after its call: either way the call ended here
            if until is not None and r.t_start + inside > until:
                continue
            c = r.compile
            entry = {"key": r.key, "serial": r.serial, "t_start": r.t_start,
                     "wall_s": r.wall, "outside_s": r.outside,
                     **dataclasses.asdict(c)}
            if r.verdict == "build":
                build = dict(entry, phases=dict(r.phases))
                continue
            # what the call cost the host beyond compiling: loading the
            # executable, its transfers, the step itself
            entry["first_call_s"] = inside - c.trace_s - c.lower_s \
                - c.compile_s - c.cache_read_s
            programs.append(entry)
        if build is None and not programs:
            return None
        return {"engine": self.engine, "build": build, "programs": programs}


def step_report(last=None):
    """The step timelines of this process's engines (the newest 16,
    whether or not the engine still lives), process-wide as
    `ops.dispatch_report()` is: `{"clock": clock_pair(), "timelines":
    [StepTimeline.report(last), ...]}`, train engines first."""
    return {"clock": clock_pair(),
            "timelines": sorted((t.report(last) for t in list(_TIMELINES)),
                                key=lambda r: r["engine"] != "train")}


def setup_report(until=None):
    """Where a process's set-up went, process-wide beside `step_report()`:

        {"clock": clock_pair(),
         "import_s": seconds inside the package's own `__init__` files,
         "engines": [{"engine", "build": {"wall_s", "phases", the compile
                      delta's fields, ...}, "programs": [one entry a
                      record that compiled: "key", "serial", "t_start",
                      "wall_s", "outside_s", the delta, "first_call_s"]}],
         "caller": {the delta's sums of whatever compiled outside every
                    engine call, "programs_by_name": the ten dearest
                    of the entries kept},
         "totals": {the whole process's: the engines' records, the
                    caller's and what a still open record holds},
         "complete": bool}

    `until` (a `perf_counter` reading; None: everything) leaves out every
    entry stamped after it: a benchmark cuts the account where its window
    opens, so that a reference compiled after the window is no set-up.
    The caller's sums and the totals at `until` are added up from the
    entries kept: the process's first `EVENTS_KEPT` and its newest (a run
    of trace events is one entry). They are exact while `until` lies
    before the first entry that fell between the two (a set-up of fewer
    than 4,096 entries, whatever compiles after it); `complete` False
    says it does not, and they are then too small.
    The engines' parts come from their records and are exact either way.
    docs/observability.md, "Set-up"."""
    engines = [e for e in (t.setup(until) for t in sorted(
        list(_TIMELINES), key=lambda t: t.engine != "train")) if e]
    cut = until is not None
    caller, totals = (CompileDelta(), CompileDelta()) if cut \
        else (_CALLER, _TOTALS)
    by_name = {}
    for stamp, kind, name, seconds, owner in _entries():
        if cut and stamp > until:
            continue
        if cut:
            totals.add(kind, name, seconds)
        if owner is None:
            if cut:
                caller.add(kind, name, seconds)
            if name and kind in _SECONDS_OF:
                by_name[name] = by_name.get(name, 0.0) + seconds
    dearest = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    caller, totals = dataclasses.asdict(caller), dataclasses.asdict(totals)
    del caller["fun_names"], totals["fun_names"]
    caller["programs_by_name"] = [
        {"fun_name": name, "seconds": seconds}
        for name, seconds in dearest if seconds > 0.0]
    return {"clock": clock_pair(),
            "import_s": sum(b - a if until is None
                            else max(min(b, until) - a, 0.0)
                            for a, b in _IMPORTS),
            "engines": engines, "caller": caller, "totals": totals,
            "complete": until is None or _DROPPED[0] is None
            or until < _DROPPED[0]}


class _NullTelemetry:
    """The absent-config telemetry object: every hook is a no-op and
    `span()` hands back one shared do-nothing context manager."""

    enabled = False
    wants_flops = False
    spans_enabled = False
    fleet = None

    def span(self, name):  # noqa: ARG002
        return _NULL_SPAN

    def step_annotation(self, step):  # noqa: ARG002
        return _NULL_SPAN

    def on_step_start(self, step):  # noqa: ARG002
        pass

    def on_step_end(self, engine, verdict="ok", flops=None, steps=1,
                    tokens=None, offload=None):
        pass

    def on_anomaly(self, engine, kind, step=None):
        pass

    def register_compiled(self, key, flops):
        pass

    def close(self):
        pass


NULL_TELEMETRY = _NullTelemetry()


class Telemetry:
    """Config-driven engine telemetry (the ``"telemetry"`` JSON block).

    Constructed by the engine AFTER the monitor; emits scalars through
    `monitor.record` keyed by the engine's global sample count, so
    goodput/MFU/memory series line up with the loss series."""

    enabled = True

    def __init__(self, monitor=None, devices=None, goodput=True, mfu=True,
                 spans=True, trace_dir=None, capture=None,
                 memory_watermark_interval_steps=0,
                 capture_on_anomaly=False, anomaly_capture_steps=1,
                 fleet=None):
        self.monitor = monitor
        self.devices = list(devices or [])
        self.goodput_enabled = bool(goodput)
        self.mfu_enabled = bool(mfu)
        self.spans_enabled = bool(spans)
        self.trace_dir = trace_dir
        self.capture_start_step = None
        self.capture_num_steps = 0
        if capture:
            self.capture_start_step = int(capture["start_step"])
            self.capture_num_steps = int(capture["num_steps"])
        self.memory_watermark_interval = int(memory_watermark_interval_steps)
        self.capture_on_anomaly = bool(capture_on_anomaly)
        self.anomaly_capture_steps = int(anomaly_capture_steps)

        self.tracer = SpanTracer(mirror_annotations=self.spans_enabled)
        self.timeline = None        # the engine's, once it `attach`es
        self.goodput = GoodputMeter()
        self.compiled_flops = {}    # step-variant key -> per-device flops

        # fleet observability (runtime/fleet.py; the telemetry.fleet
        # sub-block): cross-host scalar aggregation, merged Perfetto
        # capture, collective-skew straggler probe. None when absent —
        # the per-host path is unchanged.
        from .fleet import build_fleet
        self.fleet = build_fleet(fleet)

        self._step_t0 = None
        self._steps_seen = 0
        self._last_ckpt_stall = None
        self._peak_flops = None
        # packed-batch effective-token accounting (runtime/packing.py):
        # cumulative (non-pad, non-cross-document) vs possible targets
        self._tokens_effective = 0
        self._tokens_total = 0

        # capture-window state. `started_jax` lives in a dict shared
        # with a weakref.finalize below: a Telemetry collected mid-window
        # (bench ladders delete failed engines and retry) must still stop
        # the jax trace and release the process-wide flag — the atexit
        # hook alone no-ops once the object is gone.
        self._window_open = False
        self._window_tag = None
        self._window_steps_left = 0
        self._wstate = {"started_jax": False}
        self._finalizer = weakref.finalize(self, _release_orphaned_trace,
                                           self._wstate)
        self._scheduled_done = False
        self._armed = []            # pending (tag, num_steps) requests

        # anomaly episode state
        self._anomaly_episode = False
        self.anomaly_captures = 0
        self.exported_traces = []   # chrome-trace JSON paths written

        # flush an open capture window at interpreter exit: a run that
        # ends (or dies) mid-window must still stop the jax trace and
        # export the collected spans — and release the process-wide
        # active-trace flag for any later engine. Weakly held, like the
        # monitor's and checkpoint manager's hooks.
        from .utils import register_weak_atexit
        self._atexit = register_weak_atexit(self, "close")

    # ------------------------------------------------------------------
    # spans
    # ------------------------------------------------------------------

    def attach(self, timeline):
        """Join the engine's `StepTimeline`: from here on a span writes
        into the open step record first, carries its serial, and reaches
        the tracer (goodput account, capture buffer) through it."""
        self.timeline = timeline
        timeline.tracer = self.tracer
        timeline.mirror_annotations = self.tracer.mirror_annotations

    def span(self, name):
        # goodput keeps phase timing alive even with spans off: the
        # data_wait / ckpt-stall buckets are fed by these spans, and
        # `spans: false` must not silently blind the meter. What
        # spans: false DOES turn off: the jax.profiler annotation
        # mirroring (tracer.mirror_annotations) and span capture/export
        # (_open_window skips start_capture).
        if self.timeline is not None:
            return self.timeline.span(name)
        if not (self.spans_enabled or self.goodput_enabled
                or self.fleet is not None):
            return _NULL_SPAN
        return self.tracer.span(name)

    def step_annotation(self, step):
        """`jax.profiler.StepTraceAnnotation` around the train-step
        dispatch: device timelines group kernels by train step."""
        if not self.spans_enabled:
            return _NULL_SPAN
        import jax
        return jax.profiler.StepTraceAnnotation("train",
                                                step_num=int(step))

    # ------------------------------------------------------------------
    # MFU
    # ------------------------------------------------------------------

    @property
    def wants_flops(self):
        return self.mfu_enabled

    def register_compiled(self, key, flops):
        """Record a step variant's per-device program flops (harvested
        once from `compiled.cost_analysis()` at compile time)."""
        if flops:
            self.compiled_flops[key] = float(flops)
            log_dist(f"telemetry: step variant {key} costs "
                     f"{flops / 1e9:.2f} GFLOPs/device per call",
                     ranks=[0])

    def _peak(self):
        """bf16 peak FLOPS of this engine's device, or None when its
        kind has no row in `profiling.hardware` (the CPU, say): the MFU
        scalars are then left out — said once in the log — and never
        priced against some other chip's peak."""
        if self._peak_flops is None:
            from ..profiling.hardware import peak_flops_per_chip
            try:
                self._peak_flops = peak_flops_per_chip(
                    self.devices[0] if self.devices else None)
            except ValueError as e:
                logger.warning(f"telemetry: MFU scalars left out — {e}")
                self._peak_flops = 0.0
        return self._peak_flops or None

    # ------------------------------------------------------------------
    # step hooks
    # ------------------------------------------------------------------

    def on_step_start(self, step):
        self._step_t0 = time.perf_counter()
        # scheduled window: arm once when the step counter reaches it
        if (self.capture_start_step is not None
                and not self._scheduled_done
                and step >= self.capture_start_step):
            self._scheduled_done = True
            self._armed.append((f"step{step}", self.capture_num_steps))
        if self._armed and not self._window_open:
            tag, n_steps = self._armed.pop(0)
            self._open_window(tag, n_steps)

    def on_step_end(self, engine, verdict="ok", flops=None, steps=1,
                    tokens=None, offload=None):
        """Close one step window: goodput accounting, MFU/memory
        scalars, capture-window bookkeeping. `steps` > 1 for fused
        `train_steps` windows (one call covers n optimizer steps).

        `offload` = the tiered-offload runner's per-step counters
        ({prefetch_stall_s, bytes_h2d, bytes_d2h, ...}): emitted as
        `Train/Offload/*` scalars so the streaming tier's wire traffic
        and residual prefetch stalls sit next to the goodput series
        (the stall seconds are ALSO in the param_wait bucket via the
        param_gather span — this scalar is the per-step ms view).

        `tokens` = (effective, total) target counts for packed ragged
        batches (`runtime.packing.packed_batch_token_stats`): raw
        throughput/MFU count pad tokens and cross-document positions as
        productive work, so packing wins would be invisible — these
        emit effective-tokens/s and effective-MFU next to the raw
        scalars, plus the running effective-token fraction."""
        t1 = time.perf_counter()
        dt = (t1 - self._step_t0) if self._step_t0 is not None else 0.0
        self._step_t0 = None
        self._steps_seen += steps
        phases = self.tracer.drain_phases()

        scalars = {}
        data_wait = phases.get("data_fetch", 0.0)
        param_wait = phases.get("param_gather", 0.0)
        ckpt_delta = 0.0
        if self.goodput_enabled or self.fleet is not None:
            # checkpoint stall is shared by the goodput meter and the
            # fleet window summaries: read it once per step
            manager = getattr(engine, "checkpoint_manager", None)
            stall = getattr(manager, "total_stall_s", 0.0)
            if self._last_ckpt_stall is None:
                self._last_ckpt_stall = stall
            ckpt_delta = max(stall - self._last_ckpt_stall, 0.0)
            self._last_ckpt_stall = stall
        if self.goodput_enabled:
            self.goodput.account(dt, verdict,
                                 data_wait=data_wait,
                                 param_wait=param_wait,
                                 ckpt_stall=ckpt_delta)
            scalars.update(self.goodput.scalars())
            if self.timeline is not None:
                # seconds slow steps ran over their typical (one step
                # late: a train record closes at the next entry)
                scalars["Train/Goodput/slow_step_s"] = \
                    self.timeline.counters["slow_step_excess_s"]
        if self.fleet is not None:
            scalars.update(self.fleet.on_step_end(
                dt, data_wait_s=data_wait, ckpt_stall_s=ckpt_delta,
                steps=steps))

        if self.mfu_enabled and flops and dt > 0:
            achieved = flops / dt          # per-device FLOPS/s
            scalars["Train/Samples/achieved_tflops"] = achieved / 1e12
            if self._peak():
                scalars["Train/Samples/mfu"] = achieved / self._peak()

        if tokens is not None and dt > 0:
            eff, total = tokens
            self._tokens_effective += int(eff)
            self._tokens_total += int(total)
            scalars["Train/Samples/tokens_per_sec"] = total / dt
            scalars["Train/Samples/effective_tokens_per_sec"] = eff / dt
            if self._tokens_total:
                scalars["Train/Goodput/effective_token_fraction"] = (
                    self._tokens_effective / self._tokens_total)
            if self.mfu_enabled and flops and total and self._peak():
                # MFU counting only loss-bearing tokens as productive:
                # the raw scalar times flops the kernels BURNED; this
                # one credits only the fraction the loss consumed
                scalars["Train/Samples/effective_mfu"] = (
                    flops / dt / self._peak()) * (eff / total)

        if offload is not None:
            scalars["Train/Offload/prefetch_stall_ms"] = \
                offload.get("prefetch_stall_s", 0.0) * 1e3
            scalars["Train/Offload/bytes_h2d"] = \
                offload.get("bytes_h2d", 0)
            scalars["Train/Offload/bytes_d2h"] = \
                offload.get("bytes_d2h", 0)

        if (self.memory_watermark_interval > 0
                and self._steps_seen % self.memory_watermark_interval < steps):
            scalars.update(self._memory_scalars())

        if scalars and self.monitor is not None:
            self.monitor.record(getattr(engine, "global_samples", 0),
                                scalars)

        if verdict == "ok":
            self._anomaly_episode = False

        if self._window_open:
            self._window_steps_left -= steps
            if self._window_steps_left <= 0:
                self._close_window()

    # ------------------------------------------------------------------
    # anomaly hook (sentinel escalation path + hang watchdog)
    # ------------------------------------------------------------------

    def on_anomaly(self, engine, kind, step=None):
        """Called by the sentinel when a step is flagged (and by the
        hang watchdog on expiry): snapshot device memory NOW and arm a
        trace window over the next step(s). Fires at most once per
        anomaly episode — a run of consecutive anomalous steps produces
        one capture, and the episode re-arms after the next healthy
        step."""
        if not self.capture_on_anomaly or self._anomaly_episode:
            return
        self._anomaly_episode = True
        self.anomaly_captures += 1
        step = step if step is not None else \
            getattr(engine, "global_steps", 0)
        tag = f"anomaly_{kind}_step{step}"
        self.write_memory_snapshot(tag)
        # trace the FOLLOWING step(s): the flagged step already ran
        self._armed.append((tag, self.anomaly_capture_steps))
        log_dist(f"telemetry: anomaly ({kind}) at step {step} — memory "
                 f"snapshot written, trace armed for the next "
                 f"{self.anomaly_capture_steps} step(s)", ranks=[0])

    # ------------------------------------------------------------------
    # capture windows
    # ------------------------------------------------------------------

    def _open_window(self, tag, n_steps):
        global _TRACE_ACTIVE
        self._window_open = True
        self._window_tag = tag
        self._window_steps_left = max(int(n_steps), 1)
        if self.spans_enabled:
            # spans: false turns span capture/export off entirely — the
            # window still drives the jax profiler trace below
            self.tracer.start_capture()
        self._wstate["started_jax"] = False
        if self.trace_dir:
            with _TRACE_LOCK:
                if not _TRACE_ACTIVE:
                    try:
                        import jax
                        jax.profiler.start_trace(self.trace_dir)
                        _TRACE_ACTIVE = True
                        self._wstate["started_jax"] = True
                    except Exception as e:  # noqa: BLE001
                        logger.warning(
                            f"telemetry: jax profiler trace failed to "
                            f"start ({e}); host spans still captured")

    def _close_window(self):
        global _TRACE_ACTIVE
        events = self.tracer.stop_capture()
        tag = self._window_tag
        self._window_open = False
        self._window_tag = None
        if self._wstate["started_jax"]:
            with _TRACE_LOCK:
                try:
                    import jax
                    jax.profiler.stop_trace()
                except Exception as e:  # noqa: BLE001
                    logger.warning(f"telemetry: stop_trace failed ({e})")
                _TRACE_ACTIVE = False
                self._wstate["started_jax"] = False
        if self.trace_dir and events:
            try:
                import jax
                pid = jax.process_index()
            except Exception:  # noqa: BLE001
                pid = 0
            path = os.path.join(self.trace_dir, f"spans_{tag}.json")
            # the capture artifact carries the kernel dispatch report:
            # WHICH flash/decode geometry produced these timings is as
            # load-bearing as the timings themselves
            from .fleet import _safe_dispatch_report
            self.exported_traces.append(
                SpanTracer.export_chrome_trace(
                    events, path, pid=pid,
                    metadata={"host": pid,
                              "dispatch": _safe_dispatch_report()}))
            log_dist(f"telemetry: capture window '{tag}' closed — "
                     f"{len(events)} host spans -> {path}", ranks=[0])
        if self.fleet is not None and self.trace_dir:
            # cross-host merge: every host ships its (bounded) events;
            # rank 0 collects one lane per host into a single Perfetto
            # trace next to the per-host exports
            self.fleet.ship_capture(tag, events)
            merged = self.fleet.merged_trace(tag, self.trace_dir)
            if merged:
                self.exported_traces.append(merged)

    # ------------------------------------------------------------------
    # memory
    # ------------------------------------------------------------------

    def _memory_scalars(self):
        """HBM watermark scalars from the first local device (watermarks
        are per-chip and SPMD keeps chips symmetric)."""
        stats = self._device_memory_stats()
        first = next(iter(stats.values()), None) or {}
        out = {}
        if "bytes_in_use" in first:
            out["Train/Memory/hbm_bytes_in_use"] = first["bytes_in_use"]
        if "peak_bytes_in_use" in first:
            out["Train/Memory/hbm_peak_bytes"] = \
                first["peak_bytes_in_use"]
        return out

    def _device_memory_stats(self):
        out = {}
        for dev in self.devices:
            try:
                out[str(dev)] = dev.memory_stats() or {}
            except Exception:  # noqa: BLE001 - backends without stats
                out[str(dev)] = {}
        return out

    def write_memory_snapshot(self, tag):
        """Per-device `memory_stats` JSON under the trace dir (the
        anomaly hook's 'what was HBM doing' artifact). Thread-safe: the
        hang watchdog calls this from its own thread."""
        if not self.trace_dir:
            return None
        path = os.path.join(self.trace_dir, f"memory_{tag}.json")
        os.makedirs(self.trace_dir, exist_ok=True)
        # true epoch timestamp: snapshot files are correlated with logs
        # and other hosts' artifacts offline
        payload = {"tag": tag, "time": time.time(),  # dslint: disable=wall-clock
                   "devices": self._device_memory_stats()}
        with open(path, "w") as f:
            json.dump(payload, f, indent=2, default=str)
        return path

    # ------------------------------------------------------------------
    # shutdown
    # ------------------------------------------------------------------

    def close(self):
        """Flush an open capture window (export what was collected) and
        detach the atexit hook. Idempotent."""
        if self._window_open:
            self._close_window()
        try:
            import atexit
            atexit.unregister(self._atexit)
        except Exception:  # pragma: no cover
            pass


def build_telemetry(config_dict, monitor=None, devices=None):
    """Telemetry (or NULL_TELEMETRY) from a parsed telemetry config
    dict (`DeepSpeedConfig.telemetry_config`)."""
    if not config_dict or not config_dict.get("enabled"):
        return NULL_TELEMETRY
    kwargs = {k: v for k, v in config_dict.items() if k != "enabled"}
    return Telemetry(monitor=monitor, devices=devices, **kwargs)


_listen()
