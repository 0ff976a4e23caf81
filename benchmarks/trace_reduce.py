"""From a profiler trace (`*.xplane.pb`) to busy and idle time, op groups
and attributed idle gaps. The only reader of traces in the benchmark.

What a TPU trace holds (looked at by hand, PR 23): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Ops` has one event per HLO operation
that ran on the TensorCore, nested where an operation contains others (a
`while` holds its body's operations), and whose line `Async XLA Ops` has
one event per transfer in flight, from its `-start` to its `-done`; and
the plane `/host:CPU` with one line per host thread, where
`jax.profiler.TraceAnnotation` spans and jax's own runtime spans lie on
the thread that opened them. All planes share one clock. A device
event's name is the whole HLO instruction as text,
`%fusion.12 = bf16[8,128]{...} fusion(...), kind=kLoop, ...`: the
instruction's name, its result's shape and its opcode are parsed from it.

- busy: the union of the op intervals of a device, clipped to the window;
  idle share is 1 - busy / window. The window is the host span named
  `WINDOW_SPAN` where the trace has one, else the extent of the device's
  own events.
- an operation's time is its self time: its interval minus what its
  children cover, so a `while` does not count its body twice.
- collectives are recognised by opcode or instruction name
  (`all-gather`, `all-reduce`, `reduce-scatter`, `collective-permute`,
  `all-to-all`, their `-start` / `-done` halves, and the TPU compiler's
  `async-collective-start` / `-done` fusions). On the TensorCore's op line operations do not overlap, so the
  time inside a collective operation is time in which nothing else ran:
  it is all exposed. What the compiler hid shows only as a transfer in
  flight (the async line, or a `-start` to its `-done`), which
  `collective_time` adds.
- Mosaic (Pallas) kernels are the custom calls whose target is
  `tpu_custom_call`. The trace gives them no stable kernel name today
  (`%closed_call.9`, `%checkpoint.88`: PERF.md, list for the tracing
  issue), so they are one group.
- operations are grouped for the breakdown by opcode, instruction name
  without its number, and result shape: the shape is what tells the CE
  head's fusions from a block's while there are no named scopes.
- an idle gap is attributed to the innermost span open on the window's
  host thread when the gap began.
"""

import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = re.compile(r"^XLA Ops$")
ASYNC_LINE = re.compile(r"^Async XLA Ops$")
HOST_PLANE = re.compile(r"^/host:CPU$")
WINDOW_SPAN = "bench.traced"
COLLECTIVE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|async-collective)")
MOSAIC = "tpu_custom_call"
_NUMBERED = re.compile(r"[.\d]+$")
_HLO = re.compile(r"^%(?P<inst>\S+) = (?P<rest>.*)$", re.S)
_OPCODE = re.compile(r"(?:^|[ )}\]])(?P<op>[a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
MIN_GAP_S = 5e-6


def _profile(path):
    """The profiler's data from an `.xplane.pb` file, or one packed with
    xz (the recorded trace in `testdata/`)."""
    from jax.profiler import ProfileData
    if path.endswith(".xz"):
        import lzma
        with lzma.open(path) as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def load(path):
    """[(plane name, [(line name, [(name, start_s, end_s)])])]"""
    planes = []
    for plane in _profile(path).planes:
        lines = []
        for line in plane.lines:
            events = [(e.name, e.start_ns * 1e-9,
                       (e.start_ns + e.duration_ns) * 1e-9)
                      for e in line.events]
            lines.append((line.name, events))
        planes.append((plane.name, lines))
    return planes


def union(intervals):
    """Sorted, disjoint [[start, end]] covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def measure(intervals):
    return float(sum(e - s for s, e in intervals))


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """Points of the disjoint sorted `a` not in the disjoint sorted `b`."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def self_times(events):
    """[(name, start, end, self seconds)] for nested events of one line:
    an event's self time is its length minus its children's."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out, stack = [], []        # stack of [name, start, end, child seconds]

    def close(upto):
        while stack and stack[-1][2] <= upto:
            name, s, e, child = stack.pop()
            out.append((name, s, e, max(e - s - child, 0.0)))
            if stack:
                stack[-1][3] += e - s
    for name, s, e in order:
        close(s)
        stack.append([name, s, e, 0.0])
    close(float("inf"))
    return out


def parse_op(name):
    """(instruction name, opcode, first result shape) of a device event's
    name; a name that is not HLO text is its own instruction."""
    m = _HLO.match(name)
    if not m:
        return name, "", ""
    rest = m.group("rest")
    op = _OPCODE.search(rest)
    shape = _SHAPE.search(rest)
    return (m.group("inst"), op.group("op") if op else "",
            shape.group(0) if shape and (not op or shape.start() < op.start())
            else "")


def is_collective(name):
    inst, op, _ = parse_op(name)
    return bool(COLLECTIVE.search(op) or COLLECTIVE.search(inst))


def group_name(name):
    """`%fusion.123 = bf16[8,128]{..} fusion(..)` -> `fusion fusion
    bf16[8,128]`: operations of one kind and shape count together."""
    inst, op, shape = parse_op(name)
    inst = _NUMBERED.sub("", inst) or inst
    return " ".join(p for p in (op, inst if inst != op else "", shape) if p)


def _host_thread(planes):
    """(window, spans) of the host thread that opened `WINDOW_SPAN`;
    spans are that thread's events other than the Python tracer's."""
    for pname, lines in planes:
        if not HOST_PLANE.match(pname):
            continue
        for _, events in lines:
            window = [(s, e) for n, s, e in events if n == WINDOW_SPAN]
            if window:
                spans = [(n, s, e) for n, s, e in events
                         if not n.startswith("$") and n != WINDOW_SPAN]
                return window[0], spans
    return None, []


def _open_span(spans, t):
    """Name of the innermost span open at time `t` (the shortest one
    that contains it)."""
    best = None
    for n, s, e in spans:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (n, e - s)
    return best[0] if best else "(no span open)"


def reduce_planes(planes):
    window, spans = _host_thread(planes)
    devices = []
    for pname, lines in planes:
        m = DEVICE_PLANE.match(pname)
        if not m:
            continue
        events = [ev for lname, evs in lines if OP_LINE.match(lname)
                  for ev in evs if ev[2] > ev[1]]
        if not events:
            continue
        lo, hi = window if window else (min(e[1] for e in events),
                                        max(e[2] for e in events))
        events = [(n, max(s, lo), min(e, hi)) for n, s, e in events
                  if min(e, hi) > max(s, lo)]
        busy = union([[s, e] for _, s, e in events])
        selfs = self_times(events)
        ops = {}
        for n, s, e, t in selfs:
            ops[group_name(n)] = ops.get(group_name(n), 0.0) + t
        collective = {n: is_collective(n) for n in {ev[0] for ev in selfs}}
        coll = [ev for ev in selfs if collective[ev[0]]]
        leaves_other = union([[s, e] for n, s, e, t in selfs
                              if t >= 0.999 * (e - s) and not collective[n]])
        coll_iv = union([[s, e] for _, s, e, _ in coll])
        # in flight: the async line's spans, or a `-start` to the `-done`
        # of the same number on the op line
        spans_async = [[max(s, lo), min(e, hi)] for lname, evs in lines
                       if ASYNC_LINE.match(lname)
                       for n, s, e in evs if is_collective(n)]
        starts = {}
        for n, s, e, _ in sorted(coll, key=lambda ev: ev[1]):
            inst = parse_op(n)[0]
            key = inst.replace("-start", "").replace("-done", "")
            if "-start" in inst:
                starts[key] = s
            elif "-done" in inst and key in starts:
                spans_async.append([starts.pop(key), e])
        gaps = subtract([[lo, hi]], busy)
        devices.append({
            "id": m.group(1),
            "window_s": hi - lo, "busy_s": measure(busy),
            "collective_s": measure(union(coll_iv + spans_async)),
            "collective_exposed_s": measure(subtract(coll_iv, leaves_other)),
            "mosaic_s": sum(t for n, _, _, t in selfs if MOSAIC in n),
            "ops": ops,
            "gaps": [(s, e - s) for s, e in gaps if e - s >= MIN_GAP_S]})
    return {"devices": devices, "host_spans": spans,
            "window_found": window is not None}


def summarize(reduced):
    """Means over the devices, the worst device, and the two lists of the
    contract's `breakdown`."""
    devs = reduced["devices"]
    if not devs:
        return None
    mean = lambda key: float(np.mean([d[key] for d in devs]))  # noqa: E731
    window = mean("window_s")
    ops = {}
    for d in devs:
        for n, t in d["ops"].items():
            ops[n] = ops.get(n, 0.0) + t / len(devs)
    by_span = {}
    for d in devs:
        for start, dur in d["gaps"]:
            n = _open_span(reduced["host_spans"], start)
            by_span[n] = by_span.get(n, 0.0) + dur / len(devs)
    top = lambda d: [[n, t] for n, t in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:10] if t > 0]
    worst = max(devs, key=lambda d: 1 - d["busy_s"] / d["window_s"])
    return {
        "window_s": window, "busy_s": mean("busy_s"),
        "idle_share": 1 - mean("busy_s") / window,
        "collective_s": mean("collective_s"),
        "collective_exposed_s": mean("collective_exposed_s"),
        "mosaic_s": mean("mosaic_s"),
        "device_ops": top(ops), "idle_gaps": top(by_span),
        "worst_device": {"id": worst["id"],
                         "idle_share": 1 - worst["busy_s"] /
                         worst["window_s"]},
        "n_devices": len(devs), "window_found": reduced["window_found"]}


def reduce_file(path):
    return summarize(reduce_planes(load(path)))


def digest(path, top=40):
    """What a trace holds, for reading by hand: planes, lines, event
    counts and the most frequent names with their stats."""
    out = []
    for plane in _profile(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            total = {}
            sample = {}
            for e in events:
                if e.name.startswith("$"):
                    continue
                total[e.name] = total.get(e.name, 0.0) + e.duration_ns
                sample.setdefault(e.name, e)
            for n, t in sorted(total.items(), key=lambda kv: -kv[1])[:top]:
                e = sample[n]
                stats = {k: (str(v)[:80]) for k, v in e.stats}
                out.append(f"    {t * 1e-6:10.3f} ms  {n[:70]}  "
                           f"start={e.start_ns:.0f} {stats}")
    return "\n".join(out)


if __name__ == "__main__":
    import json
    import sys
    if sys.argv[1] == "--digest":
        print(digest(sys.argv[2]))
    else:
        print(json.dumps(reduce_file(sys.argv[1]), indent=1))
