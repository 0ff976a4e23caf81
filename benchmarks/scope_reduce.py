"""Device time by the program's own names.

The program opens `jax.named_scope("ds.<name>")` around its kernels and
model regions (`deeperspeed_tpu/scopes.py`); the name travels in each
operation's `tf_op` (`xplane_meta`). This gives every device operation's
self time (`trace_reduce.self_times`, clipped to the traced window as
the reducer clips it) to the innermost `ds.*` scope of its `tf_op`, or
to `(unscoped)` where it has none: the compiler's own copies, a
collective GSPMD put in, an operation of a program that names nothing.
So the scopes' seconds and `(unscoped)` add up to the device's busy time.

Beside that, per device: the self time of operations whose `tf_op`
passes through `rematted_computation` (work `jax.checkpoint` runs a
second time in the backward pass), and for each kernel scope the Mosaic
custom calls that lie wholly inside the window, counted and timed, for
a mean time per call. Several devices are averaged, as
`trace_reduce.summarize` averages them.

It reads `trace_reduce.DEVICE_PLANE` and `OP_LINE` when called, so a
rehearsal on the CPU steers it as it steers the reducer. One reduction
per trace file, kept: the readers of one run share it.
"""

import functools
import re

from benchmarks import trace_reduce, xplane_meta

SCOPE = re.compile(r"ds\.[a-z0-9_]+")
REMAT = "rematted_computation"
UNSCOPED = "(unscoped)"
# one layer's attention backward: the two tiled passes, or the fused
# single-block kernel
FLASH_BACKWARD = ("ds.flash_bwd_dq", "ds.flash_bwd_dkv", "ds.flash_bwd")


def innermost(tf_op):
    found = SCOPE.findall(tf_op or "")
    return found[-1] if found else UNSCOPED


def window_of(planes):
    """(start, end) of the host span `trace_reduce.WINDOW_SPAN`, or None."""
    for pname, lines in planes:
        if trace_reduce.HOST_PLANE.match(pname):
            for _, events in lines:
                for name, start, end in events:
                    if name == trace_reduce.WINDOW_SPAN:
                        return start, end
    return None


def device_operations(planes, meta):
    """For each device plane that ran something: (its operations'
    `tf_op` by name, their (name, start, end, self seconds) clipped to
    the window, the unclipped events, the window)."""
    window = window_of(planes)
    tables = dict(meta)
    for pname, lines in planes:
        if not trace_reduce.DEVICE_PLANE.match(pname):
            continue
        events = [ev for lname, evs in lines
                  if trace_reduce.OP_LINE.match(lname)
                  for ev in evs if ev[2] > ev[1]]
        if not events:
            continue
        lo, hi = window if window else (min(e[1] for e in events),
                                        max(e[2] for e in events))
        table = tables.get(pname, {})
        tf_op = {n: table.get(n, {}).get("tf_op", "")
                 for n in {ev[0] for ev in events}}
        clipped = [(n, *iv) for n, s, e in events
                   for iv in trace_reduce.clip([[s, e]], lo, hi)]
        yield tf_op, trace_reduce.self_times(clipped), events, (lo, hi)


def reduce_planes(planes, meta):
    """`planes` as `trace_reduce.load` gives them, `meta` as
    `xplane_meta.planes` does. Means over the devices:

    busy_s    the union of the operations' intervals
    scopes    {scope or "(unscoped)": self seconds}; sums to busy_s
    remat_s   self seconds under `rematted_computation`
    calls     {kernel scope: [custom calls inside the window, their
              seconds]}
    """
    devices = []
    for tf_op, selfs, events, (lo, hi) in device_operations(planes, meta):
        scope_of = {n: innermost(t) for n, t in tf_op.items()}
        calls = {}
        for n, s, e in events:
            if trace_reduce.MOSAIC in n and lo <= s and e <= hi and \
                    scope_of[n] != UNSCOPED:
                got = calls.setdefault(scope_of[n], [0, 0.0])
                got[0] += 1
                got[1] += e - s
        scopes, remat = {}, 0.0
        for n, _, _, t in selfs:
            scopes[scope_of[n]] = scopes.get(scope_of[n], 0.0) + t
            if REMAT in tf_op[n]:
                remat += t
        busy = trace_reduce.measure(trace_reduce.union(
            [[s, e] for _, s, e, _ in selfs]))
        devices.append({"busy_s": busy, "scopes": scopes,
                        "remat_s": remat, "calls": calls})
    if not devices:
        return None
    n = len(devices)
    out = {"n_devices": n,
           "busy_s": sum(d["busy_s"] for d in devices) / n,
           "remat_s": sum(d["remat_s"] for d in devices) / n,
           "scopes": {}, "calls": {}}
    for d in devices:
        for name, t in d["scopes"].items():
            out["scopes"][name] = out["scopes"].get(name, 0.0) + t / n
        for name, (count, t) in d["calls"].items():
            got = out["calls"].setdefault(name, [0.0, 0.0])
            got[0] += count / n
            got[1] += t / n
    return out


@functools.lru_cache(maxsize=4)
def _reduce_file(path, device_plane, op_line):  # noqa: ARG001 - cache keys
    return reduce_planes(trace_reduce.load(path), xplane_meta.load(path))


def reduce_file(path):
    # the two patterns are part of the key: a rehearsal that steers them
    # must not be handed a reduction made under the others
    return _reduce_file(path, trace_reduce.DEVICE_PLANE.pattern,
                        trace_reduce.OP_LINE.pattern)


def of_run(rec, named=True):
    """The reduction of the run's traced stretch, or None: an untraced
    run, or (`named`) a program that opens no `ds.*` scope (a commit
    from before the scopes), which has nothing for a scope's reader to
    read."""
    path = rec.get("trace_path")
    reduced = reduce_file(path) if path else None
    if reduced is None or not reduced["busy_s"] or \
            (named and set(reduced["scopes"]) <= {UNSCOPED}):
        return None
    return reduced


def share(rec, names):
    """100 * the self time under the scopes `names` / busy time; None
    where the traced program opens none of them."""
    reduced = of_run(rec)
    if reduced is None or not any(n in reduced["scopes"] for n in names):
        return None
    return 100.0 * sum(reduced["scopes"].get(n, 0.0)
                       for n in names) / reduced["busy_s"]


def unscoped_share(rec, containers=("ds.layers",)):
    """What the names do not cover: `(unscoped)` and the containers' own
    time."""
    reduced = of_run(rec)
    if reduced is None:
        return None
    return 100.0 * sum(reduced["scopes"].get(n, 0.0)
                       for n in (UNSCOPED, *containers)) / reduced["busy_s"]


def remat_share(rec):
    """Needs no scope: `jax.checkpoint` marks recomputed work itself."""
    reduced = of_run(rec, named=False)
    if reduced is None:
        return None
    return 100.0 * reduced["remat_s"] / reduced["busy_s"]


def seconds_per_call(rec, names, per):
    """Mean seconds of the kernel calls under `names` taken together,
    per call of `per` (flash backward: the dq and dkv calls of a layer
    are one backward, counted by its dkv calls)."""
    reduced = of_run(rec)
    if reduced is None:
        return None
    count = sum(reduced["calls"].get(n, [0, 0.0])[0] for n in per)
    if not count:
        return None
    return sum(reduced["calls"].get(n, [0, 0.0])[1] for n in names) / count


def roofline(rec, flops, bytes_, seconds):
    """100 * the least time the chip could take / the time it took."""
    from benchmarks import harness, kernel_costs
    if not seconds:
        return None
    peaks = harness.peaks_for(rec["spec"], rec["device_kind"])
    if not {"bf16_flops_per_s", "hbm_bytes_per_s"} <= set(peaks):
        return None
    return 100.0 * kernel_costs.least_seconds(flops, bytes_, peaks)[0] / \
        seconds


def flash_shape(rec):
    """(sequences a chip, heads, sequence length, head dim) of a train
    cell's attention calls."""
    conf = rec["spec"]["config"]
    heads = conf["num_attention_heads"]
    per_chip = rec["tokens_per_step"] // rec["seq_len"] // rec["chips"]
    return per_chip, heads, rec["seq_len"], conf["hidden_size"] // heads


def digest(path, top=40):
    """For reading by hand: the heaviest (scope, operation group) pairs
    of a trace's first device, as shares of its busy time; groups as
    `trace_reduce.group_name` builds them."""
    for tf_op, selfs, _, _ in device_operations(trace_reduce.load(path),
                                                xplane_meta.load(path)):
        pairs = {}
        for n, _, _, t in selfs:
            key = (innermost(tf_op[n]) + (" remat" if REMAT in tf_op[n]
                                          else ""),
                   trace_reduce.group_name(n))
            pairs[key] = pairs.get(key, 0.0) + t
        total = sum(pairs.values())
        return [[scope, group, 100.0 * t / total] for (scope, group), t in
                sorted(pairs.items(), key=lambda kv: -kv[1])[:top]]
    return []


if __name__ == "__main__":
    import json
    import sys
    if sys.argv[1] == "--digest":
        for scope, group, pct in digest(sys.argv[2]):
            print(f"{pct:6.2f}%  {scope:28s} {group}")
    else:
        print(json.dumps(reduce_file(sys.argv[1]), indent=1))
