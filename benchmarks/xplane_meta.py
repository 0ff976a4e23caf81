"""What a device trace says about where each operation came from.

`jax.profiler.ProfileData` (what `trace_reduce.load` reads a trace with)
gives an event's name, times and per-event stats. The jax name stack of
the operation, which carries the program's `ds.*` scopes
(`deeperspeed_tpu/scopes.py`), is not among them: it is a stat of the
event's *metadata* (`tf_op`, beside `source`, `hlo_category`, `flops`,
`bytes_accessed`), kept once per distinct operation in the plane's
`event_metadata` table, which `ProfileData` does not expose. No
`xplane_pb2` can be imported here, so this reads the protobuf wire format
itself; the few field numbers it needs (tsl/profiler/protobuf/xplane.proto):

    XSpace          planes=1
    XPlane          name=2 lines=3 event_metadata=4 stat_metadata=5
      (both tables are maps: key=1, value=2)
    XEventMetadata  id=1 name=2 display_name=4 stats=5
    XStatMetadata   id=1 name=2
    XStat           metadata_id=1 str_value=5 ref_value=7

A string stat holds its text (`str_value`) or points at a name of the
plane's stat-metadata table (`ref_value`). Lines, the bulk of a file,
are skipped by their length.
"""

import lzma

VARINT, FIXED64, BYTES, FIXED32 = 0, 1, 2, 5
WANTED = ("tf_op", "source")


def fields(buf):
    """(field number, wire type, value) of one message: an int for a
    varint or a fixed-width field, a memoryview for a length-delimited
    one."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        value, shift = 0, 0
        while True:
            b = buf[i]
            i += 1
            value |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                return value

    while i < n:
        key = varint()
        number, wire = key >> 3, key & 7
        if wire == VARINT:
            value = varint()
        elif wire == BYTES:
            size = varint()
            value = buf[i:i + size]
            i += size
        elif wire in (FIXED64, FIXED32):
            width = 8 if wire == FIXED64 else 4
            value = int.from_bytes(buf[i:i + width], "little")
            i += width
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an "
                             f"xplane file")
        yield number, wire, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _map_value(entry):
    """The value message of a protobuf map entry."""
    return next((v for n, w, v in fields(entry) if n == 2 and w == BYTES),
                None)


def read_bytes(path):
    if path.endswith(".xz"):
        with lzma.open(path) as f:
            return f.read()
    with open(path, "rb") as f:
        return f.read()


def planes(raw):
    """[(plane name, {event name: {"tf_op": ..., "source": ...}})] of a
    serialized XSpace: for every distinct operation of a plane, the
    wanted string stats its metadata carries. An operation without them
    (a host event, a step marker) maps to an empty dict."""
    out = []
    for number, wire, plane in fields(memoryview(raw)):
        if number != 1 or wire != BYTES:
            continue
        name, events, stat_names = "", [], {}
        for n, w, v in fields(plane):
            if n == 2 and w == BYTES:
                name = _text(v)
            elif n == 4 and w == BYTES:
                events.append(_map_value(v))
            elif n == 5 and w == BYTES:
                meta = _map_value(v)
                if meta is not None:
                    got = {fn: fv for fn, _, fv in fields(meta)
                           if fn in (1, 2)}
                    stat_names[got.get(1, 0)] = _text(got.get(2, b""))
        table = {}
        for meta in events:
            if meta is None:
                continue
            event_name, stats = "", {}
            for n, w, v in fields(meta):
                if n == 2 and w == BYTES:
                    event_name = _text(v)
                elif n == 5 and w == BYTES:
                    stat = {fn: fv for fn, _, fv in fields(v)
                            if fn in (1, 5, 7)}
                    key = stat_names.get(stat.get(1))
                    if key in WANTED:
                        stats[key] = _text(stat[5]) if 5 in stat else \
                            stat_names.get(stat.get(7), "")
            table[event_name] = stats
        out.append((name, table))
    return out


def load(path):
    return planes(read_bytes(path))


if __name__ == "__main__":
    import sys
    for plane_name, table in load(sys.argv[1]):
        named = {k: v for k, v in table.items() if v.get("tf_op")}
        print(f"PLANE {plane_name}: {len(table)} operations, "
              f"{len(named)} with tf_op")
        for event_name, stats in sorted(named.items())[:int(
                sys.argv[2]) if len(sys.argv) > 2 else 20]:
            print(f"  {event_name[:60]!r}\n    tf_op={stats['tf_op']!r}"
                  f"\n    source={stats.get('source')!r}")
