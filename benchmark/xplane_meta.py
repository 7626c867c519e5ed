"""What `jax.profiler.ProfileData` leaves out of an `.xplane.pb`: the
stats of an op's EVENT METADATA. The profiler keeps there, once an op
and not once an event, the JAX name stack the op was traced under
(`tf_op`: `jit(step)/pt.mlp/transpose(jvp())/dot_general`), the source
line (`source`), XLA's category (`hlo_category`) and the program the op
belongs to (`program_id`). `ProfileData` gives an event's own stats
only, and the generated `xplane_pb2` may not be installed where the
benchmark runs, so this reads the protobuf wire format itself: varints
and length-delimited fields of

    XSpace.planes=1 -> XPlane{name=2, lines=3, event_metadata=4 (map),
        stat_metadata=5 (map)}
    XLine{name=2, timestamp_ns=3, events=4}
    XEvent{metadata_id=1, offset_ps=2, duration_ps=3}
    XEventMetadata{id=1, name=2, stats=5}    XStatMetadata{id=1, name=2}
    XStat{metadata_id=1, double=2, uint64=3, int64=4, str=5, bytes=6,
        ref=7 (the id of a stat metadata whose NAME is the value)}

(tsl/profiler/protobuf/xplane.proto). Only the planes and lines asked
for are decoded; an event's own stats are skipped.
"""
import struct


def _varint(buf, at):
    out = shift = 0
    while True:
        b = buf[at]
        at += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, at
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one message: an int for a
    varint or a fixed field, a memoryview for a length-delimited one."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        kind = key & 7
        if kind == 0:
            value, at = _varint(buf, at)
        elif kind == 2:
            n, at = _varint(buf, at)
            value, at = buf[at:at + n], at + n
        elif kind == 1:
            value, at = struct.unpack_from("<Q", buf, at)[0], at + 8
        elif kind == 5:
            value, at = struct.unpack_from("<I", buf, at)[0], at + 4
        else:
            raise ValueError(f"xplane: wire type {kind}")
        yield key >> 3, kind, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _signed(value):
    return value - (1 << 64) if value >> 63 else value


def _map_entry(buf):
    key, value = 0, b""
    for num, _, v in fields(buf):
        if num == 1:
            key = _signed(v)        # int64: a program's id may be negative
        elif num == 2:
            value = v
    return key, value


def _stat(buf, stat_names):
    """(stat name, value) of one XStat."""
    name, value = None, None
    for num, kind, v in fields(buf):
        if num == 1:
            name = stat_names.get(v, str(v))
        elif num == 2:
            value = struct.unpack("<d", struct.pack("<Q", v))[0]
        elif num == 3:
            value = v
        elif num == 4:
            value = _signed(v)
        elif num == 5:
            value = _text(v)
        elif num == 6:
            value = bytes(v)
        elif num == 7:
            value = stat_names.get(v, str(v))
    return name, value


class Plane:
    """name; lines {line name: [(metadata id, start_ps, end_ps)]} in the
    file's order, on the plane's clock (a line's `timestamp_ns` added
    in); metadata {id: (event name, {stat name: value})}."""

    def __init__(self, name, lines, metadata):
        self.name, self.lines, self.metadata = name, lines, metadata


def _line(buf, keep):
    name, t0, events = "", 0, []
    for num, _, v in fields(buf):
        if num == 2:
            name = _text(v)
            if keep is not None and name not in keep:
                return name, None
        elif num == 3:
            t0 = _signed(v)
        elif num == 4:
            events.append(v)
    out = []
    for ev in events:
        mid = off = dur = 0
        for num, _, v in fields(ev):
            if num == 1:
                mid = _signed(v)
            elif num == 2:
                off = v
            elif num == 3:
                dur = v
            elif num == 4:
                break       # the event's own stats: written last, not read
        start = t0 * 1000 + off
        out.append((mid, start, start + dur))
    return name, out


def _plane(buf, want, lines):
    name, raw_lines, raw_meta, stat_names = "", [], [], {}
    for num, _, v in fields(buf):
        if num == 2:
            name = _text(v)
            if not want(name):
                return None
        elif num == 3:
            raw_lines.append(v)
        elif num == 4:
            raw_meta.append(v)
        elif num == 5:
            key, value = _map_entry(v)
            for n2, _, v2 in fields(value):
                if n2 == 2:
                    stat_names[key] = _text(v2)
    found = {}
    for raw in raw_lines:
        line_name, events = _line(raw, lines)
        if events is not None:
            found.setdefault(line_name, []).extend(events)
    metadata = {}
    for raw in raw_meta:
        key, value = _map_entry(raw)
        ev_name, stats = "", {}
        for n2, _, v2 in fields(value):
            if n2 == 2:
                ev_name = _text(v2)
            elif n2 == 5:
                stat, val = _stat(v2, stat_names)
                stats[stat] = val
        metadata[key] = (ev_name, stats)
    return Plane(name, found, metadata) if want(name) else None


def planes(data, want=lambda name: True, lines=None):
    """The planes of a serialized XSpace whose name `want` accepts, with
    the lines named in `lines` (None: all of them)."""
    out = []
    for num, _, v in fields(memoryview(data)):
        if num == 1:
            plane = _plane(v, want, lines)
            if plane is not None:
                out.append(plane)
    return out
