"""Reduction of a `jax.profiler` trace (`.xplane.pb`) to the numbers the
per-layer readers use: device busy time, kernel time by name pattern, and the
longest idle gaps named by what the host was doing.

Reads the file with `jax.profiler.ProfileData` and nothing else. On a
TPU the device planes are named `/device:TPU:<n>`; each has a line
`XLA Ops` with one event per executed HLO op (its name is the op's,
e.g. `fusion.12`, or the `name=` of a `pallas_call`). Host threads are
lines of the `/host:CPU` plane; `jax.profiler.TraceAnnotation` spans
written by the harness land there under their own names.
"""
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"


class Trace:
    """devices: {plane name: [(op name, start_ns, end_ns)] sorted};
    host: [(span name, start_ns, end_ns)] sorted."""

    def __init__(self, devices, host):
        self.devices = devices
        self.host = host


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path, host_names=None):
    """Parse one `.xplane.pb`. `host_names`: the span names to keep from
    the host plane (None keeps none: the host plane is large)."""
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path), host_names)


def from_profile(data, host_names=None):
    """The same reduction from a `ProfileData` already in memory."""
    devices, host = {}, []
    keep = set(host_names or ())
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append((op_name(ev.name), int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns)))
            ops.sort(key=lambda e: e[1])
            devices[plane.name] = ops
        elif keep and plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in keep:
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns)))
    host.sort(key=lambda e: e[1])
    return Trace(devices, host)


def op_name(event_name):
    """The op's own name from an `XLA Ops` event, which on a TPU is the
    whole HLO line: `%paged_decode.19 = f32[...] custom-call(...)` ->
    `paged_decode.19`."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def base_name(op_name):
    """`fusion.123` -> `fusion`; `paged_decode` stays."""
    return re.sub(r"[.\d]+$", "", op_name) or op_name


def union(intervals):
    """Merged, sorted list of [start, end) from any list of them."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def busy_seconds(trace):
    """Seconds in which an op ran, averaged over the device planes."""
    if not trace.devices:
        return 0.0
    per = [total(union([(s, e) for _, s, e in ops])) * 1e-9
           for ops in trace.devices.values()]
    return sum(per) / len(per)


def kernel_seconds(trace, pattern):
    """Summed duration of ops whose name matches `pattern` (a regex,
    searched), averaged over the device planes; None if none ran."""
    rx = re.compile(pattern)
    per, seen = [], False
    for ops in trace.devices.values():
        t = 0
        for name, s, e in ops:
            if rx.search(name):
                t += e - s
                seen = True
        per.append(t * 1e-9)
    return sum(per) / len(per) if seen else None


def subtract(a, b):
    """Parts of merged intervals `a` not covered by merged `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def top_ops(trace, k=10):
    """[[base op name, seconds]] by summed duration over the first
    device plane (the planes of a mesh run the same program)."""
    if not trace.devices:
        return []
    ops = trace.devices[sorted(trace.devices)[0]]
    acc = {}
    for name, s, e in ops:
        b = base_name(name)
        acc[b] = acc.get(b, 0) + (e - s)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t * 1e-9] for n, t in rows]


def idle_gaps(trace, window, k=10, default="unattributed"):
    """[[host span name, seconds]]: idle time of the first device plane
    inside `window` (start_ns, end_ns), each gap named by the innermost
    kept host span that covers its midpoint, summed by name."""
    if not trace.devices:
        return []
    ops = trace.devices[sorted(trace.devices)[0]]
    busy = union([(max(s, window[0]), min(e, window[1]))
                  for _, s, e in ops if e > window[0] and s < window[1]])
    gaps = subtract([[window[0], window[1]]], busy)
    acc = {}
    for s, e in gaps:
        mid = (s + e) // 2
        name, best = default, None
        for hn, hs, he in trace.host:
            if hs > mid:
                break
            if he >= mid and (best is None or he - hs < best):
                name, best = hn, he - hs
        acc[name] = acc.get(name, 0) + (e - s)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t * 1e-9] for n, t in rows]


def window_of(trace):
    """(first op start, last op end) over all device planes, in ns."""
    starts = [ops[0][1] for ops in trace.devices.values() if ops]
    ends = [max(e for _, _, e in ops) for ops in trace.devices.values()
            if ops]
    return (min(starts), max(ends)) if starts else (0, 0)
