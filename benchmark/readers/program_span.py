"""The program's own spans, read from the device trace.

`paddle_tpu.telemetry.span` writes every span of the program into the
profiler's trace (a `jax.profiler.TraceAnnotation`), so in the
`.xplane.pb` of a traced run the spans of `ServingEngine.step` lie on
the line of the thread that ran the step, on the clock of the device's
ops, with their attributes as the event's stats. This reader loads that
file once a run and answers, by `args.mode`:

`idle_overlap`  the idle time of the first device plane inside the
    traced window (as `trace_reduce.idle_gaps` takes it), each instant
    of it given to the INNERMOST span of the step's family that covers
    it on the engine's thread: time-weighted, so a gap that runs from
    one step's `emit` into the next step's `build` is shared among the
    spans it crosses. Returns 100 x the seconds that fell to
    `args.span` over the window's seconds; `span: null` is the idle
    time under no `serving_step` at all.
`duration`  the `args.q`-th percentile of the durations of `args.span`,
    in ms. `args.thread` "any" looks on every host thread (a client's
    `serving_submit`); the default is the engine's thread.
`attr_ratio`  the median over the spans `args.span` of their attribute
    `args.attr` over the cell's `engine[args.over]`, in %.

`duration` and `attr_ratio` take `args.where`: `{"attr", "prefix"}`
keeps the spans whose attribute starts with the prefix.

The engine's thread is the host line that holds `serving_step`. The
step's family is `serving_step`, its `serving_step.<phase>` children
and `serving_dispatch`; the harness's own spans (`engine_step`,
`serving_decode`, ...) and JAX's are not the program's and are left
out, or they would be the innermost. Finds nothing (no device plane
for `idle_overlap`, a span that never occurs, a program that writes no
spans into the trace): returns None, never 0 and never an error.
"""
import re

from benchmark import harness, trace_reduce

STEP = "serving_step"
FAMILY = re.compile(r"^(serving_step(\..+)?|serving_dispatch)$")
KEPT = re.compile(r"^serving_(step|dispatch|submit)(\.|$)")
KEY = "program_spans"       # where a run keeps what was loaded


class Spans:
    """lines: [[(name, start_ns, end_ns, stats)]] per host thread that
    holds a kept span, each sorted by start; engine: index of the line
    with the most `serving_step`, or None; devices as `Trace.devices`;
    window_s: seconds the shares are taken over; idle: the device's
    window and its idle time by span, worked out once (`idle_of`)."""

    def __init__(self, lines, devices, window_s=None):
        self.lines = [sorted(ln, key=lambda e: (e[1], -e[2]))
                      for ln in lines if ln]
        self.devices = devices
        steps = [sum(1 for e in ln if e[0] == STEP) for ln in self.lines]
        self.engine = steps.index(max(steps)) if steps and max(steps) \
            else None
        self.window_s = window_s
        self.idle = None


def from_profile(data, window_s=None):
    """The kept spans and the device planes of a `ProfileData`."""
    lines = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            kept = []
            for ev in line.events:
                if KEPT.match(ev.name):
                    start = int(ev.start_ns)
                    kept.append((ev.name, start,
                                 start + int(ev.duration_ns),
                                 dict(ev.stats)))
            lines.append(kept)
    return Spans(lines, trace_reduce.from_profile(data).devices, window_s)


def spans_of(run):
    """What a run's trace holds, loaded once and kept on `run`."""
    if KEY not in run:
        run[KEY] = None
        tracer = run.get("tracer")
        if tracer is not None and tracer.state == "done":
            from jax.profiler import ProfileData
            path = trace_reduce.find_xplane(tracer.out_dir)
            run[KEY] = from_profile(ProfileData.from_file(path),
                                    tracer.window_s or None)
    return run[KEY]


def self_segments(events):
    """[(start, end, name)] of one thread's nested spans, cut so that
    every instant belongs to the innermost span that covers it."""
    out, stack = [], []     # stack of [name, end, covered up to]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, at = stack.pop()
            if end > at:
                out.append((at, end, name))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, start, end, _ in events:
        close(start)
        if stack:
            # a child lies in its parent and after its elder siblings,
            # whatever the clock's rounding says
            parent = stack[-1]
            start, end = max(start, parent[2]), min(end, parent[1])
            if end <= start:
                continue
            if start > parent[2]:
                out.append((parent[2], start, parent[0]))
            parent[2] = start
        stack.append([name, end, start])
    close(float("inf"))
    out.sort()
    return out


def device_gaps(spans):
    """(merged idle intervals of the first device plane, its window),
    in ns, inside the window of the device's ops; ([], (0, 0)) where
    the trace holds no device."""
    if not spans.devices:
        return [], (0, 0)
    window = trace_reduce.window_of(trace_reduce.Trace(spans.devices, []))
    ops = spans.devices[sorted(spans.devices)[0]]
    busy = trace_reduce.union(
        [(max(s, window[0]), min(e, window[1]))
         for _, s, e in ops if e > window[0] and s < window[1]])
    return trace_reduce.subtract([[window[0], window[1]]], busy), window


def idle_by_span(spans, gaps):
    """{span name or None: ns of `gaps`}: each instant of the device's
    idle intervals given to the innermost span of the step's family
    that covers it on the engine's thread; None holds what lies under
    no `serving_step`."""
    family = [e for e in spans.lines[spans.engine] if FAMILY.match(e[0])]
    acc = {None: trace_reduce.total(gaps)}
    for name in {e[0] for e in family}:
        acc[name] = 0
    segs, j = self_segments(family), 0
    for s, e in gaps:
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < e:
            part = min(e, segs[k][1]) - max(s, segs[k][0])
            acc[segs[k][2]] += part
            acc[None] -= part
            k += 1
    return acc


def idle_of(spans):
    """(window, {span name or None: idle ns}) of a trace with a device
    and an engine's thread, kept on `spans` for the next metric."""
    if spans.idle is None:
        gaps, window = device_gaps(spans)
        spans.idle = (window, idle_by_span(spans, gaps))
    return spans.idle


def _matching(spans, args):
    lines = spans.lines if args.get("thread") == "any" \
        else [spans.lines[spans.engine]]
    where = args.get("where")
    out = []
    for line in lines:
        for name, start, end, stats in line:
            if name != args["span"]:
                continue
            if where and not str(stats.get(where["attr"], "")).startswith(
                    where["prefix"]):
                continue
            out.append((start, end, stats))
    return out


def read(args, run):
    spans = spans_of(run)
    if spans is None or spans.engine is None:
        return None
    mode = args["mode"]
    if mode == "idle_overlap":
        window, acc = idle_of(spans)
        if window[1] <= window[0] or args["span"] not in acc:
            return None
        seconds = spans.window_s or (window[1] - window[0]) * 1e-9
        return 100.0 * acc[args["span"]] * 1e-9 / seconds
    found = _matching(spans, args)
    if not found:
        return None
    if mode == "duration":
        return harness.percentile([(e - s) * 1e-6 for s, e, _ in found],
                                  args["q"])
    if mode == "attr_ratio":
        values = [st[args["attr"]] for _, _, st in found
                  if args["attr"] in st]
        over = run["spec"].cell["engine"][args["over"]]
        return 100.0 * harness.median(values) / over if values else None
    raise ValueError(f"program_span: no mode {mode!r}")
