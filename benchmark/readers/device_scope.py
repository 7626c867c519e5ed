"""The device's busy time by the model's layers, read from the trace.

`paddle_tpu.telemetry.scope` puts `pt.<layer>` into the name stack of
every op traced inside a layer; the compiler keeps the stack in the
op's metadata and the profiler in the op's event metadata (`tf_op`),
which `benchmark/xplane_meta.py` reads. This reader loads a run's
`.xplane.pb` once and gives every instant in which an op ran on the
first device plane to one op (where ops overlap, the one that started
last), and every op to one owner:

    its scope     the INNERMOST `pt.` component of its `tf_op` (the
                  shared experts inside the expert layer are `mlp`); an
                  op without a `tf_op` that ends an asynchronous op
                  (`copy-done.3 = copy-done(%copy-start.3)`) takes the
                  scope of the op it ends, where that has one
    "unscoped"    a `tf_op` and no `pt.` in it: the program's own work
                  that no layer named
    "xla_own"     no `tf_op` at all: copies, slices and prefetches the
                  compiler put in

so the owners' shares add up to the busy time. By `args.mode`:

`scope`     100 x the seconds owned by `pt.<args.scope>` over the
            device's busy seconds
`unscoped`, `xla_own`   the same for those two owners (`unscoped` only
            of a program that opens scopes at all)
`program`   100 x the seconds of the `XLA Modules` events (one a run of
            a compiled program) whose name matches `args.pattern`, over
            the busy seconds

A fusion bears one name, its root's, so shares blur where the compiler
fuses across a seam (PERF.md section 3 says which pairs to read
together). Finds nothing (no device plane, a program that opens no
scopes, a scope this model does not have): returns None, never 0 and
never an error.
"""
import re

from benchmark import trace_reduce, xplane_meta

KEY = "device_scope"        # where a run keeps what was loaded
OPS, MODULES = "XLA Ops", "XLA Modules"
UNSCOPED, XLA_OWN = "unscoped", "xla_own"
PT = re.compile(r"(?:^|[/(])pt\.([a-z_]+)")
ENDS = re.compile(r"%([\w.\-]+)\)\s*$")     # the operand of an X-done


class Op:
    """One op of one program: its name (`fusion.12`), its HLO line,
    `tf_op`, XLA's category, source line, owner, and the picoseconds
    that fell to it."""
    __slots__ = ("name", "text", "tf_op", "category", "source", "owner",
                 "ps")

    def __init__(self, text, stats):
        self.name, self.text = trace_reduce.op_name(text), text
        self.tf_op = stats.get("tf_op")
        self.category = stats.get("hlo_category")
        self.source = stats.get("source")
        self.owner = innermost(self.tf_op) or (UNSCOPED if self.tf_op
                                               else XLA_OWN)
        self.ps = 0


class Scoped:
    """ops: [Op] that ran; modules: [(name, ps)] a run of a program;
    busy_ps: picoseconds in which an op ran."""

    def __init__(self, ops, modules):
        self.ops, self.modules = ops, modules
        self.busy_ps = sum(op.ps for op in ops)

    def seconds(self, owner):
        """Seconds that fell to `owner`, None where it owns no op."""
        mine = [op.ps for op in self.ops if op.owner == owner]
        return sum(mine) * 1e-12 if mine else None

    def owners(self):
        return sorted({op.owner for op in self.ops})


def self_times(events):
    """{metadata id: ps}: every instant in which an event of
    [(id, start, end)] ran, given to the running event that started
    last. The values add up to the union of the intervals."""
    acc, stack, t = {}, [], 0

    def advance(upto):
        nonlocal t
        while stack:
            mid, end = stack[-1]
            if end <= t:
                stack.pop()
                continue
            stop = min(end, upto)
            acc[mid] = acc.get(mid, 0) + stop - t
            t = stop
            if t >= upto:
                return
        t = upto

    for mid, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        advance(start)
        stack.append((mid, end))
    advance(float("inf"))
    return acc


def innermost(tf_op):
    found = PT.findall(tf_op or "")
    return found[-1] if found else None


def from_plane(plane):
    """The reduction of one device plane of `xplane_meta.planes`."""
    ops, by_name = {}, {}
    for mid, ps in self_times(plane.lines.get(OPS, ())).items():
        text, stats = plane.metadata.get(mid, ("", {}))
        op = ops[mid] = Op(text, stats)
        op.ps = ps
        by_name[stats.get("program_id"), op.name] = op
    # an op XLA split in two: the end has no name stack, the start does
    for mid, op in ops.items():
        if op.owner == XLA_OWN and "-done" in op.name:
            began = ENDS.search(op.text)
            start = by_name.get((plane.metadata[mid][1].get("program_id"),
                                 began.group(1))) if began else None
            if start is not None and start.tf_op:
                op.owner, op.tf_op = start.owner, start.tf_op
    modules = [(plane.metadata.get(mid, ("", {}))[0], end - start)
               for mid, start, end in plane.lines.get(MODULES, ())]
    return Scoped(list(ops.values()), modules)


def from_bytes(data):
    """The first device plane of a serialized XSpace, or None."""
    found = xplane_meta.planes(
        data, lambda name: trace_reduce.DEVICE_PLANE.match(name),
        (OPS, MODULES))
    found.sort(key=lambda p: p.name)
    return from_plane(found[0]) if found else None


def load(path):
    """The same of an `.xplane.pb` file."""
    with open(path, "rb") as f:
        return from_bytes(f.read())


def scoped_of(run):
    """What a run's trace holds, loaded once and kept on `run`."""
    if KEY not in run:
        run[KEY] = None
        tracer = run.get("tracer")
        if tracer is not None and tracer.state == "done":
            run[KEY] = load(trace_reduce.find_xplane(tracer.out_dir))
    return run[KEY]


def read(args, run):
    scoped = scoped_of(run)
    if scoped is None or not scoped.busy_ps:
        return None
    # the yardstick of every `*_time_share`; a run handed over without
    # its reduced trace (a test) is measured on the ops' own union
    busy = trace_reduce.busy_seconds(run["trace"]) if run.get("trace") \
        else scoped.busy_ps * 1e-12
    mode = args["mode"]
    if mode == "program":
        rx = re.compile(args["pattern"])
        mine = [ps for name, ps in scoped.modules if rx.search(name)]
        return 100.0 * sum(mine) * 1e-12 / busy if mine else None
    if mode not in ("scope", UNSCOPED, XLA_OWN):
        raise ValueError(f"device_scope: no mode {mode!r}")
    if mode == UNSCOPED and set(scoped.owners()) <= {UNSCOPED, XLA_OWN}:
        # a program that opens no scopes (the parent commit, or an
        # executable a scope-less tree left in a shared compile cache):
        # all of its work would read as nobody's, which says nothing
        return None
    seconds = scoped.seconds(args["scope"] if mode == "scope" else mode)
    return None if seconds is None else 100.0 * seconds / busy
