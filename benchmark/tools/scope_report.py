"""What a traced run's device time was spent on, by the program's own
names: for every owner (`telemetry.scope`'s layers, `unscoped`,
`xla_own`; `readers/device_scope.py`) its seconds and share of the busy
time, and under it the ops by XLA's name, category and source line; the
programs that ran; and the `transpose(` ops that bear no scope. By
hand, for whoever sizes a `perf_opt` claim:

    python3 benchmark/tools/scope_report.py <trace dir or .xplane.pb[.gz]>
    python3 benchmark/tools/scope_report.py --workload <cell> --seed 1

The second form is one traced run of a cell on the chip (`run.py
--trace 1` throws its trace away), ending in the run's result line.
"""
import gzip
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def report(scoped, rows=12, log=print):
    from benchmark import trace_reduce
    busy = scoped.busy_ps * 1e-12
    log(f"busy {busy:.4f} s; owner / XLA op / category / source: "
        "seconds, % of busy")
    by_owner = {}
    for op in scoped.ops:
        key = (trace_reduce.base_name(op.name), op.category or "-",
               os.path.relpath(op.source, ROOT) if op.source else "-")
        acc = by_owner.setdefault(op.owner, {})
        acc[key] = acc.get(key, 0) + op.ps
    for owner in sorted(by_owner, key=lambda o: -sum(by_owner[o].values())):
        total = sum(by_owner[owner].values()) * 1e-12
        log(f"{owner:10s} {total:9.4f} {100 * total / busy:6.2f}")
        for (name, cat, src), ps in sorted(
                by_owner[owner].items(), key=lambda kv: -kv[1])[:rows]:
            log(f"    {name:34s} {cat:22s} {src:44s} {ps * 1e-12:9.4f} "
                f"{100 * ps * 1e-12 / busy:6.2f}")
    # the compiler's own ops by what they produce: an asynchronous
    # copy or slice of a weight names its shape and memory space
    own = {}
    for op in scoped.ops:
        if op.owner == "xla_own":
            made = op.text.split(" = ", 1)[-1].split(" ", 1)[0]
            key = (trace_reduce.base_name(op.name), made[-70:])
            own[key] = own.get(key, 0) + op.ps
    for (name, made), ps in sorted(own.items(), key=lambda kv: -kv[1])[:rows]:
        log(f"xla_own {name:22s} {made:70s} {ps * 1e-12:9.4f} "
            f"{100 * ps * 1e-12 / busy:6.2f}")
    back = sum(op.ps for op in scoped.ops
               if "transpose(" in (op.tf_op or "")
               and op.owner in ("unscoped", "xla_own")) * 1e-12
    log(f"transpose( ops without a scope: {back:.4f} s "
        f"{100 * back / busy:.2f}%")
    acc = {}
    for name, ps in scoped.modules:
        acc[name] = acc.get(name, 0) + ps
    for name, ps in sorted(acc.items(), key=lambda kv: -kv[1]):
        log(f"program {name:48s} {ps * 1e-12:9.4f} "
            f"{100 * ps * 1e-12 / busy:6.2f}")


def load(path):
    from benchmark import trace_reduce
    from benchmark.readers import device_scope
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        return device_scope.from_bytes(f.read())


def run_cell(workload, seed, seconds, log=print):
    """One traced run of a cell, as `run.py --trace 1` makes it, with
    the report taken before the trace is thrown away; also the trace's
    size and the seconds this reader took to load it."""
    import json
    import shutil
    import time
    from benchmark import harness, run, trace_reduce
    from benchmark.readers import device_scope
    from paddle_tpu import compile_cache
    t_start = run.process_start()
    spec = harness.Spec(workload)
    devices = harness.device_look(spec)
    compile_cache.enable()
    trace_dir = os.path.join(ROOT, ".bench_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    result = harness.execute(spec, seed, seconds, True, t_start, devices,
                             log=log, trace_dir=trace_dir)
    path = trace_reduce.find_xplane(trace_dir)
    t0 = time.perf_counter()
    found = device_scope.load(path)
    log(f"{path}: {os.path.getsize(path)} bytes, device_scope.load "
        f"{time.perf_counter() - t0:.3f} s")
    report(found, log=log)
    shutil.rmtree(trace_dir, ignore_errors=True)
    harness.print_compared(result)
    log(json.dumps(result))


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("trace", nargs="?")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--rows", type=int, default=12)
    args = ap.parse_args(argv)
    if args.workload:
        return run_cell(args.workload, args.seed, args.seconds)
    found = load(args.trace)
    if found is None:
        raise SystemExit("scope_report: no device plane in the trace")
    report(found, args.rows)


if __name__ == "__main__":
    main()
