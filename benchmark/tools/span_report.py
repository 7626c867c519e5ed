"""One traced run of a serving cell, and what the program's spans say of
it: for every span of the step's family on the engine's thread, how
often it ran, its time a step, its median and 95th percentile, and the
device's idle time that fell to it (the `idle_share.*` metrics are that
column), and how much of that lay between two programs and not between
the ops of one. By hand, on the chip, for whoever works on the host loop:

    python3 benchmark/tools/span_report.py --workload gpt3-125m.serve-chat \
        --seed 1 [--seconds 45]

The last line is the run's result line, as `run.py --trace 1` prints it.
"""
import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def programs(data, window):
    """Merged intervals in which a compiled program ran on the first
    device (its `XLA Modules` line), cut to `window`."""
    from benchmark import trace_reduce
    planes = sorted((p for p in data.planes
                     if trace_reduce.DEVICE_PLANE.match(p.name)),
                    key=lambda p: p.name)
    if not planes:
        return []
    return trace_reduce.union(
        [(max(int(ev.start_ns), window[0]),
          min(int(ev.start_ns + ev.duration_ns), window[1]))
         for line in planes[0].lines if line.name == "XLA Modules"
         for ev in line.events
         if ev.start_ns < window[1]
         and ev.start_ns + ev.duration_ns > window[0]])


def report(trace_dir, window_s, log=print):
    from jax.profiler import ProfileData
    from benchmark import harness, trace_reduce
    from benchmark.readers import program_span as ps
    data = ProfileData.from_file(trace_reduce.find_xplane(trace_dir))
    spans = ps.from_profile(data, window_s)
    if spans.engine is None:
        log("no serving_step in the trace: the program writes no spans")
        return
    gaps, window = ps.device_gaps(spans)
    line = spans.lines[spans.engine]
    seconds = spans.window_s or (window[1] - window[0]) * 1e-9 \
        or (line[-1][2] - line[0][1]) * 1e-9    # no device: the steps' own
    # idle inside a running program (gaps between its ops) is no
    # host's to close: `between` is the idle time outside every program
    between = trace_reduce.subtract(gaps, programs(data, window))
    acc, acc_b = ps.idle_by_span(spans, gaps), \
        ps.idle_by_span(spans, between)
    steps = sum(1 for e in line if e[0] == ps.STEP)
    self_ns = {}
    for s, e, name in ps.self_segments(
            [e for e in line if ps.FAMILY.match(e[0])]):
        self_ns[name] = self_ns.get(name, 0) + e - s
    log(f"{steps} steps in {seconds:.3f} s; per span: count, self ms a "
        "step, p50 us, p95 us, idle ms a step, idle % of the window, of "
        "which between programs")

    def share(ns):
        return 100 * ns * 1e-9 / seconds

    for name in sorted(self_ns, key=lambda n: -acc[n]):
        durs = [(e - s) * 1e-3 for n, s, e, _ in line if n == name]
        log(f"  {name:28s} {len(durs):6d} {self_ns[name] * 1e-6 / steps:8.3f} "
            f"{harness.percentile(durs, 50):9.1f} "
            f"{harness.percentile(durs, 95):9.1f} "
            f"{acc[name] * 1e-6 / steps:8.3f} {share(acc[name]):7.2f} "
            f"{share(acc_b[name]):7.2f}")
    log(f"  {'(under no serving_step)':28s} {'':6s} {'':8s} {'':9s} {'':9s} "
        f"{acc[None] * 1e-6 / steps:8.3f} {share(acc[None]):7.2f} "
        f"{share(acc_b[None]):7.2f}")
    log(f"  idle in all: {share(sum(acc.values())):.2f}% of {seconds:.3f} s, "
        f"between programs {share(sum(acc_b.values())):.2f}%")
    for ln in spans.lines:
        waits = [(e - s) * 1e-3 for n, s, e, _ in ln
                 if n == "serving_submit.lock_wait"]
        if waits:
            log(f"  serving_submit.lock_wait on a client's thread: "
                f"{len(waits)}, p50 {harness.percentile(waits, 50):.1f} us, "
                f"p95 {harness.percentile(waits, 95):.1f} us")


def main(argv=None):
    from benchmark import run
    t_start = run.process_start()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    args = ap.parse_args(argv)
    from benchmark import harness
    spec = harness.Spec(args.workload)
    devices = harness.device_look(spec)
    from paddle_tpu import compile_cache
    compile_cache.enable()
    trace_dir = os.path.join(ROOT, ".bench_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    result = harness.execute(spec, args.seed, args.seconds, True, t_start,
                             devices, trace_dir=trace_dir)
    report(trace_dir, result["device"].get("window_s"))
    shutil.rmtree(trace_dir, ignore_errors=True)
    harness.print_compared(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
