"""benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json on the TPU this process is started
on. Exits non-zero, with no result line, where JAX finds no TPU, fewer
chips than the cell asks for, a device kind missing from peaks.json, or
no program to measure. The last line of standard output is the result
object; everything else goes on earlier lines.
"""
import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def process_start():
    """Wall-clock time at which this process started (set-up is counted
    from here, interpreter start-up and imports included)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            boot = next(int(l.split()[1]) for l in f if l.startswith("btime"))
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        hz = os.sysconf("SC_CLK_TCK")
        # btime is whole seconds; now - uptime is the precise boot time
        return (time.time() - up) + ticks / hz if boot else time.time()
    except (OSError, ValueError, IndexError, StopIteration):
        return time.time()


def main(argv=None):
    t_start = process_start()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        print("benchmark: no paddle_tpu beside the benchmark; there is "
              "nothing to measure", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    from benchmark import harness
    spec = harness.Spec(args.workload)
    devices = harness.device_look(spec)

    # the program's own placement: JAX_COMPILATION_CACHE_DIR where the
    # machine sets it, else <checkout>/.jax_cache (a fixed path)
    from paddle_tpu import compile_cache
    cache_dir = compile_cache.enable()
    print(f"compile cache: {cache_dir}", flush=True)

    trace_dir = os.path.join(ROOT, ".bench_trace")
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)

    def log(msg):
        print(msg, flush=True)

    result = harness.execute(spec, args.seed, args.seconds, bool(args.trace),
                             t_start, devices, log=log, trace_dir=trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    sys.stdout.flush()
    harness.print_compared(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
