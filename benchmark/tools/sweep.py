"""Find the knee of an open-loop serving cell once, on the chip: one run
at one offered rate, which overrides the rate in the cell's traffic file
and drains what is due at the close (and nothing else). Run it once per rate, each a new process.

    python3 benchmark/tools/sweep.py --workload <cell> --rate 9 \
        --seed 1 --seconds 45

Prints the tails, the rate delivered, how late first tokens were in the
first and in the second half of the window (a backlog that grows shows
as a rising trend), and how long the drain took. The benchmark's own
runs never run this: the rate a cell runs at is a number in its traffic
file.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness    # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rate", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=45.0)
    a = ap.parse_args()
    t_start = time.time()
    spec = harness.Spec(a.workload)
    spec.traffic["arrivals"]["rate_per_s"] = a.rate
    # a knee is judged on what every request due was answered with
    spec.traffic.update(at_close="drain", drain_s=60)
    devices = harness.device_look(spec)
    from paddle_tpu import compile_cache
    compile_cache.enable()
    from benchmark.drivers.serve import Driver
    d = Driver(spec, a.seed, a.seconds, devices, log=print)
    d.setup()
    t0 = time.monotonic()
    m = d.window(None)
    took = time.monotonic() - t0
    recs = [r for r in d.recs if r.times]
    half = a.seconds / 2
    first = [(r.times[0] - r.due_s) for r in recs if r.due_s < half]
    second = [(r.times[0] - r.due_s) for r in recs if r.due_s >= half]
    base = min(r.times[0] - r.due_s for r in recs)      # t0 offset
    out = {"rate": a.rate, "due": len(d.recs), **m["end_to_end"],
           "ttft_mean_first_half_ms": 1e3 * (sum(first) / len(first) - base),
           "ttft_mean_second_half_ms":
           1e3 * (sum(second) / len(second) - base),
           "drain_s": took - a.seconds, "failed": m["failed"],
           "setup_s": t0 - (time.monotonic() - (time.time() - t_start)),
           "counters": m["records"]["counters"]}
    print(json.dumps(out), flush=True)
    d.release()


if __name__ == "__main__":
    main()
