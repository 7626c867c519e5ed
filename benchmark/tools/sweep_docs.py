"""Find the knee of the DeepSeek-V2 serving cell once, on the chip:
`tools/sweep.py` for a cell whose traffic names another driver than
`drivers/serve.py`. Every rate is a window of its own (a new engine and
a new schedule, drained at the close), all in one process over one set
of seeded weights, so that a point costs its window and not the 40 s of
drawing 10 GB again.

    python3 benchmark/tools/sweep_docs.py --workload deepseek-v2.serve-docs \
        --rates 1,1.5,2,2.5 --seed 1 --seconds 45

One JSON line a rate: the rate delivered, the tails, how late first
tokens were in the first and in the second half of the window (a
backlog that grows shows as a rising trend) and how long the drain
took. The benchmark's own runs never run this: the rate a cell runs at
is a number in its traffic file.
"""
import argparse
import copy
import gc
import importlib
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=45.0)
    a = ap.parse_args()
    spec = harness.Spec(a.workload)
    devices = harness.device_look(spec)
    from paddle_tpu import compile_cache
    compile_cache.enable()
    driver = importlib.import_module(
        "benchmark.drivers." + spec.traffic["driver"]).Driver
    kept = {}

    class OneModel(driver):
        def build_model(self, *args):
            if "model" not in kept:
                kept["model"] = super().build_model(*args)
            return kept["model"]

    traffic = copy.deepcopy(spec.traffic)
    for rate in (float(r) for r in a.rates.split(",")):
        spec.traffic = copy.deepcopy(traffic)
        spec.traffic["arrivals"]["rate_per_s"] = rate
        # a knee is judged on what every request due was answered with
        spec.traffic.update(at_close="drain", drain_s=90)
        d = OneModel(spec, a.seed, a.seconds, devices, log=print)
        d.setup()
        t0 = time.monotonic()
        m = d.window(None)
        took = time.monotonic() - t0
        recs = [r for r in d.recs if r.times]
        half = a.seconds / 2
        first = [r.times[0] - r.due_s for r in recs if r.due_s < half]
        second = [r.times[0] - r.due_s for r in recs if r.due_s >= half]
        base = t0
        mean = lambda xs: 1e3 * (sum(xs) / len(xs) - base) if xs else None
        print(json.dumps({
            "rate": rate, "due": len(d.recs), **m["end_to_end"],
            "finished": sum(r.state == "finished" for r in d.recs),
            "ttft_mean_first_half_ms": mean(first),
            "ttft_mean_second_half_ms": mean(second),
            "drain_s": took - a.seconds, "failed": m["failed"],
            "counters": m["records"]["counters"]}), flush=True)
        d.release()
        del d
        gc.collect()


if __name__ == "__main__":
    main()
