"""Find the knee of the Qwen3-Next serving cell once, on the chip:
`tools/sweep_docs.py`'s sweep (every rate a window of its own, drained
at the close, all in one process over one set of seeded weights) for the
cell whose traffic names `drivers/serve_qwen3next.py`, with the numbers
that see the engine saturate in a window shorter than its requests.

    python3 benchmark/tools/sweep_longgen.py \
        --workload qwen3-next-80b-a3b.serve-longgen \
        --rates 1.5,2,2.5,3,4 --seed 1 --seconds 45

An answer here runs for longer than the 45 s window, so first tokens
are not late while slots are free, however far the device has fallen
behind: the engine admits every request and decodes them all more
slowly. What does show it is the share of the output tokens asked in
the window that the window delivers: an engine that keeps up delivers
the same share at every rate (the rest falls after the close), one
that has saturated the device delivers a falling share. The knee is the
highest rate whose share is within a tenth of the lowest rate's; the
cell runs at 1.5 x that, and the traffic file's `rate_from` keeps the
readings. One JSON line a rate: sweep_docs's numbers, and the tokens
asked and delivered in the window and their share.
"""
import argparse
import copy
import gc
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
    from benchmark.drivers.serve_qwen3next import Driver
    kept = {}

    class OneModel(Driver):
        def build_model(self, *args):
            if "model" not in kept:
                kept["model"] = super().build_model(*args)
            return kept["model"]

    traffic = copy.deepcopy(spec.traffic)
    for rate in (float(r) for r in a.rates.split(",")):
        spec.traffic = copy.deepcopy(traffic)
        spec.traffic["arrivals"]["rate_per_s"] = rate
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
        mean = lambda xs: 1e3 * (sum(xs) / len(xs) - t0) if xs else None
        asked = sum(r.want for r in d.recs)
        delivered = m["end_to_end"]["serve_tokens_per_s"] * a.seconds
        print(json.dumps({
            "rate": rate, "due": len(d.recs), **m["end_to_end"],
            "asked": asked, "delivered": delivered,
            "delivered_share": delivered / asked if asked else None,
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
