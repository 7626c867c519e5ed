"""Readings for the limits of `correct`, taken on the chip at a cell's
own size, many seeds in one process (set-up is most of a run's cost).

    python3 benchmark/tools/calibrate.py --workload <cell> \
        --seeds 11,12,... [--control-seeds 11,12,13] [--seconds 15]

For each seed: the program's numbers against the plain reference (the
lower reading is the largest of them), and for the control seeds the
same numbers with the reference computed in the lower precision put in
the program's place, and for a training cell with each fault planted in
it. Every reading goes through the comparison of a run, at the limits in
the cell's file, and carries the `correct` that a run would print. Prints one JSON line per reading; PERF.md section 2 has the table
the limits were set from. The benchmark's own runs never run this.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import correct, harness    # noqa: E402

# the nearest precision below what the configurations state (bf16
# compute, int8 weights where served): fp8 operands; int4 weights
TRAIN_CONTROL = {"act": "fp8", "wbits": None}
# (the control), and what else a later PR might be tempted by: an fp8
# K/V cache alone; int4 weights
SERVE_CONTROLS = {"fp8_operands": {"act": "fp8", "wbits": 8},
                  "fp8_kv_only": {"act": "f32", "wbits": 8, "kv": "fp8"},
                  "int4_weights": {"act": "f32", "wbits": 4}}


def emit(**row):
    print(json.dumps(row), flush=True)


def verdict(rows):
    """`correct` as harness.execute decides it, at the cell's limits."""
    return all(r["value"] <= r["limit"] for r in rows)


def rows_of(prog, ref, limits):
    rows = correct.train_rows(prog, ref, limits)
    out = {r["name"]: r["value"] for r in rows}
    out["loss_gaps"] = correct.loss_gaps(prog, ref)
    out["correct"] = verdict(rows)
    return out


def train(spec, seeds, control_seeds, devices):
    from benchmark.drivers.train import Driver
    for seed in seeds:
        t0 = time.perf_counter()
        d = Driver(spec, seed, 0, devices, log=lambda m: None)
        d.setup()
        prog = d.readings
        d.release()
        ref = d.reference_readings()
        emit(kind="program", seed=seed, losses=prog["losses"],
             s=time.perf_counter() - t0, **rows_of(prog, ref, spec.cell["limits"]))
        if seed in control_seeds:
            low = d.reference_readings(prec=TRAIN_CONTROL)
            emit(kind="control_fp8", seed=seed,
                 **rows_of(low, ref, spec.cell["limits"]))
            half = [(i[:len(i) // 2], l[:len(l) // 2]) for i, l in d.pool[:3]]
            emit(kind="fault_half_batch", seed=seed,
                 **rows_of(d.reference_readings(batches=half), ref,
                           spec.cell["limits"]))
            bf16 = d.reference_readings(prec={"act": "bf16", "wbits": None})
            emit(kind="reference_in_bf16", seed=seed,
                 **rows_of(bf16, ref, spec.cell["limits"]))
        del d
        gc.collect()


def serve(spec, seeds, control_seeds, devices, seconds):
    from benchmark.drivers.serve import Driver
    for seed in seeds:
        t0 = time.perf_counter()
        d = Driver(spec, seed, seconds, devices, log=lambda m: None)
        d.setup()
        m = d.window(None)
        d.release()
        if seed in control_seeds:
            for name, prec in SERVE_CONTROLS.items():
                g = d.gaps(prec_low=prec)
                emit(kind="control_" + name, seed=seed,
                     served_logit_gap=g["control"],
                     flipped=g["control_flipped"], tokens=g["tokens"],
                     correct=verdict(d.compared(g["control"])))
        else:
            g = d.gaps()
        emit(kind="program", seed=seed, served_logit_gap=g["served"],
             correct=verdict(d.compared(g["served"])),
             flipped=g["flipped"], tokens=g["tokens"],
             requests=len(d.sample()), attempted=m["attempted"],
             failed=m["failed"], s=time.perf_counter() - t0)
        del d
        gc.collect()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    a = ap.parse_args()
    seeds = [int(s) for s in a.seeds.split(",")]
    controls = {int(s) for s in a.control_seeds.split(",") if s}
    spec = harness.Spec(a.workload)
    devices = harness.device_look(spec)
    from paddle_tpu import compile_cache
    compile_cache.enable()
    if spec.traffic["driver"] == "train":
        train(spec, seeds, controls, devices)
    else:
        serve(spec, seeds, controls, devices, a.seconds)


if __name__ == "__main__":
    main()
