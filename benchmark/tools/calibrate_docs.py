"""Readings for `served_logit_gap` in a cell of the `serve_mla` driver,
taken on the chip at the cell's own size, many seeds in one process
(`tools/calibrate.py` does this for the GPT cells; it builds
`drivers.serve.Driver` by name).

    python3 benchmark/tools/calibrate_docs.py --workload <cell> \
        --seeds 11,12,13 [--control-seeds 11,12:fp8_latent_only] \
        [--seconds 15] [--faults zero_routed:21,shifted_held:22]

For each seed: the program's reading against the plain reference, and
for the control seeds the tokens the reference puts first when computed
in the lower precision (fp8 operands, the step below the bfloat16 the
configuration states; an fp8 latent cache alone; and bf16 operands, the
stated precision itself, which a sound limit must pass), each through
the comparison of a run at the limits in the cell's file. A fault is
planted in the PROGRAM for one window on a seed of its own
(`zero_routed`: the grouped expert products give zeros; `shifted_held`:
the expert layer is told it holds experts first+1.. while its weights
are first..) and must come out not correct. One JSON line a reading;
PERF.md section 2 has the table.
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

from benchmark import harness    # noqa: E402

CONTROLS = {"fp8_operands": {"act": "fp8"},
            "fp8_latent_only": {"act": "f32", "latent": "fp8"},
            "bf16_operands": {"act": "bf16"}}


def emit(**row):
    print(json.dumps(row), flush=True)


def verdict(rows):
    return all(r["value"] <= r["limit"] for r in rows)


def planted(base, fault):
    """The driver with `fault` planted in the program it builds."""
    from paddle_tpu.moe import serving as moe

    class Planted(base):
        def setup(self):
            real = moe.moe_grouped_ffn
            if fault == "zero_routed":
                moe.moe_grouped_ffn = lambda *a, **kw: 0 * real(*a, **kw)
            try:
                return super().setup()      # the steps are traced here
            finally:
                moe.moe_grouped_ffn = real

        def build_model(self, *args):
            model = super().build_model(*args)
            if fault == "shifted_held":
                first, count = model.config.held
                model.config.held = (first + 1, count)
            return model
    return Planted


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="",
                    help="seed or seed:control+control, ... (all of "
                         f"{'+'.join(CONTROLS)} where none is named)")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--faults", default="",
                    help="fault:seed,... (zero_routed, shifted_held)")
    ap.add_argument("--dump", default="",
                    help="directory for each reading's margins and gaps "
                         "a token (.npz)")
    a = ap.parse_args()
    controls = {int(s.split(":")[0]): (s.split(":")[1].split("+")
                                       if ":" in s else list(CONTROLS))
                for s in a.control_seeds.split(",") if s}
    runs = [(int(s), None) for s in a.seeds.split(",") if s] \
        + [(int(f.split(":")[1]), f.split(":")[0])
           for f in a.faults.split(",") if f]
    spec = harness.Spec(a.workload)
    devices = harness.device_look(spec)
    from paddle_tpu import compile_cache
    compile_cache.enable()
    from benchmark.drivers.serve_mla import Driver
    for seed, fault in runs:
        t0 = time.perf_counter()
        d = (planted(Driver, fault) if fault else Driver)(
            spec, seed, a.seconds, devices, log=lambda m: None)
        d.setup()
        m = d.window(None)
        d.release()
        g = d.gaps({n: CONTROLS[n] for n in controls.get(seed, ())}
                   if not fault else None)
        if a.dump:
            import numpy as np
            os.makedirs(a.dump, exist_ok=True)
            np.savez(os.path.join(a.dump, f"{fault or 'program'}_{seed}.npz"),
                     margin=g["margin"], below=g["below"],
                     rows=np.array(["served"] + list(g["controls"])))
        for name, c in g["controls"].items():
            emit(kind="control_" + name, seed=seed,
                 served_logit_gap=c["gap"], flipped=c["flipped"],
                 compared=g["compared"], tokens=g["tokens"],
                 correct=verdict(d.compared(c["gap"], g["left_out"])))
        emit(kind="fault_" + fault if fault else "program", seed=seed,
             served_logit_gap=g["served"], route_left_out=g["left_out"],
             correct=verdict(d.compared(g["served"], g["left_out"])),
             flipped=g["flipped"], compared=g["compared"],
             tokens=g["tokens"], widest=g["widest"],
             widest_of_all=g["widest_of_all"], attempted=m["attempted"], failed=m["failed"],
             s=time.perf_counter() - t0)
        del d
        gc.collect()


if __name__ == "__main__":
    main()
