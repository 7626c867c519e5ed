"""Readings for `served_logit_gap` and `state_gap` in a cell of the
`serve_hybrid` driver, taken on the chip at the cell's own size and
load, many seeds in one process (`tools/calibrate_docs.py` does this for
the DeepSeek-V2 cell; it builds `drivers.serve_mla.Driver` by name).

    python3 benchmark/tools/calibrate_many.py --workload <cell> \
        --seeds 11,12,13 [--control-seeds 11:fp8_operands+bf16_state] \
        [--seconds 45] [--faults state_dropped:21,row_kept:22] \
        [--block-scale 1.5] [--detail chiprun_out/states]

For each seed: the program's readings against the plain reference, and
for the control seeds what the reference gives when computed in the
lower precision (`fp8_operands`, the step below the bfloat16 the
configuration states; `bf16_state`, the recurrent state alone rounded to
bfloat16 after every token; `bf16_operands`, the stated precision
itself, which a sound limit must pass): the tokens it puts first and the
states it reaches over the live requests' tokens, each through the
comparison of a run at the limits in the cell's file. A fault is
planted in the PROGRAM for one window on a seed of its own and must come
out not correct: `state_dropped` (a chunk starts from zeros whatever its
request's row holds: the state is not carried from one chunk to the
next), `row_kept` (a chunk at position 0 starts from what the row held:
a row is not cleared on reuse), `bf16_state` (the program's own state
rounded to bfloat16 after every step and chunk). `--block-scale` runs at
another `init` than the cell's file; `--detail` writes each reading's
distances by request, layer and head to `<prefix>_<kind>_<seed>.npz`.
One JSON line a reading; PERF.md section 2 has the table.
"""
import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness    # noqa: E402

CONTROLS = {"fp8_operands": {"act": "fp8"},
            "bf16_state": {"act": "f32", "state": "bf16"},
            "bf16_operands": {"act": "bf16"}}
FAULTS = ("state_dropped", "row_kept", "bf16_state")


def emit(**row):
    print(json.dumps(row), flush=True)


def verdict(rows):
    return all(r["value"] <= r["limit"] for r in rows)


def reading(d, detail, kind, gap, flipped, tokens, st, **more):
    """One reading's line through the run's comparison, and its
    distances by request, layer and head where `detail` names a place."""
    if detail:
        np.savez(f"{detail}_{kind}_{d.seed}.npz", by_layer=st["by_layer"],
                 by_head=st["by_head"], tokens=np.asarray(st["tokens"]))
    emit(kind=kind, seed=d.seed, served_logit_gap=gap,
         state_gap=st["worst"], state_gap_head=st["worst_head"],
         correct=verdict(d.compared(gap, st["worst"])), flipped=flipped,
         tokens=tokens, live_tokens=st["tokens"], **more)


def planted(base, fault):
    """The driver with `fault` planted in the program it builds: the
    engine's steps are traced during set-up, with the fault in place."""
    import jax
    from paddle_tpu.models import granite_hybrid as gh
    if fault not in FAULTS:
        raise SystemExit(f"no fault {fault!r} (one of {FAULTS})")

    def rounded(fn, state_at):
        """`fn` with output `state_at` (the state) rounded to the
        numbers bfloat16 holds (`reduce_precision`: a pair of
        conversions XLA drops on the TPU, and the fault with it)."""
        def run(*args, **kw):
            out = list(fn(*args, **kw))
            out[state_at] = jax.lax.reduce_precision(
                out[state_at], exponent_bits=8, mantissa_bits=7)
            return tuple(out)
        return run

    class Planted(base):
        def setup(self):
            real = (gh.Mamba2.prefill, gh.mamba2_state_step,
                    gh.mamba2_chunk_scan)
            if fault == "bf16_state":
                gh.mamba2_state_step = rounded(real[1], 0)  # (state, y)
                gh.mamba2_chunk_scan = rounded(real[2], 1)  # (y, state)
            else:
                at = 0 if fault == "state_dropped" else 1
                gh.Mamba2.prefill = lambda mixer, x, pages, view: real[0](
                    mixer, x, pages, view._replace(p0=0 * view.p0 + at))
            try:
                return super().setup()
            finally:
                (gh.Mamba2.prefill, gh.mamba2_state_step,
                 gh.mamba2_chunk_scan) = real
    return Planted


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="",
                    help="seed or seed:control+control, ... (all of "
                         f"{'+'.join(CONTROLS)} where none is named)")
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--faults", default="",
                    help=f"fault:seed,... ({', '.join(FAULTS)})")
    ap.add_argument("--block-scale", type=float, default=None)
    ap.add_argument("--detail", default="")
    a = ap.parse_args()
    controls = {int(s.split(":")[0]): (s.split(":")[1].split("+")
                                       if ":" in s else list(CONTROLS))
                for s in a.control_seeds.split(",") if s}
    runs = [(int(s), None) for s in a.seeds.split(",") if s] \
        + [(int(f.split(":")[1]), f.split(":")[0])
           for f in a.faults.split(",") if f]
    spec = harness.Spec(a.workload)
    if a.block_scale is not None:
        spec.cell.setdefault("init", {})["block_scale"] = a.block_scale
    devices = harness.device_look(spec)
    from paddle_tpu import compile_cache
    compile_cache.enable()
    from benchmark.drivers.serve_hybrid import Driver
    for seed, fault in runs:
        t0 = time.perf_counter()
        d = (planted(Driver, fault) if fault else Driver)(
            spec, seed, a.seconds, devices, log=lambda m: None)
        d.setup()
        m = d.window(None)
        d.release()
        names = controls.get(seed, ()) if not fault else ()
        g = d.gaps({n: CONTROLS[n] for n in names})

        for name in names:
            c = g["controls"][name]
            reading(d, a.detail, "control_" + name, c["gap"], c["flipped"],
                    g["tokens"], d.state_gaps(CONTROLS[name]))
        reading(d, a.detail, "fault_" + fault if fault else "program",
                g["served"], g["flipped"], g["tokens"], d.state_gaps(),
                init=spec.cell.get("init", {}),
                tokens_per_s=m["end_to_end"]["serve_tokens_per_s"],
                attempted=m["attempted"], failed=m["failed"],
                s=time.perf_counter() - t0)
        del d
        gc.collect()


if __name__ == "__main__":
    main()
