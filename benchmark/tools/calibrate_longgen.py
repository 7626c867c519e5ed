"""Readings for `served_logit_gap_p<share>`, `route_left_out`,
`state_gap` and `state_gap_first` in a cell of the `serve_qwen3next` driver, taken on the chip
at the cell's own size, many seeds in one process over a fresh engine a
seed.

    python3 benchmark/tools/calibrate_longgen.py --workload <cell> \
        --seeds 11,12,13 [--control-seeds 11:fp8_operands+bf16_state] \
        [--seconds 30] [--faults norm_order:21,state_dropped:22] \
        [--dump dir]

For each seed: the program's readings against the plain reference, and
for the control seeds what the reference gives when computed in the
lower precision (`fp8_operands`, the step below the bfloat16 the
configuration states; `bf16_state`, the stated precision with the
delta-rule state rounded to bfloat16 after every token, as a bfloat16
state arena would hold it; `bf16_operands`, the stated precision
itself, which a sound limit must pass): the tokens it puts first and the
states it reaches over the live requests' tokens, each through the
comparison of a run at the limits in the cell's file. A fault is
planted in the PROGRAM for one window on a seed of its own and must come
out not correct:

  norm_order      the linear layers' gated norm in Mamba-2's order:
                  RMSNorm(o * silu(z)) * w
  no_output_gate  the full layers' output not multiplied by
                  sigmoid(gate)
  rope_full       rotary over all 256 dimensions of a head
  state_dropped   a chunk starts from zeros whatever its request's row
                  holds: the state is not carried from one chunk to the
                  next
  no_shared_gate  the shared expert not scaled by its sigmoid gate

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
            "bf16_state": {"act": "bf16", "state": "bf16"},
            "bf16_operands": {"act": "bf16"}}
FAULTS = ("norm_order", "no_output_gate", "rope_full", "state_dropped",
          "no_shared_gate")


def emit(**row):
    print(json.dumps(row), flush=True)


def verdict(rows):
    return all(r["value"] <= r["limit"] for r in rows)


def planted(base, fault):
    """The driver with `fault` planted in the program it builds: the
    engine's steps are traced during set-up, with the fault in place."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import qwen3_next as prog
    if fault not in FAULTS:
        raise SystemExit(f"no fault {fault!r}: one of {FAULTS}")

    def mamba_order(x, gate, w, eps):
        f = x.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
        f = f * jax.lax.rsqrt(jnp.mean(f * f, axis=-1, keepdims=True) + eps)
        return (f * w.astype(jnp.float32)).astype(x.dtype)

    def ungated(attn, o, gate, x):
        return prog.matmul(o.astype(x.dtype), attn.o._value)

    class Planted(base):
        def setup(self):
            real = (prog.gated_rms_norm, prog.GatedAttention._out,
                    prog.GatedDeltaNet.prefill, prog.SharedExpert.run)
            if fault == "norm_order":
                prog.gated_rms_norm = mamba_order
            if fault == "no_output_gate":
                prog.GatedAttention._out = ungated
            if fault == "state_dropped":
                prog.GatedDeltaNet.prefill = \
                    lambda mixer, x, pages, view: real[2](
                        mixer, x, pages, view._replace(p0=0 * view.p0))
            if fault == "no_shared_gate":
                prog.SharedExpert.run = prog.GatedMLP.run
            try:
                return super().setup()      # the steps are traced here
            finally:
                (prog.gated_rms_norm, prog.GatedAttention._out,
                 prog.GatedDeltaNet.prefill, prog.SharedExpert.run) = real

        def build_model(self, max_seq_len, dtype):
            model = super().build_model(max_seq_len, dtype)
            for block in model.blocks:
                if fault == "rope_full" \
                        and isinstance(block.mixer, prog.GatedAttention):
                    block.mixer.rotary_dim = model.config.head_dim
            return model
    return Planted


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="",
                    help="seed or seed:control+control, ... (all of "
                         f"{'+'.join(CONTROLS)} where none is named)")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--faults", default="",
                    help="fault:seed,... of " + ", ".join(FAULTS))
    ap.add_argument("--dump", default="",
                    help="directory for each reading's margins and gaps "
                         "a token, and its state distances (.npz)")
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
    from benchmark.drivers.serve_qwen3next import Driver
    for seed, fault in runs:
        t0 = time.perf_counter()
        d = (planted(Driver, fault) if fault else Driver)(
            spec, seed, a.seconds, devices, log=lambda m: None)
        d.setup()
        m = d.window(None)
        d.release()
        names = controls.get(seed, ()) if not fault else ()
        g = d.gaps({n: CONTROLS[n] for n in names})
        st = d.state_gaps()
        kind = "fault_" + fault if fault else "program"
        if a.dump:
            os.makedirs(a.dump, exist_ok=True)
            np.savez(os.path.join(a.dump, f"{kind}_{seed}.npz"),
                     margin=g["margin"], below=g["below"],
                     rows=np.array(["served"] + list(g["controls"])),
                     by_layer=st["by_layer"], by_head=st["by_head"],
                     live_tokens=np.asarray(st["tokens"]))
        for name in names:
            c = g["controls"][name]
            low = d.state_gaps(CONTROLS[name])
            if a.dump:
                np.savez(os.path.join(a.dump, f"control_{name}_{seed}.npz"),
                         by_layer=low["by_layer"], by_head=low["by_head"])
            emit(kind="control_" + name, seed=seed,
                 served_logit_gap=c["gap"], flipped=c["flipped"],
                 state_gap=low["worst"], state_gap_first=low["first"],
                 compared=g["compared"], tokens=g["tokens"],
                 correct=verdict(d.compared(c["gap"], g["left_out"], low)))
        emit(kind=kind, seed=seed, served_logit_gap=g["served"],
             route_left_out=g["left_out"], state_gap=st["worst"],
             state_gap_first=st["first"], state_gap_head=st["worst_head"],
             live_tokens=st["tokens"],
             correct=verdict(d.compared(g["served"], g["left_out"], st)),
             flipped=g["flipped"], compared=g["compared"],
             tokens=g["tokens"], widest=g["widest"],
             widest_of_all=g["widest_of_all"], lengths=g["lengths"],
             attempted=m["attempted"], failed=m["failed"],
             tokens_per_s=m["end_to_end"]["serve_tokens_per_s"],
             s=time.perf_counter() - t0)
        del d
        gc.collect()


if __name__ == "__main__":
    main()
