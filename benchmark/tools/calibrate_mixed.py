"""Readings for `served_logit_gap_p80` and `route_left_out` in a cell of
the `serve_exaone` driver, taken on the chip at the cell's own size,
many seeds in one process over a fresh engine a seed.

    python3 benchmark/tools/calibrate_mixed.py --workload <cell> \
        --seeds 11,12,13 [--control-seeds 11,12:fp8_operands] \
        [--seconds 20] [--faults window_129:21,stale_ring:22] [--dump dir]

For each seed: the program's reading against the plain reference, and
for the control seeds the tokens the reference puts first when computed
in the lower precision (fp8 operands, the step below the bfloat16 the
configuration states; bf16 operands, the stated precision itself, which
a sound limit must pass), each through the comparison of a run at the
limits in the cell's file. A fault is planted in the PROGRAM for one
window on a seed of its own and must come out not correct:

  window_129       a window layer sees 129 keys (its ring holds 129)
  rope_in_full     the full layers rotate q and k too
  bias_in_weights  the router's weights are taken from score + bias
  no_renorm        the chosen weights are not divided by their sum
  stale_ring       a chunk takes every ring row for a position of its
                   own request: a request's first window of positions
                   sees what the row's last owner left

One JSON line a reading; PERF.md section 2 has the table.
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
            "bf16_operands": {"act": "bf16"}}
FAULTS = ("window_129", "rope_in_full", "bias_in_weights", "no_renorm",
          "stale_ring")


def emit(**row):
    print(json.dumps(row), flush=True)


def verdict(rows):
    return all(r["value"] <= r["limit"] for r in rows)


def planted(base, fault):
    """The driver with `fault` planted in the program it builds."""
    import jax
    import jax.numpy as jnp
    from benchmark.drivers.serve_exaone import seeded_program_model
    from paddle_tpu.models import exaone_moe as prog
    if fault not in FAULTS:
        raise SystemExit(f"no fault {fault!r}: one of {FAULTS}")

    def biased_weights(x, w_gate, bias, k, scale=1.0, renorm=True):
        scores = jax.nn.sigmoid(jnp.dot(
            x, w_gate.astype(x.dtype), preferred_element_type=jnp.float32))
        weights, experts = jax.lax.top_k(
            scores + bias.astype(jnp.float32), k)
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
        return weights * scale, experts.astype(jnp.int32)

    class Planted(base):
        def setup(self):
            real = {n: getattr(prog, n) for n in
                    ("route_sigmoid_topk", "window_prefill_chunk")}
            if fault == "bias_in_weights":
                prog.route_sigmoid_topk = biased_weights
            if fault == "stale_ring":
                # one window further on, every ring row is a position of
                # this request, and nothing else of the mask moves
                def shifted(q, k, v, rk, rv, row, p0, *a, **kw):
                    return real["window_prefill_chunk"](
                        q, k, v, rk, rv, row, p0 + rk.shape[1], *a, **kw)
                prog.window_prefill_chunk = shifted
            try:
                return super().setup()      # the steps are traced here
            finally:
                for n, fn in real.items():
                    setattr(prog, n, fn)

        def build_model(self, max_seq_len, dtype):
            m = self.m
            if fault == "window_129":
                m = dict(m, sliding_window=m["sliding_window"] + 1)
            model = seeded_program_model(m, self.seed, self.init,
                                         max_seq_len, dtype=dtype)
            if fault == "rope_in_full":
                for block in model.blocks:
                    block.attn.rotates = True
            if fault == "no_renorm":
                model.config.norm_topk_prob = False
            return model
    return Planted


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="",
                    help="seed or seed:control+control, ... (all of "
                         f"{'+'.join(CONTROLS)} where none is named)")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--faults", default="",
                    help="fault:seed,... of " + ", ".join(FAULTS))
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
    from benchmark.drivers.serve_exaone import Driver
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
             widest_of_all=g["widest_of_all"], lengths=g["lengths"],
             attempted=m["attempted"], failed=m["failed"],
             tokens_per_s=m["end_to_end"]["serve_tokens_per_s"],
             s=time.perf_counter() - t0)
        del d
        gc.collect()


if __name__ == "__main__":
    main()
