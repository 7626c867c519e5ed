#!/usr/bin/env python
"""Times the two latent-attention prefill kernels alone on a TPU and fits
the two cost ratios that `ops/pallas_mla.mla_expanded_pays` weighs their
operations by (`_FORM_COST`, `_ATTEND_COST`).

    python tools/mla_prefill_timing.py [--out timing.json]
    JAX_PLATFORMS=cpu python tools/mla_prefill_timing.py --tiny

At DeepSeek-V2's widths (128 heads, rank 512 in rows of 640 lanes, nope
128, rope 64, v 128; 16-row pages, a 576-page table of scattered pages,
chunks of 512; bf16 Gaussian data), `mla_prefill_chunk` (absorbed) and
`mla_prefill_chunk_expanded` run at p0 0 / 3,584 / 7,680 / 8,192 and
n_real 32 ... 512, each case `K` calls in one profiler trace; a case's
time is the median device time of the kernel's calls.

The fit, at each p0 past the first chunk (where the rows walked, not
the causal triangle of the chunk's own rows, set both kernels' work):
the absorbed time is a line in n_real; the expanded time is flat within
a query block, so the line meets it at one crossover for a chunk of one
block and at another for two. The rule's crossovers are

    n1 = (F rank (nope + v) + A B (nope + rope + v)) / (W + rank)
    n2 = n1 + A B (nope + rope + v) / (W + rank)

(B the query block, W the row's lanes), so the mean measured n1 and n2
give A = `_ATTEND_COST` and F = `_FORM_COST`. The script prints the
table, the measured crossovers, the fitted constants and the crossovers
the rule gives with them and with the module's own; `--out` writes all
of it as JSON. Before timing, the expanded kernel is held to its
gather+dense fallback at two cases.

It refuses any platform but the TPU (exit 2). `--tiny` runs both
kernels once at toy widths through the interpreter, times nothing and
exits 3.
"""
import argparse
import json
import os
import statistics
import sys
import tempfile

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from paddle_tpu.ops import pallas_mla as pm  # noqa: E402

FULL = dict(N=128, W=640, rank=512, nope=128, rope=64, v=128, bs=16,
            mb=576, C=512, pages=1200, P0S=(0, 3584, 7680, 8192),
            NS=(32, 128, 160, 200, 232, 256, 272, 320, 384, 512))
TINY = dict(N=8, W=256, rank=128, nope=128, rope=64, v=128, bs=16,
            mb=40, C=64, pages=60, P0S=(0, 500), NS=(8, 64))
K = 4


def inputs(s):
    rng = np.random.default_rng(0)

    def rand(shape, sd=1.0):
        return jnp.asarray(rng.standard_normal(shape) * sd, jnp.bfloat16)
    pages = rand((s["pages"], s["bs"], s["W"]))
    pages = pages.at[:, :, s["rank"] + s["rope"]:].set(0)
    table = jnp.asarray(rng.permutation(np.arange(1, s["pages"]))[:s["mb"]]
                        .astype(np.int32))
    C, N = s["C"], s["N"]
    return dict(
        pages=pages, table=table, q_abs=rand((C, N, s["W"]), 0.5),
        q_nope=rand((C, N, s["nope"]), 0.5), q_pe=rand((C, N, s["rope"]), 0.5),
        w_uk=rand((N, s["nope"], s["rank"]), s["rank"] ** -0.5),
        w_uv=rand((N, s["rank"], s["v"]), s["rank"] ** -0.5))


def programs(s, x):
    """(absorbed, expanded, expanded's fallback), each (p0, n_real) ->
    output, compiled once for every case."""
    scale = (s["nope"] + s["rope"]) ** -0.5
    i32 = jax.ShapeDtypeStruct((), jnp.int32)

    def absorbed(p0, n):
        return pm.mla_prefill_chunk(x["q_abs"], x["pages"], x["table"], p0,
                                    s["rank"], scale, use_kernel=True,
                                    n_real=n)

    def expanded(p0, n, use_kernel=True):
        return pm.mla_prefill_chunk_expanded(
            x["q_nope"], x["q_pe"], x["pages"], x["w_uk"], x["w_uv"],
            x["table"], p0, scale, use_kernel=use_kernel, n_real=n)
    return tuple(jax.jit(f).lower(i32, i32).compile() for f in (
        absorbed, expanded, lambda p0, n: expanded(p0, n, False)))


def device_times(s, progs):
    """{(form, p0, n_real): median device us of the kernel a call}."""
    from benchmark import trace_reduce as tr
    cases = [(form, p0, n) for p0 in s["P0S"] for n in s["NS"]
             for form in ("absorbed", "expanded")]
    prog = dict(zip(("absorbed", "expanded"), progs))

    def run(case):
        return prog[case[0]](jnp.int32(case[1]), jnp.int32(case[2]))
    for case in cases:
        jax.block_until_ready(run(case))
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    for i, case in enumerate(cases):
        with jax.profiler.TraceAnnotation(f"case{i}"):
            for _ in range(K):
                out = run(case)
            jax.block_until_ready(out)
    jax.profiler.stop_trace()
    trace = tr.load(tr.find_xplane(d),
                    host_names={f"case{i}" for i in range(len(cases))})
    ops = next(iter(trace.devices.values()))
    spans = {name: (a, b) for name, a, b in trace.host}
    times = {}
    for i, case in enumerate(cases):
        a, b = spans[f"case{i}"]
        kern = [e - s_ for name, s_, e in ops
                if s_ >= a and e <= b and "mla_prefill_chunk" in name]
        times[case] = statistics.median(kern) / 1e3
    return times


def crossovers(s, times):
    """{p0: (n1, n2)} where the absorbed time's line in n_real meets the
    expanded time of a chunk of one query block and of two."""
    block = min(pm._QUERY_BLOCK, s["C"])
    out = {}
    for p0 in s["P0S"][1:]:
        ns = np.array(s["NS"], float)
        slope, icept = np.polyfit(
            ns, [times["absorbed", p0, n] for n in s["NS"]], 1)
        out[p0] = tuple(
            (statistics.median(times["expanded", p0, n] for n in s["NS"]
                               if -(-n // block) == k) - icept) / slope
            for k in (1, 2))
    return out


def fit(s, n1, n2):
    """(_FORM_COST, _ATTEND_COST) whose rule crosses at n1 and n2."""
    block = min(pm._QUERY_BLOCK, s["C"])
    per_pos = pm._lanes(s["rank"] + s["rope"]) + s["rank"]
    attend = block * (s["nope"] + s["rope"] + s["v"])
    a = (n2 - n1) * per_pos / attend
    f = (n1 * per_pos - a * attend) / (s["rank"] * (s["nope"] + s["v"]))
    return f, a


def rule_crossovers(s, form, attend):
    saved = pm._FORM_COST, pm._ATTEND_COST
    pm._FORM_COST, pm._ATTEND_COST = form, attend
    try:
        pays = [n for n in range(1, s["C"] + 1) if pm.mla_expanded_pays(
            n, s["N"], s["rank"], s["nope"], s["rope"], s["v"], s["C"])]
    finally:
        pm._FORM_COST, pm._ATTEND_COST = saved
    return [n for n in pays if n - 1 not in pays]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", help="write the table and the fit as JSON")
    ap.add_argument("--tiny", action="store_true",
                    help="toy widths on any platform; times nothing")
    args = ap.parse_args()
    if not args.tiny and jax.default_backend() != "tpu":
        print(f"refused: platform {jax.default_backend()}, not tpu")
        return 2
    s = TINY if args.tiny else FULL
    progs = programs(s, inputs(s))
    # the kernel against its fallback, bf16, scaled by the largest |out|
    errors = {}
    for p0, n in ((s["P0S"][-2], s["C"]), (s["P0S"][-1], s["C"] // 2 + 16)):
        got, want = (np.asarray(p(jnp.int32(p0), jnp.int32(n)), np.float32)
                     for p in progs[1:])
        errors[f"p0={p0} n_real={n}"] = float(
            np.abs(got - want).max() / max(1.0, np.abs(want).max()))
        assert not got[n:].any(), (p0, n)
    print("expanded kernel against its fallback:", errors, flush=True)
    if args.tiny:
        for p in progs[:2]:
            jax.block_until_ready(p(jnp.int32(s["P0S"][-1]),
                                    jnp.int32(s["NS"][-1])))
        print("NOT A CHIP RUN: nothing timed")
        return 3

    times = device_times(s, progs)
    print("device us a call, absorbed / expanded")
    print("p0 \\ n_real " + " ".join(f"{n:>15}" for n in s["NS"]))
    for p0 in s["P0S"]:
        print(f"{p0:>11} " + " ".join(
            f"{times['absorbed', p0, n]:>7.1f}/{times['expanded', p0, n]:<7.1f}"
            for n in s["NS"]))
    cross = crossovers(s, times)
    n1 = statistics.mean(c[0] for c in cross.values())
    n2 = statistics.mean(c[1] for c in cross.values())
    form, attend = fit(s, n1, n2)
    result = dict(
        device=jax.devices()[0].device_kind, errors=errors,
        us={f"{f} p0={p} n={n}": t for (f, p, n), t in times.items()},
        crossovers={str(p): c for p, c in cross.items()},
        mean_crossovers=(n1, n2), form_cost=form, attend_cost=attend,
        rule_fitted=rule_crossovers(s, form, attend),
        rule_module=rule_crossovers(s, pm._FORM_COST, pm._ATTEND_COST),
        module=(pm._FORM_COST, pm._ATTEND_COST))
    for k in ("crossovers", "mean_crossovers", "form_cost", "attend_cost",
              "rule_fitted", "rule_module", "module"):
        print(k, result[k])
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
