"""Op-level micro-benchmark suite — the op-benchmark CI input.

Parity target: the reference's benchmark CI
(`tools/test_ci_op_benchmark.sh` driving the op-benchmark repo, results
checked by `tools/check_op_benchmark_result.py`). Each case times an op
with the loop INSIDE one jit program (`lax.fori_loop` chaining iterates
on the output) — per-dispatch timing is unfair to sub-millisecond ops.

Usage:
    python tools/op_bench.py --out op_bench.json [--iters 30] [--small]
Emits one JSON object {case_name: {"ms": float, "shape": ..., ...}}.
Compare two runs with tools/check_op_benchmark_result.py.

NOTE: for the REGISTERED Pallas kernels, prefer
`tools/kernellab.py` — it measures kernel vs declared fallback on
identical seeded inputs, attributes time against the KN503-traced
roofline, and persists best-known timings to tools/kernel_db.json.
This suite stays for ops without a registry entry (elementwise,
reductions, XLA-lowered composites) and for A/B runs across commits.
"""
import argparse
import json
import sys
import time


def _cases(small):
    import numpy as np

    s = 4 if small else 1
    rs = np.random.RandomState(0)

    def t(*shape):
        return rs.randn(*shape).astype(np.float32)

    B, S, D, F = 8 // s, 1024 // s, 768 // s, 3072 // s
    return {
        "matmul_f32": dict(op="matmul", args=[t(B * S, D), t(D, D)]),
        "matmul_bf16": dict(op="matmul_bf16", args=[t(B * S, D), t(D, D)]),
        "conv2d_3x3": dict(op="conv2d",
                           args=[t(8 // s, 64 // s, 56, 56),
                                 t(64 // s, 64 // s, 3, 3)]),
        "layer_norm": dict(op="layer_norm", args=[t(B, S, D)]),
        "softmax": dict(op="softmax", args=[t(B, S, S)]),
        "gelu": dict(op="gelu", args=[t(B, S, F)]),
        "embedding": dict(op="embedding",
                          args=[rs.randint(0, 50000 // s,
                                           (B, S)).astype(np.int32),
                                t(50000 // s, D)]),
        "attention": dict(op="attention",
                          args=[t(B, S, 12 // max(1, s // 2), 64)]),
        "cross_entropy": dict(op="cross_entropy",
                              args=[t(B * S, 50000 // s),
                                    rs.randint(0, 50000 // s, (B * S,))
                                    .astype(np.int32)]),
    }


def _op_fn(name):
    import jax
    import jax.numpy as jnp

    if name == "matmul":
        return lambda a, b: a @ b
    if name == "matmul_bf16":
        return lambda a, b: (a.astype(jnp.bfloat16)
                             @ b.astype(jnp.bfloat16)).astype(jnp.float32)
    if name == "conv2d":
        return lambda x, w: jax.lax.conv_general_dilated(
            x, w, (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
    if name == "layer_norm":
        def ln(x):
            m = jnp.mean(x, -1, keepdims=True)
            v = jnp.mean(jnp.square(x - m), -1, keepdims=True)
            return (x - m) * jax.lax.rsqrt(v + 1e-5)
        return ln
    if name == "softmax":
        return lambda x: jax.nn.softmax(x, -1)
    if name == "gelu":
        return lambda x: jax.nn.gelu(x)
    if name == "embedding":
        return lambda ids, w: w[ids]
    if name == "attention":
        def attn(qkv):
            q = k = v = qkv
            s = jnp.einsum("bshd,bthd->bhst", q, k) / q.shape[-1] ** 0.5
            return jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), v)
        return attn
    if name == "cross_entropy":
        def ce(logits, labels):
            lp = jax.nn.log_softmax(logits, -1)
            return -jnp.mean(jnp.take_along_axis(
                lp, labels[:, None], 1))
        return ce
    raise ValueError(name)


def bench_case(fn, args, iters):
    """Per-iteration op time: `iters` chained applications inside one
    jitted fori_loop, so one dispatch covers the whole window; best of
    two runs after the compile."""
    import jax
    import jax.numpy as jnp

    args = [jnp.asarray(a) for a in args]

    def make_loop(n):
        @jax.jit
        def loop(*a):
            def body(i, carry):
                out = fn(*([carry[0]] + list(a[1:]))) if len(a) > 1 \
                    else fn(carry[0])
                scale = (1.0 + i.astype(jnp.float32) * 1e-9)
                if out.shape == a[0].shape and out.dtype == a[0].dtype:
                    # chain directly — no per-iteration reduce overhead
                    nxt = out * scale.astype(out.dtype)
                    extra = jnp.zeros((), jnp.float32)
                else:
                    # shape changes: keep a (cheap) data dependence on
                    # out so the op cannot be dead-code-eliminated
                    extra = jnp.sum(out.astype(jnp.float32)) * 1e-20
                    nxt = a[0] * (scale + extra).astype(a[0].dtype)
                return (nxt, carry[1] + extra)
            final, acc = jax.lax.fori_loop(
                0, n, body, (a[0], jnp.zeros((), jnp.float32)))
            return acc + jnp.sum(final.astype(jnp.float32))
        return loop

    def run(loop):
        t0 = time.perf_counter()
        float(loop(*args))
        return time.perf_counter() - t0

    loop = make_loop(iters)
    run(loop)                                # compile
    return min(run(loop) for _ in range(2)) / iters * 1000.0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="op_bench.json")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--small", action="store_true",
                    help="tiny shapes (CI smoke / CPU)")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend in-process (hermetic CI)")
    args = ap.parse_args(argv)

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    results = {"_device": jax.devices()[0].device_kind}
    for name, case in _cases(args.small).items():
        ms = bench_case(_op_fn(case["op"]), case["args"], args.iters)
        results[name] = {"ms": round(ms, 4),
                         "shapes": [list(getattr(a, "shape", ()))
                                    for a in case["args"]]}
        print(f"{name:18s} {ms:9.3f} ms", file=sys.stderr)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps({"cases": len(results) - 1, "out": args.out}))


if __name__ == "__main__":
    main()
