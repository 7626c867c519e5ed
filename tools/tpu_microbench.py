#!/usr/bin/env python
"""On-chip A/B microbenches behind the perf flags: decide, with hardware
numbers, whether `use_pallas_layernorm` / `use_fused_ce` should default
on at bench shapes, and where `pallas_attention_min_seq` should sit.

Run on the real chip:  python tools/tpu_microbench.py [ln] [ce] [attn]
(no args = all phases).  Each phase prints one JSON line.

Timing discipline is bench.py's: every timed iteration chains on the
previous result and the window ends in `block_until_ready`.

NOTE: the kernel-vs-fallback half of these phases is superseded by
`tools/kernellab.py` (same-input fallback timing + roofline
attribution + the persistent kernel_db.json for every registered
kernel). This script remains the flag-decision harness: it times the
FULL op path behind each perf flag (dispatch + layout + surrounding
XLA fusion), which is the number the flag defaults actually ride on.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def _timed_chain(fn, x0, steps, warmup):
    """fn: x -> x (same shape/dtype so iterations chain). Returns s/iter."""
    import jax

    fn = jax.jit(fn)
    x = x0
    for _ in range(warmup):
        x = fn(x)
    x.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps):
        x = fn(x)
    x.block_until_ready()
    return (time.perf_counter() - t0) / steps


def bench_ln(steps=200, warmup=5):
    """Fused residual+LayerNorm: Pallas kernel vs composed XLA, fwd+bwd,
    GPT-125M bench shapes ([16*1024, 768] bf16)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_layernorm import fused_add_layer_norm

    rows, h = 16 * 1024, 768
    rs = np.random.RandomState(0)
    w = jnp.asarray(rs.randn(h), jnp.float32)
    b = jnp.asarray(rs.randn(h), jnp.float32)
    x0 = jnp.asarray(rs.randn(rows, h), jnp.bfloat16)

    def composed(x, res):
        y = (x + res).astype(jnp.float32)
        mu = y.mean(-1, keepdims=True)
        var = ((y - mu) ** 2).mean(-1, keepdims=True)
        return ((y - mu) * jax.lax.rsqrt(var + 1e-5) * w + b).astype(x.dtype)

    def mk(f):
        def loss(x):
            o = f(x, x)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        def step(x):
            g = jax.grad(loss)(x).astype(jnp.float32)
            n = jax.lax.rsqrt(jnp.mean(g * g) + 1e-9)
            return (g * n).astype(x.dtype)
        return step

    pallas_fn = lambda x, res: fused_add_layer_norm(x, res, w, b)
    t_x = _timed_chain(mk(composed), x0, steps, warmup)
    t_p = _timed_chain(mk(pallas_fn), x0, steps, warmup)
    return {"metric": "pallas_vs_xla_fused_add_ln_fwd_bwd",
            "xla_ms": round(t_x * 1e3, 3), "pallas_ms": round(t_p * 1e3, 3),
            "pallas_speedup": round(t_x / t_p, 3),
            "shape": [rows, h]}


def bench_ce(steps=30, warmup=3):
    """LM loss tail: fused chunked projection+CE vs naive logits+CE,
    fwd+bwd, GPT-125M bench scale ([16384, 768] x vocab 50257)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy

    n, h, v = 16 * 1024, 768, 50257
    rs = np.random.RandomState(0)
    wv = jnp.asarray(rs.randn(v, h) * 0.02, jnp.bfloat16)
    labels = jnp.asarray(rs.randint(0, v, (n,)), jnp.int32)
    x0 = jnp.asarray(rs.randn(n, h), jnp.bfloat16)

    def naive(hd, w):
        logits = (hd @ w.T).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[:, None], 1)[:, 0]
        return jnp.mean(lse - picked)

    def fused(hd, w):
        return jnp.mean(fused_linear_cross_entropy(hd, w, labels))

    def mk(f):
        def step(x):
            g = jax.grad(lambda hd: f(hd, wv))(x).astype(jnp.float32)
            nrm = jax.lax.rsqrt(jnp.mean(g * g) + 1e-9)
            return (g * nrm).astype(x.dtype)
        return step

    t_n = _timed_chain(mk(naive), x0, steps, warmup)
    t_f = _timed_chain(mk(fused), x0, steps, warmup)
    return {"metric": "fused_ce_vs_naive_lm_loss_fwd_bwd",
            "naive_ms": round(t_n * 1e3, 2), "fused_ms": round(t_f * 1e3, 2),
            "fused_speedup": round(t_n / t_f, 3),
            "shape": [n, h, v]}


def bench_attn(steps=50, warmup=3, seqs=(512, 1024, 2048)):
    """Pallas flash attention vs composed XLA across seq lengths around
    the `pallas_attention_min_seq` crossover (GPT-125M head dims).
    Override lengths as `attn:128,256` on the CLI."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_attention import flash_attention_fwd
    from paddle_tpu.ops import attention as attn_mod

    B, H, D = 16, 12, 64
    rs = np.random.RandomState(0)
    rows = []
    for S in seqs:
        x0 = jnp.asarray(rs.randn(B, S, H, D) * 0.1, jnp.bfloat16)

        def mk(f):
            def loss(x):
                o = f(x, x, x)
                return jnp.sum(o.astype(jnp.float32) ** 2)

            def step(x):
                g = jax.grad(loss)(x).astype(jnp.float32)
                n = jax.lax.rsqrt(jnp.mean(g * g) + 1e-9)
                return (g * n).astype(x.dtype)
            return step

        pal = lambda q, k, v: flash_attention_fwd(q, k, v, causal=True)
        com = lambda q, k, v: attn_mod._composed_attention(
            q, k, v, causal=True)
        t_p = _timed_chain(mk(pal), x0, steps, warmup)
        t_c = _timed_chain(mk(com), x0, steps, warmup)
        rows.append({"seq": S, "pallas_ms": round(t_p * 1e3, 2),
                     "xla_ms": round(t_c * 1e3, 2),
                     "pallas_speedup": round(t_c / t_p, 3)})
    return {"metric": "pallas_vs_xla_attention_fwd_bwd", "rows": rows}


def main():
    raw = sys.argv[1:] or ["ln", "ce", "attn"]
    want = {}
    for a in raw:
        key, _, opts = a.partition(":")
        want[key] = opts
    import jax
    if jax.default_backend() != "tpu":
        print(json.dumps({"error": "not on tpu; this is an on-chip bench"}))
        sys.exit(1)
    for key, fn in (("ln", bench_ln), ("ce", bench_ce),
                    ("attn", bench_attn)):
        if key in want:
            kwargs = {}
            if key == "attn" and want[key]:
                kwargs["seqs"] = tuple(
                    int(s) for s in want[key].split(","))
            try:
                print(json.dumps(fn(**kwargs)), flush=True)
            except Exception as e:  # keep later phases alive
                print(json.dumps({"metric": key,
                                  "error": f"{type(e).__name__}: {e}"[:400]}),
                      flush=True)


if __name__ == "__main__":
    main()
