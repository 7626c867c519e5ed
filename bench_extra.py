"""Secondary on-chip benchmarks: autoregressive decode, BERT, and
long-context flash attention.

Not part of the driver's `bench.py` contract (kept fast); run manually:
    python bench_extra.py
Prints one JSON line per phase, each naming platform, device_kind and
device count. Timing follows bench.py's discipline — chained dispatches
timed to `block_until_ready`. Like bench.py it exits 2 unless JAX
reports a TPU (`--cpu` is the explicit tiny-shape smoke), and 1 when
any phase failed.
"""
import json
import sys
import time
import traceback

import numpy as np


def bench_decode():
    """GPT-125M greedy decode, bf16 + W8A16 — bench.py's
    bench_decode_wo8 phase; this wrapper keeps the manual tool."""
    import jax
    from bench import bench_decode_wo8
    r = bench_decode_wo8(jax.default_backend() == "tpu")
    return {"metric": "gpt3_125m_greedy_decode_tokens_per_sec",
            "value": r["bf16_tokens_per_sec"], "unit": "tokens/sec",
            "wo8_tokens_per_sec": r["wo8_tokens_per_sec"],
            "wo8_speedup": r["speedup"]}


def bench_gpt350m():
    """Full gpt3-350M train step on one chip — the mid-scale MFU point
    between the 125M flagship bench and the true-1.3B-dims single-layer
    microbench (the full 1.3B model needs the pod slice). 350M fits:
    params+AdamW f32 state ~5.6GB of 16GB HBM. Shares bench.py's
    gpt_train_bench body so the timing discipline and MFU formula can
    never drift between scale points."""
    import jax
    from paddle_tpu.models.gpt import GPTConfig
    from bench import gpt_train_bench

    if jax.default_backend() == "tpu":
        cfg = GPTConfig.gpt3_350m(max_seq_len=1024, dropout=0.0)
        batch, seq, steps, warmup = 8, 1024, 15, 2
    else:   # --cpu smoke: same code path, toy dims
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=256, dropout=0.0,
                        use_flash_attention=False)
        batch, seq, steps, warmup = 2, 256, 2, 1
    r = gpt_train_bench(cfg, batch, seq, steps, warmup,
                        amp_on=jax.default_backend() == "tpu")
    return {"metric": "gpt3_350m_train_tokens_per_sec_per_chip",
            "value": round(r["tokens_per_sec"], 1), "unit": "tokens/sec",
            "mfu": r["mfu"], "batch": batch, "seq": seq,
            "params_m": round(r["n_params"] / 1e6, 1)}


def bench_bert():
    """BERT-base train step — bench.py's phase, as a manual tool."""
    import jax
    from bench import bench_bert as impl
    r = impl(jax.default_backend() == "tpu")
    return {"metric": "bert_base_train_tokens_per_sec_per_chip",
            "value": r["tokens_per_sec"], "unit": "tokens/sec"}


def bench_long_context():
    """Flash-attention fwd+bwd at 16k — bench.py's bench_attn_16k
    phase; ring/Ulysses shard longer sequences across chips
    (tests/test_ring_attention.py)."""
    import jax
    from bench import bench_attn_16k
    r = bench_attn_16k(jax.default_backend() == "tpu")
    return {"metric": "flash_attention_long_context_fwd_bwd",
            "value": r["ms"], "unit": "ms@16k", "tflops": r["tflops"]}


def bench_ocr():
    """PP-OCRv2-style CRNN recognizer train step (BASELINE capability
    config: OCR) — images/sec through conv backbone + BiLSTM + CTC."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.models.ocr import CRNN

    on_tpu = __import__("jax").default_backend() == "tpu"
    # steps=60: at ~10ms/step a 15-step window (~150ms) is too short
    # to average out host jitter
    batch, steps, warmup = (64, 60, 5) if on_tpu else (2, 2, 1)
    paddle.seed(0)
    model = CRNN(num_classes=37)
    opt = optimizer.Adam(learning_rate=1e-3,
                         parameters=model.parameters())
    rs = np.random.RandomState(0)
    imgs = paddle.to_tensor(rs.randn(batch, 3, 32, 100).astype(np.float32))
    labels = paddle.to_tensor(rs.randint(1, 37, (batch, 12)), "int32")
    lens = paddle.to_tensor(np.full((batch,), 12, np.int32))

    def loss_fn(x, y, yl):
        with amp.auto_cast(enable=on_tpu, dtype="bfloat16"):
            return model.loss(x, y, yl)

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    from bench import _time_train_steps
    dt, _ = _time_train_steps(step, (imgs, labels, lens), steps, warmup)
    return {"metric": "crnn_ocr_train_images_per_sec", "unit": "img/s",
            "value": round(batch / dt, 1),
            "step_ms": round(dt * 1000, 2)}


def bench_int8_linear():
    """Per-channel int8 inference linear vs bf16 (the MXU int8 2x-
    throughput claim behind the quant deploy path): chained matmuls at
    GPT-1.3B ffn dims, tokens/sec each."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.quant import Int8Linear

    on_tpu = jax.default_backend() == "tpu"
    tokens, d_in, d_out = (4096, 2048, 8192) if on_tpu else (64, 32, 64)
    # one matmul at these dims is ~0.7ms; the timed window must dwarf
    # the per-dispatch host jitter
    steps, warmup = (400, 5) if on_tpu else (16, 2)
    paddle.seed(0)
    rs = np.random.RandomState(0)
    lin = nn.Linear(d_in, d_out)
    x0 = rs.randn(tokens, d_in).astype(np.float32)

    def timed(fn, x_init, dtype):
        x = paddle.to_tensor(x_init.astype(np.float32)).astype(dtype)
        import jax as _jax

        @_jax.jit
        def chain(v):
            # project back to d_in so steps chain
            out = fn(paddle.to_tensor(v))
            return out._value[:, :d_in].astype(v.dtype)
        v = x._value
        for _ in range(warmup):
            v = chain(v)
        v.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(steps):
            v = chain(v)
        v.block_until_ready()
        return tokens * steps / (time.perf_counter() - t0)

    bf16_tps = timed(lambda t: lin(t), x0, "bfloat16")
    q = Int8Linear(lin, float(np.abs(x0).max()))
    int8_tps = timed(lambda t: q(t), x0, "float32")
    return {"metric": "int8_vs_bf16_linear_tokens_per_sec",
            "unit": "tokens/s",
            "value": round(int8_tps, 1),
            "bf16_tokens_per_sec": round(bf16_tps, 1),
            "int8_speedup": round(int8_tps / max(bf16_tps, 1e-9), 3)}


def main():
    from bench import device_stamp, start
    start("--cpu" in sys.argv[1:])
    stamp = device_stamp()
    failed = []
    for fn in (bench_decode, bench_gpt350m, bench_bert,
               bench_long_context, bench_ocr, bench_int8_linear):
        try:
            print(json.dumps({**fn(), **stamp}))
        except Exception as e:  # keep later phases running; exit 1 below
            traceback.print_exc()
            print(json.dumps({"metric": fn.__name__, **stamp,
                              "error": f"{type(e).__name__}: {e}"}))
            failed.append(fn.__name__)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
