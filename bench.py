"""Single-chip flagship benchmark: GPT train step (fwd+bwd+AdamW, one fused
XLA program) tokens/sec/chip and MFU, plus the ResNet-50 conv-path
images/sec (BASELINE.md config 2).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", "device_count"} with the other phases'
numbers as extra keys on the same object.
vs_baseline = achieved GPT MFU / 0.40 (the BASELINE.json north-star MFU
target; the reference publishes no absolute numbers, see BASELINE.md).

Without `--cpu` (the hermetic tiny-shape smoke tools/ci.sh runs) the
script exits 2 unless JAX reports a TPU. A phase that fails prints no
number; the remaining phases still run and the process exits 1.
"""
import json
import sys
import time
import traceback

import numpy as np


def _peak_flops():
    """bf16 peak FLOP/s of the live device — one table for bench +
    training telemetry (paddle_tpu.telemetry.mfu owns it). None on the
    CPU; an accelerator the table does not list raises."""
    from paddle_tpu.telemetry.mfu import device_peak_flops
    return device_peak_flops()


def _mfu(rate, flops_per_unit, peak):
    """rate x FLOPs over the peak, rounded; None where there is no peak
    (the CPU smoke has no utilization to report)."""
    return round(rate * flops_per_unit / peak, 4) if peak else None


def device_stamp():
    """What every JSON line a bench script prints says about where it
    ran, as JAX reports it."""
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def start(force_cpu):
    """Common entry of bench.py / bench_extra.py / bench_serving.py:
    pin the CPU when asked (`--cpu`, the hermetic smoke tools/ci.sh
    runs), place the compile cache, and refuse to measure anything else
    than a TPU — a run that was not asked for the CPU and finds no TPU
    exits 2. Returns on_tpu."""
    import jax
    if force_cpu:
        jax.config.update("jax_platforms", "cpu")
    from paddle_tpu import compile_cache
    compile_cache.enable()
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not force_cpu:
        print(f"# no TPU: JAX reports {device_stamp()}; pass --cpu for "
              "the hermetic CPU smoke", file=sys.stderr)
        sys.exit(2)
    return on_tpu


def _sync(x):
    """Wait for a Tensor / jax value to be computed."""
    import jax
    jax.block_until_ready(getattr(x, "_value", x))


def _time_train_steps(step, inputs, steps, warmup):
    """Shared timing discipline for every phase: warm up (compile),
    then time `steps` dispatches to `block_until_ready` on the last
    loss — the steps chain through the donated params, so the last
    result waits for all of them. Returns (seconds_per_step,
    last_loss)."""
    for _ in range(warmup):
        loss = step(*inputs)
    _sync(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(*inputs)
    _sync(loss)
    return (time.perf_counter() - t0) / steps, loss


# summary key -> (phase, key in that phase's result); a phase that
# failed contributes no key at all
_SUMMARY = {
    "resnet50_images_per_sec_per_chip": ("resnet50", "images_per_sec"),
    "resnet50_mfu": ("resnet50", "mfu"),
    "resnet50_pipelined_images_per_sec":
        ("resnet50", "pipelined_images_per_sec"),
    "resnet50_loader_images_per_sec": ("resnet50", "loader_images_per_sec"),
    "gpt1_3b_layer_tokens_per_sec": ("gpt1_3b_layer", "tokens_per_sec"),
    "gpt1_3b_layer_mfu": ("gpt1_3b_layer", "mfu"),
    "gpt1_3b_full_tokens_per_sec": ("gpt1_3b_full", "tokens_per_sec"),
    "gpt1_3b_full_mfu": ("gpt1_3b_full", "mfu"),
    "gpt1_3b_full_params": ("gpt1_3b_full", "n_params"),
    "gpt1_3b_4k_tokens_per_sec": ("gpt1_3b_full_4k", "tokens_per_sec"),
    "gpt1_3b_4k_mfu": ("gpt1_3b_full_4k", "mfu"),
    "decode_bf16_tokens_per_sec": ("decode_wo8", "bf16_tokens_per_sec"),
    "decode_wo8_tokens_per_sec": ("decode_wo8", "wo8_tokens_per_sec"),
    "decode_wo8_speedup": ("decode_wo8", "speedup"),
    "bert_base_train_tokens_per_sec": ("bert_base", "tokens_per_sec"),
    "attn_16k_fwd_ms": ("attn_16k", "fwd_ms"),
    "attn_16k_bwd_ms": ("attn_16k", "bwd_ms"),
    "attn_16k_fwd_bwd_ms": ("attn_16k", "ms"),
    "attn_16k_tflops": ("attn_16k", "tflops"),
    "attn_16k_d64_fwd_ms": ("attn_16k", "d64_fwd_ms"),
    "attn_16k_d64_bwd_ms": ("attn_16k", "d64_bwd_ms"),
    "attn_16k_d64_fwd_bwd_ms": ("attn_16k", "d64_ms"),
    "attn_16k_d64_tflops": ("attn_16k", "d64_tflops"),
    "moe_train_tokens_per_sec": ("moe_train", "tokens_per_sec"),
    "moe_train_step_ms": ("moe_train", "step_ms"),
    "moe_train_dropped_frac": ("moe_train", "dropped_frac"),
    "ringattn_128k_fwd_bwd_ms": ("ringattn_128k", "fwd_bwd_ms"),
    "ringattn_128k_tflops": ("ringattn_128k", "tflops"),
}


def main():
    on_tpu = start("--cpu" in sys.argv[1:])
    stamp = device_stamp()

    from paddle_tpu import telemetry
    from paddle_tpu.models.gpt import GPTConfig

    if on_tpu:
        cfg = GPTConfig.gpt3_125m(max_seq_len=1024, dropout=0.0)
        # batch sweep on v5e (an earlier revision, jax 0.4.37; not
        # re-measured): 16 -> 108.9k tok/s, 24 -> 112.5k, 32 -> 110.7k
        batch, seq, steps, warmup = 24, 1024, 30, 3
    else:  # --cpu: the hermetic smoke, tiny shapes
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=256, dropout=0.0,
                        use_flash_attention=False)
        batch, seq, steps, warmup = 2, 256, 3, 1

    r = gpt_train_bench(cfg, batch, seq, steps, warmup, amp_on=on_tpu)
    tokens_per_sec, mfu = r["tokens_per_sec"], r["mfu"]
    peak = _peak_flops()

    # every phase result also goes through the telemetry sink (one
    # schema for bench lines AND training-run logs; tools/trace_check.py
    # validates it). --telemetry PATH overrides the default file.
    tpath = "bench_telemetry.jsonl"
    if "--telemetry" in sys.argv[1:-1]:   # flag needs a following value
        tpath = sys.argv[sys.argv.index("--telemetry") + 1]
    tsink = telemetry.JsonlSink(tpath)
    tsink.write(telemetry.make_phase_record("gpt3_125m_train", {
        "tokens_per_sec": round(tokens_per_sec, 1), "mfu": mfu,
        "sec_per_step": r["sec_per_step"], "n_params": r["n_params"],
        "device": stamp["device_kind"]}))

    results, failed = {}, []

    def phase(name, fn, *args):
        """One bench phase. A failure is reported and the remaining
        phases still run, but the phase contributes no number and the
        process exits non-zero."""
        try:
            results[name] = fn(*args)
        except Exception as e:
            traceback.print_exc()
            print(f"# phase {name} FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr)
            failed.append(name)
            return
        print(f"# phase {name} ran: {results[name]}", file=sys.stderr)
        tsink.write(telemetry.make_phase_record(name, results[name]))

    # the compile observatory shares the phase sink: every TrainStep
    # (re)compile in the phases below lands in the same JSONL with its
    # cause diff + HBM/cost analysis, and tools/compile_report.py gates
    # the file in CI (a clean bench must have no retrace storm)
    with telemetry.CompileObservatory(sink=tsink, action="record"):
        phase("resnet50", bench_resnet50, on_tpu, peak)
        phase("gpt1_3b_layer", bench_gpt1_3b_layer, on_tpu, peak)
        phase("gpt1_3b_full", bench_gpt1_3b_full, on_tpu, peak)
        phase("gpt1_3b_full_4k", bench_gpt1_3b_full, on_tpu, peak, 4096)
        phase("decode_wo8", bench_decode_wo8, on_tpu)
        phase("bert_base", bench_bert, on_tpu)
        phase("attn_16k", bench_attn_16k, on_tpu)
        # sparse + long-context workloads (paddle_tpu/moe +
        # ops/ring_attention)
        phase("moe_train", bench_moe_train, on_tpu, peak)
        phase("ringattn_128k", bench_ringattn_128k, on_tpu)

    summary = {
        "metric": "gpt3_125m_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(mfu / 0.40, 4) if mfu else None,
        **stamp,
    }
    for key, (name, field) in _SUMMARY.items():
        if name in results:
            summary[key] = results[name][field]
    if failed:
        summary["failed_phases"] = failed
    # every tracked scalar also lands as a TYPED kind='bench' record in
    # the telemetry JSONL — the perf-regression gate's unit of account
    # (tools/bench_gate.py diffs these against the rolling baseline, so
    # a silent throughput plateau is a CI failure, not a vibe)
    tsink.write(telemetry.make_bench_record(
        summary["metric"], summary["value"], unit=summary["unit"],
        device=stamp["device_kind"]))
    for metric, value in summary.items():
        if metric in ("metric", "value", "unit", "device_count") \
                or not isinstance(value, (int, float)):
            continue
        tsink.write(telemetry.make_bench_record(
            metric, value, device=stamp["device_kind"]))
    print(json.dumps(summary))
    print(f"# {stamp} loss={r['loss'].item():.4f} "
          f"params={r['n_params']/1e6:.1f}M "
          f"step={r['sec_per_step']*1000:.1f}ms; "
          f"{len(results)} phases ran, failed: {failed or 'none'}",
          file=sys.stderr)
    if failed:
        sys.exit(1)


def build_gpt_train_step(cfg, amp_on=True):
    """The GPT trainer every train phase times and chip_smoke.py
    checks: seeded model, AdamW, bf16 autocast, one fused TrainStep.
    Returns (model, step)."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.models.gpt import GPTForPretraining

    paddle.seed(0)
    model = GPTForPretraining(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                          parameters=model.parameters())

    def loss_fn(ids, labels):
        with amp.auto_cast(enable=amp_on, dtype="bfloat16"):
            return model.loss(ids, labels)

    return model, paddle.jit.TrainStep(model, loss_fn, opt)


def seeded_token_batch(vocab_size, batch, seq):
    """Fixed (ids, labels) int32 batch from seed 0."""
    import paddle_tpu as paddle
    rs = np.random.RandomState(0)
    return tuple(
        paddle.to_tensor(rs.randint(0, vocab_size, (batch, seq)), "int32")
        for _ in range(2))


def gpt_train_bench(cfg, batch, seq, steps, warmup, amp_on=True):
    """Shared GPT train-step benchmark body (model + AdamW + TrainStep +
    chained timing + PaLM-style MFU): one timing discipline and one
    FLOPs-per-token formula for every GPT scale point (125M here, 350M
    in bench_extra)."""
    model, step = build_gpt_train_step(cfg, amp_on)
    ids, lbl = seeded_token_batch(cfg.vocab_size, batch, seq)
    sec_per_step, loss = _time_train_steps(step, (ids, lbl), steps, warmup)
    tokens_per_sec = batch * seq / sec_per_step
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    # PaLM-style train FLOPs/token: 6N for matmuls + 12*L*H*S for attention
    flops_per_token = (6 * n_params
                       + 12 * cfg.num_layers * cfg.hidden_size * seq)
    return {"tokens_per_sec": tokens_per_sec,
            "mfu": _mfu(tokens_per_sec, flops_per_token, _peak_flops()),
            "loss": loss,
            "n_params": n_params, "sec_per_step": sec_per_step}


def bench_resnet50(on_tpu, peak):
    """ResNet-50 fwd+bwd+Momentum images/sec/chip (BASELINE.md config 2:
    the conv/BN path). Same chained-on-donated-params timing discipline as
    the GPT phase. Train FLOPs/img ~= 3 x 4.089 GFLOP fwd at 224^2."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import resnet50

    if on_tpu:
        # batch sweep on v5e: 64 -> 1822 img/s, 128 -> 2129, 256 -> 2162
        # (bandwidth-bound past 128; 128 is the knee at half the memory)
        batch, steps, warmup = 128, 15, 3
    else:
        batch, steps, warmup = 2, 2, 1

    paddle.seed(0)
    model = resnet50(num_classes=1000)
    opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                             parameters=model.parameters())

    def loss_fn(x, y):
        with amp.auto_cast(enable=on_tpu, dtype="bfloat16"):
            return F.cross_entropy(model(x), y)

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    rs = np.random.RandomState(0)
    x = paddle.to_tensor(rs.randn(batch, 3, 224, 224).astype(np.float32))
    y = paddle.to_tensor(rs.randint(0, 1000, (batch,)).astype(np.int32))

    sec_per_step, _ = _time_train_steps(step, (x, y), steps, warmup)
    ips = batch / sec_per_step
    mfu = _mfu(ips, 3 * 4.089e9, peak)

    piped, loader_ips = _resnet_pipelined(model, opt, on_tpu, batch,
                                          steps, warmup)
    return {"images_per_sec": round(ips, 1), "mfu": mfu,
            "pipelined_images_per_sec": piped,
            "loader_images_per_sec": loader_ips}


class _SynthImages:
    """Synthetic image dataset for the pipelined phase — module-level and
    PICKLABLE so the loader's fork-safe worker processes (spawn/
    forkserver, io.prefetch) can receive it: pickling ships only the
    config, and each worker regenerates the raw-image pool from the seed
    on first use. The per-sample CPU work is the representative decode:
    random crop + flip on uint8 + contiguous copy, deterministic per
    index."""

    def __init__(self, n_items, pool=512, seed=1):
        self.n_items = n_items
        self.pool = min(pool, n_items)
        self.seed = seed
        self._raw = None
        self._labels = None

    def __getstate__(self):
        return {"n_items": self.n_items, "pool": self.pool,
                "seed": self.seed}

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._raw = None
        self._labels = None

    def _ensure(self):
        if self._raw is None:
            rs = np.random.RandomState(self.seed)
            self._raw = rs.randint(0, 256, (self.pool, 3, 256, 256),
                                   dtype=np.uint8)
            self._labels = rs.randint(0, 1000,
                                      (self.n_items,)).astype(np.int32)

    def __len__(self):
        return self.n_items

    def __getitem__(self, i):
        self._ensure()
        img = self._raw[i % self.pool]
        # the representative CPU work: random crop + flip on uint8
        rr = np.random.RandomState(i)
        top, left = rr.randint(0, 32), rr.randint(0, 32)
        img = img[:, top:top + 224, left:left + 224]
        if rr.rand() < 0.5:
            img = img[:, :, ::-1]
        return np.ascontiguousarray(img), self._labels[i]


def _resnet_pipelined(model, opt, on_tpu, batch, steps, warmup):
    """images/sec with the HOST INPUT PIPELINE in the measured loop
    (the compute-only number overstates a real epoch): worker
    PROCESSES (fork-safe spawn/forkserver — never os.fork under the
    multithreaded JAX parent) run the per-sample CPU transform (crop +
    flip on uint8) and assemble batches zero-copy into shared-memory
    slots; batches ship to the device as uint8 (4x fewer H2D bytes than
    f32 — the BufferedReader/ptio recipe) through the double-buffered
    prefetch_to_device stage so the H2D hop overlaps step N's compute;
    normalization runs ON DEVICE inside the compiled step."""
    import os
    import paddle_tpu as paddle
    from paddle_tpu import amp
    import paddle_tpu.nn.functional as F
    from paddle_tpu.io import DataLoader, prefetch_to_device

    # one epoch must cover the warm batches (2) + loader-rate probe (6)
    # + warmup + timed steps + real slack, or the timed window pays
    # iterator re-creation
    n_items = batch * (steps + warmup + 12)
    workers = min(8, os.cpu_count() or 2) if on_tpu else 2

    mean = np.array([0.485, 0.456, 0.406], np.float32) * 255.0
    std = np.array([0.229, 0.224, 0.225], np.float32) * 255.0

    def loss_fn(x8, y):
        # device-side normalize: uint8 -> f32 -> (x-mean)/std
        xf = (x8.astype("float32")
              - paddle.to_tensor(mean.reshape(1, 3, 1, 1))) \
            / paddle.to_tensor(std.reshape(1, 3, 1, 1))
        with amp.auto_cast(enable=on_tpu, dtype="bfloat16"):
            return F.cross_entropy(model(xf), y)

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    loader = DataLoader(_SynthImages(n_items), batch_size=batch,
                        shuffle=False, num_workers=workers,
                        worker_mode="process", persistent_workers=True,
                        drop_last=True)
    it = iter(loader)   # workers spawn ONCE, before any timing

    # loader-only rate: how fast the worker pipeline PRODUCES device-
    # ready batches (decode + zero-copy slot assembly + the blocking
    # transfer, no compute in the loop). Warm TWO batches first —
    # measuring from the very first next() charges worker spawn +
    # first-fill to the steady-state rate.
    for _ in range(2):
        next(it)
    t0 = time.perf_counter()
    k_loader = min(6, steps)
    for _ in range(k_loader):
        next(it)
    loader_ips = round(batch * k_loader /
                       max(1e-9, time.perf_counter() - t0), 1)

    # double-buffered device stage over the SAME live iterator (the
    # worker pool keeps running; the stage thread overlaps the next
    # batch's H2D with the current step's compute)
    dev_it = iter(prefetch_to_device(it, size=2))

    def run(k):
        nonlocal it, dev_it
        loss = None
        for _ in range(k):
            try:
                bx, by = next(dev_it)
            except StopIteration:
                it = iter(loader)
                dev_it = iter(prefetch_to_device(it, size=2))
                bx, by = next(dev_it)
            loss = step(bx, by)
        return loss

    _sync(run(warmup))
    t0 = time.perf_counter()
    _sync(run(steps))
    dt = time.perf_counter() - t0
    dev_it.close()      # stop the stage thread BEFORE the pool/slots go
    loader.shutdown()
    return round(batch * steps / dt, 1), loader_ips


def bench_gpt1_3b_layer(on_tpu, peak):
    """One transformer block at TRUE gpt3_1_3b dims (hidden 2048, ffn
    8192, 16 heads) fwd+bwd+SGD on one chip — the first on-hardware
    evidence behind the >=40%-MFU-at-1.3B north star: per-layer MFU at
    real dims upper-bounds what the full 24-layer model can reach once
    sharded (BASELINE.md config 5; the full model needs the pod slice).
    Same chained-on-donated-params timing discipline as the GPT phase."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.models.gpt import GPTConfig, GPTBlock

    cfg = GPTConfig.gpt3_1_3b(max_seq_len=2048, dropout=0.0,
                              attn_dropout=0.0)
    if on_tpu:
        batch, seq, steps, warmup = 8, 2048, 15, 3
    else:
        batch, seq, steps, warmup = 1, 128, 2, 1

    paddle.seed(0)
    model = GPTBlock(cfg)
    opt = optimizer.SGD(learning_rate=1e-6,
                        parameters=model.parameters())

    def loss_fn(x):
        with amp.auto_cast(enable=on_tpu, dtype="bfloat16"):
            return model(x).mean()

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    rs = np.random.RandomState(0)
    x = paddle.to_tensor(
        rs.randn(batch, seq, cfg.hidden_size).astype(np.float32) * 0.02)

    sec_per_step, _ = _time_train_steps(step, (x,), steps, warmup)
    tokens_per_sec = batch * seq / sec_per_step
    h = cfg.hidden_size
    layer_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    flops_per_token = 6 * layer_params + 12 * h * seq
    return {"tokens_per_sec": round(tokens_per_sec, 1),
            "mfu": _mfu(tokens_per_sec, flops_per_token, peak)}


def bench_gpt1_3b_full(on_tpu, peak, seq_len=2048):
    """FULL GPT-1.3B — 24 layers at TRUE dims (hidden 2048, ffn 8192,
    vocab 50304) — fwd+bwd+AdamW end-to-end on ONE chip. This is the
    model-level north-star measurement (BASELINE.md: >=40% MFU), not the
    single-layer extrapolation: bf16 device params with the f32
    master+moments in pinned HOST memory (OffloadTrainStep — the
    reference's optimizer-state CPU offload, sharding/offload_helper.py),
    per-block remat, fused linear+CE head, flash attention. K micro-steps
    accumulate grads; the chunked optimizer update streams states
    through HBM. Timed over full accumulation rounds INCLUDING the
    update, synced by fetching a last-chunk param element (the updates
    are the final dispatches on the stream)."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu import distributed as dist
    from paddle_tpu.flags import set_flags, get_flag
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    if on_tpu and seq_len == 4096:
        # long-context training point at true model scale (B=8 fits with
        # remat at 4k; K=8 amortizes the offload update)
        cfg = GPTConfig.gpt3_1_3b(max_seq_len=4096, dropout=0.0,
                                  attn_dropout=0.0, remat=True)
        batch, seq, K, rounds, warm = 8, 4096, 8, 2, 2
    elif on_tpu:
        cfg = GPTConfig.gpt3_1_3b(max_seq_len=2048, dropout=0.0,
                                  attn_dropout=0.0, remat=True)
        # micro-batch 16 fits with remat (measured; per-micro MFU 0.585);
        # K=16 accumulation -> 524k-token global batch (GPT-3 1.3B trains
        # at ~1M, so still conservative); K sweep at B=16: K=4 -> MFU
        # .488, K=8 -> .536, K=16 -> .560 (update amortization). warm=2
        # FULL rounds: round 0 compiles micro+update, round 1 still pays
        # donation rebinding (measured 92/67/43.3 s for rounds 0/1/2 at
        # K=16 — steady state from round 2)
        batch, seq, K, rounds, warm = 16, 2048, 16, 2, 2
    else:
        cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=3,
                        num_heads=4, max_seq_len=128, dropout=0.0,
                        use_flash_attention=False, remat=True)
        batch, seq, K, rounds, warm = 2, 128, 2, 1, 1

    paddle.seed(0)
    model = GPTForPretraining(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                          parameters=model.parameters())

    old_fused = get_flag("use_fused_ce")
    set_flags({"use_fused_ce": on_tpu})  # never materialize [B*S, V]
    try:
        def loss_fn(ids, labels):
            with amp.auto_cast(enable=on_tpu, dtype="bfloat16"):
                return model.loss(ids, labels)

        step = dist.OffloadTrainStep(
            model, loss_fn, opt, accumulate_steps=K,
            param_dtype="bfloat16" if on_tpu else None)
        rs = np.random.RandomState(0)
        ids = paddle.to_tensor(
            rs.randint(0, cfg.vocab_size, (batch, seq)), "int32")
        lbl = paddle.to_tensor(
            rs.randint(0, cfg.vocab_size, (batch, seq)), "int32")

        def sync():
            # last dispatch of a round is the FINAL chunk update; its
            # first param being ready means the whole round is done
            _sync(step.params[step._chunks[-1][0]])

        for _ in range(warm * K):
            loss = step(ids, lbl)
        sync()
        t0 = time.perf_counter()
        for _ in range(rounds * K):
            loss = step(ids, lbl)
        final_loss = float(loss.item())
        sync()
        sec_per_round = (time.perf_counter() - t0) / rounds
        tokens_per_sec = K * batch * seq / sec_per_round
        n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
        flops_per_token = (6 * n_params
                           + 12 * cfg.num_layers * cfg.hidden_size * seq)
        if not np.isfinite(final_loss):
            raise FloatingPointError(
                f"non-finite loss {final_loss} after {rounds} rounds")
        return {"tokens_per_sec": round(tokens_per_sec, 1),
                "mfu": _mfu(tokens_per_sec, flops_per_token, peak),
                "n_params": n_params}
    finally:
        set_flags({"use_fused_ce": old_fused})


def bench_decode_wo8(on_tpu):
    """GPT-125M greedy KV-cache decode, bf16 baseline then weight-only
    int8 (W8A16 serving recipe, quant/wo8.py) on the SAME model — the
    same phase bench_extra's decode row wraps. Decode is
    weight-bandwidth bound, so int8 storage is the headline serving
    lever."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.quant import quantize_for_decode

    paddle.seed(0)
    if on_tpu:
        cfg = GPTConfig.gpt3_125m(max_seq_len=1024, dropout=0.0)
        B, prompt_len, new, reps = 8, 128, 128, 3
    else:
        cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=128, dropout=0.0,
                        use_flash_attention=False)
        B, prompt_len, new, reps = 2, 16, 16, 1
    model = GPTForPretraining(cfg)
    rs = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rs.randint(0, cfg.vocab_size, (B, prompt_len)), "int32")

    def timed():
        out, _ = model.generate(ids, max_new_tokens=new)   # compile
        _sync(out)
        t0 = time.perf_counter()
        for _ in range(reps):
            out, _ = model.generate(ids, max_new_tokens=new)
        _sync(out)
        return B * new * reps / (time.perf_counter() - t0)

    bf16_tps = timed()
    # the serving engine's weights="wo8" mode and this phase share ONE
    # quantization entry (paddle_tpu/quant/wo8.py quantize_for_decode)
    quantize_for_decode(model)
    wo8_tps = timed()
    return {"bf16_tokens_per_sec": round(bf16_tps, 1),
            "wo8_tokens_per_sec": round(wo8_tps, 1),
            "speedup": round(wo8_tps / max(bf16_tps, 1e-9), 3)}


def bench_bert(on_tpu):
    """BERT-base fwd+bwd+AdamW tokens/sec/chip (BASELINE.md config 3's
    encoder family)."""
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    import paddle_tpu.nn.functional as F
    from paddle_tpu.models.bert import BertConfig, \
        BertForSequenceClassification

    paddle.seed(0)
    if on_tpu:
        cfg = BertConfig(hidden_dropout=0.0, attn_dropout=0.0)  # 12L/768
        B, S, steps, warmup = 32, 512, 15, 3
    else:
        cfg = BertConfig(vocab_size=512, hidden_size=64, num_layers=2,
                         num_heads=4, hidden_dropout=0.0, attn_dropout=0.0)
        B, S, steps, warmup = 2, 32, 2, 1
    model = BertForSequenceClassification(cfg, num_classes=2)
    opt = optimizer.AdamW(learning_rate=2e-5,
                          parameters=model.parameters())
    rs = np.random.RandomState(0)
    ids = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (B, S)), "int32")
    lbl = paddle.to_tensor(rs.randint(0, 2, (B,)), "int32")

    def loss_fn(i, y):
        with amp.auto_cast(enable=on_tpu, dtype="bfloat16"):
            return F.cross_entropy(model(i), y)

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    sec_per_step, _ = _time_train_steps(step, (ids, lbl), steps, warmup)
    return {"tokens_per_sec": round(B * S / sec_per_step, 1)}


def bench_moe_train(on_tpu, peak):
    """GPTMoE train step (fwd+bwd+AdamW, routed expert FFNs + aux/z
    losses, fused dispatch/combine on TPU) tokens/sec/chip — the sparse
    scenario point (paddle_tpu/moe). Same chained-on-donated-params
    timing discipline as the dense GPT phase; MFU uses the ACTIVE
    FLOPs/token (top-k experts, not all E), so dense and sparse MFU
    are comparable utilization numbers."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.moe import GPTMoEConfig

    if on_tpu:
        cfg = GPTMoEConfig(vocab_size=50304, hidden_size=768,
                           num_layers=12, num_heads=12, max_seq_len=1024,
                           dropout=0.0, num_experts=8, expert_top_k=2,
                           capacity_factor=1.25)
        batch, seq, steps, warmup = 8, 1024, 15, 3
    else:
        cfg = GPTMoEConfig(vocab_size=512, hidden_size=128, num_layers=2,
                           num_heads=4, max_seq_len=128, dropout=0.0,
                           num_experts=4, expert_top_k=2,
                           capacity_factor=2.0,
                           use_flash_attention=False)
        batch, seq, steps, warmup = 2, 128, 3, 1

    import jax
    from paddle_tpu.moe import GPTMoE
    paddle.seed(0)
    model = GPTMoE(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                          parameters=model.parameters())

    def loss_fn(ids, labels):
        with amp.auto_cast(enable=on_tpu, dtype="bfloat16"):
            return model.loss(ids, labels)

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    rs = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rs.randint(0, cfg.vocab_size, (batch, seq)), "int32")
    lbl = paddle.to_tensor(
        rs.randint(0, cfg.vocab_size, (batch, seq)), "int32")
    sec_per_step, _ = _time_train_steps(step, (ids, lbl), steps, warmup)
    tokens_per_sec = batch * seq / sec_per_step
    # active params: dense skeleton + router + top-k of E expert pairs
    d, f, L, E = (cfg.hidden_size, cfg.ffn_hidden_size, cfg.num_layers,
                  cfg.num_experts)
    total = sum(int(np.prod(p.shape)) for p in model.parameters())
    active = total - L * (E - cfg.expert_top_k) * 2 * d * f
    flops_per_token = 6 * active + 12 * L * d * seq
    # routing health of the final step (the trainer's device-side moe
    # taps — the layer attributes themselves hold traced values)
    stats = getattr(step, "_last_moe", None)
    dropped = float(np.asarray(stats)[1]) if stats is not None else 0.0
    return {"tokens_per_sec": round(tokens_per_sec, 1),
            "step_ms": round(sec_per_step * 1000.0, 3),
            "mfu": _mfu(tokens_per_sec, flops_per_token, peak),
            "dropped_frac": round(dropped, 4)}


def bench_ringattn_128k(on_tpu):
    """>=128k-context causal attention fwd+bwd — the long-context
    production point (GPTConfig.gpt3_1_3b_128k head shape: D=128,
    H=16). With multiple devices the sequence is sharded over an sp
    ring and ops/ring_attention runs the blockwise path (HBM per chip
    O(seq/sp)); on a single chip the flash kernel runs the full
    131072-token sequence — whose backward resolves to the
    block_q=512/block_k=1024 triangle-grid decode (the r=2 config the
    rect-block parity tests pin). CPU smoke shrinks the sequence."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed import env as dist_env
    from paddle_tpu.ops.ring_attention import ring_attention_values

    if on_tpu:
        S, B, H, D, reps = 131072, 1, 16, 128, 2
        dtype = jnp.bfloat16
    else:
        S, B, H, D, reps = 2048, 1, 2, 64, 2
        dtype = jnp.float32

    n_dev = len(jax.devices())
    sp = n_dev if (n_dev > 1 and S % n_dev == 0) else 1
    mesh = None
    if sp > 1:
        mesh = dist_env.build_mesh(sp=sp, devices=jax.devices()[:sp])

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(B, S, H, D), dtype) * 0.3

    def f(x):
        if mesh is not None:
            o = ring_attention_values(x, x, x, causal=True, mesh=mesh)
        else:
            from paddle_tpu.ops.attention import \
                scaled_dot_product_attention
            o = scaled_dot_product_attention(x, x, x, is_causal=True)
            o = o._value if hasattr(o, "_value") else o
        return jnp.sum(o.astype(jnp.float32) ** 2)

    try:
        step = jax.jit(jax.grad(f))
        g = step(q)
        _sync(g)                                       # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            g = step(q)
        _sync(g)
        dt = (time.perf_counter() - t0) / reps
    finally:
        if mesh is not None:
            dist_env.clear_mesh()
    # causal fwd+bwd matmul FLOPs: 6 * B*H*S^2*D — the bench_attn_16k
    # convention (the 6x is already the causal half of the 12*S^2*D
    # dense fwd+bwd count), so 16k and 128k tflops are comparable
    flops = 6 * B * H * S * S * D
    return {"fwd_bwd_ms": round(dt * 1000.0, 2),
            "tflops": round(flops / dt / 1e12, 3),
            "seq_len": S, "sp": sp}


def bench_attn_16k(on_tpu):
    """Causal flash-attention at 16k sequence on one chip — the
    long-context single-chip number (ring/Ulysses shard longer sequences
    across chips), forward and backward split. Two head shapes:
    D=128/H=16 (the GPT-1.3B head shape — the long-context critical
    path, and the headline tflops) and D=64/H=12 (the 125M shape; its
    64-wide MXU contraction halves the attainable peak). Reps are
    chained inside one jitted fori_loop and two inner-rep counts are
    differenced, so the fixed cost of a dispatch cancels and its jitter
    divides by (r2 - r1)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import scaled_dot_product_attention

    def norm(g):
        g32 = g.astype(jnp.float32)
        n = jax.lax.rsqrt(jnp.mean(g32 * g32) + 1e-9)
        return (g32 * n).astype(g.dtype)

    def timeit(step, q0, r1, r2):
        def chain(reps):
            @jax.jit
            def multi(x):
                return jax.lax.fori_loop(0, reps, lambda i, v: step(v), x)
            return multi
        m1, m2 = chain(r1), chain(r2)
        state = m2(m1(q0))
        _sync(state)
        t0 = time.perf_counter()
        state = m1(state)
        _sync(state)
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        state = m2(state)
        _sync(state)
        t2 = time.perf_counter() - t0
        return max(1e-9, (t2 - t1) / (r2 - r1))

    def point(S, B, H, D, r1, r2):
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(B, S, H, D), jnp.bfloat16)

        def fwd_step(x):
            o = scaled_dot_product_attention(x, x, x, is_causal=True)._value
            return norm(o)

        def f(x):
            o = scaled_dot_product_attention(x, x, x, is_causal=True)._value
            return jnp.sum(o.astype(jnp.float32) ** 2)

        def fwdbwd_step(x):
            return norm(jax.grad(f)(x))

        causal_mm = B * H * S * S * D
        tf = timeit(fwd_step, q, r1, r2)
        tb = timeit(fwdbwd_step, q, r1, r2)
        return {"fwd_ms": round(tf * 1000, 2),
                "bwd_ms": round(max(tb - tf, 0.0) * 1000, 2),
                "ms": round(tb * 1000, 1),
                "tflops": round(6 * causal_mm / tb / 1e12, 1)}

    if on_tpu:
        d128 = point(16384, 1, 16, 128, 8, 24)
        d64 = point(16384, 1, 12, 64, 8, 24)
    else:
        d128 = point(512, 1, 2, 128, 1, 3)
        d64 = point(512, 1, 2, 64, 1, 3)
    return {"fwd_ms": d128["fwd_ms"], "bwd_ms": d128["bwd_ms"],
            "ms": d128["ms"], "tflops": d128["tflops"],
            "d64_fwd_ms": d64["fwd_ms"], "d64_bwd_ms": d64["bwd_ms"],
            "d64_ms": d64["ms"], "d64_tflops": d64["tflops"]}


if __name__ == "__main__":
    main()
