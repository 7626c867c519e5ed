"""Serving benchmark: offered-load sweep over the continuous-batching
engine (paddle_tpu/serving), reported as throughput at a fixed p99
TTFT/TPOT SLO.

The training benches (bench.py) answer "how fast is a step"; this one
answers the serving question: how many tokens/sec does the engine
sustain while every request still meets its latency SLO. Method:

1. **single-request predictor baseline** — `run_generate` serving the
   requests one at a time (the inference/predictor.py serving model):
   median-of-3 sequential sweeps -> `serving.single_stream_tokens_per_sec`.
2. **offered-load sweep** — the engine serves rising levels of
   concurrency (1, 2, ..., max_slots requests in flight, 2 waves each
   so continuous batching actually rotates the slots). Each level
   reports aggregate tokens/sec and per-request TTFT/TPOT p50/p99 from
   the request handles themselves.
3. **headline** — the highest-throughput level whose p99s meet the SLO
   (`--slo-ttft-ms` / `--slo-tpot-ms`) becomes
   `serving.throughput_tokens_per_sec` (+ its percentiles);
   `serving.throughput_vs_single` is the continuous-batching win over
   the sequential predictor.
4. **shared-prefix sweep** — N templated requests (>= 50% shared
   tokens) through a WARM prefix-cache engine vs a cold-cache control
   with bit-identical streams required: TTFT p50/p99, warm-vs-cold p50
   speedup, hit rate, and tokens saved over the offered prompt-token
   volume (`serving.prefill_tokens_offered` is the denominator that
   makes `tokens_saved` auditable).

Every tracked scalar is emitted as a typed kind=bench record
(telemetry.sink.SERVING_BENCH_METRICS) into the telemetry JSONL, so
tools/bench_gate.py gates serving throughput/latency against the
rolling baseline exactly like the training metrics, and the sweep runs
under a CompileObservatory so a recompiling engine loop is visible in
the same file (tools/compile_report.py gates it clean in CI).

    python bench_serving.py --cpu --telemetry serving_telemetry.jsonl
    python bench_serving.py --cpu --check-vs-single 1.5   # CI floor

**Fleet mode** (`--fleet N`) benches the tier ABOVE the engine
(paddle_tpu/fleet): the same concurrent wave through a `FleetRouter`
over N in-process replicas vs over 1 — `fleet.rated_throughput_
tokens_per_sec` and `fleet.scaling_efficiency` (aggregate / N x
single-replica; a fleet whose efficiency decays is paying routing
overhead the ~linear-scaling target does not allow) — plus a
shared-prefix affinity leg: templated prompts rendezvous-route to ONE
replica, so the fleet-wide `serving.prefix_hit_rate` must be > 0 with
every hit CONCENTRATED on that affine replica, and the streams must
stay bit-identical to a cold (prefix-cache-off) single engine. Those
rows are owned by this mode; the default sweep never writes them.

    python bench_serving.py --cpu --fleet 2 --telemetry fleet.jsonl

Exit codes: 0 ok; 2 when `--cpu` (the hermetic tiny-shape smoke) was
not given and JAX reports no TPU; 4 when --check-vs-single is given and
the measured ratio falls below it (the bench_gate findings code), or
when the fleet leg's affinity/identity invariants fail. A phase that
raises ends the run with a traceback. Every JSON line names platform,
device_kind and device count.
"""
import argparse
import json
import sys
import threading
import time

import numpy as np


def _percentile(vals, q):
    return float(np.percentile(vals, q)) if vals else None


def _r2(v):
    return None if v is None else round(v, 2)


def _fmt(v):
    return "n/a" if v is None else f"{v:.1f}"


def serve_level(engine, prompts, max_new, level):
    """Offer `level` concurrent streams (two waves, 2*level requests)
    through the engine; returns (aggregate tok/s, stats dict)."""
    from paddle_tpu.serving import SamplingParams

    reqs = [prompts[i % len(prompts)] for i in range(2 * level)]
    t0 = time.perf_counter()
    handles = [engine.submit(p, SamplingParams(max_new_tokens=max_new))
               for p in reqs]
    engine.run_until_idle()
    dt = max(1e-9, time.perf_counter() - t0)
    n_tokens = sum(len(h.output_tokens) for h in handles)
    ttft = [h.stats["ttft_ms"] for h in handles
            if h.stats["ttft_ms"] is not None]
    tpot = [h.stats["tpot_ms"] for h in handles
            if h.stats["tpot_ms"] is not None]
    return n_tokens / dt, {
        "level": level,
        "requests": len(handles),
        "tokens_per_sec": round(n_tokens / dt, 1),
        "ttft_p50_ms": _percentile(ttft, 50),
        "ttft_p99_ms": _percentile(ttft, 99),
        "tpot_p50_ms": _percentile(tpot, 50),
        "tpot_p99_ms": _percentile(tpot, 99),
    }


def shared_prefix_phase(model, on_tpu, seed=0, n_requests=None):
    """Shared-prefix sweep: N requests over K prompt templates through
    a WARM prefix-cache engine vs a cold-cache control engine.

    Real serving traffic shares most prompt tokens across requests
    (system prompts, few-shot templates, multi-turn chat); this phase
    measures what the prefix cache buys on exactly that shape: >= 50%
    of each prompt is a shared template, the cache is warmed with one
    short request per template (both engines pay the same warmup, so
    the comparison isolates CACHING, not compilation), then the same
    seeded request wave runs through both. Reports TTFT p50/p99 (warm),
    the warm-vs-cold p50 speedup, hit rate, tokens saved / offered /
    recomputed-per-request — and asserts the token streams are
    IDENTICAL between the two engines (sharing must be invisible in
    the output or it is corruption, not caching).

    Deterministic per seed: prompts, schedule, and hit accounting all
    derive from the seeded generator over a single-threaded engine
    loop, so two runs return identical streams and counters.
    """
    from paddle_tpu.serving import (EngineConfig, SamplingParams,
                                    ServingEngine)

    if on_tpu:
        tpl_len, tail_len, max_new = 96, 32, 16
        n_requests = n_requests or 32
        kw = dict(max_slots=8, block_size=16, prefill_chunk=32,
                  max_model_len=256)
    else:
        tpl_len, tail_len, max_new = 24, 8, 4
        n_requests = n_requests or 16
        kw = dict(max_slots=4, block_size=8, prefill_chunk=8,
                  max_model_len=64)
    vocab = model.config.vocab_size
    rs = np.random.RandomState(seed)
    templates = [rs.randint(0, vocab, (tpl_len,)).tolist()
                 for _ in range(2)]
    prompts = [templates[i % 2]
               + rs.randint(0, vocab, (tail_len,)).tolist()
               for i in range(n_requests)]

    def run(enable):
        engine = ServingEngine(model, config=EngineConfig(
            enable_prefix_cache=enable, **kw))
        # same warmup both sides: compiles the step functions and (warm
        # engine only) seeds the index with each template's blocks
        for tpl in templates:
            engine.submit(tpl, SamplingParams(max_new_tokens=2))
        engine.run_until_idle()
        before = engine.prefix_stats()
        t0 = time.perf_counter()
        handles = [engine.submit(p, SamplingParams(max_new_tokens=max_new))
                   for p in prompts]
        engine.run_until_idle()
        dt = max(1e-9, time.perf_counter() - t0)
        streams = [h.output_tokens for h in handles]
        ttft = [h.stats["ttft_ms"] for h in handles
                if h.stats["ttft_ms"] is not None]
        after = engine.prefix_stats()
        stats = {k: after[k] - before[k]
                 for k in ("tokens_saved", "tokens_offered", "hits",
                           "lookups")}
        return streams, ttft, stats, dt

    warm_streams, warm_ttft, stats, warm_dt = run(True)
    cold_streams, cold_ttft, _, cold_dt = run(False)
    identical = warm_streams == cold_streams
    offered = stats["tokens_offered"]
    saved = stats["tokens_saved"]
    warm_p50 = _percentile(warm_ttft, 50)
    cold_p50 = _percentile(cold_ttft, 50)
    return {
        "serving.prefix_hit_rate":
            round(saved / offered, 4) if offered else 0.0,
        "serving.prefill_tokens_saved": saved,
        "serving.prefill_tokens_offered": offered,
        "serving.prefix_ttft_p50_ms": _r2(warm_p50),
        "serving.prefix_ttft_p99_ms": _r2(_percentile(warm_ttft, 99)),
        "serving.prefix_ttft_speedup":
            round(cold_p50 / warm_p50, 3)
            if warm_p50 and cold_p50 else None,
        "serving.prefix_tokens_recomputed_per_request":
            round((offered - saved) / len(prompts), 2),
        "prefix_streams_identical": identical,
        "prefix_requests": len(prompts),
        "prefix_hits": stats["hits"],
        "prefix_cold_ttft_p50_ms": _r2(cold_p50),
        "prefix_warm_s": round(warm_dt, 3),
        "prefix_cold_s": round(cold_dt, 3),
        "_streams": warm_streams,
    }


def trace_overhead_phase(model, ecfg, prompts, max_new, level):
    """Tracer-cost leg at the RATED level: the same offered-load wave
    through a tracing-off then a tracing-on engine (each warmed so
    compile stays out of the clock), best-of-2 waves per side.

    Reported as `serving.trace_overhead_frac` = (tps_off - tps_on) /
    tps_off, floored at 0 (negative deltas are host noise) — a typed
    kind=bench record gated by tools/bench_gate.py against the seeded
    baseline row like every other regression, which is what holds the
    tracer to its <=2% rated-throughput budget. Runs OUTSIDE the
    CompileObservatory: the control engine is a second jit closure
    family and would pollute the recompile-free gate."""
    from paddle_tpu.serving import SamplingParams, ServingEngine

    def best_tps(enable):
        ecfg.enable_tracing = enable
        engine = ServingEngine(model, config=ecfg)
        engine.submit(prompts[0][:4], SamplingParams(max_new_tokens=2))
        engine.run_until_idle()      # warm: compile out of the clock
        best = 0.0
        for _ in range(2):
            tps, _ = serve_level(engine, prompts, max_new, level)
            best = max(best, tps)
        return best

    try:
        tps_off = best_tps(False)
        tps_on = best_tps(True)
    finally:
        ecfg.enable_tracing = True
    return {
        "serving.trace_overhead_frac":
            round(max(0.0, (tps_off - tps_on) / max(tps_off, 1e-9)), 4),
        "trace_on_tokens_per_sec": round(tps_on, 1),
        "trace_off_tokens_per_sec": round(tps_off, 1),
    }


def single_stream_baseline(model, prompts, max_new, reps=3):
    """The predictor serving model: one request at a time through
    run_generate, median of `reps` sequential sweeps."""
    import paddle_tpu as paddle

    ids0 = paddle.to_tensor(np.asarray([prompts[0]], np.int32))
    out, _ = model.generate(ids0, max_new_tokens=max_new)   # compile
    float(out.sum().item())
    runs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for p in prompts:
            out, _ = model.generate(
                paddle.to_tensor(np.asarray([p], np.int32)),
                max_new_tokens=max_new)
            float(out.sum().item())
        runs.append(len(prompts) * max_new /
                    max(1e-9, time.perf_counter() - t0))
    return sorted(runs)[len(runs) // 2]


def fleet_phase(args, n_replicas):
    """Fleet-tier leg: rated throughput + scaling efficiency through a
    FleetRouter over N in-process replicas (each replica owns its own
    identically-seeded model — concurrently-tracing engines must not
    share one), plus the shared-prefix affinity proof. Owns the
    fleet.* SERVING_BENCH_METRICS rows."""
    import jax
    import paddle_tpu as paddle
    from bench import device_stamp
    from paddle_tpu import telemetry
    from paddle_tpu.fleet import FleetRouter, InProcessReplica
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.serving import (EngineConfig, SamplingParams,
                                    ServingEngine)

    on_tpu = jax.default_backend() == "tpu"
    dev = jax.devices()[0]
    if on_tpu:
        mcfg = GPTConfig.gpt3_125m(max_seq_len=1024, dropout=0.0)
        ekw = dict(max_slots=16, block_size=16, prefill_chunk=128,
                   max_model_len=512, weights="wo8")
        prompt_len, max_new, tpl_len, tail_len = 128, 64, 96, 32
    else:
        # small enough that N replicas + a cold control compile inside
        # the CI budget; the fleet rows measure SCALING, not the engine
        mcfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                         num_heads=4, max_seq_len=128, dropout=0.0,
                         use_flash_attention=False)
        ekw = dict(max_slots=4, block_size=8, prefill_chunk=8,
                   max_model_len=64)
        prompt_len, max_new, tpl_len, tail_len = 12, 12, 16, 6
    block_size = ekw["block_size"]
    vocab = mcfg.vocab_size

    def build_engine(engine_id, enable_prefix=True):
        paddle.seed(0)                 # identical weights per replica
        m = GPTForPretraining(mcfg)
        if ekw.get("weights") == "wo8":
            from paddle_tpu.quant import quantize_for_decode
            quantize_for_decode(m)
        e = ServingEngine(m, config=EngineConfig(
            engine_id=engine_id, enable_prefix_cache=enable_prefix,
            **ekw))
        # warm NOW: compiles land sequentially at build time, outside
        # the timed waves and outside any concurrent trace
        e.submit(list(range(2, 2 + block_size)),
                 SamplingParams(max_new_tokens=2))
        e.run_until_idle()
        return e

    engines = [build_engine(i) for i in range(n_replicas)]
    replicas = [InProcessReplica(f"b{i}", e)
                for i, e in enumerate(engines)]
    for e in engines:
        e.start()

    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, vocab, (prompt_len + (i % 5) - 2,)).tolist()
               for i in range(8)]

    def wave(router, n_requests, wave_prompts):
        results = [None] * n_requests
        errors = []

        def worker(i):
            try:
                results[i] = router.generate(
                    wave_prompts[i % len(wave_prompts)],
                    {"max_new_tokens": max_new})
            except Exception as e:      # noqa: BLE001 — surfaced below
                errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_requests)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = max(1e-9, time.perf_counter() - t0)
        if errors:
            raise RuntimeError(f"fleet wave failed: {errors[:3]}")
        return sum(len(r) for r in results) / dt, results

    try:
        # single-replica rated baseline through the SAME router
        # machinery (1-replica fleet), so the efficiency ratio isolates
        # fleet scaling, not router/threading overhead; best-of-2 waves
        single_router = FleetRouter(replicas[:1], block_size=block_size,
                                    probe_interval_s=0.05)
        n_single = 2 * ekw["max_slots"]
        single_tps = max(wave(single_router, n_single, prompts)[0]
                         for _ in range(2))

        router = FleetRouter(replicas, block_size=block_size,
                             probe_interval_s=0.05)
        fleet_tps = max(
            wave(router, n_replicas * n_single, prompts)[0]
            for _ in range(2))
        efficiency = fleet_tps / max(n_replicas * single_tps, 1e-9)
        print(f"# fleet rated: {fleet_tps:.1f} tok/s over {n_replicas} "
              f"replicas vs {single_tps:.1f} single "
              f"(efficiency {efficiency:.3f})", file=sys.stderr)

        # shared-prefix affinity leg: every prompt opens with the same
        # template (>= 1 full block), so rendezvous prefix affinity must
        # land ALL of them on one replica where the radix index is warm
        template = rs.randint(0, vocab, (tpl_len,)).tolist()
        shared = [template + rs.randint(0, vocab, (tail_len,)).tolist()
                  for _ in range(8)]
        before = [e.prefix_stats() for e in engines]
        router.generate(shared[0], {"max_new_tokens": 2})   # warm the
        _, warm_streams = wave(router, len(shared), shared)  # affine one
        after = [e.prefix_stats() for e in engines]
        hits = [a["hits"] - b["hits"] for a, b in zip(after, before)]
        saved = sum(a["tokens_saved"] - b["tokens_saved"]
                    for a, b in zip(after, before))
        offered = sum(a["tokens_offered"] - b["tokens_offered"]
                      for a, b in zip(after, before))
        hit_rate = saved / offered if offered else 0.0
        affine = int(np.argmax(hits)) if any(hits) else None
        concentrated = sum(hits) > 0 and max(hits) == sum(hits)
        print(f"# fleet shared-prefix: hit_rate {round(hit_rate, 4)}, "
              f"hits per replica {hits} (affine b{affine}, "
              f"concentrated={concentrated})", file=sys.stderr)
    finally:
        for e in engines:
            e.stop()

    # the cold reference: a fresh prefix-cache-OFF single engine must
    # produce bit-identical streams — affinity is placement, and
    # placement must be invisible in the output
    control = build_engine(1000 + n_replicas, enable_prefix=False)
    refs = []
    for p in shared:
        h = control.submit(p, SamplingParams(max_new_tokens=max_new))
        control.run_until_idle()
        refs.append(list(h.output_tokens))
    identical = [list(s) for s in warm_streams] == refs

    tsink = telemetry.JsonlSink(args.telemetry)
    summary = {
        "metric": "fleet.rated_throughput_tokens_per_sec",
        "value": round(fleet_tps, 1),
        "unit": "tokens/sec",
        **device_stamp(),
        "fleet.rated_throughput_tokens_per_sec": round(fleet_tps, 1),
        "fleet.scaling_efficiency": round(efficiency, 4),
        "fleet.replicas": n_replicas,
        "single_replica_tokens_per_sec": round(single_tps, 1),
        "serving.prefix_hit_rate": round(hit_rate, 4),
        "prefix_hits_per_replica": hits,
        "prefix_affine_replica": affine,
        "prefix_hits_concentrated": concentrated,
        "prefix_streams_identical": identical,
    }
    for name, unit in (("fleet.rated_throughput_tokens_per_sec",
                        "tokens/sec"),
                       ("fleet.scaling_efficiency", "frac"),
                       ("fleet.replicas", "replicas")):
        tsink.write(telemetry.make_bench_record(
            name, summary[name], unit=unit, device=dev.device_kind))
    tsink.close()
    print(json.dumps(summary))

    rc = 0
    if not identical:
        print("FAIL: fleet shared-prefix streams diverged from the "
              "cold single-engine control", file=sys.stderr)
        rc = 4
    if hit_rate <= 0:
        print("FAIL: fleet-wide prefix hit rate is zero — affinity "
              "routing never landed a prompt on its warm replica",
              file=sys.stderr)
        rc = 4
    elif not concentrated:
        print(f"FAIL: prefix hits scattered across replicas {hits} — "
              "rendezvous affinity is not concentrating the shared "
              "template", file=sys.stderr)
        rc = 4
    return rc


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cpu", action="store_true",
                    help="hermetic CPU smoke config (CI)")
    ap.add_argument("--fleet", type=int, default=None, metavar="N",
                    help="fleet mode: bench a FleetRouter over N "
                         "in-process replicas (owns the fleet.* rows); "
                         "skips the single-engine sweep")
    ap.add_argument("--telemetry", default="serving_telemetry.jsonl")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="p99 TTFT SLO (default: config-dependent)")
    ap.add_argument("--slo-tpot-ms", type=float, default=None,
                    help="p99 TPOT SLO (default: config-dependent)")
    ap.add_argument("--check-vs-single", type=float, default=None,
                    metavar="R", help="exit 4 unless engine throughput "
                    ">= R x the single-request predictor")
    args = ap.parse_args(argv)

    from bench import device_stamp, start
    on_tpu = start(args.cpu)
    if args.fleet:
        if args.fleet < 1:
            ap.error("--fleet needs N >= 1")
        return fleet_phase(args, args.fleet)
    import jax
    import paddle_tpu as paddle
    from paddle_tpu import telemetry
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.serving import EngineConfig, ServingEngine

    dev = jax.devices()[0]
    paddle.seed(0)
    if on_tpu:
        # bench.py's wo8 decode recipe, engine-served: GPT-125M
        # W8A16 at serving batch sizes (decode is weight-bandwidth
        # bound, so slot count ~multiplies the weight-sweep yield)
        mcfg = GPTConfig.gpt3_125m(max_seq_len=1024, dropout=0.0)
        ecfg = EngineConfig(max_slots=16, block_size=16,
                            prefill_chunk=128, max_model_len=512,
                            weights="wo8")
        prompt_len, max_new = 128, 128
        slo_ttft = args.slo_ttft_ms or 2000.0
        slo_tpot = args.slo_tpot_ms or 20.0
    else:
        # --cpu smoke: big enough that the model step dominates the
        # per-step host work (h=128 toys measure engine overhead, not
        # batching), small enough for the CI budget
        mcfg = GPTConfig(vocab_size=2048, hidden_size=256, num_layers=4,
                         num_heads=8, max_seq_len=128, dropout=0.0,
                         use_flash_attention=False)
        ecfg = EngineConfig(max_slots=8, block_size=8, prefill_chunk=16,
                            max_model_len=48)
        prompt_len, max_new = 12, 24
        slo_ttft = args.slo_ttft_ms or 60000.0
        slo_tpot = args.slo_tpot_ms or 250.0

    model = GPTForPretraining(mcfg)
    if ecfg.weights == "wo8":
        # quantize BEFORE the single-stream baseline so the ratio
        # isolates CONTINUOUS BATCHING: both sides serve wo8 weights
        # (the engine's own quantize call is then an idempotent no-op);
        # otherwise the ~1.36x quantization win would inflate
        # serving.throughput_vs_single
        from paddle_tpu.quant import quantize_for_decode
        quantize_for_decode(model)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, mcfg.vocab_size,
                          (prompt_len + (i % 5) - 2,)).tolist()
               for i in range(8)]

    tsink = telemetry.JsonlSink(args.telemetry)
    single_tps = single_stream_baseline(model, prompts[:3], max_new)

    with telemetry.CompileObservatory(sink=tsink, action="record"):
        engine = ServingEngine(model, config=ecfg)
        # warmup: compile prefill + decode outside the timed levels
        h = engine.submit(prompts[0][:prompt_len],
                          max_new_tokens=4)
        engine.run_until_idle()
        levels = []
        level = 1
        while level <= ecfg.max_slots:
            _, stats = serve_level(engine, prompts, max_new, level)
            levels.append(stats)
            print(f"# level {level}: {stats['tokens_per_sec']} tok/s "
                  f"ttft_p99 {_fmt(stats['ttft_p99_ms'])}ms "
                  f"tpot_p99 {_fmt(stats['tpot_p99_ms'])}ms",
                  file=sys.stderr)
            level *= 2

        # shared-prefix sweep: warm prefix-cache engine vs cold-cache
        # control over templated prompts (>= 50% shared tokens)
        prefix = shared_prefix_phase(model, on_tpu)
        print(f"# shared-prefix: hit_rate {prefix['serving.prefix_hit_rate']} "
              f"ttft_p50 {_fmt(prefix['serving.prefix_ttft_p50_ms'])}ms "
              f"(cold {_fmt(prefix['prefix_cold_ttft_p50_ms'])}ms, "
              f"speedup {prefix['serving.prefix_ttft_speedup']}x), "
              f"saved {prefix['serving.prefill_tokens_saved']}/"
              f"{prefix['serving.prefill_tokens_offered']} tokens, "
              f"streams_identical={prefix['prefix_streams_identical']}",
              file=sys.stderr)

    within = [s for s in levels
              if s["ttft_p99_ms"] is not None
              and s["ttft_p99_ms"] <= slo_ttft
              and (s["tpot_p99_ms"] is None
                   or s["tpot_p99_ms"] <= slo_tpot)]
    best = max(within or levels, key=lambda s: s["tokens_per_sec"])

    # tracer cost at the rated level (outside the observatory — see
    # trace_overhead_phase): on-vs-off throughput as a gated fraction
    overhead = trace_overhead_phase(model, ecfg, prompts, max_new,
                                    best["level"])
    print(f"# trace overhead: {overhead['serving.trace_overhead_frac']} "
          f"(on {overhead['trace_on_tokens_per_sec']} vs off "
          f"{overhead['trace_off_tokens_per_sec']} tok/s at level "
          f"{best['level']})", file=sys.stderr)

    summary = {
        "metric": "serving.throughput_tokens_per_sec",
        "value": best["tokens_per_sec"],
        "unit": "tokens/sec",
        **device_stamp(),
        "slo_ttft_ms": slo_ttft,
        "slo_tpot_ms": slo_tpot,
        "slo_met": bool(within),
        "best_level": best["level"],
        "serving.single_stream_tokens_per_sec": round(single_tps, 1),
        "serving.throughput_vs_single":
            round(best["tokens_per_sec"] / max(single_tps, 1e-9), 3),
        # percentiles may be None on degenerate levels (every request
        # finished with <2 tokens -> no TPOT); bench records keep the
        # null + the gate flags it rather than crashing the sweep here
        "serving.ttft_p50_ms": _r2(best["ttft_p50_ms"]),
        "serving.ttft_p99_ms": _r2(best["ttft_p99_ms"]),
        "serving.tpot_p50_ms": _r2(best["tpot_p50_ms"]),
        "serving.tpot_p99_ms": _r2(best["tpot_p99_ms"]),
        "serving.requests": sum(s["requests"] for s in levels),
        "serving.preemptions": self_preempt(engine),
        "serving.kv_block_utilization_peak":
            round(engine.kv_peak_utilization, 4),
        "levels": levels,
    }
    summary.update({k: v for k, v in prefix.items()
                    if not k.startswith("_")})
    summary.update(overhead)

    # typed records: the declared serving family, one record each —
    # tools/bench_gate.py's unit of account from round r06 on
    from paddle_tpu.telemetry.sink import SERVING_BENCH_METRICS
    units = {"tokens_per_sec": "tokens/sec", "_ms": "ms",
             "vs_single": "x", "speedup": "x", "hit_rate": "frac",
             "recomputed": "tokens", "tokens_saved": "tokens",
             "tokens_offered": "tokens", "requests": "requests",
             "preemptions": "preemptions", "utilization": "frac",
             "overhead": "frac"}

    def unit_of(name):
        for suffix, u in units.items():
            if suffix in name:
                return u
        return "count"

    values = dict(summary)
    values["serving.throughput_tokens_per_sec"] = summary["value"]
    for name in SERVING_BENCH_METRICS:
        if name.startswith("serving.rated_") or name.startswith("fleet."):
            # the rated-load SLO rows are owned by the resilience
            # drill's leg (tools/serving_drill.py --rated-only) and the
            # fleet.* rows by this bench's own --fleet mode — both run
            # into the same gated file; a null placeholder here would
            # shadow a real measurement
            continue
        v = values.get(name)
        extra = {}
        if v is None:
            # null values must carry their reason (sink schema): the
            # gate then reports a null_value finding, not a schema error
            extra["error"] = ("no measurement: degenerate level "
                              "(every request finished with <2 tokens)")
        tsink.write(telemetry.make_bench_record(
            name, v, unit=unit_of(name), device=dev.device_kind,
            **extra))

    print(json.dumps(summary))
    print(f"# device={dev.device_kind} engine "
          f"{best['tokens_per_sec']:.0f} tok/s at level {best['level']} "
          f"vs single {single_tps:.0f} tok/s "
          f"({summary['serving.throughput_vs_single']}x), "
          f"slo_met={summary['slo_met']}", file=sys.stderr)

    if not prefix["prefix_streams_identical"]:
        print("FAIL: shared-prefix streams diverged from the "
              "cold-cache control — prefix sharing corrupted a stream",
              file=sys.stderr)
        return 4
    if args.check_vs_single is not None and \
            summary["serving.throughput_vs_single"] < args.check_vs_single:
        print(f"FAIL: throughput_vs_single "
              f"{summary['serving.throughput_vs_single']} < required "
              f"{args.check_vs_single}", file=sys.stderr)
        return 4
    return 0


def self_preempt(engine):
    from paddle_tpu import monitor
    return int(monitor.get("serving.preemptions", 0))


if __name__ == "__main__":
    sys.exit(main())
