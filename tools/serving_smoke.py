#!/usr/bin/env python
"""CPU serving smoke: the continuous-batching engine must be
token-for-token identical to run_generate, streaming, live on
/metrics, and recompile-free — plus an eviction selfcheck.

Default leg (CI stage: the engine's correctness gate):
  - N concurrent requests (mixed prompt lengths, greedy) submitted to a
    BACKGROUND-THREADED engine and consumed as live token streams from
    client threads (the real serving shape, not a lockstep test loop);
  - every stream must equal the single-request `run_generate` output
    token-for-token (the engine's numerics contract);
  - one request is also driven through the real HTTP front
    (serving/http.py POST /generate stream=true) and must match;
  - the run executes under a CompileObservatory: each serving step
    family must compile EXACTLY once — a recompile anywhere in the run
    (admission churn, varied prompt lengths, slot rotation, request
    TRACING) means the fixed-shape contract broke; the compile ledger
    must also pass tools/trace_check.py;
  - serving.* gauges must be live on the HTTP /metrics scrape, the
    scrape must carry parseable Prometheus HISTOGRAM series for
    ttft/tpot/queue_wait whose scrape-side p99 tracks the legacy
    gauges, and /traces must serve the exemplar timelines;
  - request tracing (telemetry.reqtrace): every finished request must
    yield a validated kind=reqtrace record whose span durations sum to
    its end-to-end latency (the decomposition invariant — enforced by
    the trace_check pass over the same file).

Shared-prefix leg (the prefix-sharing KV cache round): 6 streams over
2 prompt templates through a prefix-cache engine must
  - report `prefix_hit_rate > 0` (later admissions ride the earlier
    requests' cached template blocks),
  - stay recompile-free (prefill RESUMES at the first uncached token,
    and that resume offset is a traced scalar — it must not widen any
    compile-signature family),
  - and stream tokens IDENTICAL to a cold-cache engine (sharing must
    be invisible in the output, or it is corruption).

--selfcheck (the graphdoctor pattern — prove the failure is visible):
  - an OVER-ADMITTED schedule (block pool far smaller than the offered
    load) must trip eviction: serving.preemptions must rise, and every
    evicted-and-recomputed stream must STILL match run_generate
    token-for-token (preemption is recompute, not corruption);
  - a STALE-INDEX specimen: rebuild the arenas the buggy way (pool
    swapped, prefix index neither flushed nor rebound) — the next
    admission's prefix match MUST raise `StaleIndexError` instead of
    silently splicing dead physical ids into a live block table, and
    the correct rebuild path (`_rebuild_arenas`) must then serve the
    same prompt cleanly.

Exit codes: 0 ok; 10 findings; 9 selfcheck miss. Distinct from
trace_check 7 / healthwatch 5 / compile_report 6 / chaos_drill 8
so CI logs disambiguate.
"""
import argparse
import json
import os
import sys
import tempfile
import threading
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _build(seed=0):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                    num_heads=4, max_seq_len=128, dropout=0.0,
                    use_flash_attention=False)
    return GPTForPretraining(cfg)


def _lockwatch_arm():
    """Arm the lock-order witness BEFORE the engine/sink construct
    their locks, so the run's real acquisition graph is observed."""
    from paddle_tpu.analysis import lockwatch

    lockwatch.reset()
    lockwatch.arm()


def _lockwatch_close(sink):
    """Ledger the witness evidence into `sink` and disarm. Writes the
    static nested-acquisition graph record next to the observed one so
    trace_check's cross-rules gate observed ⊆ static on this very
    file; any observed cycle is a finding here (deadlock-in-waiting
    under the smoke load), as is any static finding (the armed run
    doubles as a live threadlint pass)."""
    from paddle_tpu.analysis import lockwatch, threadlint
    from paddle_tpu.telemetry import sink as sink_mod

    findings = []
    cycles = lockwatch.observed_cycles()
    if cycles:
        findings.append(
            f"observed lock-order cycle(s) under load: {cycles}")
    s_findings, graph = threadlint.lint_repo()
    findings += [f"threadlint: {f!r}" for f in s_findings]
    sink.write(sink_mod.make_thread_lint_record(
        source="static", findings=s_findings, edges=graph["edges"],
        modules=threadlint.MODULES))
    sink.write(lockwatch.observed_record())
    lockwatch.disarm()
    lockwatch.reset()
    return findings


def _references(model, prompts, max_new):
    import paddle_tpu as paddle

    refs = []
    for p in prompts:
        ids = paddle.to_tensor(np.asarray([p], np.int32))
        out, _ = model.generate(ids, max_new_tokens=max_new)
        refs.append(np.asarray(out.numpy())[0, len(p):].tolist())
    return refs


def smoke(n_requests=6, max_new=12):
    from paddle_tpu import monitor, telemetry
    from paddle_tpu.serving import (SamplingParams, ServingEngine,
                                    ServingHTTPServer)

    findings = []
    model = _build()
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 512, (4 + 5 * (i % 3) + i,)).tolist()
               for i in range(n_requests)]
    refs = _references(model, prompts, max_new)

    tel_path = os.path.join(tempfile.mkdtemp(prefix="serving_smoke_"),
                            "serving_smoke.jsonl")
    _lockwatch_arm()
    sink = telemetry.JsonlSink(tel_path)
    with telemetry.CompileObservatory(sink=sink, action="record") as obs:
        engine = ServingEngine(model, max_slots=4, block_size=8,
                               prefill_chunk=8, max_model_len=64,
                               sink=sink)
        with engine, ServingHTTPServer(engine, port=0) as srv:
            # concurrent client threads consuming live streams
            streams = [[] for _ in prompts]

            def client(i, handle):
                for tok in handle.tokens(timeout=120):
                    streams[i].append(tok)

            handles = [engine.submit(p, SamplingParams(
                max_new_tokens=max_new)) for p in prompts]
            threads = [threading.Thread(target=client, args=(i, h))
                       for i, h in enumerate(handles)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)
            for i, (got, ref) in enumerate(zip(streams, refs)):
                if got != ref:
                    findings.append(
                        f"stream {i} diverged from run_generate: "
                        f"got {got} want {ref}")

            # one request through the real HTTP front, streamed
            body = json.dumps({"prompt": prompts[0],
                               "max_new_tokens": max_new,
                               "stream": True}).encode()
            resp = urllib.request.urlopen(urllib.request.Request(
                srv.url + "/generate", data=body,
                headers={"Content-Type": "application/json"}),
                timeout=120)
            lines = [json.loads(ln) for ln in
                     resp.read().decode().strip().splitlines()]
            if lines[-1].get("tokens") != refs[0]:
                findings.append(
                    f"HTTP stream diverged: {lines[-1].get('tokens')} "
                    f"want {refs[0]}")
            if len(lines) != max_new + 1:
                findings.append(
                    f"HTTP stream emitted {len(lines) - 1} token lines, "
                    f"want {max_new}")

            # live metrics on the scrape endpoint
            mtext = urllib.request.urlopen(srv.url + "/metrics",
                                           timeout=30).read().decode()
            for gauge in ("serving_kv_block_utilization",
                          "serving_queue_depth", "serving_ttft_p50_ms",
                          "serving_slo_gauge_age_s"):
                if f"paddle_tpu_{gauge}" not in mtext:
                    findings.append(f"gauge {gauge} missing from /metrics")
            findings += _check_histogram_scrape(mtext)

            # the tail-exemplar timelines endpoint
            tr = json.loads(urllib.request.urlopen(
                srv.url + "/traces?n=4", timeout=30).read().decode())
            if not tr.get("tracing") or not tr.get("traces"):
                findings.append("/traces served no timelines on a "
                                "traced run")
            elif not all(t.get("spans") for t in tr["traces"]):
                findings.append("/traces timelines carry no spans")

        # recompile-free contract: each family compiled EXACTLY once
        fams = {}
        for rec in obs.records:
            fams[rec["fn"]] = fams.get(rec["fn"], 0) + 1
        for fam in ("serving_prefill", "serving_decode"):
            if fams.get(fam, 0) == 0:
                findings.append(f"no compile record for {fam} — the "
                                "observatory never saw the engine")
            elif fams[fam] > 1:
                findings.append(
                    f"{fam} compiled {fams[fam]} times — the engine's "
                    "fixed-shape contract broke (see cause diffs in "
                    f"{tel_path})")
        if monitor.get("serving.preemptions", 0) > 0:
            findings.append("preemptions fired on an under-committed "
                            "pool — the allocator is leaking blocks")

    # the ledger itself must validate: compile records, serving
    # lifecycle records, the reqtrace decomposition cross-rule (every
    # trace's spans must sum to its e2e latency within 1%), AND the
    # lock witness pair (observed acquisition edges ⊆ static graph)
    findings += _lockwatch_close(sink)
    sink.close()
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_check
    problems, stats = trace_check.check_pair(tel_path)
    findings += [f"telemetry invalid: {p}" for p in problems]

    # every finished request must have yielded a trace (N threaded
    # streams + the HTTP leg's request)
    finished_traces = sum(
        1 for r in telemetry.read_jsonl(tel_path)
        if r.get("kind") == "reqtrace" and r.get("outcome") == "finished")
    if finished_traces != n_requests + 1:
        findings.append(
            f"{finished_traces} finished reqtrace record(s) for "
            f"{n_requests + 1} finished requests — a request finished "
            "untraced")

    n_tok = int(monitor.get("serving.tokens_generated", 0))
    print(f"serving smoke: {n_requests} concurrent streams, "
          f"{n_tok} tokens, {finished_traces} traces, "
          f"{len(findings)} finding(s)")
    for f in findings:
        print(f"FAIL: {f}")
    return 10 if findings else 0


def _check_histogram_scrape(mtext):
    """The /metrics text must carry a parseable Prometheus histogram
    for the serving latencies, and the quantile computed FROM THE
    SCRAPE must track the legacy p99 gauge (which the engine now
    recomputes from the same histogram at scrape time)."""
    findings = []
    for fam in ("serving_ttft_ms", "serving_tpot_ms",
                "serving_queue_wait_ms"):
        prefix = f"paddle_tpu_{fam}_bucket{{le="
        p99_name = f"paddle_tpu_{fam}".replace(
            "_ms", "_p99_ms" if fam != "serving_queue_wait_ms"
            else "_ms_p99")
        buckets = []
        gauge = None
        for line in mtext.splitlines():
            if line.startswith(prefix):
                le, _, cum = line[len(prefix):].partition("} ")
                le = le.strip('"')
                buckets.append((float("inf") if le == "+Inf"
                                else float(le), int(cum)))
            if line.startswith(p99_name + " "):
                gauge = float(line.split()[-1])
        if not buckets:
            findings.append(f"no histogram buckets for {fam} on "
                            "/metrics")
            continue
        total = buckets[-1][1]
        if total <= 0:
            findings.append(f"{fam} histogram scraped empty")
            continue
        # scrape-side quantile: same interpolation Prometheus's
        # histogram_quantile applies to the cumulative le series
        target = max(1.0, 0.99 * total)
        p99 = None
        prev_le, prev_cum = 0.0, 0
        for le, cum in buckets:
            if cum >= target:
                hi = le if le != float("inf") else prev_le
                n_in = cum - prev_cum
                p99 = prev_le + (hi - prev_le) * (
                    (target - prev_cum) / max(1, n_in))
                break
            prev_le, prev_cum = le, cum
        if gauge is None:
            findings.append(f"{fam}: p99 gauge missing from the scrape")
        elif p99 is None or abs(p99 - gauge) > 0.15 * max(gauge, 1.0):
            findings.append(
                f"{fam}: scrape-side p99 {p99} does not track the "
                f"legacy gauge {gauge} — the histogram and the gauge "
                "disagree about the same distribution")
    return findings


def prefix_smoke(n_requests=6, max_new=8):
    """Shared-prefix leg: 6 streams over 2 templates. Hit rate must be
    positive, the run recompile-free, and every stream identical to a
    cold-cache engine serving the same schedule."""
    from paddle_tpu import monitor, telemetry
    from paddle_tpu.serving import SamplingParams, ServingEngine

    findings = []
    model = _build(seed=2)
    rs = np.random.RandomState(2)
    templates = [rs.randint(0, 512, (24,)).tolist() for _ in range(2)]
    prompts = [templates[i % 2] + rs.randint(0, 512, (4 + i,)).tolist()
               for i in range(n_requests)]

    # cold-cache control: the same schedule with sharing disabled
    cold = ServingEngine(model, max_slots=4, block_size=8,
                         prefill_chunk=8, max_model_len=64,
                         enable_prefix_cache=False)
    cold_handles = [cold.submit(p, SamplingParams(max_new_tokens=max_new))
                    for p in prompts]
    cold.run_until_idle()
    cold_streams = [h.output_tokens for h in cold_handles]

    tel_path = os.path.join(tempfile.mkdtemp(prefix="serving_prefix_"),
                            "serving_prefix.jsonl")
    _lockwatch_arm()
    sink = telemetry.JsonlSink(tel_path)
    with telemetry.CompileObservatory(sink=sink, action="record") as obs:
        engine = ServingEngine(model, max_slots=4, block_size=8,
                               prefill_chunk=8, max_model_len=64)
        streams = [[] for _ in prompts]
        with engine:
            def client(i, handle):
                for tok in handle.tokens(timeout=120):
                    streams[i].append(tok)

            handles = [engine.submit(p, SamplingParams(
                max_new_tokens=max_new)) for p in prompts]
            threads = [threading.Thread(target=client, args=(i, h))
                       for i, h in enumerate(handles)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)
        for i, (got, want) in enumerate(zip(streams, cold_streams)):
            if got != want:
                findings.append(
                    f"prefix stream {i} diverged from the cold-cache "
                    f"engine: got {got} want {want}")
        ps = engine.prefix_stats()
        if ps["hit_rate"] <= 0 or ps["hits"] <= 0:
            findings.append(
                f"prefix_hit_rate {ps['hit_rate']} on a 2-template "
                f"6-stream schedule — the index matched nothing "
                f"({ps})")
        if monitor.get_gauge("serving.prefix_hit_rate", 0.0) <= 0:
            findings.append("serving.prefix_hit_rate gauge is not live")
        if engine.pool.num_shared != 0:
            findings.append(
                f"{engine.pool.num_shared} blocks still shared after "
                "quiesce — a holder was dropped without release")
        # zero recompiles: prefill-resume offsets ride ONE compiled
        # family; a second compile of any serving family means the
        # prefix path widened a signature
        fams = {}
        for rec in obs.records:
            fams[rec["fn"]] = fams.get(rec["fn"], 0) + 1
        for fam, n in fams.items():
            if fam.startswith("serving_") and n > 1:
                findings.append(
                    f"{fam} compiled {n} times during the shared-prefix "
                    "leg — prefix resume broke the fixed-shape contract "
                    f"(cause diffs in {tel_path})")
    findings += _lockwatch_close(sink)
    sink.close()
    n_saved = int(monitor.get_gauge("serving.prefill_tokens_saved", 0))
    print(f"prefix smoke: {n_requests} streams over 2 templates, "
          f"hit_rate {ps['hit_rate']:.3f}, {n_saved} tokens saved, "
          f"{len(findings)} finding(s)")
    for f in findings:
        print(f"FAIL: {f}")
    return findings


def stale_index_selfcheck():
    """Specimen: a stale index entry surviving an arena rebuild must be
    CAUGHT (StaleIndexError), and the correct rebuild path must then
    serve the same prompt cleanly."""
    from paddle_tpu.serving import (BlockPool, SamplingParams,
                                    ServingEngine, StaleIndexError)

    misses = []
    model = _build(seed=3)
    rs = np.random.RandomState(3)
    prompt = rs.randint(0, 512, (16,)).tolist()
    engine = ServingEngine(model, max_slots=2, block_size=8,
                           prefill_chunk=8, max_model_len=64)
    engine.submit(prompt, SamplingParams(max_new_tokens=2))
    engine.run_until_idle()
    assert engine.prefix_index.num_blocks > 0, "index never populated"
    # the BUGGY rebuild: swap the pool, leave the index bound to the
    # old one with its dead physical ids intact
    engine.pool = BlockPool(engine.pool.num_blocks)
    engine.sched.pool = engine.pool
    engine.submit(prompt, SamplingParams(max_new_tokens=2))
    try:
        engine.run_until_idle(max_steps=50)
        misses.append("a stale index entry survived an arena rebuild "
                      "undetected — admission served dead physical ids")
    except StaleIndexError:
        print("stale-index specimen caught (StaleIndexError at the "
              "first post-rebuild admission)")
    # the CORRECT path: _rebuild_arenas flushes + rebinds; the same
    # prompt must then serve cleanly (cold, no stale hits)
    engine._rebuild_arenas()
    h = engine.submit(prompt, SamplingParams(max_new_tokens=2))
    engine.run_until_idle()
    if len(h.output_tokens) != 2:
        misses.append("post-rebuild serving is broken after the "
                      "correct flush+rebind path")
    return misses


def selfcheck(n_requests=4, max_new=24):
    """Over-admit against a tiny pool: eviction MUST fire and MUST be
    invisible in the streams."""
    from paddle_tpu import monitor
    from paddle_tpu.serving import SamplingParams, ServingEngine

    model = _build()
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, 512, (10,)).tolist()
               for _ in range(n_requests)]
    refs = _references(model, prompts, max_new)
    before = monitor.get("serving.preemptions", 0)
    # pool holds ~2 full sequences; 4 slots all growing must collide
    engine = ServingEngine(model, max_slots=4, block_size=8,
                           prefill_chunk=8, max_model_len=64,
                           num_blocks=11)
    handles = [engine.submit(p, SamplingParams(max_new_tokens=max_new))
               for p in prompts]
    engine.run_until_idle(max_steps=20000)
    fired = monitor.get("serving.preemptions", 0) - before
    misses = []
    if fired <= 0:
        misses.append("over-admitted schedule tripped ZERO preemptions "
                      "— the eviction path is dead or the counter is "
                      "disconnected")
    for i, h in enumerate(handles):
        if h.output_tokens != refs[i]:
            misses.append(f"stream {i} corrupted by eviction: "
                          f"{h.output_tokens} want {refs[i]}")
    stats = [h.stats["preemptions"] for h in handles]
    misses += stale_index_selfcheck()
    print(f"serving selfcheck: {fired} preemptions "
          f"(per-request {stats}), {len(misses)} miss(es)")
    for m in misses:
        print(f"SELFCHECK MISS: {m}")
    if not misses:
        print("serving_smoke selfcheck OK")
    return 9 if misses else 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--selfcheck", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() != "tpu":
        jax.config.update("jax_platforms", "cpu")
    if args.selfcheck:
        return selfcheck()
    rc = smoke(args.requests, args.max_new)
    prefix_findings = prefix_smoke()
    return 10 if (rc or prefix_findings) else 0


if __name__ == "__main__":
    sys.exit(main())
