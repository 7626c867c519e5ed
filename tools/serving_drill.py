#!/usr/bin/env python
"""Serving resilience chaos drill: overload + client disconnects + an
injected transient step fault + graceful drain, leak-checked.

The PR-8 serving smoke proves the engine is CORRECT under concurrency;
this drill proves it is ROBUST under abuse (paddle_tpu/serving/
resilience). Default run:

  1. **Overload wave** — 2x the engine's slots submitted as concurrent
     live streams; every admitted stream must be token-identical to
     single-request `run_generate`.
  2. **Injected transient step fault** — one decode step raises a
     `.transient`-tagged OSError mid-wave: the engine must warm-restart
     (rebuild arenas, REQUEUE in-flight requests for recompute-replay)
     and the admitted streams must STILL be token-identical — the
     restart is invisible on the wire.
  3. **Mid-stream client disconnect** — a real HTTP client goes away
     mid-stream; the engine must detect it and CANCEL the request
     (slot + KV blocks released, `serving.client_disconnects` and
     `serving.cancelled` rise).
  4. **Load shedding + deadlines** — probes with tight queue-wait
     budgets must be shed up front (HTTP 429 + Retry-After) while the
     queue is deep, and a probe with an unmeetable TTFT deadline must
     terminate as `expired` with `serving.deadline_exceeded` counted.
  5. **Graceful drain under load** — `engine.drain()` mid-wave:
     /healthz must flip to 503-draining while /livez stays 200 and a
     new submission bounces 503, the accepted requests must all finish,
     and the drain must emit a balanced quiesce record.
  6. **Quiesce** — zero leaked KV blocks (`BlockPool.assert_quiesced`),
     cancelled+expired+finished+failed == admitted, and the combined
     kind=serving ledger must pass tools/trace_check.py.
  7. **Rated-load leg** — the shed-free SLO leg: offered load at the
     engine's rated level with deadlines ARMED must complete every
     stream with ZERO sheds, zero expiries and a quiesced pool.

--selfcheck (the graphdoctor pattern — prove the failures are visible):
  - the checked-in LEAK specimen (tools/specimens/serving_leak.jsonl —
    a quiesce record holding KV blocks) must be caught by trace_check;
  - the checked-in DEADLINE-MISS specimen
    (tools/specimens/serving_deadline_miss.jsonl — a request run to
    completion past its recorded queue deadline) must be caught;
  - `BlockPool.assert_quiesced` must catch an in-process leak;
  - a mini drill (smaller wave, same legs) must come back clean.

Exit codes: 0 ok; 11 findings; 9 selfcheck miss. Distinct from
trace_check 7 / healthwatch 5 / compile_report 6 / chaos_drill 8 /
serving_smoke 10 so CI logs disambiguate.
"""
import argparse
import json
import os
import socket
import struct
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

LEAK_SPECIMEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "specimens", "serving_leak.jsonl")
MISS_SPECIMEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "specimens", "serving_deadline_miss.jsonl")


def _build(seed=0):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                    num_heads=4, max_seq_len=128, dropout=0.0,
                    use_flash_attention=False)
    return GPTForPretraining(cfg)


def _references(model, prompts, max_new):
    import paddle_tpu as paddle

    refs = []
    for p in prompts:
        ids = paddle.to_tensor(np.asarray([p], np.int32))
        out, _ = model.generate(ids, max_new_tokens=max_new)
        refs.append(np.asarray(out.numpy())[0, len(p):].tolist())
    return refs


def _http_stream_then_hangup(url, prompt, max_new, read_lines=2):
    """POST /generate stream=true over a raw socket, read a couple of
    token lines, then slam the connection shut — the abandoned-client
    shape the engine must detect and cancel."""
    from urllib.parse import urlparse
    u = urlparse(url)
    body = json.dumps({"prompt": prompt, "max_new_tokens": max_new,
                       "stream": True}).encode()
    sk = socket.create_connection((u.hostname, u.port), timeout=30)
    try:
        sk.sendall(b"POST /generate HTTP/1.1\r\n"
                   b"Host: drill\r\n"
                   b"Content-Type: application/json\r\n"
                   + f"Content-Length: {len(body)}\r\n\r\n".encode()
                   + body)
        got = b""
        while got.count(b'"token"') < read_lines:
            part = sk.recv(4096)
            if not part:
                break
            got += part
    finally:
        # hard close: RST instead of a graceful FIN drain, so the
        # server's next chunk write fails like a real dead client
        try:
            sk.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                          struct.pack("ii", 1, 0))
        except OSError:
            pass
        sk.close()


def _wait_for(predicate, timeout_s, interval=0.02):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


def overload_fault_leg(model, sink, findings, n_wave=8, max_new=12,
                       fault_at_call=7):
    """Legs 1-6: overload, fault replay, disconnect, shed/expire,
    drain under load, quiesce."""
    import urllib.error
    import urllib.request
    from paddle_tpu import monitor
    from paddle_tpu.resilience.retry import tag_transient
    from paddle_tpu.serving import (Deadlines, EngineDrainingError,
                                    SamplingParams, ServingEngine,
                                    ServingHTTPServer)

    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 512, (4 + (i % 5),)).tolist()
               for i in range(n_wave)]
    refs = _references(model, prompts, max_new)
    drain_prompts = [rs.randint(0, 512, (6,)).tolist() for _ in range(4)]
    drain_refs = _references(model, drain_prompts, max_new)

    engine = ServingEngine(model, max_slots=4, block_size=8,
                           prefill_chunk=8, max_model_len=64,
                           max_queue=32, restart_backoff_s=0.01,
                           sink=sink)
    # warmup: compiles land + the admission controller gets a measured
    # TPOT (shed prediction abstains until one request has finished)
    w = engine.submit(prompts[0], SamplingParams(max_new_tokens=max_new))
    engine.run_until_idle(max_steps=4000)
    if w.output_tokens != refs[0]:
        findings.append("warmup stream diverged from run_generate")
    if engine.admission.tpot_ema_ms is None:
        findings.append("no measured TPOT after warmup — shed "
                        "prediction can never arm")

    # arm the one-shot transient step fault
    calls = {"n": 0}
    orig = engine._decode_greedy_jit

    def flaky(*args, **kw):
        calls["n"] += 1
        if calls["n"] == fault_at_call:
            raise tag_transient(OSError(5, "injected transient step "
                                           "fault (drill)"))
        return orig(*args, **kw)

    engine._decode_greedy_jit = flaky
    engine.start()
    srv = ServingHTTPServer(engine, port=0).start()
    base_cancel = monitor.get("serving.cancelled", 0)
    base_disc = monitor.get("serving.client_disconnects", 0)
    base_restart = monitor.get("serving.restarts", 0)
    base_expired = monitor.get("serving.deadline_exceeded", 0)
    try:
        # overload wave: 2x slots of concurrent live streams
        handles = [engine.submit(p, SamplingParams(max_new_tokens=max_new))
                   for p in prompts]
        streams = [[] for _ in prompts]
        errors = [None] * len(prompts)

        def client(i, h):
            try:
                for tok in h.tokens(timeout=180):
                    streams[i].append(tok)
            except Exception as e:          # noqa: BLE001 — recorded
                errors[i] = e

        threads = [threading.Thread(target=client, args=(i, h))
                   for i, h in enumerate(handles)]
        for t in threads:
            t.start()

        # shed probes while the queue is deep: tight queue budgets must
        # bounce 429 + Retry-After at the HTTP front
        shed_429 = 0
        for _ in range(3):
            body = json.dumps({"prompt": prompts[0],
                               "max_new_tokens": max_new,
                               "queue_wait_deadline_s": 0.001}).encode()
            try:
                urllib.request.urlopen(urllib.request.Request(
                    srv.url + "/generate", data=body,
                    headers={"Content-Type": "application/json"}),
                    timeout=60)
            except urllib.error.HTTPError as e:
                if e.code == 429:
                    shed_429 += 1
                    if not e.headers.get("Retry-After"):
                        findings.append("429 shed response carries no "
                                        "Retry-After header")
                e.close()
        if shed_429 == 0:
            findings.append("no shed probe bounced 429 under a deep "
                            "queue — admission control is dead")

        # an unmeetable TTFT budget: admitted, then EXPIRED at a step
        # boundary with a clean typed error
        probe = engine.submit(prompts[0],
                              SamplingParams(max_new_tokens=max_new),
                              deadlines=Deadlines(ttft_s=0.0005))
        try:
            probe.result(timeout=60)
            findings.append("0.5ms-TTFT probe finished instead of "
                            "expiring — deadline enforcement is dead")
        except Exception as e:              # noqa: BLE001 — typed below
            if type(e).__name__ != "DeadlineExceededError":
                findings.append(f"TTFT probe raised {type(e).__name__}, "
                                "want DeadlineExceededError")
        if probe.status != "expired":
            findings.append(f"TTFT probe status {probe.status!r}, "
                            "want 'expired'")

        # mid-stream client disconnect through the real HTTP front
        _http_stream_then_hangup(srv.url, prompts[1], max_new)
        if not _wait_for(lambda: monitor.get("serving.cancelled", 0)
                         > base_cancel, 30):
            findings.append("client disconnect did not cancel the "
                            "abandoned request (KV blocks pinned for "
                            "nobody)")
        if monitor.get("serving.client_disconnects", 0) <= base_disc:
            findings.append("serving.client_disconnects did not rise "
                            "on a mid-stream hangup")

        for t in threads:
            t.join(timeout=240)
        for i, (got, ref) in enumerate(zip(streams, refs)):
            if errors[i] is not None:
                findings.append(f"admitted stream {i} raised "
                                f"{type(errors[i]).__name__}: {errors[i]}")
            elif got != ref:
                findings.append(
                    f"admitted stream {i} diverged from run_generate "
                    f"through the fault replay: got {got} want {ref}")
        if monitor.get("serving.restarts", 0) <= base_restart:
            findings.append("the injected transient fault tripped no "
                            "warm restart — the fault path is dead")
        if calls["n"] < fault_at_call:
            findings.append(f"fault never injected (decode called "
                            f"{calls['n']} < {fault_at_call} times) — "
                            "the drill under-loaded the engine")
        if monitor.get("serving.deadline_exceeded", 0) <= base_expired:
            findings.append("serving.deadline_exceeded did not rise")

        # graceful drain under load: readiness flips, liveness stays,
        # accepted work finishes
        dh = [engine.submit(p, SamplingParams(max_new_tokens=max_new))
              for p in drain_prompts]
        drained = {}

        def do_drain():
            drained["ok"] = engine.drain(timeout=180)

        dt = threading.Thread(target=do_drain)
        dt.start()
        if not _wait_for(lambda: engine.draining, 10):
            findings.append("drain() did not flip the draining flag")
        try:
            r = urllib.request.urlopen(srv.url + "/healthz", timeout=30)
            findings.append(f"/healthz answered {r.status} during "
                            "drain, want 503")
            r.close()
        except urllib.error.HTTPError as e:
            if e.code != 503 or \
                    json.loads(e.read().decode()).get("status") != \
                    "draining":
                findings.append(f"/healthz during drain: code {e.code}, "
                                "want 503-draining")
            e.close()
        r = urllib.request.urlopen(srv.url + "/livez", timeout=30)
        if r.status != 200:
            findings.append(f"/livez answered {r.status} during drain "
                            "— liveness must stay green")
        r.close()
        try:
            engine.submit(drain_prompts[0],
                          SamplingParams(max_new_tokens=4))
            findings.append("submit during drain was accepted")
        except EngineDrainingError:
            pass
        dt.join(timeout=240)
        if not drained.get("ok"):
            findings.append("drain did not complete under load")
        for i, h in enumerate(dh):
            if h.output_tokens != drain_refs[i]:
                findings.append(f"drain-window stream {i} diverged: "
                                f"{h.output_tokens} want {drain_refs[i]}")
    finally:
        srv.stop()
        engine._decode_greedy_jit = orig
        engine.stop()

    # quiesce: zero leaked blocks, balanced accounting
    try:
        engine.pool.assert_quiesced()
    except AssertionError as e:
        findings.append(f"KV blocks leaked at quiesce: {e}")
    c = dict(engine._counts)
    terminal = c["finished"] + c["failed"] + c["cancelled"] + c["expired"]
    if c["admitted"] != terminal:
        findings.append(f"request accounting does not balance at "
                        f"quiesce: admitted {c['admitted']} != "
                        f"finished+failed+cancelled+expired {terminal}")
    if c["shed"] == 0:
        findings.append("no shed was recorded engine-side")
    return engine


def rated_leg(model, sink, findings, waves=3, max_new=12):
    """Leg 7: the shed-free SLO leg at rated load. Deadlines are ARMED
    (generous — rated load must never trip them) so the run exercises
    the enforcement machinery."""
    from paddle_tpu.serving import (Deadlines, SamplingParams,
                                    ServingEngine)

    # tracing OFF: this harness offers each wave as one burst, so the
    # late admissions are queue-dominated BY DESIGN — their reqtrace
    # records would trip the healthwatch tail_latency rule on a healthy
    # run (the tracer's own gates live in serving_smoke / tail_report)
    engine = ServingEngine(model, max_slots=4, block_size=8,
                           prefill_chunk=8, max_model_len=64,
                           max_queue=32, sink=sink, enable_tracing=False)
    rs = np.random.RandomState(7)
    warm = engine.submit(rs.randint(0, 512, (6,)).tolist(),
                         SamplingParams(max_new_tokens=max_new))
    engine.run_until_idle(max_steps=4000)
    assert warm.finished
    n_req = waves * engine.cfg.max_slots
    prompts = [rs.randint(0, 512, (4 + (i % 7),)).tolist()
               for i in range(n_req)]
    slo = Deadlines(queue_wait_s=60.0, ttft_s=120.0, total_s=300.0)
    engine.start()
    handles = [engine.submit(p, SamplingParams(max_new_tokens=max_new),
                             deadlines=slo) for p in prompts]
    done = [None] * n_req

    def client(i, h):
        done[i] = list(h.tokens(timeout=300))

    threads = [threading.Thread(target=client, args=(i, h))
               for i, h in enumerate(handles)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    engine.drain(timeout=120)
    engine.stop()

    if any(d is None or len(d) != max_new for d in done):
        findings.append("rated-load leg: not every stream completed")
    shed = engine._counts["shed"]
    expired = engine._counts["expired"]
    if shed or expired:
        findings.append(f"rated-load leg shed {shed} / expired "
                        f"{expired} request(s) — the engine cannot "
                        "hold its own rated load inside the SLO")
    try:
        engine.pool.assert_quiesced()
    except AssertionError as e:
        findings.append(f"rated-load leg leaked KV blocks: {e}")
    print(f"rated load: {n_req} requests, {shed} shed, {expired} expired")


def drill(telemetry_path=None, n_wave=8, max_new=12):
    from paddle_tpu import telemetry

    findings = []
    if telemetry_path is None:
        telemetry_path = os.path.join(
            tempfile.mkdtemp(prefix="serving_drill_"),
            "serving_drill.jsonl")
    # arm the lock-order witness for the whole drill: overload +
    # shedding is exactly the load shape that surfaces an acquisition
    # order the smoke's polite traffic never takes
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from serving_smoke import _lockwatch_arm, _lockwatch_close
    _lockwatch_arm()
    sink = telemetry.JsonlSink(telemetry_path)
    model = _build()
    overload_fault_leg(model, sink, findings, n_wave=n_wave,
                       max_new=max_new)
    rated_leg(model, sink, findings)
    findings += _lockwatch_close(sink)
    sink.close()
    # the combined lifecycle ledger must validate — including the
    # per-engine quiesce accounting cross-rules
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_check
    problems, stats = trace_check.check_pair(telemetry_path)
    findings += [f"telemetry invalid: {p}" for p in problems]
    if stats.get("n_serving", 0) == 0:
        findings.append("no kind=serving records in the drill "
                        "ledger — the engine emitted nothing")
    print(f"serving drill: {len(findings)} finding(s) "
          f"(ledger: {telemetry_path})")
    for f in findings:
        print(f"FAIL: {f}")
    return 11 if findings else 0


def selfcheck():
    """Prove the drill can SEE the failures it gates on."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_check
    from paddle_tpu.serving import BlockLeakError, BlockPool

    misses = []
    # 1) the leak specimen must be caught, with the leak named
    problems, _ = trace_check.check_pair(LEAK_SPECIMEN)
    if not any("still allocated at quiesce" in p for p in problems):
        misses.append("leak specimen NOT caught: a quiesce record "
                      "holding KV blocks sailed through trace_check")
    # 2) the deadline-miss specimen must be caught
    problems, _ = trace_check.check_pair(MISS_SPECIMEN)
    if not any("deadline miss" in p for p in problems):
        misses.append("deadline-miss specimen NOT caught: a request "
                      "run past its queue deadline sailed through")
    # 3) the in-process leak check must fire
    pool = BlockPool(8)
    pool.alloc(3, owner="leaker")
    try:
        pool.assert_quiesced()
        misses.append("BlockPool.assert_quiesced missed 3 leaked "
                      "blocks")
    except BlockLeakError as e:
        if "leaker" not in str(e):
            misses.append("assert_quiesced fired but did not name the "
                          "leaking owner")
    # 4) the mini drill must come back clean. The wave has to outlast
    #    the probes that lean on it (three HTTP sheds, the TTFT probe
    #    and the hangup take ~60 ms together): four rounds of 40 tokens
    #    are ~350 ms of a toy model's steps on a CPU, where two rounds
    #    of 8 were ~65 ms and lost that race more often than not
    if drill(n_wave=16, max_new=40) != 0:
        misses.append("mini drill reported findings on a healthy "
                      "engine")
    for m in misses:
        print(f"SELFCHECK MISS: {m}")
    if not misses:
        print("serving_drill selfcheck OK")
    return 9 if misses else 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--selfcheck", action="store_true")
    ap.add_argument("--telemetry", default=None,
                    help="JSONL ledger path (appended)")
    ap.add_argument("--wave", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() != "tpu":
        jax.config.update("jax_platforms", "cpu")
    if args.selfcheck:
        return selfcheck()
    return drill(args.telemetry, n_wave=args.wave, max_new=args.max_new)


if __name__ == "__main__":
    sys.exit(main())
