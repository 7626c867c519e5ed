#!/usr/bin/env python
"""Fleet chaos drill: 3 HTTP replicas under concurrent load, one
SIGKILLed mid-stream, a rolling restart under continuous traffic —
every stream token-identical to a single-engine reference and the
combined per-process ledger trace_check-clean.

The serving drill (tools/serving_drill.py) proves ONE engine is robust
under abuse; this drill proves the TIER ABOVE it (paddle_tpu/fleet) is
robust when the abuse is replica loss itself. Default run:

  1. **Spawn** — 3 replica subprocesses (`--serve` mode: own model,
     own `engine_id`, own telemetry JSONL, `serving/http.py` front),
     each warmed before it opens its door.
  2. **Chaos wave** — a wave of concurrent streams through the
     `FleetRouter` (prefix-affinity + least-loaded routing); once the
     first stream is mid-flight its replica is SIGKILLed. The router
     must detect the death (probe misses -> declared_dead), fail the
     interrupted streams over with replay, and EVERY stream must
     complete token-identical to the single-engine reference — the
     recompute-replay invariant made fleet-wide.
  3. **Respawn** — the dead replica's port gets a fresh process under a
     NEW engine_id (a new process is a new engine identity; the ledger
     joins fleet accounting to engines per incarnation), and the router
     re-admits it.
  4. **Rolling restart under load** — continuous feeder traffic while
     `router.rolling_restart()` walks the fleet: drain one replica
     (SIGTERM -> drain-to-quiesce -> exit -> respawn), wait ready,
     re-admit, next. ZERO failed requests allowed; every response
     token-identical.
  5. **Ledger** — the concatenation of every process's JSONL (replicas
     across incarnations + the router) must pass tools/trace_check.py
     INCLUDING the kind=fleet cross-rules: deaths justified by failed
     probes, failovers justified by death-or-error, splice arithmetic
     balanced, fleet quiesce counts balanced, per-engine admissions
     agreeing with each engine's own quiesce (the SIGKILLed incarnation
     is exempt — it never quiesces).

The whole drill pins JAX_PLATFORMS=cpu: replicas are separate
processes and must share numerics with the in-process reference.

--selfcheck (the graphdoctor pattern — prove the failures are visible):
  - tools/specimens/fleet_failover_no_death.jsonl (a failover with no
    preceding death and no error) must be CAUGHT by trace_check;
  - tools/specimens/fleet_splice_mismatch.jsonl (a spliced stream
    whose n_tokens != streamed_before + streamed_after) must be CAUGHT;
  - a mini in-process drill (2 engine replicas, injected mid-stream
    failure, failover replay) must come back clean AND its ledger must
    carry the failover/replay_spliced records it claims to gate on.

Exit codes: 0 ok; 12 findings; 9 selfcheck miss. Distinct from
trace_check 7 / chaos_drill 8 / serving_drill 11 /
memwatch 14 so CI logs disambiguate.
"""
import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SPECIMEN_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "specimens")
NO_DEATH_SPECIMEN = os.path.join(SPECIMEN_DIR,
                                 "fleet_failover_no_death.jsonl")
SPLICE_SPECIMEN = os.path.join(SPECIMEN_DIR,
                               "fleet_splice_mismatch.jsonl")


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


def _wait_for(predicate, timeout_s, interval=0.02):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# child: one replica process
# ---------------------------------------------------------------------------

def serve(port, engine_id, telemetry_path, seed=0):
    """Run one replica: engine + HTTP front. SIGTERM is the
    rolling-restart contract: drain to quiesce (the quiesce record
    lands in this replica's ledger), then exit 0. SIGKILL is the chaos
    case: no quiesce, torn tail, exactly what the drill's ledger rules
    must tolerate.

    No warmup submit: the engine's own quiesce counts every admission,
    and trace_check holds the router's admitted_by_engine to EXACT
    agreement with it — a warmup the router never routed would desync
    the two ledgers. The first real request pays the compile instead.
    """
    from paddle_tpu import telemetry
    from paddle_tpu.serving import ServingEngine, ServingHTTPServer

    model = _build(seed)
    sink = telemetry.JsonlSink(telemetry_path)
    engine = ServingEngine(model, max_slots=4, block_size=8,
                           prefill_chunk=8, max_model_len=64,
                           max_queue=64, engine_id=engine_id, sink=sink,
                           enable_tracing=False)
    engine.start()
    srv = ServingHTTPServer(engine, port=port).start()
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda sig, frame: stop.set())
    while not stop.is_set():
        time.sleep(0.05)
    engine.drain(timeout=180)
    srv.stop()
    engine.stop()
    sink.close()
    return 0


def _spawn(port, engine_id, telemetry_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--serve",
         "--port", str(port), "--engine-id", str(engine_id),
         "--telemetry", telemetry_path],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


def _concat_ledgers(paths, out_path):
    """Concatenate per-process JSONLs. A SIGKILLed process may leave a
    torn final line; drop ONLY a non-parsing tail line (anything torn
    mid-file is real corruption and must surface in trace_check)."""
    with open(out_path, "w") as out:
        for p in paths:
            if not os.path.exists(p):
                continue
            with open(p) as f:
                lines = f.read().splitlines()
            if lines:
                try:
                    json.loads(lines[-1])
                except (ValueError, json.JSONDecodeError):
                    lines = lines[:-1]
            for line in lines:
                if line.strip():
                    out.write(line + "\n")
    return out_path


# ---------------------------------------------------------------------------
# the drill
# ---------------------------------------------------------------------------

def drill(telemetry_dir=None, n_replicas=3, n_wave=9, max_new=16):
    from paddle_tpu import monitor, telemetry
    from paddle_tpu.fleet import FleetRouter, HTTPReplica

    findings = []
    tmpdir = telemetry_dir or tempfile.mkdtemp(prefix="fleet_drill_")
    os.makedirs(tmpdir, exist_ok=True)

    # references from an in-process single engine-equivalent: the fleet
    # must be indistinguishable from one uninterrupted model.generate
    model = _build()
    rs = np.random.RandomState(0)
    shared = rs.randint(0, 512, (12,)).tolist()     # affinity prefix
    prompts = []
    for i in range(n_wave):
        if i % 3 == 0:   # every third prompt rides the shared prefix
            prompts.append(shared + rs.randint(0, 512,
                                               (2 + i % 3,)).tolist())
        else:
            prompts.append(rs.randint(0, 512, (8 + i % 5,)).tolist())
    refs = _references(model, prompts, max_new)

    ports = [_free_port() for _ in range(n_replicas)]
    ledgers = [os.path.join(tmpdir, f"replica{i}.jsonl")
               for i in range(n_replicas)]
    procs = {}
    next_id = [n_replicas]          # engine_id allocator: respawns get
    #                                 fresh ids (new process, new engine)
    for i in range(n_replicas):
        procs[f"r{i}"] = _spawn(ports[i], i, ledgers[i])
    replicas = [HTTPReplica(f"r{i}", f"http://127.0.0.1:{ports[i]}",
                            engine_id=i) for i in range(n_replicas)]
    router_ledger = os.path.join(tmpdir, "router.jsonl")
    router_sink = telemetry.JsonlSink(router_ledger)
    router = FleetRouter(replicas, block_size=8, probe_interval_s=0.2,
                         miss_threshold=2, breaker_cooldown_s=0.5,
                         failover_budget=4, sink=router_sink)
    # the deployment's periodic prober (the router itself only probes
    # on the routing path): this is what turns a silent SIGKILL into
    # probe misses -> declared_dead within ~2 probe intervals
    stop_probe = threading.Event()

    def prober():
        while not stop_probe.is_set():
            try:
                router.probe_all()
            except Exception:       # noqa: BLE001 — keep probing
                pass
            time.sleep(0.1)

    probe_thread = threading.Thread(target=prober, daemon=True)
    try:
        for r in replicas:
            if not r.wait_ready(timeout_s=300):
                findings.append(f"{r.name} never became ready")
                return _finish(findings, tmpdir)
        probe_thread.start()

        # ---- leg 2: chaos wave, SIGKILL mid-stream --------------------
        streams = [[] for _ in prompts]
        errors = [None] * len(prompts)

        def client(i):
            try:
                for tok in router.stream(prompts[i],
                                         {"max_new_tokens": max_new},
                                         request_id=f"drill-{i}"):
                    streams[i].append(tok)
            except Exception as e:      # noqa: BLE001 — recorded
                errors[i] = e

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        if not _wait_for(lambda: len(streams[0]) >= 4
                         or not threads[0].is_alive(), 300):
            findings.append("stream 0 never reached 4 tokens — the "
                            "drill could not arm the mid-stream kill")
        with router._mu:
            routes0 = [e for e in router.events
                       if e["event"] == "route"
                       and e.get("request_id") == "drill-0"]
        victim = routes0[-1]["replica"] if routes0 else "r0"
        procs[victim].kill()            # SIGKILL: no drain, no goodbye
        procs[victim].wait(timeout=60)
        for t in threads:
            t.join(timeout=600)
        for i, (got, ref) in enumerate(zip(streams, refs)):
            if errors[i] is not None:
                findings.append(
                    f"chaos-wave stream {i} raised "
                    f"{type(errors[i]).__name__}: {errors[i]}")
            elif got != ref:
                findings.append(
                    f"chaos-wave stream {i} diverged from the single-"
                    f"engine reference through the kill: got {got} "
                    f"want {ref}")
        with router._mu:
            evs = [e["event"] for e in router.events]
        for needed in ("declared_dead", "failover", "replay_spliced"):
            if needed not in evs:
                findings.append(f"the kill produced no {needed!r} "
                                "record — the failure was invisible")
        if monitor.get("fleet.failovers", 0) == 0:
            findings.append("fleet.failovers gauge never rose")

        # ---- leg 3: respawn the dead replica under a new identity -----
        vidx = int(victim[1:])
        new_id = next_id[0]
        next_id[0] += 1
        led = os.path.join(tmpdir, f"replica{vidx}_gen{new_id}.jsonl")
        ledgers.append(led)
        procs[victim] = _spawn(ports[vidx], new_id, led)
        replicas[vidx].engine_id = new_id
        if not replicas[vidx].wait_ready(timeout_s=300):
            findings.append(f"respawned {victim} never became ready")
        router.readmit(victim)

        # ---- leg 4: rolling restart under continuous load -------------
        stop_feed = threading.Event()
        feed_errors = []
        n_feed_ok = [0]

        def feeder(tid):
            k = 0
            while not stop_feed.is_set():
                i = (tid + 3 * k) % len(prompts)
                k += 1
                try:
                    toks = router.generate(
                        prompts[i], {"max_new_tokens": max_new},
                        request_id=f"roll-{tid}-{k}")
                    if toks != refs[i]:
                        feed_errors.append(
                            f"rolling-restart request roll-{tid}-{k} "
                            f"diverged: got {toks} want {refs[i]}")
                    else:
                        n_feed_ok[0] += 1
                except Exception as e:  # noqa: BLE001 — zero allowed
                    feed_errors.append(
                        f"rolling-restart request roll-{tid}-{k} "
                        f"FAILED: {type(e).__name__}: {e}")

        feeders = [threading.Thread(target=feeder, args=(t,))
                   for t in range(3)]
        for t in feeders:
            t.start()

        def restart_fn(replica):
            idx = int(replica.name[1:])
            p = procs[replica.name]
            p.terminate()               # SIGTERM: drain-to-quiesce
            p.wait(timeout=300)
            rid = next_id[0]
            next_id[0] += 1
            lpath = os.path.join(tmpdir,
                                 f"replica{idx}_gen{rid}.jsonl")
            ledgers.append(lpath)
            procs[replica.name] = _spawn(ports[idx], rid, lpath)
            replica.engine_id = rid
            if not replica.wait_ready(timeout_s=300):
                raise RuntimeError(
                    f"{replica.name} did not come back ready")

        restarted = router.rolling_restart(restart_fn=restart_fn)
        stop_feed.set()
        for t in feeders:
            t.join(timeout=600)
        if len(restarted) != n_replicas:
            findings.append(
                f"rolling restart completed {len(restarted)}/"
                f"{n_replicas} replicas: {restarted}")
        findings += feed_errors
        if not feed_errors and n_feed_ok[0] == 0:
            findings.append("no feeder request completed during the "
                            "rolling restart — the 'under load' leg "
                            "ran unloaded")
    finally:
        # graceful teardown: every surviving replica drains (quiesce
        # records land), then the router publishes its own ledger
        stop_probe.set()
        if probe_thread.is_alive():
            probe_thread.join(timeout=10)
        for name, p in procs.items():
            if p.poll() is None:
                p.terminate()
        for name, p in procs.items():
            try:
                p.wait(timeout=300)
            except subprocess.TimeoutExpired:
                p.kill()
                findings.append(f"{name} did not drain on SIGTERM")
        router.emit_quiesce()
        router_sink.close()

    # ---- leg 5: the combined ledger must validate ---------------------
    combined = _concat_ledgers(ledgers + [router_ledger],
                               os.path.join(tmpdir, "combined.jsonl"))
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_check
    problems, stats = trace_check.check_pair(combined)
    findings += [f"combined ledger invalid: {p}" for p in problems]
    if stats.get("n_fleet", 0) == 0:
        findings.append("no kind=fleet records in the combined ledger")
    if stats.get("n_serving", 0) == 0:
        findings.append("no kind=serving records in the combined "
                        "ledger — the replicas emitted nothing")
    return _finish(findings, tmpdir)


def _finish(findings, tmpdir):
    print(f"fleet drill: {len(findings)} finding(s) (ledgers: {tmpdir})")
    for f in findings:
        print(f"FAIL: {f}")
    return 12 if findings else 0


# ---------------------------------------------------------------------------
# selfcheck
# ---------------------------------------------------------------------------

def _mini_drill():
    """In-process fleet: 2 engine replicas (each owns its model — a
    shared model leaks tracers across concurrently-compiling engines),
    an injected mid-stream failure, failover replay. Returns (findings,
    ledger_path)."""
    from paddle_tpu import telemetry
    from paddle_tpu.fleet import FleetRouter, InProcessReplica
    from paddle_tpu.fleet.replica import ReplicaStream
    from paddle_tpu.serving import ServingEngine

    findings = []
    tmpdir = tempfile.mkdtemp(prefix="fleet_mini_")
    ledger = os.path.join(tmpdir, "mini.jsonl")
    sink = telemetry.JsonlSink(ledger)

    armed = {"on": True}

    class DyingReplica(InProcessReplica):
        """First stream to reach 3 tokens dies once, fleet-wide."""

        def start_stream(self, *a, **kw):
            inner = super().start_stream(*a, **kw)
            stream = ReplicaStream(inner.request_id, None)

            def gen():
                n = 0
                for tok in inner:
                    yield tok
                    n += 1
                    if armed["on"] and n >= 3:
                        armed["on"] = False
                        raise ConnectionError(
                            "injected mid-stream replica failure "
                            "(drill)")
                stream.stats = inner.stats
            stream._it = gen()
            return stream

    engines = [ServingEngine(_build(), max_slots=4, block_size=8,
                             prefill_chunk=8, max_model_len=64,
                             engine_id=100 + i, sink=sink,
                             enable_tracing=False).start()
               for i in range(2)]
    replicas = [DyingReplica(f"m{i}", e) for i, e in enumerate(engines)]
    router = FleetRouter(replicas, block_size=8, probe_interval_s=0.0,
                         miss_threshold=3, sink=sink)

    model = _build()
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, 512, (10 + i,)).tolist() for i in range(4)]
    refs = _references(model, prompts, 10)
    try:
        for i, p in enumerate(prompts):
            got = router.generate(p, {"max_new_tokens": 10},
                                  request_id=f"mini-{i}")
            if got != refs[i]:
                findings.append(f"mini stream {i} diverged: got {got} "
                                f"want {refs[i]}")
        with router._mu:
            evs = [e["event"] for e in router.events]
        for needed in ("failover", "replay_spliced"):
            if needed not in evs:
                findings.append(f"mini drill produced no {needed!r} "
                                "record")
        for e in engines:
            e.drain(timeout=120)
        router.emit_quiesce()
    finally:
        for e in engines:
            e.stop()
        sink.close()
    return findings, ledger


def selfcheck():
    """Prove the drill can SEE the failures it gates on."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_check

    misses = []
    # 1) the failover-without-death specimen must be caught
    problems, _ = trace_check.check_pair(NO_DEATH_SPECIMEN)
    if not any("neither declared dead nor carries an error" in p
               for p in problems):
        misses.append("failover-without-death specimen NOT caught: a "
                      "failover nothing justified sailed through "
                      "trace_check")
    # 2) the splice-mismatch specimen must be caught
    problems, _ = trace_check.check_pair(SPLICE_SPECIMEN)
    if not any("spliced stream accounting broken" in p
               for p in problems):
        misses.append("splice-mismatch specimen NOT caught: a spliced "
                      "stream whose token counts don't add up sailed "
                      "through trace_check")
    # 3) the mini in-process drill must come back clean, and its ledger
    #    must validate WITH the fleet records it claims to gate on
    findings, ledger = _mini_drill()
    misses += [f"mini drill: {f}" for f in findings]
    problems, stats = trace_check.check_pair(ledger)
    misses += [f"mini ledger invalid: {p}" for p in problems]
    if stats.get("n_fleet", 0) == 0:
        misses.append("mini drill ledger carries no kind=fleet records")
    for m in misses:
        print(f"SELFCHECK MISS: {m}")
    if not misses:
        print("fleet_drill selfcheck OK")
    return 9 if misses else 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--selfcheck", action="store_true")
    ap.add_argument("--serve", action="store_true",
                    help="internal: run one replica process")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--engine-id", type=int, default=0)
    ap.add_argument("--telemetry", default=None,
                    help="serve: this replica's JSONL; drill: ledger "
                         "directory")
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--wave", type=int, default=9)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args(argv)
    # the drill is multi-process: replicas and the in-process reference
    # must share numerics, so the whole drill pins CPU
    os.environ["JAX_PLATFORMS"] = "cpu"
    if args.serve:
        return serve(args.port, args.engine_id, args.telemetry)
    if args.selfcheck:
        return selfcheck()
    return drill(args.telemetry, n_replicas=args.replicas,
                 n_wave=args.wave, max_new=args.max_new)


if __name__ == "__main__":
    sys.exit(main())
