"""Serving resilience (paddle_tpu/serving/resilience + engine wiring):
server-side deadlines reaped at step boundaries, cancellation with
immediate KV release, SLO-aware admission control / load shedding,
graceful drain + warm restart after transient step faults, EngineStopped
semantics, the kind=serving telemetry ledger, and the drill specimens."""
import json
import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.resilience.retry import classify_failure, tag_transient
from paddle_tpu.serving import (AdmissionController, BlockLeakError,
                                BlockPool, Deadlines,
                                DeadlineExceededError, EngineDeadError,
                                EngineDrainingError, EngineStoppedError,
                                QueueFullError, RequestCancelledError,
                                SamplingParams, Scheduler, ServingEngine,
                                ShedError)
from paddle_tpu.serving.resilience import expired_reason, restart_backoff
from paddle_tpu.serving.scheduler import Request

TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")


def _small_gpt(seed=0):
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                    num_heads=4, max_seq_len=128, dropout=0.0,
                    use_flash_attention=False)
    return GPTForPretraining(cfg)


def _refs(model, prompts, max_new):
    out = []
    for p in prompts:
        ids = paddle.to_tensor(np.asarray([p], np.int32))
        o, _ = model.generate(ids, max_new_tokens=max_new)
        out.append(np.asarray(o.numpy())[0, len(p):].tolist())
    return out


def _req(prompt_len=4, max_new=8, deadlines=None, priority="normal",
         submit_time=None):
    return Request(list(range(1, prompt_len + 1)),
                   SamplingParams(max_new_tokens=max_new),
                   np.zeros((2,), np.uint32), submit_time=submit_time,
                   deadlines=deadlines, priority=priority)


# ---------------------------------------------------------------------------
# pure-host policy: deadlines, priorities, admission, backoff
# ---------------------------------------------------------------------------

class TestPolicy:
    def test_deadlines_validate_and_budget(self):
        d = Deadlines(queue_wait_s=0.5, total_s=2.0)
        assert d.admission_budget_s() == 0.5
        assert Deadlines(ttft_s=1.0).admission_budget_s() is None
        assert Deadlines().admission_budget_s() is None
        with pytest.raises(ValueError):
            Deadlines(queue_wait_s=0)
        with pytest.raises(ValueError):
            Deadlines(total_s=-1)

    def test_expired_reason_fake_clock(self):
        t0 = 100.0
        r = _req(deadlines=Deadlines(queue_wait_s=1.0, ttft_s=2.0,
                                     total_s=5.0), submit_time=t0)
        assert expired_reason(r, t0 + 0.5) is None
        assert expired_reason(r, t0 + 1.5) == "queue_wait"
        r.state = "prefill"                 # admitted: queue bound off
        assert expired_reason(r, t0 + 1.5) is None
        assert expired_reason(r, t0 + 2.5) == "ttft"
        r.first_token_time = t0 + 1.9       # first token landed in time
        assert expired_reason(r, t0 + 2.5) is None
        assert expired_reason(r, t0 + 5.5) == "total"
        assert expired_reason(_req(submit_time=t0), t0 + 1e6) is None

    def test_requeue_does_not_rearm_queue_deadline(self):
        """A preempted / warm-restart-requeued request already met its
        queue budget once — back in the WAITING state it must not be
        expired on a clock that kept running since submit."""
        t0 = 100.0
        r = _req(deadlines=Deadlines(queue_wait_s=1.0), submit_time=t0)
        r.admit_time = t0 + 0.3             # admitted inside budget
        r.state = "waiting"                 # ... then requeued
        assert expired_reason(r, t0 + 50.0) is None
        sched = Scheduler(BlockPool(64), block_size=8, max_slots=2,
                          max_model_len=64)
        sched.enqueue(r)
        assert sched.reap(t0 + 50.0) == []

    def test_priority_queue_ordering_and_requeue_front(self):
        sched = Scheduler(BlockPool(64), block_size=8, max_slots=2,
                          max_model_len=64)
        batch = _req(priority="batch")
        norm1 = _req(priority="normal")
        inter = _req(priority="interactive")
        norm2 = _req(priority="normal")
        for r in (batch, norm1, inter, norm2):
            sched.submit(r)
        # interactive first, FIFO within normal, batch last
        assert sched.waiting == [inter, norm1, norm2, batch]
        # a requeued request goes to the FRONT of its class, not ahead
        # of more urgent classes
        sched.waiting.remove(norm2)
        norm2.state = "prefill"
        sched.requeue(norm2)
        assert sched.waiting == [inter, norm2, norm1, batch]

    def test_admission_controller_sheds(self):
        ac = AdmissionController(max_queue=3, max_slots=2)
        waiting = [_req(max_new=10) for _ in range(2)]
        # no measured TPOT yet: prediction abstains, queue bound holds
        assert ac.admit_or_raise(
            _req(deadlines=Deadlines(queue_wait_s=0.001)), waiting) \
            is None
        ac.note_tpot_ms(10.0)
        ac.note_tpot_ms(20.0)
        assert 10.0 < ac.tpot_ema_ms < 20.0
        # predicted: 2 waiting * 10 tokens * ema / 2 slots = 10*ema ms
        predicted = ac.predicted_queue_wait_ms(waiting)
        assert predicted == pytest.approx(10 * ac.tpot_ema_ms)
        with pytest.raises(ShedError) as e:
            ac.admit_or_raise(
                _req(deadlines=Deadlines(queue_wait_s=0.001)), waiting)
        assert e.value.queue_depth == 2
        assert e.value.predicted_wait_ms == pytest.approx(predicted)
        assert e.value.retry_after_s > 0
        # headroom: not shed
        assert ac.admit_or_raise(
            _req(deadlines=Deadlines(queue_wait_s=60.0)), waiting) \
            is not None
        # bounded queue sheds EVERYONE past the cap, deadline or not
        with pytest.raises(QueueFullError):
            ac.admit_or_raise(_req(), waiting + [_req()])
        # prediction counts only requests AHEAD in the class order: an
        # interactive request jumps a batch backlog, so a queue full of
        # batch work must not shed it
        batch_backlog = [_req(max_new=10, priority="batch")
                         for _ in range(2)]
        assert ac.admit_or_raise(
            _req(deadlines=Deadlines(queue_wait_s=0.001),
                 priority="interactive"), batch_backlog) is not None
        with pytest.raises(ShedError):      # same-class backlog DOES shed
            ac.admit_or_raise(
                _req(deadlines=Deadlines(queue_wait_s=0.001),
                     priority="batch"), batch_backlog)

    def test_scheduler_reap_fake_clock(self):
        sched = Scheduler(BlockPool(64), block_size=8, max_slots=2,
                          max_model_len=64)
        t0 = 50.0
        ok = _req(submit_time=t0)
        late = _req(deadlines=Deadlines(queue_wait_s=1.0),
                    submit_time=t0)
        gone = _req(submit_time=t0)
        for r in (ok, late, gone):
            sched.submit(r)
        gone.cancel_requested = True
        reaped = dict((r.rid, why) for r, why in sched.reap(t0 + 2.0))
        assert reaped == {late.rid: "queue_wait", gone.rid: "cancelled"}

    def test_restart_backoff_schedule(self):
        assert restart_backoff(1, 0.5) == 0.5
        assert restart_backoff(2, 0.5) == 1.0
        assert restart_backoff(3, 0.5) == 2.0
        assert restart_backoff(20, 0.5) == 30.0    # capped

    def test_tag_transient_overrides_classification(self):
        assert classify_failure(tag_transient(ValueError("x"))) \
            == "transient"
        assert classify_failure(
            tag_transient(OSError(5, "io"), transient=False)) \
            == "permanent"
        assert classify_failure(ValueError("x")) == "permanent"
        assert classify_failure(RuntimeError("x")) == "infra"

    def test_block_pool_assert_quiesced(self):
        pool = BlockPool(8)
        blocks = pool.alloc(2, owner="r1")
        with pytest.raises(BlockLeakError, match="r1"):
            pool.assert_quiesced()
        pool.free(blocks)
        pool.assert_quiesced()              # clean pool passes


# ---------------------------------------------------------------------------
# kind=serving telemetry: schema + trace_check cross-rules + specimens
# ---------------------------------------------------------------------------

def _tc():
    sys.path.insert(0, TOOLS)
    import trace_check
    return trace_check


def _write(tmp_path, name, recs):
    p = tmp_path / name
    p.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    return str(p)


def _srec(event, **kw):
    from paddle_tpu.telemetry import make_serving_record
    return make_serving_record(event, **kw)


def test_serving_record_schema():
    from paddle_tpu.telemetry import validate_step_record
    ok = _srec("shed", queue_depth=4, predicted_wait_ms=120.0,
               retry_after_s=1.0, reason="queue_full")
    assert validate_step_record(ok) == []
    with pytest.raises(ValueError):
        _srec("vanished")                   # unknown event
    bad = dict(ok, queue_depth=-1)
    assert any("queue_depth" in p for p in validate_step_record(bad))
    q = _srec("quiesce", kv_blocks_used=0,
              counts={"admitted": 1, "finished": 1})
    assert validate_step_record(q) == []
    # a quiesce that cannot be audited is invalid per-record
    naked = {k: v for k, v in q.items()
             if k not in ("kv_blocks_used", "counts")}
    probs = validate_step_record(naked)
    assert any("kv_blocks_used" in p for p in probs)
    assert any("counts" in p for p in probs)


def test_trace_check_serving_cross_rules(tmp_path):
    tc = _tc()
    counts = {"admitted": 2, "finished": 1, "failed": 0, "cancelled": 1,
              "expired": 0, "shed": 1}
    clean = [
        _srec("admitted", rid=0, engine=0, queue_depth=1),
        _srec("shed", rid=1, engine=0, queue_depth=2,
              reason="queue_full"),
        _srec("admitted", rid=2, engine=0, queue_depth=1),
        _srec("cancelled", rid=2, engine=0, n_tokens=3),
        _srec("finished", rid=0, engine=0, n_tokens=8,
              queue_wait_ms=5.0, queue_deadline_ms=100.0),
        _srec("quiesce", engine=0, kv_blocks_used=0, counts=counts),
    ]
    problems, stats = tc.check_pair(_write(tmp_path, "ok.jsonl", clean))
    assert problems == [] and stats["n_serving"] == 6

    # shed without queue_depth
    problems, _ = tc.check_pair(_write(tmp_path, "shed.jsonl", [
        _srec("shed", rid=0, reason="queue_full")]))
    assert any("no queue_depth" in p for p in problems)

    # leaked blocks at quiesce
    problems, _ = tc.check_pair(_write(tmp_path, "leak.jsonl", [
        _srec("quiesce", kv_blocks_used=2,
              counts={"admitted": 0, "finished": 0})]))
    assert any("still allocated at quiesce" in p for p in problems)

    # unbalanced accounting
    problems, _ = tc.check_pair(_write(tmp_path, "bal.jsonl", [
        _srec("quiesce", kv_blocks_used=0,
              counts={"admitted": 3, "finished": 2})]))
    assert any("don't balance" in p for p in problems)

    # ledger records contradicting the quiesce snapshot
    problems, _ = tc.check_pair(_write(tmp_path, "tally.jsonl", [
        _srec("admitted", rid=0, engine=1, queue_depth=0),
        _srec("admitted", rid=1, engine=1, queue_depth=1),
        _srec("finished", rid=0, engine=1),
        _srec("finished", rid=1, engine=1),
        _srec("quiesce", engine=1, kv_blocks_used=0,
              counts={"admitted": 1, "finished": 1, "failed": 0,
                      "cancelled": 0, "expired": 0})]))
    assert any("disagree" in p for p in problems)

    # deadline miss: run to completion past the recorded queue budget
    problems, _ = tc.check_pair(_write(tmp_path, "miss.jsonl", [
        _srec("finished", rid=0, n_tokens=4, queue_wait_ms=900.0,
              queue_deadline_ms=50.0)]))
    assert any("deadline miss" in p for p in problems)


def test_drill_specimens_are_caught():
    """The checked-in specimens gate the drill's --selfcheck: each must
    trip exactly its family."""
    tc = _tc()
    leak, _ = tc.check_pair(os.path.join(TOOLS, "specimens",
                                         "serving_leak.jsonl"))
    assert any("still allocated at quiesce" in p for p in leak)
    assert not any("deadline miss" in p for p in leak)
    miss, _ = tc.check_pair(os.path.join(TOOLS, "specimens",
                                         "serving_deadline_miss.jsonl"))
    assert any("deadline miss" in p for p in miss)
    assert not any("still allocated" in p for p in miss)


def test_metrics_http_healthz_has_serving_section():
    from paddle_tpu.telemetry.metrics_http import MetricsServer
    monitor.incr("serving.shed", 0)
    _, body = MetricsServer().healthz()
    assert "serving" in body
    for key in ("queue_depth", "shed", "cancelled", "deadline_exceeded",
                "queue_wait_ms_p99", "restarts", "draining"):
        assert key in body["serving"]


# ---------------------------------------------------------------------------
# engine wiring (real model; lockstep where possible)
# ---------------------------------------------------------------------------

def test_cancel_releases_blocks_immediately():
    model = _small_gpt()
    rs = np.random.RandomState(0)
    p = rs.randint(0, 512, (8,)).tolist()
    ref = _refs(model, [p], 8)[0]
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=64)
    before = monitor.get("serving.cancelled", 0)
    h = eng.submit(p, SamplingParams(max_new_tokens=8))
    for _ in range(3):
        eng.step()
    assert eng.pool.num_used > 0            # mid-flight, blocks held
    assert h.cancel() is True
    assert eng.pool.num_used == 0           # released NOW, not at idle
    assert h.status == "cancelled"
    assert h.cancel() is False              # idempotent
    assert monitor.get("serving.cancelled", 0) == before + 1
    with pytest.raises(RequestCancelledError):
        h.result(timeout=5)
    # streamed prefix was real: it matches the reference stream
    assert h.output_tokens == ref[:len(h.output_tokens)]
    # the engine keeps serving
    h2 = eng.submit(p, SamplingParams(max_new_tokens=8))
    eng.run_until_idle(max_steps=2000)
    assert h2.output_tokens == ref


def test_deadline_expiry_statuses_and_counters():
    model = _small_gpt()
    rs = np.random.RandomState(0)
    p = rs.randint(0, 512, (6,)).tolist()
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=64)
    before = monitor.get("serving.deadline_exceeded", 0)
    # an unmeetable TTFT budget: admitted, then expired at a boundary
    h = eng.submit(p, SamplingParams(max_new_tokens=8),
                   deadlines=Deadlines(ttft_s=1e-4))
    time.sleep(0.002)
    eng.run_until_idle(max_steps=200)
    assert h.status == "expired"
    with pytest.raises(DeadlineExceededError) as e:
        h.result(timeout=5)
    assert e.value.which == "ttft"
    # queue-wait budget binds while WAITING only
    h2 = eng.submit(p, SamplingParams(max_new_tokens=8),
                    deadlines=Deadlines(queue_wait_s=1e-4))
    time.sleep(0.002)
    eng.run_until_idle(max_steps=200)
    assert h2.status == "expired"
    assert monitor.get("serving.deadline_exceeded", 0) == before + 2
    assert eng._counts["expired"] == 2
    assert eng.pool.num_used == 0


def test_shed_queue_full_and_ledger(tmp_path):
    from paddle_tpu.telemetry import JsonlSink
    model = _small_gpt()
    rs = np.random.RandomState(0)
    p = rs.randint(0, 512, (6,)).tolist()
    ref = _refs(model, [p], 6)[0]
    path = str(tmp_path / "serving.jsonl")
    sink = JsonlSink(path)
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=64,
                        max_queue=2, sink=sink)
    before = monitor.get("serving.shed", 0)
    eng.admission.tpot_ema_ms = 50.0        # pretend measured TPOT
    handles = [eng.submit(p, SamplingParams(max_new_tokens=6))
               for _ in range(2)]
    # predicted-deadline shed: 2 waiting * 6 tok * 50ms / 2 slots
    with pytest.raises(ShedError) as e:
        eng.submit(p, SamplingParams(max_new_tokens=6),
                   deadlines=Deadlines(queue_wait_s=0.001))
    assert e.value.retry_after_s > 0
    # queue-full shed binds regardless of deadlines
    with pytest.raises(QueueFullError):
        eng.submit(p, SamplingParams(max_new_tokens=6))
    assert monitor.get("serving.shed", 0) == before + 2
    eng.run_until_idle(max_steps=2000)
    assert all(h.output_tokens == ref for h in handles)
    eng.emit_quiesce()
    sink.close()
    # the ledger validates, including the per-engine quiesce accounting
    problems, stats = _tc().check_pair(path)
    assert problems == []
    assert stats["n_serving"] == 2 + 2 + 2 + 1  # admit+shed+finish+quiesce


def test_stop_fails_blocked_submitters():
    model = _small_gpt()
    rs = np.random.RandomState(0)
    p = rs.randint(0, 512, (6,)).tolist()
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=64)
    handles = [eng.submit(p, SamplingParams(max_new_tokens=8))
               for _ in range(3)]
    eng.stop()                              # loop never ran: queue stuck
    for h in handles:
        assert h.status == "failed"
        with pytest.raises(EngineStoppedError):
            h.result(timeout=5)
    with pytest.raises(EngineStoppedError):
        eng.submit(p, SamplingParams(max_new_tokens=4))
    assert eng._counts["failed"] == 3


def test_stop_stays_bounded_when_loop_is_wedged():
    """A wedged step holding the engine lock past the join window must
    not turn stop() into an unbounded hang: stop gives up after its
    bounded lock window and returns (leftovers wait for a later stop)."""
    import threading
    model = _small_gpt()
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=64)
    eng._join_timeout_s = 0.1
    eng._stop_lock_timeout_s = 0.1
    release = threading.Event()

    def wedged():
        with eng._mu:                       # a step stuck on "device"
            release.wait(30)

    t = threading.Thread(target=wedged, daemon=True)
    t.start()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:      # until the holder owns it
        if not eng._mu.acquire(blocking=False):
            break
        eng._mu.release()
        time.sleep(0.005)
    eng._thread = t                         # stands in for the loop
    t0 = time.monotonic()
    assert eng.stop() is False
    assert time.monotonic() - t0 < 2.0      # bounded, not forever
    release.set()
    t.join(timeout=10)
    eng._thread = None


@pytest.mark.slow
def test_warm_restart_replays_streams_identically():
    """A .transient-tagged step fault must warm-restart the engine:
    arenas rebuilt, in-flight requests REQUEUED, and every stream
    token-identical to run_generate — the restart is invisible."""
    model = _small_gpt()
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 512, (n,)).tolist() for n in (7, 5, 9)]
    refs = _refs(model, prompts, 10)
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=64,
                        restart_backoff_s=0.01)
    before = monitor.get("serving.restarts", 0)
    calls = {"n": 0}
    orig = eng._decode_greedy_jit

    def flaky(*a, **k):
        calls["n"] += 1
        if calls["n"] == 4:
            raise tag_transient(OSError(5, "injected transient fault"))
        return orig(*a, **k)

    eng._decode_greedy_jit = flaky
    with eng:
        handles = [eng.submit(pp, SamplingParams(max_new_tokens=10))
                   for pp in prompts]
        for h, ref in zip(handles, refs):
            assert h.result(timeout=180) == ref
    assert calls["n"] >= 4                  # the fault really fired
    assert monitor.get("serving.restarts", 0) == before + 1
    assert eng._counts["finished"] == 3 and eng._counts["failed"] == 0


@pytest.mark.slow
def test_engine_dead_after_restart_cap():
    """A PERSISTENT transient fault must not restart forever: past
    max_restarts consecutive failures the engine declares itself dead,
    fails everything outstanding, and refuses new work."""
    model = _small_gpt()
    rs = np.random.RandomState(0)
    p = rs.randint(0, 512, (6,)).tolist()
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=64,
                        max_restarts=2, restart_backoff_s=0.01)

    def always_down(*a, **k):
        raise tag_transient(OSError(5, "device gone"))

    eng._decode_greedy_jit = always_down
    eng.start()
    h = eng.submit(p, SamplingParams(max_new_tokens=4))
    with pytest.raises(EngineDeadError, match="device gone"):
        h.result(timeout=120)
    assert eng.dead
    with pytest.raises(EngineDeadError):
        eng.submit(p, SamplingParams(max_new_tokens=4))
    with pytest.raises(EngineDeadError):
        eng.start()
    eng.stop()
    assert eng.pool.num_used == 0
    assert monitor.get_gauge("serving.engine_dead", 0) == 1


@pytest.mark.slow
def test_drain_flips_readiness_and_finishes_load():
    import threading
    import urllib.error
    import urllib.request
    from paddle_tpu.serving import ServingHTTPServer
    model = _small_gpt()
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 512, (6,)).tolist() for _ in range(4)]
    refs = _refs(model, prompts, 10)
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=64)
    with eng, ServingHTTPServer(eng, port=0) as srv:
        handles = [eng.submit(pp, SamplingParams(max_new_tokens=10))
                   for pp in prompts]
        done = {}
        t = threading.Thread(
            target=lambda: done.update(ok=eng.drain(timeout=120)))
        t.start()
        deadline = time.monotonic() + 10
        while not eng.draining and time.monotonic() < deadline:
            time.sleep(0.005)
        assert eng.draining
        # readiness flips 503-draining, liveness stays green
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(srv.url + "/healthz", timeout=30)
        assert e.value.code == 503
        assert json.loads(e.value.read().decode())["status"] == \
            "draining"
        assert urllib.request.urlopen(srv.url + "/livez",
                                      timeout=30).status == 200
        with pytest.raises(EngineDrainingError):
            eng.submit(prompts[0], SamplingParams(max_new_tokens=4))
        # ... and over HTTP: 503 with Retry-After
        body = json.dumps({"prompt": prompts[0],
                           "max_new_tokens": 4}).encode()
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(urllib.request.Request(
                srv.url + "/generate", data=body,
                headers={"Content-Type": "application/json"}),
                timeout=30)
        assert e.value.code == 503
        t.join(timeout=180)
        assert done.get("ok") is True
        for h, ref in zip(handles, refs):
            assert h.output_tokens == ref   # accepted work FINISHED
        eng.resume_admission()
        h = eng.submit(prompts[0], SamplingParams(max_new_tokens=4))
        assert h.result(timeout=120) == refs[0][:4]


@pytest.mark.slow
def test_http_midstream_error_ends_stream_cleanly():
    """An engine error mid-stream must terminate the JSONL stream with
    a final {"error": ...} event and a valid chunked epilogue (the
    non-stream path answers 500 with the error note) — regression for
    the broken-chunked-body path."""
    import urllib.error
    import urllib.request
    from paddle_tpu.serving import ServingHTTPServer
    model = _small_gpt()
    rs = np.random.RandomState(0)
    p = rs.randint(0, 512, (6,)).tolist()
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=64)

    def boom(*a, **k):
        raise ValueError("injected raising decode")

    with eng, ServingHTTPServer(eng, port=0) as srv:
        eng._decode_greedy_jit = boom
        body = json.dumps({"prompt": p, "max_new_tokens": 6,
                           "stream": True}).encode()
        r = urllib.request.urlopen(urllib.request.Request(
            srv.url + "/generate", data=body,
            headers={"Content-Type": "application/json"}), timeout=120)
        raw = r.read().decode()             # full chunked body decodes
        lines = [json.loads(ln) for ln in raw.strip().splitlines()]
        assert "error" in lines[-1]
        assert "injected raising decode" in lines[-1]["error"]
        assert lines[-1]["status"] == "failed"
        # non-stream path: 500 + the error note
        body = json.dumps({"prompt": p, "max_new_tokens": 6}).encode()
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(urllib.request.Request(
                srv.url + "/generate", data=body,
                headers={"Content-Type": "application/json"}),
                timeout=120)
        assert e.value.code == 500
        assert "injected raising decode" in \
            json.loads(e.value.read().decode())["error"]


@pytest.mark.slow
def test_http_shed_answers_429_with_retry_after():
    import urllib.error
    import urllib.request
    from paddle_tpu.serving import ServingHTTPServer
    model = _small_gpt()
    rs = np.random.RandomState(0)
    p = rs.randint(0, 512, (6,)).tolist()
    ref = _refs(model, [p], 6)[0]
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=64, max_queue=2)
    eng.admission.tpot_ema_ms = 50.0
    with ServingHTTPServer(eng, port=0) as srv:   # engine paused
        handles = [eng.submit(p, SamplingParams(max_new_tokens=6))
                   for _ in range(2)]
        body = json.dumps({"prompt": p, "max_new_tokens": 6,
                           "queue_wait_deadline_s": 0.001}).encode()
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(urllib.request.Request(
                srv.url + "/generate", data=body,
                headers={"Content-Type": "application/json"}),
                timeout=30)
        assert e.value.code == 429
        assert int(e.value.headers["Retry-After"]) >= 1
        payload = json.loads(e.value.read().decode())
        assert payload["status"] == "shed"
        assert payload["queue_depth"] == 2
        # a malformed priority is a client error (400), never a shed
        body = json.dumps({"prompt": p, "max_new_tokens": 6,
                           "priority": "urgent"}).encode()
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(urllib.request.Request(
                srv.url + "/generate", data=body,
                headers={"Content-Type": "application/json"}),
                timeout=30)
        assert e.value.code == 400
        eng.run_until_idle(max_steps=2000)
        assert all(h.output_tokens == ref for h in handles)


@pytest.mark.slow
def test_http_request_timeout_cancels_request():
    """A request that outlives the server's request_timeout must be
    CANCELLED, not left decoding to max_tokens with KV blocks pinned —
    the timeout path gets the same treatment as a disconnect."""
    import urllib.request
    from paddle_tpu.serving import ServingHTTPServer
    model = _small_gpt()
    rs = np.random.RandomState(0)
    p = rs.randint(0, 512, (6,)).tolist()
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=128)
    before = monitor.get("serving.cancelled", 0)
    with eng, ServingHTTPServer(eng, port=0,
                                request_timeout=0.05) as srv:
        body = json.dumps({"prompt": p, "max_new_tokens": 100,
                           "stream": True}).encode()
        r = urllib.request.urlopen(urllib.request.Request(
            srv.url + "/generate", data=body,
            headers={"Content-Type": "application/json"}), timeout=120)
        lines = [json.loads(ln) for ln in
                 r.read().decode().strip().splitlines()]
        assert "error" in lines[-1]         # clean terminal event
        assert monitor.get("serving.cancelled", 0) > before
        deadline = time.monotonic() + 30
        while eng.pool.num_used and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.pool.num_used == 0       # blocks released, not pinned


@pytest.mark.slow
def test_http_client_disconnect_cancels_request():
    """An abandoned stream must not decode to max_tokens pinning KV
    blocks: the engine cancels it the moment the chunk write fails."""
    import socket
    import struct
    from urllib.parse import urlparse
    from paddle_tpu.serving import ServingHTTPServer
    model = _small_gpt()
    rs = np.random.RandomState(0)
    p = rs.randint(0, 512, (6,)).tolist()
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=64)
    before = monitor.get("serving.cancelled", 0)
    with eng, ServingHTTPServer(eng, port=0) as srv:
        u = urlparse(srv.url)
        body = json.dumps({"prompt": p, "max_new_tokens": 48,
                           "stream": True}).encode()
        sk = socket.create_connection((u.hostname, u.port), timeout=30)
        sk.sendall(b"POST /generate HTTP/1.1\r\nHost: t\r\n"
                   b"Content-Type: application/json\r\n"
                   + f"Content-Length: {len(body)}\r\n\r\n".encode()
                   + body)
        got = b""
        while got.count(b'"token"') < 2:
            part = sk.recv(4096)
            if not part:
                break
            got += part
        sk.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                      struct.pack("ii", 1, 0))
        sk.close()                          # RST mid-stream
        deadline = time.monotonic() + 60
        while monitor.get("serving.cancelled", 0) <= before and \
                time.monotonic() < deadline:
            time.sleep(0.01)
        assert monitor.get("serving.cancelled", 0) > before
        assert monitor.get("serving.client_disconnects", 0) > 0
        deadline = time.monotonic() + 30
        while eng.pool.num_used and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.pool.num_used == 0       # blocks back, not pinned
