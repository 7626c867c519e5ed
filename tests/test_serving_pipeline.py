"""`ServingEngine` keeps one decode step in flight: `step()` dispatches
decode n+1 from n's tokens on the device and fetches n's afterwards.

What must not change is what a client sees: every stream equals the
single-request `run_generate` (greedy) or the same engine retired after
every step (seeded sampling: the engine folds the token index into the
request's key, `run_generate` splits a chain), whatever joins, leaves,
is cancelled, expires or is evicted while a step is on the device.
Everything here runs on the CPU at toy sizes; nothing asserts a time.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.resilience.retry import tag_transient
from paddle_tpu.serving import (Deadlines, DeadlineExceededError,
                                EngineStoppedError, RequestCancelledError,
                                SamplingParams, ServingEngine)


@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    paddle.seed(0)
    return GPTForPretraining(GPTConfig(
        vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
        max_seq_len=128, dropout=0.0, use_flash_attention=False))


def _engine(model, **kw):
    cfg = dict(max_slots=4, block_size=8, prefill_chunk=8, max_model_len=64)
    cfg.update(kw)
    return ServingEngine(model, **cfg)


def _refs(model, prompts, max_new, **kw):
    out = []
    for p in prompts:
        ids = paddle.to_tensor(np.asarray([p], np.int32))
        o, _ = model.generate(ids, max_new_tokens=max_new, **kw)
        out.append(np.asarray(o.numpy())[0, len(p):].tolist())
    return out


def _prompts(lengths, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 512, (n,)).tolist() for n in lengths]


def _delta(name, since):
    return monitor.get(name, 0) - since


def _step_until_in_flight(eng, handle, tokens=2):
    """Step until `handle`'s request has streamed `tokens` tokens and is
    part of the decode step that is on the device."""
    req = handle._req
    for _ in range(200):
        eng.step()
        flight = eng._in_flight
        if len(req.out_tokens) >= tokens and flight is not None and \
                any(r is req for _, r in flight.entries):
            return flight
    raise AssertionError("the request never was in a step in flight")


def _lockstep(eng):
    """The same engine with every step retired before the next is
    dispatched: `run_until_idle(max_steps=1)` flushes the step in
    flight, so no step ever starts from a token on the device."""
    while eng.run_until_idle(max_steps=1):
        pass


# ---------------------------------------------------------------------------
# streams: what joins and leaves the batch with a step in flight
# ---------------------------------------------------------------------------

def test_greedy_parity_when_requests_end_on_consecutive_steps(model):
    """Equal prompts prefill one a step, so they are placed, and with
    equal answers end, on consecutive steps: a slot leaves the batch in
    every one of the last steps while the next is already dispatched."""
    prompts = _prompts((6, 6, 6, 6))
    refs = _refs(model, prompts, 7)
    steps = monitor.get("serving.decode_steps", 0)
    over = monitor.get("serving.decode_steps_overlapped", 0)
    eng = _engine(model)
    handles = [eng.submit(p, SamplingParams(max_new_tokens=7))
               for p in prompts]
    eng.run_until_idle(max_steps=500)
    ends = [h._req.finish_time for h in handles]
    assert ends == sorted(ends) and len(set(ends)) == 4
    for h, ref in zip(handles, refs):
        assert h.output_tokens == ref
    assert eng.pool.num_used == 0 and eng.sched.num_running() == 0
    assert eng._in_flight is None
    n = _delta("serving.decode_steps", steps)
    assert 0 < _delta("serving.decode_steps_overlapped", over) <= n


def test_one_token_requests_never_enter_a_decode_step(model):
    """`max_new_tokens=1` ends with the first token, the one its last
    chunk samples; it is fetched in the late phase of that step and no
    slot is taken."""
    prompts = _prompts((5, 11, 9))
    refs = _refs(model, prompts, 6)
    eng = _engine(model)
    long_one = eng.submit(prompts[0], SamplingParams(max_new_tokens=6))
    ones = [eng.submit(p, SamplingParams(max_new_tokens=1))
            for p in prompts[1:]]
    eng.run_until_idle(max_steps=500)
    assert long_one.output_tokens == refs[0]
    for h, ref in zip(ones, refs[1:]):
        assert h.output_tokens == ref[:1] and h.status == "finished"
    assert eng.pool.num_used == 0


def test_request_placed_while_a_step_is_in_flight(model):
    """A slot filled since the last dispatch has its token on the host,
    its neighbours theirs on the device: the batch's tokens are merged
    on the device by the program compiled when the engine was built."""
    prompts = _prompts((9, 5, 12))
    refs = _refs(model, prompts, 12)
    eng = _engine(model)
    merges, merge = [], eng._merge_tokens

    def counted(prev, host, from_host):
        merges.append(from_host.copy())
        return merge(prev, host, from_host)

    eng._merge_tokens = counted
    first = eng.submit(prompts[0], SamplingParams(max_new_tokens=12))
    _step_until_in_flight(eng, first)
    later = [eng.submit(p, SamplingParams(max_new_tokens=12))
             for p in prompts[1:]]
    eng.run_until_idle(max_steps=500)
    for h, ref in zip([first] + later, refs):
        assert h.output_tokens == ref
    # the first batch's one token, then each later request joining a
    # running batch once, alone; the steady steps between merge nothing
    assert [int(m.sum()) for m in merges] == [1, 1, 1]


@pytest.mark.parametrize("strategy", ["greedy", "sampling"])
def test_streams_equal_the_loop_retired_every_step(model, strategy):
    """The pipelined loop against the same engine retired after every
    step, for requests that arrive while others decode; sampled streams
    are seeded per request and count their tokens on the host."""
    prompts = _prompts((7, 13, 3, 10), seed=1)
    knobs = dict(max_new_tokens=9) if strategy == "greedy" else dict(
        max_new_tokens=9, decode_strategy="sampling", top_k=20, top_p=0.9,
        temperature=0.8)

    def params(i):
        return SamplingParams(seed=40 + i, **knobs) \
            if strategy == "sampling" else SamplingParams(**knobs)

    def serve(run):
        eng = _engine(model)
        hs = [eng.submit(p, params(i))
              for i, p in enumerate(prompts[:2])]
        for _ in range(4):
            eng.step()
        hs += [eng.submit(p, params(2 + i))
               for i, p in enumerate(prompts[2:])]
        run(eng)
        assert eng.pool.num_used == 0
        return [h.output_tokens for h in hs]

    pipelined = serve(lambda eng: eng.run_until_idle(max_steps=500))
    retired = serve(_lockstep)
    assert pipelined == retired
    assert all(len(s) == 9 for s in pipelined)
    if strategy == "greedy":
        assert pipelined == _refs(model, prompts, 9)


def test_eos_overrun_is_discarded(model):
    """A request with an EOS runs one step past it; that step's token
    is never emitted and never counted, and its block goes back."""
    p, = _prompts((10,))
    ref, = _refs(model, [p], 16)
    eos = ref[4]
    ref_eos, = _refs(model, [p], 16, eos_token_id=eos, pad_token_id=0)
    discarded = monitor.get("serving.tokens_discarded", 0)
    generated = monitor.get("serving.tokens_generated", 0)
    eng = _engine(model, max_slots=2)
    h = eng.submit(p, SamplingParams(max_new_tokens=16, eos_token_id=eos))
    eng.run_until_idle(max_steps=500)
    got = h.output_tokens
    assert got[-1] == eos and eos not in got[:-1]
    assert got + [0] * (16 - len(got)) == ref_eos
    assert list(h.tokens(timeout=5)) == got     # the stream closed there
    assert _delta("serving.tokens_discarded", discarded) == 1
    assert _delta("serving.tokens_generated", generated) == len(got)
    assert eng.pool.num_used == 0 and eng._in_flight is None


# ---------------------------------------------------------------------------
# cancel, deadline, eviction with a step in flight
# ---------------------------------------------------------------------------

def test_cancel_with_a_step_in_flight(model):
    p, other = _prompts((8, 6))
    ref, ref_other = _refs(model, [p, other], 12)
    discarded = monitor.get("serving.tokens_discarded", 0)
    eng = _engine(model, max_slots=2)
    h = eng.submit(p, SamplingParams(max_new_tokens=12))
    bystander = eng.submit(other, SamplingParams(max_new_tokens=12))
    _step_until_in_flight(eng, h)
    assert h.cancel() is True
    seen = h.output_tokens
    eng.run_until_idle(max_steps=500)
    with pytest.raises(RequestCancelledError):
        h.result(timeout=5)
    assert h.output_tokens == seen == ref[:len(seen)]   # none after it
    assert _delta("serving.tokens_discarded", discarded) == 1
    assert bystander.output_tokens == ref_other
    assert eng.pool.num_used == 0


def test_deadline_with_a_step_in_flight(model):
    p, = _prompts((8,))
    ref, = _refs(model, [p], 12)
    eng = _engine(model, max_slots=2)
    h = eng.submit(p, SamplingParams(max_new_tokens=12))
    _step_until_in_flight(eng, h)
    h._req.deadlines = Deadlines(total_s=1e-6)      # blown at the next reap
    seen = h.output_tokens
    eng.run_until_idle(max_steps=500)
    with pytest.raises(DeadlineExceededError):
        h.result(timeout=5)
    assert h.status == "expired"
    assert h.output_tokens == seen == ref[:len(seen)]
    assert eng.pool.num_used == 0 and eng._in_flight is None


def test_preemption_with_a_step_in_flight(model):
    """A pool too small for the load: a request is evicted while the
    step that sampled its next token is still on the device. It keeps
    that token, replays, and ends with the stream it would have had."""
    prompts = _prompts((10, 10, 10, 10))
    refs = _refs(model, prompts, 24)
    eng = _engine(model, num_blocks=11)
    caught, preempt = [], eng.sched.preempt

    def watched(req):
        flight = eng._in_flight
        caught.append(flight is not None
                      and any(r is req for _, r in flight.entries))
        return preempt(req)

    eng.sched.preempt = watched
    handles = [eng.submit(p, SamplingParams(max_new_tokens=24))
               for p in prompts]
    eng.run_until_idle(max_steps=20000)
    assert any(caught), "no request was evicted out of a step in flight"
    for h, ref in zip(handles, refs):
        assert h.output_tokens == ref
    assert eng.pool.num_used == 0


def test_block_freed_under_a_step_in_flight_serves_its_next_owner(model):
    """The device runs programs in dispatch order and each takes the
    arenas from the one before, so a block freed while a step that
    writes it is in flight may go to the next request at once: that
    request's stream is the reference's."""
    a, b = _prompts((24, 16), seed=2)
    ref_b, = _refs(model, [b], 10)
    # 5 usable blocks: `a` comes to hold 4, `b`'s first chunk takes the
    # fifth and its second has to wait for what `a` gives back
    eng = _engine(model, max_slots=2, num_blocks=6,
                  enable_prefix_cache=False)
    ha = eng.submit(a, SamplingParams(max_new_tokens=8))
    _step_until_in_flight(eng, ha, tokens=3)
    hb = eng.submit(b, SamplingParams(max_new_tokens=10))
    eng.step()
    eng.step()
    assert hb.status == "prefill" and len(hb._req.blocks) == 1
    assert eng.pool.num_free == 0
    flight = eng._in_flight
    assert any(r is ha._req for _, r in flight.entries)
    held = set(ha._req.blocks)
    assert ha.cancel() is True          # frees a's blocks under `flight`
    assert eng._in_flight is flight
    eng.step()
    assert set(hb._req.blocks) & held, "b took none of a's blocks"
    eng.run_until_idle(max_steps=500)
    assert hb.output_tokens == ref_b
    assert eng.pool.num_used == 0


# ---------------------------------------------------------------------------
# errors surface a step late; lifecycle calls leave nothing in flight
# ---------------------------------------------------------------------------

class _Unfetchable:
    """A step output whose fetch raises: what a device error looks like
    to the host, a step after the dispatch that caused it."""

    def __init__(self, exc):
        self.exc = exc

    def __array__(self, *a, **k):
        raise self.exc


def _fail_fetch_of_call(eng, n, exc):
    calls = {"n": 0, "after": 0}
    orig = eng._decode_greedy_jit

    def flaky(*a, **k):
        calls["n"] += 1
        tok, logp, new_k, new_v, stats = orig(*a, **k)
        if calls["n"] == n:
            return tok, _Unfetchable(exc), new_k, new_v, stats
        calls["after"] += calls["n"] > n
        return tok, logp, new_k, new_v, stats

    eng._decode_greedy_jit = flaky
    return calls


def test_transient_error_at_the_fetch_voids_both_steps_and_replays(model):
    prompts = _prompts((7, 5, 9))
    refs = _refs(model, prompts, 10)
    eng = _engine(model, max_slots=2, restart_backoff_s=0.01)
    restarts = monitor.get("serving.restarts", 0)
    calls = _fail_fetch_of_call(
        eng, 4, tag_transient(OSError(5, "injected at the fetch")))
    with eng:
        handles = [eng.submit(p, SamplingParams(max_new_tokens=10))
                   for p in prompts]
        for h, ref in zip(handles, refs):
            assert h.result(timeout=180) == ref
    # the step behind the failed one had been dispatched: both were void
    assert calls["n"] > 4 and calls["after"] >= 1
    assert _delta("serving.restarts", restarts) == 1
    assert eng._counts["finished"] == 3 and eng._counts["failed"] == 0
    assert eng._in_flight is None and eng.pool.num_used == 0


def test_permanent_error_at_the_fetch_fails_the_batch_and_serves_on(model):
    p, = _prompts((8,))
    ref, = _refs(model, [p], 6)
    eng = _engine(model, max_slots=2)
    calls = _fail_fetch_of_call(eng, 2, ValueError("injected at the fetch"))
    with eng:
        h = eng.submit(p, SamplingParams(max_new_tokens=6))
        with pytest.raises(RuntimeError, match="injected at the fetch"):
            h.result(timeout=120)
        assert h.status == "failed"
        assert h.output_tokens == ref[:len(h.output_tokens)]
        assert eng.pool.num_used == 0 and eng._in_flight is None
        h2 = eng.submit(p, SamplingParams(max_new_tokens=6))
        assert h2.result(timeout=120) == ref
    assert calls["n"] > 2


@pytest.mark.parametrize("where", ["decode", "chunk", "flush"])
def test_error_under_a_hand_driven_loop_loses_no_token(model, where):
    """No serve loop, so no `_on_step_error`: the exception reaches the
    caller raw, at the fetch of a decode step (a second one already
    dispatched behind it), of a last chunk's first token, or in the
    flush of a `run_until_idle` cut short. The engine takes every
    request back to the newest token the host holds, so driving it
    again finishes every stream as if nothing had happened."""
    prompts = _prompts((7, 5, 9))
    refs = _refs(model, prompts, 10)
    eng = _engine(model, max_slots=2)
    boom = OSError(5, "injected at the fetch")
    if where == "chunk":
        orig, seen = eng._prefill_jit, []

        def flaky(*a, **k):
            tok, *rest = orig(*a, **k)
            seen.append(1)
            # the second request's only chunk: the first is decoding
            return (_Unfetchable(boom) if len(seen) == 2 else tok, *rest)

        eng._prefill_jit = flaky
    else:
        _fail_fetch_of_call(eng, 4, boom)
    handles = [eng.submit(p, SamplingParams(max_new_tokens=10))
               for p in prompts]
    with pytest.raises(OSError, match="injected at the fetch"):
        if where == "flush":
            # decode call 4 is the one in flight when the cut comes
            eng.run_until_idle(max_steps=5)
        else:
            eng.run_until_idle(max_steps=500)
    assert eng._in_flight is None
    for h in handles:
        req = h._req
        assert req.n_prefilled <= len(req.tokens_all) - 1
    eng.run_until_idle(max_steps=500)
    for h, ref in zip(handles, refs):
        assert h.output_tokens == ref and h.status == "finished"
    assert eng.pool.num_used == 0 and eng._in_flight is None


def test_run_until_idle_cut_short_leaves_nothing_in_flight(model):
    p, = _prompts((8,))
    ref, = _refs(model, [p], 12)
    eng = _engine(model, max_slots=2)
    h = eng.submit(p, SamplingParams(max_new_tokens=12))
    assert eng.run_until_idle(max_steps=5) == 5
    assert eng._in_flight is None
    got = h.output_tokens
    assert 0 < len(got) < 12 and got == ref[:len(got)]
    eng.run_until_idle(max_steps=500)
    assert h.output_tokens == ref and eng._in_flight is None


def test_stop_retires_the_step_in_flight_before_it_fails_the_rest(model):
    p, = _prompts((8,))
    ref, = _refs(model, [p], 40)
    eng = _engine(model, max_slots=2)
    eng.start()
    h = eng.submit(p, SamplingParams(max_new_tokens=40))
    stream = h.tokens(timeout=120)
    head = [next(stream) for _ in range(3)]
    assert eng.stop() is True
    assert eng._in_flight is None
    with pytest.raises(EngineStoppedError):
        list(stream)
    got = h.output_tokens
    assert got[:3] == head and got == ref[:len(got)]
    assert eng.pool.num_used == 0


@pytest.mark.parametrize("served_by", ["loop", "caller"])
def test_drain_leaves_nothing_in_flight(model, served_by):
    prompts = _prompts((6, 9))
    refs = _refs(model, prompts, 8)
    eng = _engine(model, max_slots=2)
    if served_by == "loop":
        eng.start()
    handles = [eng.submit(p, SamplingParams(max_new_tokens=8))
               for p in prompts]
    assert eng.drain(timeout=120) is True
    assert eng._in_flight is None
    assert [h.output_tokens for h in handles] == refs
    eng.pool.assert_quiesced()
    eng.stop()


def test_idle_is_not_reported_with_a_step_in_flight(model):
    """The serve loop, `drain` and `run_until_idle` ask `_has_work`: a
    request whose last token is on the device has left the scheduler's
    queues only when that token was fetched."""
    p, = _prompts((8,))
    eng = _engine(model, max_slots=2)
    h = eng.submit(p, SamplingParams(max_new_tokens=3))
    while len(h._req.out_tokens) < 2:
        eng.step()
    with eng._mu:
        assert eng._in_flight is not None and eng._has_work()
    eng.step()          # dispatches nothing: the last token is in flight
    assert h.status == "finished" and len(h.output_tokens) == 3
    with eng._mu:
        assert eng._in_flight is None and not eng._has_work()


def test_consumer_sees_each_token_once_and_in_order(model):
    """A client thread on the stream while the loop keeps a step in
    flight: nothing dropped, nothing reordered."""
    prompts = _prompts((7, 13, 3, 9, 5))
    refs = _refs(model, prompts, 10)
    eng = _engine(model, max_slots=2)
    got = [[] for _ in prompts]

    def client(i, handle):
        for tok in handle.tokens(timeout=180):
            got[i].append((tok, time.monotonic()))

    with eng:
        handles = [eng.submit(p, SamplingParams(max_new_tokens=10))
                   for p in prompts]
        threads = [threading.Thread(target=client, args=(i, h))
                   for i, h in enumerate(handles)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=200)
    for stream, ref in zip(got, refs):
        assert [tok for tok, _ in stream] == ref
        stamps = [t for _, t in stream]
        assert stamps == sorted(stamps)
