"""The serving step's phases in the profiler's trace.

`telemetry.span` is also a `jax.profiler.TraceAnnotation`, so an XPlane
taken around a running `ServingEngine` shows, on the engine's thread and
on the clock of the device's ops, what the step did: the check an
operator makes with `jax.profiler.start_trace` and nothing of
`benchmark/`. One tiny engine serves a few requests through
`start()`/`submit()` under a trace (Python tracer off); every test
below reads that one `ProfileData`. Nothing here asserts a wall-clock
cost.
"""
import glob
import os
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import telemetry
from paddle_tpu.serving import EngineConfig, SamplingParams, ServingEngine

CHILDREN = ("serving_step.lock_wait", "serving_step.schedule",
            "serving_step.blocks", "serving_step.build", "serving_dispatch",
            "serving_step.fetch", "serving_step.emit",
            "serving_step.mem_snapshot", "serving_step.gauges")
NEW = 10    # tokens asked of each traced request
# the benchmark harness writes spans of these names around the engine
# from outside: the program may use none of them
HARNESS = ("engine_step", "serving_prefill", "serving_decode",
           "serving_decode_sampling", "serving_fork", "train_step_dispatch")


def _small_gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    paddle.seed(0)
    return GPTForPretraining(GPTConfig(
        vocab_size=512, hidden_size=256, num_layers=4, num_heads=4,
        max_seq_len=128, dropout=0.0, use_flash_attention=False))


class Traced:
    """lines: per host thread [(name, start, end, stats)] sorted;
    seen: what a wrapper around `_dispatch` saw, call by call."""

    def __init__(self, data, seen, rids):
        self.seen, self.rids = seen, rids
        self.lines = []
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                evs = sorted(
                    ((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                      dict(ev.stats)) for ev in line.events
                     if ev.name.startswith(("serving_", "collective."))
                     or ev.name in HARNESS),
                    key=lambda e: (e[1], -e[2]))
                if evs:
                    self.lines.append(evs)

    def line_with(self, name):
        found = [ln for ln in self.lines if any(e[0] == name for e in ln)]
        assert len(found) == 1, (name, len(found))
        return found[0]

    def named(self, name, line=None):
        return [e for e in (line or self.line_with(name)) if e[0] == name]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    import jax
    from jax.profiler import ProfileData
    from paddle_tpu import distributed as dist
    eng = ServingEngine(_small_gpt(), config=EngineConfig(
        max_slots=4, block_size=16, prefill_chunk=32, max_model_len=128))
    seen, dispatch = [], eng._dispatch

    def wrapper(family, jitted, args):
        if family.startswith("serving_decode"):
            ctx = np.asarray(args[4])
            # kv_rows: every slot's pages up to the one ctx lies in
            seen.append((family, int((ctx > 0).sum()),
                         int((ctx[ctx > 0] + 1).sum()),
                         int(((ctx // 16 + 1) * 16).sum())))
        elif family == "serving_prefill":
            seen.append((family, int(args[4]), int(args[5])))
        else:
            seen.append((family,))
        return dispatch(family, jitted, args)

    eng._dispatch = wrapper
    rs = np.random.RandomState(3)
    head = rs.randint(1, 512, 24)       # shared: the second hit forks
    prompts = [np.concatenate([head, rs.randint(1, 512, n)])
               .astype(np.int32) for n in (40, 5, 17, 9, 30)]
    out_dir = str(tmp_path_factory.mktemp("xplane"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    eng.start()
    try:
        # compile outside the trace, then wait for the step that served
        # the last warm token to end: a span that began before the
        # trace is not in it, and its later siblings would be orphans
        for p in prompts[:2]:
            eng.submit(p, SamplingParams(max_new_tokens=3)).result(
                timeout=300)
        with eng._mu:
            seen.clear()
        jax.profiler.start_trace(out_dir, profiler_options=options)
        try:
            handles = [eng.submit(p, SamplingParams(max_new_tokens=NEW))
                       for p in prompts]
            for h in handles:
                h.result(timeout=300)
            with eng._mu:       # the last step has ended
                pass
            dist.all_reduce(paddle.to_tensor(np.ones(4, np.float32)))
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.stop()
    files = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(files) == 1
    return Traced(ProfileData.from_file(files[0]), seen,
                  [h.rid for h in handles])


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_every_span_of_the_table_is_on_the_steps_thread(traced):
    line = traced.line_with("serving_step")
    names = {e[0] for e in line}
    assert names >= set(CHILDREN) | {"serving_step"}
    # nothing the program writes bears a name the harness keeps
    assert not [e[0] for ln in traced.lines for e in ln if e[0] in HARNESS]
    # the phases exist on no other thread
    for ln in traced.lines:
        if ln is not line:
            assert not {e[0] for e in ln} & (set(CHILDREN)
                                            | {"serving_step"})


def test_each_child_lies_inside_a_step(traced):
    line = traced.line_with("serving_step")
    steps = traced.named("serving_step", line)
    for ev in line:
        if ev[0] != "serving_step":
            assert any(_inside(ev, st) for st in steps), ev
    # a step counts itself: `step` is the engine's step index
    idx = [st[3]["step"] for st in steps]
    assert idx == list(range(idx[0], idx[0] + len(idx)))


def test_children_tile_the_steps_that_did_work(traced):
    """The named phases cover 95% of the steps that dispatched: of all
    of them taken together, and of the median step (a thread that the
    host deschedules between two spans loses a millisecond that is no
    code of the step's, so single steps are not held to it)."""
    line = traced.line_with("serving_step")
    shares, covered_all, length_all = [], 0, 0
    for st in traced.named("serving_step", line):
        if not any(e[0] == "serving_dispatch" and _inside(e, st)
                   for e in line):
            continue
        covered, at = 0, st[1]
        for s, e in sorted((e[1], e[2]) for e in line
                           if e[0] in CHILDREN and _inside(e, st)):
            if e > at:
                covered += e - max(s, at)
                at = e
        shares.append(covered / (st[2] - st[1]))
        covered_all += covered
        length_all += st[2] - st[1]
    assert len(shares) >= 10
    assert covered_all >= 0.95 * length_all, sorted(shares)
    assert sorted(shares)[len(shares) // 2] >= 0.95, sorted(shares)


def test_phases_carry_their_attributes(traced):
    line = traced.line_with("serving_step")
    for name in ("serving_step.blocks", "serving_step.build",
                 "serving_step.fetch", "serving_step.emit"):
        kinds = {e[3]["kind"] for e in traced.named(name, line)}
        assert kinds == {"prefill", "decode"}, (name, kinds)
    sched = traced.named("serving_step.schedule", line)
    assert sum(e[3]["admitted"] for e in sched) == len(traced.rids)
    assert all(e[3]["waiting"] >= 0 for e in sched)
    emits = traced.named("serving_step.emit", line)
    # every token of every request is emitted once
    assert sum(e[3]["tokens"] for e in emits) == NEW * len(traced.rids)


def test_dispatch_attributes_equal_what_dispatch_saw(traced):
    from paddle_tpu.ops.pallas_decode import flash_prefill_kv_rows
    spans = traced.named("serving_dispatch")
    assert len(spans) == len(traced.seen) > 10
    families = set()
    for ev, saw in zip(spans, traced.seen):
        st = ev[3]
        assert st["family"] == saw[0]
        families.add(saw[0])
        if saw[0].startswith("serving_decode"):
            assert (st["slots"], st["ctx_tokens"],
                    st["kv_rows"]) == saw[1:]
            assert st["kv_rows"] >= st["ctx_tokens"]
        elif saw[0] == "serving_prefill":
            assert (st["p0"], st["n_real"]) == saw[1:]
            assert st["rid"] in traced.rids
            # whole 16-row pages up to the chunk's last real position
            assert st["kv_rows"] == flash_prefill_kv_rows(
                st["p0"], st["n_real"], 16)
    assert families == {"serving_prefill", "serving_decode", "serving_fork"}


def test_dispatch_says_whether_a_step_was_in_flight(traced):
    """`in_flight`: was a decode step dispatched and not yet fetched
    when this program was. The loop keeps one in flight whenever a
    batch is decoding, so most decode dispatches say 1; the first
    after an idle engine says 0."""
    spans = traced.named("serving_dispatch")
    assert all(ev[3]["in_flight"] in (0, 1) for ev in spans)
    decodes = [ev[3]["in_flight"] for ev in spans
               if ev[3]["family"] == "serving_decode"]
    assert decodes[0] == 0 or 0 in decodes
    assert sum(decodes) >= 0.8 * len(decodes)
    assert {ev[3]["in_flight"] for ev in spans
            if ev[3]["family"] == "serving_prefill"} == {0, 1}


def test_each_phase_occurs_once_in_a_steady_step(traced):
    """A step that dispatched a decode batch behind one in flight has
    every phase of PERF.md's table: once the four that frame it, once
    a decode batch's blocks, build, dispatch and fetch, and the two
    emits around the fetch (the arenas' swap, the tokens)."""
    line = traced.line_with("serving_step")
    steady = 0
    for st in traced.named("serving_step", line):
        inside = [e for e in line if e is not st and _inside(e, st)]
        if not any(e[0] == "serving_dispatch"
                   and e[3]["family"] == "serving_decode"
                   and e[3]["in_flight"] for e in inside):
            continue
        steady += 1
        count = {}
        for e in inside:
            key = e[0] if "kind" not in e[3] else (e[0], e[3]["kind"])
            count[key] = count.get(key, 0) + 1
        for name in ("serving_step.lock_wait", "serving_step.schedule",
                     "serving_step.mem_snapshot", "serving_step.gauges",
                     ("serving_step.blocks", "decode"),
                     ("serving_step.build", "decode"),
                     ("serving_step.fetch", "decode")):
            assert count.get(name) == 1, (name, count)
        assert count.get(("serving_step.emit", "decode")) == 2, count
    assert steady >= 10


def test_a_steady_run_overlaps_nine_steps_in_ten():
    """50 decode steps of one request: every one but the first is
    dispatched while the one before it is still unfetched."""
    from paddle_tpu import monitor
    eng = ServingEngine(_small_gpt(), config=EngineConfig(
        max_slots=2, block_size=16, prefill_chunk=32, max_model_len=128))
    steps = monitor.get("serving.decode_steps", 0)
    over = monitor.get("serving.decode_steps_overlapped", 0)
    h = eng.submit(np.arange(1, 20, dtype=np.int32),
                   SamplingParams(max_new_tokens=51))
    eng.run_until_idle(max_steps=500)
    assert len(h.output_tokens) == 51
    steps = monitor.get("serving.decode_steps", 0) - steps
    over = monitor.get("serving.decode_steps_overlapped", 0) - over
    assert steps == 50 and steps >= over >= 0.9 * steps


def test_dispatch_arguments_are_laid_out_as_the_taps_read_them():
    """The benchmark's traced runs wrap `_dispatch` and refuse a run
    whose decode call has not 12 arguments with int32 [max_slots] at 3
    (tokens; a device array when they continue from the step in flight)
    and 4 (the real contexts, on the host) and the tables at 5, or whose
    prefill call has not 13 with ids, p0 and n_real at 3, 4, 5."""
    S, C, length = 4, 32, 128
    eng = ServingEngine(_small_gpt(), config=EngineConfig(
        max_slots=S, block_size=16, prefill_chunk=C, max_model_len=length))
    seen, dispatch = [], eng._dispatch

    def int32(x, shape):
        x = np.asarray(x)
        assert x.dtype == np.int32 and x.shape == shape, (x.dtype, x.shape)
        return x

    def tap(family, jitted, args):
        if family == "serving_decode":
            assert len(args) == 12
            tokens = int32(args[3], (S,))
            assert isinstance(args[4], np.ndarray)
            ctx = int32(args[4], (S,))
            tables = np.asarray(args[5])
            assert tables.ndim == 2 and tables.shape[0] == S
            assert ctx.min() >= 0 and ctx.max() < length
            assert ((0 <= tokens) & (tokens < 512)).all()
            seen.append((family, sorted(ctx[ctx > 0].tolist())))
        elif family == "serving_prefill":
            assert len(args) == 13
            int32(args[3], (1, C))
            p0, n_real = int(int32(args[4], ())), int(int32(args[5], ()))
            assert 0 <= p0 and 1 <= n_real <= C and p0 + n_real <= length
            seen.append((family, p0, n_real))
        return dispatch(family, jitted, args)

    eng._dispatch = tap
    rs = np.random.RandomState(5)
    handles = [eng.submit(rs.randint(1, 512, n).astype(np.int32),
                          SamplingParams(max_new_tokens=6))
               for n in (40, 7)]
    eng.run_until_idle(max_steps=500)
    assert all(len(h.output_tokens) == 6 for h in handles)
    assert ("serving_prefill", 0, 32) in seen and \
        ("serving_prefill", 32, 8) in seen
    # the contexts are the real ones, step by step, with a step in flight
    contexts = [e[1] for e in seen if e[0] == "serving_decode"]
    assert contexts[0] == [40]
    both = [c for c in contexts if len(c) == 2]
    assert both and all(b[0] == a[0] + 1 and b[1] == a[1] + 1
                        for a, b in zip(both, both[1:]))


def test_submit_is_on_the_callers_thread_with_the_rid(traced):
    line = traced.line_with("serving_submit")
    assert line is not traced.line_with("serving_step")
    submits = traced.named("serving_submit", line)
    assert [e[3]["rid"] for e in submits] == traced.rids
    waits = traced.named("serving_submit.lock_wait", line)
    assert len(waits) == len(submits)
    assert all(_inside(w, s) for w, s in zip(waits, submits))


def test_a_span_the_program_already_had_is_in_the_xplane(traced):
    line = traced.line_with("collective.all_reduce")
    assert line is traced.line_with("serving_submit")    # the caller's
    ev, = traced.named("collective.all_reduce", line)
    assert ev[3]["shape"] == "(4,)" and ev[3]["bytes"] == 16


def test_span_still_feeds_the_recorder_and_the_open_span_table():
    rec = telemetry.TelemetryRecorder(track_memory=False)
    with rec:
        with telemetry.span("phase", cat="serving", kind="decode",
                            shape=(2, 3)) as sp:
            row, = [s for s in telemetry.open_spans()
                    if s["name"] == "phase"]
            assert row["cat"] == "serving" and row["rank"] == 0
            assert row["thread"] == threading.current_thread().name
            assert row["attrs"] == {"kind": "decode", "shape": (2, 3)}
            sp.set(tokens=4)
    assert not [s for s in telemetry.open_spans() if s["name"] == "phase"]
    got, = [s for s in rec.spans if s["name"] == "phase"]
    assert got["cat"] == "serving" and got["dur"] >= 0
    assert got["args"] == {"kind": "decode", "shape": "(2, 3)", "tokens": 4}
    # begin()/end() where the region is not a block, no recorder active
    sp = telemetry.span("loose").begin()
    assert [s for s in telemetry.open_spans() if s["name"] == "loose"]
    sp.end()
    assert not [s for s in telemetry.open_spans() if s["name"] == "loose"]
    assert not [s for s in rec.spans if s["name"] == "loose"]
