"""The `program_span` reader: on a hand-written trace whose numbers can
be worked out on paper, on a small trace recorded on the v5e
(benchmark/fixtures/v5e_serve_steps.xplane.pb.gz: a toy engine's steps,
by tools/record_serve_fixture.py), and in a traced run of the toy
serving cell on the CPU, where what the program's spans say is held
against what the driver's taps on `_dispatch` saw."""
import gzip
import os

import pytest

from benchmark import harness, trace_reduce
from benchmark.readers import program_span as ps

US = 1_000_000      # picoseconds in a microsecond
BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
ROWS = [r for r in BENCH["per_layer"] if harness.load_json(
    harness.HERE, "metrics", r["name"] + ".json")["reader"] == "program_span"]
SHARES = [r["name"] for r in ROWS if r["name"].startswith("idle_share.")]


def _plane(name, lines, stat_names=()):
    """lines: {line name: [(event name, start_us, dur_us, {stat: value})]}"""
    names, out = {}, []
    stat_id = {s: i + 1 for i, s in enumerate(stat_names)}
    for line, events in lines.items():
        evs = []
        for ev, start, dur, stats in events:
            mid = names.setdefault(ev, len(names) + 1)
            st = " ".join(
                f"stats {{ metadata_id: {stat_id[k]} "
                + (f'str_value: "{v}"' if isinstance(v, str)
                   else f"int64_value: {v}") + " }"
                for k, v in stats.items())
            evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                       f"{start * US} duration_ps: {dur * US} {st} }}")
        out.append(f'lines {{ name: "{line}" timestamp_ns: 0 '
                   f'{" ".join(evs)} }}')
    meta = "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in names.items())
    smeta = "\n".join(
        f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in stat_id.items())
    return f'planes {{ name: "{name}" {" ".join(out)} {meta} {smeta} }}'


DEVICE = _plane("/device:TPU:0", {"XLA Ops": [
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 0, 100, {}),
    ("%paged_decode.7 = f32[32,1,768]{2,1,0} custom-call(...)", 200, 100, {}),
    ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %q), kind=kLoop", 450, 50, {}),
    ("%paged_decode.8 = f32[32,1,768]{2,1,0} custom-call(...)", 600, 100,
     {})]})
ENGINE = [
    ("serving_step", 50, 370, {"step": 7}),
    ("engine_step", 45, 380, {}),               # the harness's, outside
    ("serving_step.lock_wait", 50, 10, {}),
    ("serving_step.schedule", 60, 30, {"admitted": 1, "waiting": 2}),
    ("serving_step.build", 90, 70, {"kind": "decode"}),
    ("serving_dispatch", 160, 160, {"family": "serving_decode", "slots": 3,
                                    "ctx_tokens": 100}),
    ("serving_decode", 170, 140, {}),           # the harness's, inside
    ("PjitFunction(decode_fn)", 175, 100, {}),  # JAX's own
    ("serving_step.emit", 320, 80, {"kind": "decode", "tokens": 3}),
    ("serving_step.fetch", 330, 50, {"kind": "decode"}),
    ("serving_step.gauges", 400, 15, {}),
    ("serving_step", 440, 210, {"step": 8}),
    ("serving_step.build", 440, 80, {"kind": "prefill"}),
    ("serving_dispatch", 520, 120, {"family": "serving_prefill", "rid": 5,
                                    "p0": 0, "n_real": 17})]
CLIENT = [
    ("serving_step.build", 100, 100, {"kind": "decode"}),   # not the engine
    ("serving_submit", 300, 40, {"rid": 6}),
    ("serving_submit.lock_wait", 305, 30, {})]
STATS = ("step", "admitted", "waiting", "kind", "family", "slots",
         "ctx_tokens", "tokens", "rid", "p0", "n_real")


def _run(*planes, slots=4):
    """A run as the readers get it, with the trace already loaded."""
    from jax.profiler import ProfileData

    class Cell:
        cell = {"engine": {"max_slots": slots}}

    data = ProfileData.from_text_proto("\n".join(planes))
    return {"spec": Cell, ps.KEY: ps.from_profile(data)}


@pytest.fixture(scope="module")
def by_hand():
    """One device, microseconds; busy 0-100, 200-300, 450-500, 600-700,
    so idle 100-200 (A), 300-450 (B), 500-600 (C): 350 of 700.
      A: build 100-160, dispatch 160-200
      B: dispatch 300-320, emit 320-330 and 380-400, fetch 330-380,
         gauges 400-415, the step itself 415-420, no step 420-440,
         the next step's build 440-450
      C: build 500-520, dispatch 520-600"""
    return _run(DEVICE, _plane("/host:CPU", {"engine": ENGINE,
                                             "client": CLIENT}, STATS))


def _share(run, span):
    return ps.read({"mode": "idle_overlap", "span": span}, run)


def test_a_gap_is_shared_by_the_phases_it_crosses(by_hand):
    per_us = 100.0 / 700
    assert _share(by_hand, "serving_step.build") == \
        pytest.approx(90 * per_us)      # 60 of A, 10 of B, 20 of C
    assert _share(by_hand, "serving_dispatch") == \
        pytest.approx(140 * per_us)     # 40 of A, 20 of B, 80 of C
    assert _share(by_hand, "serving_step.gauges") == \
        pytest.approx(15 * per_us)
    assert _share(by_hand, None) == pytest.approx(20 * per_us)


def test_a_childs_time_is_taken_out_of_its_parent(by_hand):
    per_us = 100.0 / 700
    assert _share(by_hand, "serving_step.fetch") == \
        pytest.approx(50 * per_us)
    assert _share(by_hand, "serving_step.emit") == \
        pytest.approx(30 * per_us)      # 80 less the fetch inside it
    assert _share(by_hand, "serving_step") == pytest.approx(5 * per_us)
    segs = ps.self_segments([e for e in by_hand[ps.KEY].lines[
        by_hand[ps.KEY].engine] if ps.FAMILY.match(e[0])])
    assert all(a[1] <= b[0] for a, b in zip(segs, segs[1:]))    # disjoint
    assert sum(e - s for s, e, _ in segs) == (370 + 210) * 1000


def test_a_span_on_another_thread_or_of_another_owner_is_ignored(by_hand):
    spans = by_hand[ps.KEY]
    assert len(spans.lines) == 2
    names = {e[0] for ln in spans.lines for e in ln}
    assert not names & {"engine_step", "serving_decode",
                        "PjitFunction(decode_fn)"}
    # the client's `build` over gap A would have taken 100 us of it
    gaps, window = ps.device_gaps(spans)
    assert window == (0, 700_000) and len(gaps) == 3
    assert ps.idle_by_span(spans, gaps)["serving_step.build"] == 90_000


def test_absent_is_none_and_present_without_overlap_is_zero(by_hand):
    assert _share(by_hand, "serving_step.mem_snapshot") is None
    assert _share(by_hand, "serving_step.blocks") is None
    assert _share(by_hand, "serving_step.lock_wait") == 0
    assert _share(by_hand, "serving_step.schedule") == 0
    # no device plane: nothing idles, the spans can still be timed
    hostly = _run(_plane("/host:CPU", {"engine": ENGINE}, STATS))
    assert _share(hostly, "serving_step.build") is None
    assert _share(hostly, None) is None
    assert ps.read({"mode": "duration", "span": "serving_step", "q": 50},
                   hostly) == pytest.approx(0.29)
    # a program that writes no spans into the trace (the parent commit)
    bare = _run(DEVICE, _plane("/host:CPU", {"engine": [
        e for e in ENGINE if not ps.KEPT.match(e[0])]}, STATS))
    for row in ROWS:
        meta = harness.load_json(harness.HERE, "metrics",
                                 row["name"] + ".json")
        assert ps.read(meta["args"], bare) is None, row["name"]


def test_the_ten_shares_and_the_unnamed_rest_are_the_idle_share(by_hand):
    assert len(SHARES) == 10
    total = 0.0
    for name in SHARES:
        meta = harness.load_json(harness.HERE, "metrics", name + ".json")
        assert meta["reader"] == "program_span"
        total += ps.read(meta["args"], by_hand) or 0.0
    unnamed = _share(by_hand, "serving_step")
    tr = trace_reduce.Trace(by_hand[ps.KEY].devices, [])
    idle = 100.0 * (1 - trace_reduce.busy_seconds(tr) / 700e-6)
    assert idle == pytest.approx(50.0)
    assert total + unnamed == pytest.approx(idle)


def test_duration_and_attr_ratio(by_hand):
    step = {"mode": "duration", "span": "serving_step", "q": 50}
    assert ps.read(step, by_hand) == pytest.approx(0.29)    # 370, 210 us
    assert ps.read(dict(step, q=100), by_hand) == pytest.approx(0.37)
    decode = {"attr": "family", "prefix": "serving_decode"}
    assert ps.read({"mode": "duration", "span": "serving_dispatch",
                    "q": 50, "where": decode}, by_hand) == \
        pytest.approx(0.16)
    # a client's span is on no engine's thread
    wait = {"mode": "duration", "span": "serving_submit.lock_wait", "q": 50}
    assert ps.read(wait, by_hand) is None
    assert ps.read(dict(wait, thread="any"), by_hand) == pytest.approx(0.03)
    ratio = {"mode": "attr_ratio", "span": "serving_dispatch",
             "where": decode, "attr": "slots", "over": "max_slots"}
    assert ps.read(ratio, by_hand) == pytest.approx(75.0)
    assert ps.read(dict(ratio, where={"attr": "family", "prefix": "x"}),
                   by_hand) is None
    with pytest.raises(ValueError):
        ps.read({"mode": "mean", "span": "serving_step"}, by_hand)


def test_metric_files_and_rows_agree():
    """Thirteen rows, one file each, both serving cells in one row."""
    assert len(ROWS) == 13
    both = ["gpt3-125m.serve-chat", "gpt3-1.3b.serve-long"]
    for row in ROWS:
        meta = harness.load_json(harness.HERE, "metrics",
                                 row["name"] + ".json")
        assert row["source"] == "program_span"
        assert meta["unit"] == row["unit"] and meta["name"] == row["name"]
        assert row["moves"] == "serve_tokens_per_s"
        assert row["workloads"] == (both[:1] if row["name"] ==
                                    "submit_lock_wait_p50_ms" else both)


# -- a trace recorded on the chip ---------------------------------------------

RECORDED = os.path.join(harness.HERE, "fixtures",
                        "v5e_serve_steps.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    if not os.path.exists(RECORDED):
        pytest.skip("no recorded serving trace in the fixtures")
    from benchmark.tools.record_serve_fixture import SLOTS
    out = tmp_path_factory.mktemp("recorded")
    at = out / "plugins" / "profile" / "v5e"
    at.mkdir(parents=True)
    with gzip.open(RECORDED) as f:
        (at / "t.xplane.pb").write_bytes(f.read())
    tracer = harness.Tracer(str(out), 0.0, 0.0)
    tracer.state, tracer.t_on, tracer.t_off = "done", 0.0, 0.0

    class Cell:
        cell = {"engine": {"max_slots": SLOTS}}

    return {"spec": Cell, "tracer": tracer}


def test_recorded_v5e_trace_shares_add_up(recorded):
    spans = ps.spans_of(recorded)
    assert ps.spans_of(recorded) is spans           # loaded once
    assert list(spans.devices) == ["/device:TPU:0"] and \
        spans.engine is not None
    gaps, (start, end) = ps.device_gaps(spans)
    acc, window = ps.idle_by_span(spans, gaps), end - start
    tr = trace_reduce.load(
        trace_reduce.find_xplane(recorded["tracer"].out_dir),
        host_names=("engine_step", "serving_prefill", "serving_decode",
                    "serving_fork"))
    idle_s = window * 1e-9 - trace_reduce.busy_seconds(tr)
    assert sum(acc.values()) * 1e-9 == pytest.approx(idle_s, rel=1e-6)
    # the harness names the same idle time by its own four spans
    gaps = trace_reduce.idle_gaps(tr, trace_reduce.window_of(tr), k=100)
    assert sum(s for _, s in gaps) == pytest.approx(idle_s, rel=1e-6)
    total = sum(ps.read(harness.load_json(
        harness.HERE, "metrics", n + ".json")["args"], recorded) or 0.0
        for n in SHARES)
    unnamed = _share(recorded, "serving_step")
    assert total + unnamed == pytest.approx(100 * idle_s / (window * 1e-9))
    # the phases of the table own the idle time, not what has no name
    assert unnamed < 0.05 * total
    for name in ("serving_step.build", "serving_dispatch",
                 "serving_step.fetch", "serving_step.emit",
                 "serving_step.mem_snapshot", "serving_step.gauges"):
        assert acc[name] > 0, name


def test_recorded_v5e_trace_durations_and_slots(recorded):
    from benchmark.tools.record_serve_fixture import SLOTS
    step = ps.read({"mode": "duration", "span": "serving_step", "q": 50},
                   recorded)
    inner = ps.read({"mode": "duration", "span": "serving_dispatch",
                     "q": 50}, recorded)
    assert 0 < inner < step
    share = ps.read(harness.load_json(
        harness.HERE, "metrics", "decode_slots_share.json")["args"],
        recorded)
    assert 100.0 / SLOTS <= share <= 100.0
    assert ps.read(harness.load_json(
        harness.HERE, "metrics", "submit_lock_wait_p50_ms.json")["args"],
        recorded) >= 0


# -- the toy serving cell, traced on the CPU ----------------------------------

def test_spans_say_what_the_taps_saw(tmp_path):
    """One traced run of the toy chat cell: `decode_slots_share` is the
    taps' `decode_occupancy` over the same dispatches, and the spans'
    (slots, ctx_tokens) are the taps' contexts, dispatch by dispatch."""
    import jax
    from benchmark.drivers.serve import Driver
    tiny = harness.load_json(harness.HERE, "fixtures", "tiny",
                             "BENCHMARK.json")
    spec = harness.Spec("gpt-tiny.serve-chat", bench=tiny)
    seconds = 3.0
    d = Driver(spec, 2 ** 31 + 9, seconds, jax.devices()[:1],
               log=lambda m: None, trace=True)
    d.setup()
    tracer = harness.Tracer(str(tmp_path), 0.3 * seconds, 1.5)
    measured = d.window(tracer)
    assert tracer.state == "done" and d.tap_fault is None
    run = {"spec": spec, "tracer": tracer, "records": measured["records"]}
    d.release()
    spans = ps.spans_of(run)
    found = [e for e in spans.lines[spans.engine]
             if e[0] == "serving_dispatch"]
    assert len(found) > 20

    def of_tap(family, detail):
        if family.startswith("serving_decode"):
            ctx = detail[detail > 0]
            return family, len(ctx), int((ctx + 1).sum())
        return (family,) + (tuple(detail) if detail else ())

    def of_span(st):
        keys = ("slots", "ctx_tokens") if "slots" in st else \
            ("p0", "n_real") if "p0" in st else ()
        return (st["family"],) + tuple(st[k] for k in keys)

    # the traced dispatches are one stretch of all the taps saw
    mine = [of_span(e[3]) for e in found]
    theirs = [of_tap(f, det) for f, _, det in d.dispatches]
    at = [k for k in range(len(theirs) - len(mine) + 1)
          if theirs[k:k + len(mine)] == mine]
    assert len(at) == 1, (mine[:4], len(theirs))
    t_first = d.dispatches[at[0]][1]
    assert tracer.t_on <= t_first <= tracer.t_off
    slots = spec.cell["engine"]["max_slots"]
    occ = [100.0 * (det > 0).sum() / slots
           for f, _, det in d.dispatches[at[0]:at[0] + len(mine)]
           if f.startswith("serving_decode")]
    share = ps.read(harness.load_json(
        harness.HERE, "metrics", "decode_slots_share.json")["args"], run)
    assert share == pytest.approx(harness.median(occ))
    assert ps.read({"mode": "duration", "span": "serving_step", "q": 50},
                   run) > 0
    assert ps.read({"mode": "idle_overlap", "span": "serving_step.build"},
                   run) is None         # the CPU has no device plane
