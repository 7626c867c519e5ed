"""Request tracer (paddle_tpu/telemetry/reqtrace.py + serving wiring):
span timelines tiling each request's life, the decomposition invariant
both ways, pathology spans (preemption / warm restart / CoW), the
slowest-K exemplar ring, log-bucketed latency histograms vs
np.percentile, the /traces + histogram scrape surface, trace_check
cross-rule specimens, the tail_latency anomaly rule, and the
zero-recompile contract under tracing."""
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor, telemetry
from paddle_tpu.monitor import LogHistogram
from paddle_tpu.resilience.retry import tag_transient
from paddle_tpu.serving import SamplingParams, ServingEngine
from paddle_tpu.telemetry.health import AnomalyDetector, HealthConfig
from paddle_tpu.telemetry.reqtrace import (CAUSES, RequestTrace,
                                           RequestTracer, decompose,
                                           dominant_cause,
                                           trace_chrome_spans)
from paddle_tpu.telemetry.sink import (make_reqtrace_record,
                                       validate_step_record)

TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")


def _small_gpt(seed=0):
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                    num_heads=4, max_seq_len=128, dropout=0.0,
                    use_flash_attention=False)
    return GPTForPretraining(cfg)


def _trace_check(path):
    sys.path.insert(0, TOOLS)
    import trace_check
    return trace_check.check_metrics_jsonl(str(path))


def _synthetic_trace(rid, items, outcome="finished", **kw):
    """items: (kind, dur_ms, attrs) tiled from t0=0 — sums by
    construction, like the real tracer."""
    spans, t = [], 0.0
    for kind, dur, attrs in items:
        sp = {"kind": kind, "t0_ms": round(t, 4), "dur_ms": float(dur)}
        sp.update(attrs)
        spans.append(sp)
        t += dur
    return make_reqtrace_record(rid=rid, outcome=outcome, spans=spans,
                                e2e_ms=round(t, 4), t0_s=100.0 + rid,
                                **kw)


def _pathological(rid, cause):
    reason = {"queue_wait": "submit", "preemption": "preempt",
              "restart": "restart"}[cause]
    return _synthetic_trace(rid, [
        ("queued", 700.0, {"reason": reason}),
        ("admit", 0.0, {}),
        ("prefill_chunk", 50.0, {"p0": 0, "n_tokens": 8}),
        ("decode", 240.0, {"n_tokens": 12}),
        ("finalize", 10.0, {}),
    ], n_tokens=12, prompt_len=8)


def _healthy(rid):
    return _synthetic_trace(rid, [
        ("queued", 5.0, {"reason": "submit"}),
        ("admit", 0.0, {}),
        ("prefill_chunk", 60.0, {"p0": 0, "n_tokens": 8}),
        ("decode", 800.0, {"n_tokens": 32}),
        ("finalize", 5.0, {}),
    ], n_tokens=32, prompt_len=8)


# ---------------------------------------------------------------------------
# histogram math
# ---------------------------------------------------------------------------

class TestLogHistogram:
    def test_quantile_vs_np_percentile(self):
        rs = np.random.RandomState(0)
        samples = np.exp(rs.uniform(np.log(2.0), np.log(4000.0), 5000))
        h = LogHistogram()
        for v in samples:
            h.observe(v)
        for q in (0.5, 0.9, 0.99):
            est = h.quantile(q)
            true = float(np.percentile(samples, q * 100))
            # log2 buckets bound the relative error by one bucket width
            assert true / 2 <= est <= true * 2, (q, est, true)
        assert h.total == len(samples)
        assert abs(h.sum - samples.sum()) < 1e-6 * samples.sum()

    def test_empty_invalid_and_overflow(self):
        h = LogHistogram()
        assert h.quantile(0.5) is None
        # invalid samples RAISE (the registry's counter stance): a
        # negative or non-finite latency is a producer bug, and
        # silently bucketing it would corrupt every later scrape
        for bad in (float("nan"), -1.0, float("inf"), float("-inf")):
            with pytest.raises(ValueError):
                h.observe(bad)
        assert h.total == 0
        h.observe(1e12)          # beyond the top bound: overflow bucket
        assert h.total == 1
        assert h.quantile(0.99) == h.bounds[-1]

    def test_recent_window_recovers_sensitivity(self):
        """The compat gauges derive from a bounded RECENT window: after
        a long healthy history, a regression must move the p99 within
        ~a window of slow samples, not after 1% of lifetime traffic."""
        h = LogHistogram(window=100)
        for _ in range(10000):
            h.observe(10.0)                  # days of healthy traffic
        for _ in range(210):                 # ~2 windows of regression
            h.observe(2000.0)
        assert h.quantile(0.5) > 1000.0      # recent window: it moved
        assert h.quantile(0.5, recent=False) < 20.0   # lifetime: hasn't
        assert h.total == 10210              # export stays cumulative

    def test_prometheus_histogram_render(self):
        from paddle_tpu.telemetry.metrics_http import prometheus_text
        monitor.reset("test.lat_ms")
        for v in (1.0, 3.0, 500.0):
            monitor.observe_hist("test.lat_ms", v)
        txt = prometheus_text()
        lines = [ln for ln in txt.splitlines() if "test_lat_ms" in ln]
        assert "# TYPE paddle_tpu_test_lat_ms histogram" in lines
        assert "paddle_tpu_test_lat_ms_count 3" in lines
        assert "paddle_tpu_test_lat_ms_sum 504" in lines
        cums = [int(ln.split()[-1]) for ln in lines
                if "_bucket" in ln]
        assert cums == sorted(cums)          # cumulative le series
        assert 'le="+Inf"} 3' in lines[-3]
        monitor.reset("test.lat_ms")


# ---------------------------------------------------------------------------
# schema + decomposition invariant
# ---------------------------------------------------------------------------

class TestSchema:
    def test_valid_record_passes(self):
        rec = _healthy(1)
        assert validate_step_record(rec) == []
        assert _check_records([rec]) == []

    def test_schema_rejections(self):
        rec = _healthy(2)
        bad = dict(rec)
        bad["outcome"] = "vanished"
        assert any("outcome" in p for p in validate_step_record(bad))
        bad = json.loads(json.dumps(rec))
        bad["spans"][0]["kind"] = "teleport"
        assert any("vocabulary" in p for p in validate_step_record(bad))
        bad = json.loads(json.dumps(rec))
        bad["spans"][1]["dur_ms"] = -1.0
        assert any("dur_ms" in p for p in validate_step_record(bad))
        bad = dict(rec)
        bad["spans"] = []
        assert any("spans" in p for p in validate_step_record(bad))

    def test_decomposition_invariant_both_ways(self):
        good = _healthy(3)
        assert _check_records([good]) == []
        bad = dict(good)
        bad["e2e_ms"] = good["e2e_ms"] * 2     # claims twice the spans
        probs = _check_records([bad])
        assert any("decomposition broken" in p for p in probs)

    def test_finalize_without_admit_caught(self):
        rec = _synthetic_trace(4, [
            ("queued", 10.0, {"reason": "submit"}),
            ("decode", 100.0, {"n_tokens": 4}),
            ("finalize", 2.0, {}),
        ])
        probs = _check_records([rec])
        assert any("no admit span" in p for p in probs)

    def test_checked_in_specimens(self, tmp_path):
        sys.path.insert(0, TOOLS)
        import trace_check
        *_c, probs = trace_check.check_metrics_jsonl(
            os.path.join(TOOLS, "specimens", "reqtrace_invalid.jsonl"))
        text = "\n".join(probs)
        assert "decomposition broken" in text
        assert "no admit span" in text
        *_c2, probs2 = trace_check.check_metrics_jsonl(
            os.path.join(TOOLS, "specimens", "reqtrace_tail.jsonl"))
        assert probs2 == []


def _check_records(records):
    sys.path.insert(0, TOOLS)
    import trace_check
    return trace_check.check_reqtrace_records(records, "test")


# ---------------------------------------------------------------------------
# attribution + tail rule
# ---------------------------------------------------------------------------

class TestAttribution:
    def test_decompose_vocabulary(self):
        rec = _synthetic_trace(5, [
            ("queued", 100.0, {"reason": "submit"}),
            ("admit", 0.0, {}),
            ("prefill_chunk", 50.0, {"p0": 0, "n_tokens": 8}),
            ("decode", 30.0, {"n_tokens": 2}),
            ("preempt", 0.0, {}),
            ("queued", 200.0, {"reason": "preempt"}),
            ("admit", 0.0, {}),
            ("prefill_chunk", 80.0, {"p0": 0, "n_tokens": 10,
                                     "replay": True,
                                     "replay_cause": "preemption"}),
            ("cow_fork", 7.0, {}),
            ("restart_replay", 0.0, {}),
            ("queued", 40.0, {"reason": "restart"}),
            ("admit", 0.0, {}),
            ("prefill_chunk", 15.0, {"p0": 0, "n_tokens": 10,
                                     "replay": True,
                                     "replay_cause": "restart"}),
            ("decode", 60.0, {"n_tokens": 4}),
            ("finalize", 3.0, {}),
        ])
        causes = decompose(rec)
        assert set(causes) == set(CAUSES)
        assert causes["queue_wait"] == 100.0
        assert causes["preemption"] == 280.0   # requeue wait + replay
        assert causes["restart"] == 55.0
        assert causes["prefill"] == 50.0
        assert causes["decode"] == 90.0
        assert causes["cow_fork"] == 7.0
        cause, ms, frac = dominant_cause(rec)
        assert cause == "preemption" and ms == 280.0
        assert abs(frac - 280.0 / rec["e2e_ms"]) < 1e-9

    def test_tail_latency_rule_fires_and_stays_silent(self):
        det = AnomalyDetector(HealthConfig(
            action="record", tail_cause_frac=0.6, tail_cause_count=3))
        for i in range(8):
            assert det.observe(_healthy(i)) == []
        found = []
        for i in range(3):
            found += det.observe(_pathological(100 + i, "queue_wait"))
        assert [a.kind for a in found] == ["tail_latency"]
        assert "queue_wait" in found[0].message
        # latched: a fourth dominated request does not re-page
        assert det.observe(_pathological(103, "queue_wait")) == []
        # a different cause pages independently
        found2 = []
        for i in range(3):
            found2 += det.observe(_pathological(200 + i, "restart"))
        assert [a.kind for a in found2] == ["tail_latency"]
        assert "restart" in found2[0].message

    def test_healthwatch_replays_reqtrace(self, tmp_path):
        path = tmp_path / "traces.jsonl"
        with open(path, "w") as f:
            for i in range(5):
                f.write(json.dumps(_pathological(i, "preemption")) + "\n")
        sys.path.insert(0, TOOLS)
        import healthwatch
        rc = healthwatch.main([str(path)])
        assert rc == 5                      # findings in gate mode
        clean = tmp_path / "clean.jsonl"
        with open(clean, "w") as f:
            for i in range(5):
                f.write(json.dumps(_healthy(i)) + "\n")
        assert healthwatch.main([str(clean)]) == 0


# ---------------------------------------------------------------------------
# RequestTrace / tracer units
# ---------------------------------------------------------------------------

class TestTraceUnits:
    def test_tiling_and_decode_coalescing(self):
        tr = RequestTrace(7, 10.0)
        tr.note_admit(10.1, queue_depth=2)
        tr.note_prefill_chunk(10.2, 0, 8)
        for t in (10.25, 10.3, 10.35):      # 3 decode steps -> ONE span
            tr.note_decode(t)
        tr.note_cow_fork(10.4)
        tr.note_decode(10.45)
        tr.finish(10.5, "finished")
        kinds = [s["kind"] for s in tr.spans]
        assert kinds == ["queued", "admit", "prefill_chunk", "decode",
                         "cow_fork", "decode", "finalize"]
        dec = [s for s in tr.spans if s["kind"] == "decode"]
        assert dec[0]["n_tokens"] == 3 and dec[1]["n_tokens"] == 1
        total = sum(s["dur_ms"] for s in tr.spans)
        assert abs(total - tr.e2e_ms) < 0.01
        # spans tile: each starts where the previous ended
        cursor = 0.0
        for s in tr.spans:
            assert abs(s["t0_ms"] - cursor) < 1e-6
            cursor = s["t0_ms"] + s["dur_ms"]

    def test_replay_attribution_after_requeue(self):
        tr = RequestTrace(8, 0.0)
        tr.note_admit(0.01)
        tr.note_prefill_chunk(0.02, 0, 8)
        tr.note_decode(0.03)
        tr.note_requeue(0.04, "preempt", n_prefilled=9)
        tr.note_admit(0.06)
        tr.note_prefill_chunk(0.08, 0, 8)      # re-covers -> replay
        tr.note_prefill_chunk(0.09, 8, 8)      # past the mark -> fresh
        tr.finish(0.1, "finished")
        chunks = [s for s in tr.spans if s["kind"] == "prefill_chunk"]
        assert "replay" not in chunks[0]
        assert chunks[1]["replay"] and \
            chunks[1]["replay_cause"] == "preemption"
        assert "replay" not in chunks[2]

    def test_cancelled_in_queue_still_sums(self):
        tr = RequestTrace(9, 0.0)
        tr.finish(1.5, "cancelled")            # never admitted
        kinds = [s["kind"] for s in tr.spans]
        assert kinds == ["queued", "finalize"]
        assert abs(sum(s["dur_ms"] for s in tr.spans) - 1500.0) < 0.01

    def test_exemplar_ring_keeps_slowest_k(self):
        tracer = RequestTracer(exemplar_k=4)
        for i in range(20):
            tracer._note(_synthetic_trace(i, [
                ("queued", 1.0, {"reason": "submit"}),
                ("admit", 0.0, {}),
                ("decode", float(i * 10), {"n_tokens": 1}),
                ("finalize", 1.0, {}),
            ]))
        tl = tracer.timelines()
        assert len(tl) == 4
        assert [t["rid"] for t in tl] == [19, 18, 17, 16]  # slowest first
        assert tracer.n_traces == 20
        assert len(tracer.timelines(2)) == 2

    def test_chrome_spans_lanes(self):
        recs = [_healthy(1), _healthy(2)]
        spans = trace_chrome_spans(recs, rank=3)
        assert spans and all(sp["cat"] == "reqtrace" for sp in spans)
        assert {sp["tid"] for sp in spans} == {10001, 10002}
        assert all(sp["rank"] == 3 for sp in spans)


# ---------------------------------------------------------------------------
# engine integration (one shared traced run where possible)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """One lockstep engine run under a CompileObservatory with a sink:
    the records + observatory + engine are shared by the read-only
    assertions below (engine compiles are expensive on the test host)."""
    tmp = tmp_path_factory.mktemp("reqtrace")
    model = _small_gpt()
    path = str(tmp / "traced.jsonl")
    sink = telemetry.JsonlSink(path)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 512, (n,)).tolist() for n in (6, 11, 9)]
    with telemetry.CompileObservatory(sink=sink, action="record") as obs:
        eng = ServingEngine(model, max_slots=2, block_size=8,
                            prefill_chunk=8, max_model_len=64,
                            sink=sink)
        handles = [eng.submit(p, SamplingParams(max_new_tokens=6))
                   for p in prompts]
        eng.run_until_idle()
    sink.close()
    records = telemetry.read_jsonl(path)
    return {"engine": eng, "records": records, "path": path,
            "obs": obs, "handles": handles}


class TestEngineIntegration:
    def test_every_request_traced_and_validated(self, traced_run):
        traces = [r for r in traced_run["records"]
                  if r.get("kind") == "reqtrace"]
        assert len(traces) == 3
        assert all(t["outcome"] == "finished" for t in traces)
        for t in traces:
            assert validate_step_record(t) == []
            total = sum(sp["dur_ms"] for sp in t["spans"])
            assert abs(total - t["e2e_ms"]) <= max(
                0.01 * t["e2e_ms"], 0.5)
            kinds = [sp["kind"] for sp in t["spans"]]
            assert kinds[0] == "queued" and kinds[-1] == "finalize"
            assert "admit" in kinds and "decode" in kinds

    def test_trace_check_clean(self, traced_run):
        sys.path.insert(0, TOOLS)
        import trace_check
        probs, stats = trace_check.check_pair(traced_run["path"])
        assert probs == []
        assert stats["n_reqtrace"] == 3

    def test_zero_recompiles_under_tracing(self, traced_run):
        fams = {}
        for rec in traced_run["obs"].records:
            fams[rec["fn"]] = fams.get(rec["fn"], 0) + 1
        for fam, n in fams.items():
            if fam.startswith("serving_"):
                assert n == 1, (fam, n)

    def test_chrome_export_has_request_lanes(self, traced_run, tmp_path):
        eng = traced_run["engine"]
        out = tmp_path / "trace.json"
        n = telemetry.export_chrome_tracing(str(out), [eng.tracer])
        assert n > 0
        data = json.loads(out.read_text())
        lanes = {e["tid"] for e in data["traceEvents"]
                 if e.get("cat") == "reqtrace"}
        assert len(lanes) == 3              # one lane per request

    def test_gauges_recomputed_from_histograms(self, traced_run):
        eng = traced_run["engine"]
        h = monitor.get_hist("serving.ttft_ms")
        assert h is not None and h.total >= 3
        monitor.set_gauge("serving.ttft_p99_ms", -1.0)   # stale garbage
        eng.refresh_latency_gauges()
        assert monitor.get_gauge("serving.ttft_p99_ms") == \
            pytest.approx(h.quantile(0.99))
        assert monitor.get_gauge("serving.slo_gauge_age_s") >= 0.0

    def test_tracing_off_engine(self):
        model = _small_gpt(seed=1)
        eng = ServingEngine(model, max_slots=2, block_size=8,
                            prefill_chunk=8, max_model_len=64,
                            enable_tracing=False)
        assert eng.tracer is None
        h = eng.submit([1, 2, 3, 4], SamplingParams(max_new_tokens=3))
        eng.run_until_idle()
        assert len(h.output_tokens) == 3
        assert h._req.trace is None


# ---------------------------------------------------------------------------
# pathology spans through the real engine (heavier: own engines)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_preemption_spans_present_and_summing():
    model = _small_gpt(seed=2)
    rs = np.random.RandomState(2)
    eng = ServingEngine(model, max_slots=4, block_size=8,
                        prefill_chunk=8, max_model_len=64, num_blocks=9,
                        enable_prefix_cache=False)
    for max_new in (12, 12, 12, 6):
        eng.submit(rs.randint(0, 512, (16,)).tolist(),
                   SamplingParams(max_new_tokens=max_new))
    eng.run_until_idle(max_steps=20000)
    traces = eng.tracer.timelines()
    preempted = [t for t in traces
                 if any(sp["kind"] == "preempt" for sp in t["spans"])]
    assert preempted, "no preempt span on an over-admitted schedule"
    for t in preempted:
        kinds = [sp["kind"] for sp in t["spans"]]
        assert "preempt" in kinds
        reasons = [sp.get("reason") for sp in t["spans"]
                   if sp["kind"] == "queued"]
        assert "preempt" in reasons
        assert decompose(t)["preemption"] > 0
        assert _check_records([t]) == []


@pytest.mark.slow
def test_warm_restart_spans_and_replay_attribution():
    model = _small_gpt(seed=3)
    rs = np.random.RandomState(3)
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=64,
                        restart_backoff_s=0.05)
    calls = {"n": 0}
    orig = eng._decode_greedy_jit

    def flaky(*a, **k):
        calls["n"] += 1
        if calls["n"] == 3:
            raise tag_transient(OSError(5, "injected"))
        return orig(*a, **k)

    eng._decode_greedy_jit = flaky
    with eng:
        handles = [eng.submit(rs.randint(0, 512, (n,)).tolist(),
                              SamplingParams(max_new_tokens=6))
                   for n in (7, 9)]
        for h in handles:
            h.result(timeout=180)
    assert calls["n"] >= 3
    traces = [t for t in eng.tracer.timelines()
              if any(sp["kind"] == "restart_replay"
                     for sp in t["spans"])]
    assert traces, "no restart_replay span after a transient fault"
    for t in traces:
        causes = decompose(t)
        assert causes["restart"] > 0
        assert _check_records([t]) == []


@pytest.mark.slow
def test_cow_fork_span_on_duplicate_prompt():
    """The duplicate-prompt prefix case: the second request resumes
    INSIDE a shared block, forcing a CoW fork — the fork must show up
    as a span and the trace still sum."""
    model = _small_gpt(seed=4)
    rs = np.random.RandomState(4)
    # 16 = 2 full blocks: both get indexed, and the duplicate's match
    # (capped at len-1 = 15) resumes INSIDE the shared second block
    prompt = rs.randint(0, 512, (16,)).tolist()
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=64)
    eng.submit(prompt, SamplingParams(max_new_tokens=3))
    eng.run_until_idle()
    h2 = eng.submit(list(prompt), SamplingParams(max_new_tokens=3))
    eng.run_until_idle()
    trace = next(t for t in eng.tracer.timelines()
                 if t["rid"] == h2.rid)
    kinds = [sp["kind"] for sp in trace["spans"]]
    assert "cow_fork" in kinds
    admit = next(sp for sp in trace["spans"] if sp["kind"] == "admit")
    assert admit.get("prefix_cached_tokens", 0) > 0
    assert _check_records([trace]) == []


@pytest.mark.slow
def test_shed_trace_recorded(tmp_path):
    model = _small_gpt(seed=5)
    path = str(tmp_path / "shed.jsonl")
    sink = telemetry.JsonlSink(path)
    eng = ServingEngine(model, max_slots=1, block_size=8,
                        prefill_chunk=8, max_model_len=64, max_queue=1,
                        sink=sink)
    rs = np.random.RandomState(5)
    p = rs.randint(0, 512, (6,)).tolist()
    eng.submit(p, SamplingParams(max_new_tokens=2))     # fills the queue
    from paddle_tpu.serving import QueueFullError
    with pytest.raises(QueueFullError):
        eng.submit(p, SamplingParams(max_new_tokens=2))
    eng.run_until_idle()
    sink.close()
    sheds = [r for r in telemetry.read_jsonl(path)
             if r.get("kind") == "reqtrace" and r["outcome"] == "shed"]
    assert len(sheds) == 1
    kinds = [sp["kind"] for sp in sheds[0]["spans"]]
    assert kinds == ["queued", "shed"]
    assert validate_step_record(sheds[0]) == []
    assert _check_records(sheds) == []


@pytest.mark.slow
def test_traces_endpoint_and_histogram_scrape():
    import urllib.request
    from paddle_tpu.serving import ServingHTTPServer

    model = _small_gpt(seed=6)
    rs = np.random.RandomState(6)
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=64)
    with eng, ServingHTTPServer(eng, port=0) as srv:
        hs = [eng.submit(rs.randint(0, 512, (5 + i,)).tolist(),
                         SamplingParams(max_new_tokens=4))
              for i in range(3)]
        for h in hs:
            h.result(timeout=180)
        body = json.loads(urllib.request.urlopen(
            srv.url + "/traces?n=2", timeout=30).read().decode())
        assert body["tracing"] is True
        assert 1 <= len(body["traces"]) <= 2
        assert all(t["spans"] for t in body["traces"])
        mtext = urllib.request.urlopen(
            srv.url + "/metrics", timeout=30).read().decode()
        assert "# TYPE paddle_tpu_serving_ttft_ms histogram" in mtext
        assert "paddle_tpu_serving_ttft_ms_bucket{le=" in mtext
        assert "paddle_tpu_serving_slo_gauge_age_s" in mtext
        sys.path.insert(0, TOOLS)
        import serving_smoke
        assert serving_smoke._check_histogram_scrape(mtext) == []


@pytest.mark.slow
def test_tail_report_selfcheck_subprocess():
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "tail_report.py"),
         "--selfcheck"], capture_output=True, text=True, env=env,
        timeout=560)
    assert r.returncode == 0, r.stdout + r.stderr
