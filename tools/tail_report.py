#!/usr/bin/env python
"""Offline tail-latency attribution for the serving engine: decompose
the TTFT/TPOT/e2e tail from `kind=reqtrace` records and NAME the
dominant cause per exemplar.

A p99 gauge says a request was slow; a request trace
(paddle_tpu/telemetry/reqtrace.py) says WHY: each record is a span
timeline tiling the request's life (queued / admit / prefill_chunk /
decode / preempt / cow_fork / restart_replay / collective / transfer /
finalize), so the tail decomposes into the mechanisms that can each
make one request slow — queue wait vs preemption vs warm restart vs
long prefill vs copy-on-write forking, plus the mesh's own time:
collective sync waits and host<->device transfers carry their own
breakdown columns (previously charged to `other`, which hid whether a
slow request waited on compute or on the interconnect). Findings run
through the SAME `tail_latency`
rule the in-flight AnomalyDetector carries (paddle_tpu.telemetry.
health), so what this tool gates on offline is exactly what pages in
production (the healthwatch pattern).

    # gate mode (default): report the tail, fail on tail_latency
    python tools/tail_report.py serving_telemetry.jsonl

    # selfcheck mode (ci.sh stage 5): prove the attribution can still
    # see what it gates on —
    #  a) the checked-in pathology specimen
    #     (tools/specimens/reqtrace_tail.jsonl) must name queue_wait,
    #     preemption AND restart as dominant causes;
    #  b) the checked-in invalid specimen
    #     (tools/specimens/reqtrace_invalid.jsonl) must be CAUGHT by
    #     tools/trace_check.py both ways (non-summing decomposition +
    #     finished-without-admit);
    #  c) a LIVE mini-drill injects each pathology into a real engine
    #     (overload -> queue_wait, over-admission -> preemption,
    #     transient step fault -> restart) and the dominant cause must
    #     come out right on the actual traces.
    python tools/tail_report.py --selfcheck

Exit codes: 0 clean; 13 findings; 9 selfcheck miss. Distinct from
trace_check 7 / healthwatch 5 / compile_report 6 / serving_smoke 10 /
serving_drill 11 so CI logs disambiguate.
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

TAIL_SPECIMEN = os.path.join(REPO, "tools", "specimens",
                             "reqtrace_tail.jsonl")
INVALID_SPECIMEN = os.path.join(REPO, "tools", "specimens",
                                "reqtrace_invalid.jsonl")


def _percentile(vals, q):
    import numpy as np
    return round(float(np.percentile(vals, q)), 2) if vals else None


def load_traces(path):
    from paddle_tpu.telemetry.sink import read_jsonl

    records = [r for r in read_jsonl(path)
               if isinstance(r, dict) and r.get("kind") == "reqtrace"]
    records.sort(key=lambda r: r.get("t0_s", 0.0))
    return records


def analyze(path, config=None, top_k=8):
    """Decompose one JSONL's request traces. Returns a report dict:
    tail percentiles, slowest-`top_k` exemplar rows (each naming its
    dominant cause + full cause breakdown), the detector's tail_latency
    anomalies, and file-level problems."""
    from paddle_tpu.telemetry.health import AnomalyDetector, HealthConfig
    from paddle_tpu.telemetry.reqtrace import decompose, dominant_cause

    problems = []
    try:
        traces = load_traces(path)
    except (OSError, json.JSONDecodeError) as e:
        return {"path": path, "problems": [f"{path}: unreadable: {e}"],
                "exemplars": [], "anomalies": []}
    if not traces:
        # the healthwatch/trace_check stance: a file with no traces
        # must not green-light the serving run it claims to describe
        problems.append(f"{path}: no kind=reqtrace records — request "
                        "tracing never wrote")
    det = AnomalyDetector(config or HealthConfig(action="record"))
    for rec in traces:
        det.observe(rec)
    exemplars = []
    for rec in sorted(traces, key=lambda r: r.get("e2e_ms", 0.0),
                      reverse=True)[:top_k]:
        cause, ms, frac = dominant_cause(rec)
        causes = decompose(rec)
        exemplars.append({
            "rid": rec.get("rid"), "outcome": rec.get("outcome"),
            "e2e_ms": rec.get("e2e_ms"), "ttft_ms": rec.get("ttft_ms"),
            "n_tokens": rec.get("n_tokens"),
            "preemptions": rec.get("preemptions"),
            "dominant_cause": cause,
            "dominant_ms": round(ms, 2),
            "dominant_frac": round(frac, 4),
            "breakdown_ms": {k: round(v, 2) for k, v in causes.items()
                             if v > 0},
        })
    return {
        "path": path,
        "n_traces": len(traces),
        "ttft_p50_ms": _percentile(
            [r["ttft_ms"] for r in traces
             if isinstance(r.get("ttft_ms"), (int, float))], 50),
        "ttft_p99_ms": _percentile(
            [r["ttft_ms"] for r in traces
             if isinstance(r.get("ttft_ms"), (int, float))], 99),
        "tpot_p99_ms": _percentile(
            [r["tpot_ms"] for r in traces
             if isinstance(r.get("tpot_ms"), (int, float))], 99),
        "e2e_p99_ms": _percentile(
            [r["e2e_ms"] for r in traces
             if isinstance(r.get("e2e_ms"), (int, float))], 99),
        "exemplars": exemplars,
        "anomalies": [a.to_dict() for a in det.anomalies],
        "problems": problems,
    }


def render(report):
    print(f"tail_report: {report['path']}: "
          f"{report.get('n_traces', 0)} trace(s), "
          f"ttft p50/p99 {report.get('ttft_p50_ms')}/"
          f"{report.get('ttft_p99_ms')}ms, "
          f"e2e p99 {report.get('e2e_p99_ms')}ms")
    for ex in report["exemplars"]:
        bd = ", ".join(f"{k} {v}ms"
                       for k, v in sorted(ex["breakdown_ms"].items(),
                                          key=lambda kv: -kv[1]))
        print(f"  req {ex['rid']} [{ex['outcome']}] "
              f"e2e {ex['e2e_ms']}ms -> {ex['dominant_cause']} "
              f"({ex['dominant_frac'] * 100:.0f}%): {bd}")
    for a in report["anomalies"]:
        print(f"  [tail_latency] {a['message']}")
    for p in report["problems"]:
        print(f"  [invalid] {p}")


def _dominant_causes(records):
    from paddle_tpu.telemetry.reqtrace import dominant_cause
    return [dominant_cause(r)[0] for r in records]


# ---------------------------------------------------------------------------
# selfcheck: specimens + live pathology mini-drill
# ---------------------------------------------------------------------------

def _tiny_engine(model, **kw):
    from paddle_tpu.serving import ServingEngine
    base = dict(max_slots=2, block_size=8, prefill_chunk=8,
                max_model_len=64)
    base.update(kw)
    return ServingEngine(model, **base)


def _build_model(seed=0):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining

    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=2, max_seq_len=64, dropout=0.0,
                    use_flash_attention=False)
    return GPTForPretraining(cfg)


def _warm(eng, rs):
    """Compile the engine's step programs OUTSIDE the measured wave —
    otherwise the first prefill chunk span absorbs the jit compile and
    every drill comes out 'prefill'-dominated. The warmup's own trace
    stays in the ring (prefill-dominated, correctly)."""
    from paddle_tpu.serving import SamplingParams
    eng.submit(rs.randint(0, 256, (6,)).tolist(),
               SamplingParams(max_new_tokens=2))
    eng.run_until_idle()


def _drill_queue_wait(model, rs):
    """Overload: one slot, six requests — the tail request's life is
    mostly waiting for the slot."""
    from paddle_tpu.serving import SamplingParams
    eng = _tiny_engine(model, max_slots=1)
    _warm(eng, rs)
    for i in range(6):
        eng.submit(rs.randint(0, 256, (6,)).tolist(),
                   SamplingParams(max_new_tokens=6))
    eng.run_until_idle()
    return eng.tracer.timelines()


def _drill_preemption(model, rs):
    """Over-admission: a block pool far smaller than the offered load —
    evict-by-recompute thrash, the victims' lives dominated by requeue
    waits + replayed prefill (the prefix cache is OFF so the replays
    are real recompute, the pathology the cache exists to remove)."""
    from paddle_tpu.serving import SamplingParams
    eng = _tiny_engine(model, max_slots=4, num_blocks=9,
                       enable_prefix_cache=False)
    _warm(eng, rs)
    # three long answers + one short: the pool holds two of the long
    # ones to their ends, so the youngest long block-holder is evicted
    # again and again (six times) and WAITS for blocks before each
    # replay — requeue waits and replayed chunks outweigh its decode
    # with room to spare, whatever the host's timing
    for max_new in (40, 40, 40, 2):
        eng.submit(rs.randint(0, 256, (16,)).tolist(),
                   SamplingParams(max_new_tokens=max_new))
    eng.run_until_idle(max_steps=20000)
    return eng.tracer.timelines()


def _drill_restart(model, rs):
    """Transient step fault: the warm restart requeues the in-flight
    requests for recompute-replay; backoff + replay dominate."""
    from paddle_tpu.resilience.retry import tag_transient
    from paddle_tpu.serving import SamplingParams

    eng = _tiny_engine(model, max_slots=2, restart_backoff_s=0.3)
    _warm(eng, rs)
    calls = {"n": 0}
    orig = eng._decode_greedy_jit

    def flaky(*a, **k):
        calls["n"] += 1
        if calls["n"] == 2:
            raise tag_transient(OSError(5, "injected transient fault"))
        return orig(*a, **k)

    eng._decode_greedy_jit = flaky
    with eng:
        handles = [eng.submit(rs.randint(0, 256, (n,)).tolist(),
                              SamplingParams(max_new_tokens=8))
                   for n in (6, 9)]
        for h in handles:
            h.result(timeout=300)
    assert calls["n"] >= 2, "the injected fault never fired"
    return eng.tracer.timelines()


def selfcheck():
    import numpy as np
    misses = []

    # a) pathology specimen: all three causes must be NAMED
    report = analyze(TAIL_SPECIMEN, top_k=16)
    named = {ex["dominant_cause"] for ex in report["exemplars"]}
    fired = {a["message"].split("dominated by ")[1].split(" ")[0]
             for a in report["anomalies"]}
    for cause in ("queue_wait", "preemption", "restart"):
        if cause not in named:
            misses.append(f"specimen: {cause} not named as a dominant "
                          f"cause (got {sorted(named)})")
        if cause not in fired:
            misses.append(f"specimen: tail_latency did not fire for "
                          f"{cause} (fired: {sorted(fired)})")
    if report["problems"]:
        misses.append(f"pathology specimen should be VALID, got "
                      f"{report['problems']}")

    # b) invalid specimen: trace_check must catch BOTH defect families
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_check
    *_counts, problems = trace_check.check_metrics_jsonl(INVALID_SPECIMEN)
    text = "\n".join(problems)
    if "decomposition broken" not in text:
        misses.append("invalid specimen: the non-summing trace was NOT "
                      "caught by the decomposition cross-rule")
    if "no admit span" not in text:
        misses.append("invalid specimen: the finished-without-admit "
                      "trace was NOT caught")

    # c) live mini-drill: inject each pathology into a real engine and
    # the dominant cause must come out right on the actual traces
    model = _build_model()
    rs = np.random.RandomState(0)
    for name, drill in (("queue_wait", _drill_queue_wait),
                        ("preemption", _drill_preemption),
                        ("restart", _drill_restart)):
        traces = drill(model, rs)
        causes = _dominant_causes(traces)
        print(f"drill[{name}]: {len(traces)} trace(s), dominant causes "
              f"{sorted(set(causes))}")
        if name not in causes:
            misses.append(
                f"drill[{name}]: injected pathology not named as any "
                f"trace's dominant cause (got {causes})")
        bad = [p for t in traces
               for p in trace_check.check_reqtrace_records([t], "drill")]
        if bad:
            misses.append(f"drill[{name}]: traces invalid: {bad[:3]}")

    for m in misses:
        print(f"SELFCHECK MISS: {m}")
    if not misses:
        print("tail_report selfcheck OK (specimens caught, all three "
              "injected pathologies attributed)")
    return 9 if misses else 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", help="metrics JSONL file(s)")
    ap.add_argument("--selfcheck", action="store_true")
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--tail-frac", type=float, default=0.6)
    ap.add_argument("--tail-count", type=int, default=4)
    ap.add_argument("--json", dest="json_out", default=None)
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        jax.config.update("jax_platforms", "cpu")

    if args.selfcheck:
        return selfcheck()
    if not args.paths:
        ap.error("a metrics JSONL path is required (or --selfcheck)")

    from paddle_tpu.telemetry.health import HealthConfig
    config = HealthConfig(action="record",
                          tail_cause_frac=args.tail_frac,
                          tail_cause_count=args.tail_count)
    reports = []
    findings = 0
    for path in args.paths:
        report = analyze(path, config=config, top_k=args.top_k)
        render(report)
        findings += len(report["anomalies"]) + len(report["problems"])
        reports.append(report)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"tool": "tail_report", "reports": reports},
                      f, indent=2, sort_keys=True)
        print(f"report: {args.json_out}")
    return 13 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
