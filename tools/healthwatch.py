#!/usr/bin/env python
"""Offline training-health analyzer: replay a metrics JSONL through the
SAME anomaly rules the in-flight monitor runs (paddle_tpu.telemetry.
health.AnomalyDetector) and exit nonzero on findings.

The point of sharing the rule engine: what pages you in production is
exactly what CI gates on. Two modes:

    # gate mode (default): a clean run must stay clean
    python tools/healthwatch.py run.jsonl

    # selfcheck mode: a broken specimen must trip EVERY listed family —
    # proof the rules can still see the defects they gate on (the
    # graphdoctor selfcheck pattern)
    python tools/healthwatch.py tools/specimens/health_anomalous.jsonl \
        --expect nan,loss_spike,grad_explosion,step_time_regression

Step records (kind=step) run the rolling-window rules (NaN/Inf, loss
spike, grad explosion, step-time regression — compile steps exempt)
plus the per-rank straggler rule (step-boundary skew across ranks of
the same step); checkpoint records
(kind=ckpt, paddle_tpu.resilience) run the checkpoint_failed /
checkpoint_stall rules; mesh-observatory records (kind=commbench,
telemetry/comm_obs) run the comm_bw_degraded rule against the DB
reference riding on the record; request-trace records (kind=reqtrace,
telemetry.reqtrace) run the tail_latency rule — requests dominated by
a serving pathology (queue wait / preemption / warm restart / CoW)
count per cause and page past the threshold; memory-ledger records
(kind=memsnap, telemetry/mem_obs via tools/memwatch.py) run the
hbm_pressure / kv_thrash / mem_projection_drift rules — the budget,
rates and projection each rule judges against ride ON the record, so
replay and production see identical numbers. Detector knobs (--window,
--z-loss, --z-grad, --z-step-time, --min-points, --ckpt-stall-s,
--tail-frac, --tail-count) mirror HealthConfig; `--rules fam1,fam2`
keeps only those anomaly families in the verdict, so a replay can
isolate one rule family without muting the others at the source.

Exit codes: 0 clean / all expected families fired; 5 findings in gate
mode; 9 an expected family did NOT fire (the watcher itself is broken).
Distinct from trace_check's 7 and graphdoctor's 8/9 family so CI logs
disambiguate. Used by tools/ci.sh against the checked-in anomalous
specimen.
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def analyze_file(path, config):
    """Replay one JSONL through a fresh detector. Returns (anomalies,
    n_step, problems)."""
    from paddle_tpu.telemetry.health import AnomalyDetector
    from paddle_tpu.telemetry.sink import read_jsonl

    problems = []
    try:
        records = read_jsonl(path)
    except (OSError, json.JSONDecodeError) as e:
        return [], 0, [f"{path}: unreadable: {e}"]
    if not records:
        # same stance as trace_check: a file nothing ever wrote must
        # not green-light the run it claims to describe
        return [], 0, [f"{path}: no records — telemetry never wrote"]
    det = AnomalyDetector(config)
    n_step = 0
    for rec in records:
        kind = rec.get("kind") if isinstance(rec, dict) else None
        if kind == "step":
            n_step += 1
        elif kind == "ckpt":
            # checkpoint-lifecycle records (paddle_tpu.resilience):
            # failed saves / corrupt-checkpoint fallbacks / slow commits
            # replay through the same checkpoint_failed/checkpoint_stall
            # rules the in-flight manager runs
            pass
        elif kind == "commbench":
            # mesh-observatory measurements (telemetry/comm_obs via
            # tools/commlab): replay through the same comm_bw_degraded
            # rule the in-flight detector runs — the DB reference rides
            # ON the record (db_ms), so offline replay and production
            # judge against the identical number
            pass
        elif kind == "reqtrace":
            # per-request serving traces (telemetry.reqtrace): replay
            # through the same tail_latency rule the in-flight detector
            # runs — requests dominated by queue wait / preemption /
            # restart / CoW forking count per cause and page past the
            # threshold, offline exactly as in production
            pass
        elif kind == "memsnap":
            # memory-observatory ledger records (telemetry/mem_obs via
            # tools/memwatch): replay through the same hbm_pressure /
            # kv_thrash / mem_projection_drift rules the in-flight
            # detector runs — budget, windowed rates and static
            # projection all ride ON the record
            pass
        else:
            continue
        det.observe(rec)
    return det.anomalies, n_step, problems


def main(argv=None):
    from paddle_tpu.telemetry.health import HealthConfig

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="+", help="metrics JSONL file(s)")
    ap.add_argument("--expect", default=None,
                    help="comma-separated anomaly kinds that MUST fire "
                         "(selfcheck mode): nan,loss_spike,"
                         "grad_explosion,step_time_regression")
    ap.add_argument("--json", dest="json_out", default=None,
                    help="write the findings report here")
    ap.add_argument("--window", type=int, default=64)
    ap.add_argument("--min-points", type=int, default=8)
    ap.add_argument("--z-loss", type=float, default=8.0)
    ap.add_argument("--z-grad", type=float, default=8.0)
    ap.add_argument("--z-step-time", type=float, default=8.0)
    ap.add_argument("--ckpt-stall-s", type=float, default=300.0)
    ap.add_argument("--tail-frac", type=float, default=0.6)
    ap.add_argument("--tail-count", type=int, default=4)
    ap.add_argument("--rules", default=None,
                    help="comma-separated anomaly families to keep "
                         "(e.g. hbm_pressure,kv_thrash); everything "
                         "else is dropped from the verdict — replay "
                         "one rule family in isolation")
    args = ap.parse_args(argv)

    keep = None
    if args.rules is not None:
        keep = {k.strip() for k in args.rules.split(",") if k.strip()}
        if not keep:
            print("--rules given but no family named", file=sys.stderr)
            return 2

    config = HealthConfig(
        action="record", window=args.window, min_points=args.min_points,
        z_loss=args.z_loss, z_grad=args.z_grad,
        z_step_time=args.z_step_time, ckpt_stall_s=args.ckpt_stall_s,
        tail_cause_frac=args.tail_frac, tail_cause_count=args.tail_count)

    all_anoms, all_problems = [], []
    per_file = {}
    for path in args.paths:
        anoms, n_step, problems = analyze_file(path, config)
        if keep is not None:
            anoms = [a for a in anoms if a.kind in keep]
        all_anoms += anoms
        all_problems += problems
        per_file[path] = {
            "n_step_records": n_step,
            "anomalies": [a.to_dict() for a in anoms],
            "problems": problems,
        }
        tag = f"{len(anoms)} finding(s)" if anoms else "clean"
        print(f"healthwatch: {path}: {n_step} step record(s), {tag}")
        for a in anoms:
            print(f"  [{a.kind}] {a.message}")
        for p in problems:
            print(f"  [invalid] {p}")

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"tool": "healthwatch", "files": per_file},
                      f, indent=2, sort_keys=True)
        print(f"report: {args.json_out}")

    if args.expect is not None:
        expected = {k.strip() for k in args.expect.split(",") if k.strip()}
        fired = {a.kind for a in all_anoms}
        missing = sorted(expected - fired)
        if missing:
            print(f"SELFCHECK FAILED: expected anomaly families "
                  f"{missing} did not fire on the specimen", file=sys.stderr)
            return 9
        print(f"selfcheck OK: all {len(expected)} expected families "
              f"fired ({sorted(expected)})")
        return 0

    if all_problems:
        return 5
    if all_anoms:
        kinds = sorted({a.kind for a in all_anoms})
        print(f"healthwatch: {len(all_anoms)} anomaly(ies) across "
              f"{len(args.paths)} file(s): {kinds}", file=sys.stderr)
        return 5
    return 0


if __name__ == "__main__":
    sys.exit(main())
