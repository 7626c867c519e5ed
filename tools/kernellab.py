#!/usr/bin/env python
"""Kernel Lab CLI: measured rooflines + the persistent timing database
over every registered Pallas kernel (paddle_tpu/telemetry/kernel_obs).

The MEASURED sibling of tools/kerneldoctor.py: the doctor derives what
a kernel SHOULD cost statically (KN503 CostEstimate honesty); the lab
runs each registered kernel's seeded canonical example — warmup +
median-of-k with `block_until_ready`, compile excluded via AOT
lower/compile (the compile-observatory discipline, so compile_ms never
pollutes execute_ms) — times the declared exact fallback on the SAME
inputs, and folds the KN503-traced flops/bytes through the shared peak
tables (telemetry/mfu.py) into achieved-FLOP/s and achieved-bandwidth
fractions per (kernel, shape, dtype, backend). Results land as typed
kind=kernelbench records; measured-vs-roofline drift feeds the SAME
`kernel_time_drift` rule in-flight (AnomalyDetector) and offline
(tools/healthwatch.py), so what pages you is what CI gates on.

    JAX_PLATFORMS=cpu python tools/kernellab.py \
        [--report lab.json] [--telemetry run.jsonl] [--seeds N] \
        [--warmup N] [--k N] [--db PATH --update-db]

Modes:
  (default)    measure every registered kernel, print the table
  --smoke      the ci.sh leg: every kernel measured once (cheap
               warmup/k), records gated through tools/trace_check.py,
               zero findings or exit 13
  --selfcheck  two-sided proof the lab itself works: the checked-in
               drift specimen (tools/specimens/kernelbench_drift.jsonl)
               must trip `kernel_time_drift` BY NAME in BOTH directions
               through the real AnomalyDetector; a clean measurement
               run must validate and NOT trip it; the DB must refuse
               non-finite rows and round-trip losslessly
  --tune K     config search for kernel family K (flash_fwd): enumerate
               (block_q, block_k) candidates, KN502 vmem_footprint as
               the feasibility predicate, measured time as the
               objective, KN504 parity re-fuzzed on the winner; with
               --db PATH --update-db the winner lands in that DB

The DB is a JSON file at the path --db names; it only ever rolls
forward through --update-db, which refuses non-finite rows. The
kernels never read it: a tuned config reaches them as an edit to their
block policy.

Exit codes: 0 clean; 13 findings (invalid records, drifting kernels,
failed tune parity); 9 selfcheck miss (the lab itself is broken).
"""
import argparse
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SPECIMEN = os.path.join(REPO, "tools", "specimens",
                        "kernelbench_drift.jsonl")


def _import_all_kernels():
    """Registration is import-driven: pull in every module that owns a
    pallas_call site so registered_kernels() is the full 13."""
    from paddle_tpu.moe import kernels          # noqa: F401
    from paddle_tpu.ops import (pallas_attention, pallas_decode,  # noqa: F401
                                pallas_int8, pallas_layernorm)    # noqa: F401


def run_measure(seeds=(1234,), warmup=2, k=5):
    from paddle_tpu.telemetry import kernel_obs

    _import_all_kernels()
    return kernel_obs.measure_registry(seeds=seeds, warmup=warmup, k=k)


def print_table(results):
    print(f"{'kernel':24s} {'signature':40s} {'dtype':5s} "
          f"{'ms':>9s} {'fb x':>6s} {'FLOP%':>6s} {'BW%':>6s} bound")
    print("-" * 104)
    for r in results:
        sp = f"{r.speedup:.2f}" if r.speedup else "-"
        roof = r.roof or {}
        ff = roof.get("flops_frac")
        bf = roof.get("bw_frac")
        ff = f"{ff * 100:.1f}" if ff is not None else "-"
        bf = f"{bf * 100:.1f}" if bf is not None else "-"
        bound = roof.get("bound") or "-"
        sig = r.sig if len(r.sig) <= 40 else r.sig[:37] + "..."
        print(f"{r.kernel:24s} {sig:40s} {r.dtype:5s} "
              f"{r.kernel_ms:9.3f} {sp:>6s} {ff:>6s} {bf:>6s} {bound}")


def _validate_records(records, trace_check, label):
    """Gate a batch of records through the offline checker exactly as
    CI would see them (tempfile round-trip included — what validates
    in memory but not after json round-trip IS a finding)."""
    problems = []
    with tempfile.NamedTemporaryFile(
            "w", suffix=".jsonl", delete=False) as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
        path = f.name
    try:
        tc_problems, stats = trace_check.check_pair(path)
        problems += [f"{label}: {p}" for p in tc_problems]
        n_kb = stats["n_kernelbench"]
        if n_kb != len(records):
            problems.append(
                f"{label}: wrote {len(records)} kernelbench records, "
                f"trace_check counted {n_kb}")
    finally:
        os.unlink(path)
    return problems


def _drift_findings(records, detector=None):
    """Feed measurement records through the REAL in-flight rule — the
    lab must agree with what would page in production."""
    from paddle_tpu.telemetry.health import AnomalyDetector

    det = detector or AnomalyDetector()
    found = []
    for rec in records:
        found.extend(det.observe(rec))
    return [a for a in found if a.kind == "kernel_time_drift"]


def run_smoke(args, trace_check):
    """The ci.sh leg: every registered kernel measured once on this
    backend, records gated, drift rule consulted. Zero findings or
    exit 13."""
    results = run_measure(seeds=(1234,), warmup=1, k=3)
    print_table(results)
    records = [r.to_record() for r in results]
    problems = _validate_records(records, trace_check, "smoke")
    drifts = _drift_findings(records)
    problems += [f"smoke: {a.message}" for a in drifts]
    from paddle_tpu.ops.kernel_registry import registered_kernels
    n_reg = len(registered_kernels())
    if len(results) != n_reg:
        problems.append(f"smoke: {n_reg} registered kernels but only "
                        f"{len(results)} measured")
    return results, records, problems


def run_selfcheck():
    """Two-sided proof (the kerneldoctor --selfcheck pattern): the
    drift specimen must trip the rule by name in both directions, the
    clean run must not, and the DB must hold its refuse-non-finite
    contract."""
    from paddle_tpu.telemetry import kernel_obs
    from paddle_tpu.telemetry.health import AnomalyDetector

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_check

    ok = True
    report = {}

    # a) the drift specimen: schema-valid records whose measured time
    # left the roofline band — must page BY NAME, in BOTH directions
    with open(SPECIMEN) as f:
        specimen = [json.loads(line) for line in f if line.strip()]
    spec_problems = _validate_records(specimen, trace_check, "specimen")
    if spec_problems:
        print("SELFCHECK FAILED: the drift specimen must be SCHEMA-"
              "valid (drift is a semantics finding, not a malformed "
              "record):", file=sys.stderr)
        for p in spec_problems:
            print(f"  {p}", file=sys.stderr)
        ok = False
    drifts = _drift_findings(specimen)
    sides = {("slower" if a.z is not None and a.z > 1.0 else "faster")
             for a in drifts}
    report["specimen"] = {
        "n_records": len(specimen),
        "anomalies": [a.to_dict() for a in drifts],
        "sides": sorted(sides)}
    if not drifts:
        print("SELFCHECK FAILED: tools/specimens/kernelbench_drift"
              ".jsonl did not trip kernel_time_drift through the "
              "AnomalyDetector", file=sys.stderr)
        ok = False
    elif sides != {"slower", "faster"}:
        print(f"SELFCHECK FAILED: drift specimen only fired on the "
              f"{sorted(sides)} side(s) — both directions must be "
              "reachable", file=sys.stderr)
        ok = False

    # b) clean run: measure everything here, records validate, the
    # rule stays quiet (on CPU predicted_ms is None -> exempt; on TPU
    # an in-band kernel must not page)
    results = run_measure(seeds=(1234,), warmup=1, k=2)
    records = [r.to_record() for r in results]
    clean_problems = _validate_records(records, trace_check, "clean")
    clean_drifts = _drift_findings(records)
    report["clean"] = {
        "n_measured": len(results),
        "problems": clean_problems,
        "drifts": [a.to_dict() for a in clean_drifts]}
    if clean_problems:
        print("SELFCHECK FAILED: clean-run records did not validate:",
              file=sys.stderr)
        for p in clean_problems:
            print(f"  {p}", file=sys.stderr)
        ok = False
    if clean_drifts:
        print("SELFCHECK FAILED: clean run tripped kernel_time_drift:",
              file=sys.stderr)
        for a in clean_drifts:
            print(f"  {a.message}", file=sys.stderr)
        ok = False

    # c) DB contract: refuse non-finite, round-trip losslessly
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "db.json")
        db = kernel_obs.KernelDB(path)
        updated, refused = db.update(results)
        _, bad = db.update([("k|s|f32|cpu", {"best_ms": float("nan")})])
        db.save()
        reloaded = kernel_obs.KernelDB(path)
        report["db"] = {"updated": len(updated), "refused": len(bad)}
        if not updated:
            print("SELFCHECK FAILED: no measured row landed in the DB",
                  file=sys.stderr)
            ok = False
        if not bad:
            print("SELFCHECK FAILED: a NaN best_ms row was NOT refused "
                  "— a poisoned baseline disarms every future "
                  "comparison", file=sys.stderr)
            ok = False
        if reloaded.entries != db.entries:
            print("SELFCHECK FAILED: DB did not round-trip through "
                  "save/load", file=sys.stderr)
            ok = False
    return ok, report


def run_tune(args, trace_check):
    """Config search over the flash-forward family. Returns (winner,
    problems, records)."""
    from paddle_tpu.telemetry import kernel_obs, sink

    _import_all_kernels()
    if args.tune not in ("flash_fwd", "flash_fwd_rect"):
        return None, [f"--tune {args.tune}: only the flash_fwd family "
                      "has a search space wired up (block_q/block_k "
                      "over kernel_obs.ATTN_SWEEP_BQ x ATTN_SWEEP_BK)"], []
    winner, results, skipped = kernel_obs.tune_flash_fwd(
        seq=args.seq, warmup=args.warmup, k=args.k)
    problems, records = [], []
    for (bq, bk), why in skipped:
        print(f"  skip (block_q={bq}, block_k={bk}): {why}")
    for r in results:
        cfg = r.config or {}
        print(f"  block_q={cfg.get('block_q')} "
              f"block_k={cfg.get('block_k')}: {r.kernel_ms:.3f} ms")
        records.append(r.to_record(event="tune"))
    if winner is None:
        problems.append(f"--tune {args.tune}: no feasible candidate "
                        "survived measurement")
        return None, problems, records
    if winner["parity_findings"]:
        problems.append(
            f"--tune {args.tune}: winner (block_q="
            f"{winner['config']['block_q']}, block_k="
            f"{winner['config']['block_k']}) FAILED the KN504 parity "
            f"re-fuzz and will not be persisted: "
            f"{winner['parity_findings']}")
        return None, problems, records
    print(f"winner: block_q={winner['config']['block_q']} "
          f"block_k={winner['config']['block_k']} "
          f"({winner['best_ms']:.3f} ms, KN504 parity clean, "
          f"KN502 vmem feasible)")
    return winner, problems, records


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--report", default=None,
                    help="write the JSON report here")
    ap.add_argument("--telemetry", default=None,
                    help="append kind=kernelbench records to this JSONL")
    ap.add_argument("--seeds", type=int, default=1,
                    help="example seeds per kernel — the examples "
                         "derive shapes AND dtypes from the rng, so "
                         "extra seeds ARE the sweep (default 1)")
    ap.add_argument("--warmup", type=int, default=2,
                    help="warmup iterations before timing (default 2)")
    ap.add_argument("--k", type=int, default=5,
                    help="timed samples per kernel; median reported "
                         "(default 5)")
    ap.add_argument("--db", default=None,
                    help="timing DB path (required by --update-db)")
    ap.add_argument("--update-db", action="store_true",
                    help="roll measured/tuned rows into the --db file "
                         "(non-finite rows refused)")
    ap.add_argument("--smoke", action="store_true",
                    help="the ci.sh leg: every kernel once, records "
                         "gated through trace_check, exit 13 on any "
                         "finding")
    ap.add_argument("--selfcheck", action="store_true",
                    help="drift specimen caught by name both ways + "
                         "clean run quiet + DB refuse/round-trip proof")
    ap.add_argument("--tune", default=None, metavar="KERNEL",
                    help="config search for this kernel family "
                         "(flash_fwd)")
    ap.add_argument("--seq", type=int, default=1024,
                    help="sequence length for --tune (default 1024)")
    args = ap.parse_args(argv)
    if args.update_db and not args.db:
        ap.error("--update-db needs --db PATH")

    import jax
    from paddle_tpu.telemetry import kernel_obs, sink

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_check

    if args.selfcheck:
        ok, report = run_selfcheck()
        report["tool"] = "kernellab"
        report["platform"] = jax.default_backend()
        if args.report:
            with open(args.report, "w") as f:
                json.dump(report, f, indent=2, sort_keys=True)
        if ok:
            print("kernel lab selfcheck OK: drift specimen caught by "
                  "name in both directions, "
                  f"{report['clean']['n_measured']} kernels measured "
                  "clean, DB refuses non-finite rows and round-trips")
        return 0 if ok else 9

    records = []
    problems = []
    results = []
    winner = None

    if args.tune:
        winner, problems, records = run_tune(args, trace_check)
        problems += _validate_records(records, trace_check, "tune")
    elif args.smoke:
        results, records, problems = run_smoke(args, trace_check)
    else:
        seeds = tuple(1234 + i for i in range(max(1, args.seeds)))
        results = run_measure(seeds=seeds, warmup=args.warmup, k=args.k)
        print_table(results)
        records = [r.to_record() for r in results]
        problems += _validate_records(records, trace_check, "measure")
        drifts = _drift_findings(records)
        problems += [a.message for a in drifts]

    if args.update_db and not problems:
        db = kernel_obs.KernelDB(args.db)
        if winner is not None:
            key = kernel_obs.db_key(
                winner["kernel"], winner["sig"], winner["dtype"],
                winner["backend"])
            entry = {"best_ms": winner["best_ms"],
                     "config": dict(winner["config"])}
            updated, refused = db.update([(key, entry)])
        else:
            updated, refused = db.update(results)
        for key, why in refused:
            problems.append(f"--update-db {key}: {why}")
        if updated:
            db.save()
            print(f"kernel db: {len(updated)} row(s) rolled forward "
                  f"-> {args.db}")
            # db_update records must reference a measured row: carry
            # the key of what actually landed (trace_check cross-rule)
            for key in updated:
                e = db.entries[key]
                records.append(sink.make_kernelbench_record(
                    kernel=e["kernel"], sig=e["sig"],
                    backend=e["backend"], dtype=e.get("dtype"),
                    kernel_ms=e["best_ms"], db_key=key,
                    config=e.get("config"), event="db_update"))
        else:
            print("kernel db: no row beat the incumbents")
    elif args.update_db:
        print("kernel db: NOT updated — findings above must clear "
              "first", file=sys.stderr)

    if args.telemetry:
        out = sink.JsonlSink(args.telemetry)
        for rec in records:
            out.write(rec)
        out.close()

    if args.report:
        report = {
            "tool": "kernellab",
            "platform": jax.default_backend(),
            "problems": problems,
            "results": records,
        }
        if winner is not None:
            report["winner"] = winner
        with open(args.report, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"report: {args.report}")

    if problems:
        print(f"kernel lab: {len(problems)} finding(s)")
        for p in problems:
            print(f"  {p}", file=sys.stderr)
        return 13
    if args.tune:
        return 0
    print(f"kernel lab: {len(results)} measurement(s) clean on "
          f"{jax.default_backend()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
