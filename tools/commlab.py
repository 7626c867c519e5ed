#!/usr/bin/env python
"""Comm Lab CLI: measured collective latencies + the persistent comm
database over the live mesh (paddle_tpu/telemetry/comm_obs).

The MESH sibling of tools/kernellab.py: the kernel lab measures what
one chip computes, the comm lab measures what the mesh moves. Every
sweep point runs one shard_map collective (psum / all_gather /
reduce_scatter / all_to_all / ppermute) over one size>1 mesh axis at
one payload rung under the kernel-observatory timing discipline —
AOT lower/compile timed separately, warmup, median-of-k
``block_until_ready`` — then lands as a typed kind=commbench record
attributed against the planner's `ICI_BW_BY_CHIP` / `DCN_BW_BYTES`
peaks (achieved-bandwidth fraction; None on CPU where no peak exists).
Measured-vs-DB drift feeds the SAME `comm_bw_degraded` rule in-flight
(AnomalyDetector) and offline (tools/healthwatch.py), so what pages
you is what CI gates on.

    JAX_PLATFORMS=cpu python tools/commlab.py \
        [--report lab.json] [--telemetry run.jsonl] [--mesh dp=2,mp=4] \
        [--payloads 16384,65536] [--warmup N] [--k N] \
        [--db PATH --update-db]

Modes:
  (default)    sweep every (op, axis, payload), print the table
  --smoke      the ci.sh leg: every (op, axis) measured at the small
               CPU-scale rungs, records gated through
               tools/trace_check.py AND the comm_audit third honesty
               leg (claimed wire_bytes vs a re-trace of the same sweep
               program), zero findings or exit 13
  --selfcheck  proof the lab itself works: the checked-in specimen
               (tools/specimens/commbench_degraded.jsonl) must trip
               `comm_bw_degraded` BY NAME through the real
               AnomalyDetector — its in-band and reference-free rows
               must stay silent; a clean sweep on this host must
               validate, pass the wire-byte audit, and NOT trip the
               rule; the DB must refuse non-finite rows and round-trip
               losslessly

The DB is a JSON file at the path --db names; it only ever rolls
forward through --update-db, which refuses non-finite rows and keeps
the best-known latency per (op, axis-size, payload, backend) key.

Exit codes: 0 clean; 13 findings (invalid records, degraded
collectives, dishonest wire-byte claims); 9 selfcheck miss (the lab
itself is broken).
"""
import argparse
import json
import os
import sys
import tempfile

# 8 virtual CPU devices BEFORE jax loads (same recipe as
# tests/conftest.py) so the default dp=2,mp=4 sweep mesh builds
# anywhere; harmless on a real accelerator (host-platform-only flag)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SPECIMEN = os.path.join(REPO, "tools", "specimens",
                        "commbench_degraded.jsonl")

# the --smoke payload rungs: the 8-virtual-device CPU mesh measures
# scheduling overhead, not bandwidth — MiB-scale rungs buy nothing
# there (the real ladder, comm_obs.payload_sweep(), starts at 256 KiB)
SMOKE_PAYLOADS = (16 * 1024, 64 * 1024)


def _build_mesh(spec):
    """Install the sweep mesh from a 'dp=2,mp=4' spec — or reuse an
    already-installed one (a training harness calling into the lab
    sweeps the mesh it trains on)."""
    from paddle_tpu.distributed import env

    mesh = env.current_mesh()
    if mesh is not None:
        return mesh
    kw = {}
    for part in (spec or "").split(","):
        if not part.strip():
            continue
        k, _, v = part.partition("=")
        kw[k.strip()] = int(v)
    return env.build_mesh(**kw)


def _parse_payloads(raw):
    if not raw:
        return None
    return [int(p) for p in raw.split(",") if p.strip()]


def run_sweep(args, payloads=None, warmup=None, k=None):
    from paddle_tpu.telemetry import comm_obs

    mesh = _build_mesh(args.mesh)
    if payloads is None:
        payloads = _parse_payloads(args.payloads)
    if payloads is None:
        import jax
        # CPU default: the smoke rungs (see SMOKE_PAYLOADS); real
        # backends get the full 256 KiB..256 MiB ladder
        payloads = list(SMOKE_PAYLOADS) \
            if jax.default_backend() == "cpu" \
            else comm_obs.payload_sweep()
    return comm_obs.sweep_mesh(
        mesh=mesh, payloads=payloads,
        warmup=args.warmup if warmup is None else warmup,
        k=args.k if k is None else k)


def print_table(results):
    print(f"{'op':16s} {'axis':6s} {'n':>3s} {'payload':>12s} "
          f"{'ms':>9s} {'compile':>9s} {'BW%':>6s} medium")
    print("-" * 72)
    for r in results:
        bf = f"{r.bw_frac * 100:.1f}" if r.bw_frac is not None else "-"
        med = r.medium or "-"
        print(f"{r.op:16s} {r.axis:6s} {r.axis_size:3d} "
              f"{r.payload_bytes:12d} {r.time_ms:9.3f} "
              f"{r.compile_ms:9.1f} {bf:>6s} {med}")


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
        n_cb = stats["n_commbench"]
        n_want = sum(1 for r in records
                     if isinstance(r, dict) and r.get("kind") == "commbench")
        if n_cb != n_want:
            problems.append(
                f"{label}: wrote {n_want} commbench records, "
                f"trace_check counted {n_cb}")
    finally:
        os.unlink(path)
    return problems


def _drift_findings(records, detector=None):
    """Feed measurement records through the REAL in-flight rules — the
    lab must agree with what would page in production."""
    from paddle_tpu.telemetry.health import AnomalyDetector

    det = detector or AnomalyDetector()
    found = []
    for rec in records:
        found.extend(det.observe(rec))
    return [a for a in found
            if a.kind in ("comm_bw_degraded", "straggler")]


def _audit_findings(records, mesh):
    """The third honesty leg: each measured record's claimed wire_bytes
    vs a re-trace of the SAME sweep program through the jaxpr
    accounting (analysis/comm_audit)."""
    from paddle_tpu.analysis import comm_audit

    return comm_audit.check_commbench_wire_bytes(records, mesh=mesh)


def run_smoke(args, trace_check):
    """The ci.sh leg: every (op, size>1 axis) measured at the smoke
    rungs, records gated, drift rule consulted, wire-byte claims
    audited. Zero findings or exit 13."""
    from paddle_tpu.distributed import env
    from paddle_tpu.telemetry import comm_obs

    results = run_sweep(args, payloads=list(SMOKE_PAYLOADS),
                        warmup=1, k=2)
    print_table(results)
    records = [r.to_record() for r in results]
    problems = _validate_records(records, trace_check, "smoke")
    drifts = _drift_findings(records)
    problems += [f"smoke: {a.message}" for a in drifts]
    mesh = env.current_mesh()
    problems += [f"smoke: {p}" for p in _audit_findings(records, mesh)]
    n_axes = len(comm_obs.sweep_axes(mesh))
    n_want = len(comm_obs.SWEEP_OPS) * n_axes * len(SMOKE_PAYLOADS)
    if len(results) != n_want:
        problems.append(
            f"smoke: expected {n_want} measurements "
            f"({len(comm_obs.SWEEP_OPS)} ops x {n_axes} axes x "
            f"{len(SMOKE_PAYLOADS)} payloads), got {len(results)}")
    return results, records, problems


def run_selfcheck():
    """Proof the lab works (the kernellab --selfcheck pattern): the
    degraded specimen must trip the rule by name while its in-band and
    reference-free rows stay silent, the clean sweep must validate +
    audit + stay quiet, and the DB must hold its refuse-non-finite
    contract."""
    from paddle_tpu.distributed import env
    from paddle_tpu.telemetry import comm_obs
    from paddle_tpu.telemetry.health import AnomalyDetector

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_check

    ok = True
    report = {}

    # a) the degraded specimen: schema-valid records, one with a
    # measured time past the comm_bw_degraded band of its db_ms — must
    # page BY NAME; the in-band row and the row with no DB reference
    # must not
    with open(SPECIMEN) as f:
        specimen = [json.loads(line) for line in f if line.strip()]
    spec_problems = _validate_records(specimen, trace_check, "specimen")
    if spec_problems:
        print("SELFCHECK FAILED: the degraded specimen must be SCHEMA-"
              "valid (degradation is a semantics finding, not a "
              "malformed record):", file=sys.stderr)
        for p in spec_problems:
            print(f"  {p}", file=sys.stderr)
        ok = False
    drifts = _drift_findings(specimen)
    report["specimen"] = {
        "n_records": len(specimen),
        "anomalies": [a.to_dict() for a in drifts]}
    degraded = [a for a in drifts if a.kind == "comm_bw_degraded"]
    if not degraded:
        print("SELFCHECK FAILED: tools/specimens/commbench_degraded"
              ".jsonl did not trip comm_bw_degraded through the "
              "AnomalyDetector", file=sys.stderr)
        ok = False
    elif len(drifts) != 1:
        print(f"SELFCHECK FAILED: specimen fired {len(drifts)} "
              "anomalies — the in-band and reference-free rows must "
              "stay silent:", file=sys.stderr)
        for a in drifts:
            print(f"  {a.kind}: {a.message}", file=sys.stderr)
        ok = False

    # b) clean sweep: measure here, records validate, wire-byte claims
    # audit clean, the rule stays quiet (no DB is given, so no
    # measurement carries a reference)
    mesh = _build_mesh("dp=2,mp=4")
    results = comm_obs.sweep_mesh(
        mesh=mesh, payloads=[SMOKE_PAYLOADS[0]], warmup=1, k=2)
    records = [r.to_record() for r in results]
    clean_problems = _validate_records(records, trace_check, "clean")
    clean_problems += [f"audit: {p}"
                       for p in _audit_findings(records, mesh)]
    clean_drifts = _drift_findings(records)
    report["clean"] = {
        "n_measured": len(results),
        "problems": clean_problems,
        "drifts": [a.to_dict() for a in clean_drifts]}
    if clean_problems:
        print("SELFCHECK FAILED: clean-sweep records did not validate:",
              file=sys.stderr)
        for p in clean_problems:
            print(f"  {p}", file=sys.stderr)
        ok = False
    if clean_drifts:
        print("SELFCHECK FAILED: clean sweep tripped a drift rule:",
              file=sys.stderr)
        for a in clean_drifts:
            print(f"  {a.message}", file=sys.stderr)
        ok = False

    # c) DB contract: refuse non-finite, round-trip losslessly
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "db.json")
        db = comm_obs.CommDB(path)
        updated, refused = db.update(results)
        _, bad = db.update(
            [("psum|ax2|16384|cpu", {"best_ms": float("nan")})])
        db.save()
        reloaded = comm_obs.CommDB(path)
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


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--report", default=None,
                    help="write the JSON report here")
    ap.add_argument("--telemetry", default=None,
                    help="append kind=commbench records to this JSONL")
    ap.add_argument("--mesh", default="dp=2,mp=4",
                    help="mesh spec to build when none is installed "
                         "(default dp=2,mp=4 — the 8-device CI mesh)")
    ap.add_argument("--payloads", default=None,
                    help="comma-separated payload bytes per point "
                         "(default: the smoke rungs on CPU, the full "
                         "256KiB..256MiB ladder elsewhere)")
    ap.add_argument("--warmup", type=int, default=2,
                    help="warmup iterations before timing (default 2)")
    ap.add_argument("--k", type=int, default=5,
                    help="timed samples per point; median reported "
                         "(default 5)")
    ap.add_argument("--db", default=None,
                    help="comm DB path (required by --update-db)")
    ap.add_argument("--update-db", action="store_true",
                    help="roll measured rows into the DB (keep-best; "
                         "non-finite rows refused)")
    ap.add_argument("--smoke", action="store_true",
                    help="the ci.sh leg: every (op, axis) once at the "
                         "smoke rungs, records gated through "
                         "trace_check + the comm_audit wire-byte leg, "
                         "exit 13 on any finding")
    ap.add_argument("--selfcheck", action="store_true",
                    help="degraded specimen caught by name + clean "
                         "sweep quiet/audited + DB refuse/round-trip "
                         "proof")
    args = ap.parse_args(argv)
    if args.update_db and not args.db:
        ap.error("--update-db needs --db PATH")

    import jax
    from paddle_tpu.telemetry import comm_obs, sink

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_check

    if args.selfcheck:
        ok, report = run_selfcheck()
        report["tool"] = "commlab"
        report["platform"] = jax.default_backend()
        if args.report:
            with open(args.report, "w") as f:
                json.dump(report, f, indent=2, sort_keys=True)
        if ok:
            print("comm lab selfcheck OK: degraded specimen caught by "
                  "name (in-band and reference-free rows silent), "
                  f"{report['clean']['n_measured']} collectives "
                  "measured clean and wire-byte-audited, DB refuses "
                  "non-finite rows and round-trips")
        return 0 if ok else 9

    records = []
    problems = []
    results = []

    if args.smoke:
        results, records, problems = run_smoke(args, trace_check)
    else:
        results = run_sweep(args)
        print_table(results)
        records = [r.to_record() for r in results]
        problems += _validate_records(records, trace_check, "measure")
        from paddle_tpu.distributed import env
        problems += _audit_findings(records, env.current_mesh())
        drifts = _drift_findings(records)
        problems += [a.message for a in drifts]

    if args.update_db and not problems:
        db = comm_obs.CommDB(args.db)
        updated, refused = db.update(results)
        for key, why in refused:
            problems.append(f"--update-db {key}: {why}")
        if updated:
            db.save()
            print(f"comm db: {len(updated)} row(s) rolled forward "
                  f"-> {args.db}")
            # db_update records must reference a measured row: re-emit
            # the winning measurement with event=db_update so the
            # trace_check cross-rule can tie the update to its source
            by_key = {r.key(): r for r in results}
            for key in updated:
                if key in by_key:
                    records.append(by_key[key].to_record(
                        event="db_update"))
        else:
            print("comm db: no row beat the incumbents")
    elif args.update_db:
        print("comm db: NOT updated — findings above must clear first",
              file=sys.stderr)

    if args.telemetry:
        out = sink.JsonlSink(args.telemetry)
        for rec in records:
            out.write(rec)
        out.close()

    if args.report:
        report = {
            "tool": "commlab",
            "platform": jax.default_backend(),
            "problems": problems,
            "results": records,
        }
        with open(args.report, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"report: {args.report}")

    if problems:
        print(f"comm lab: {len(problems)} finding(s)")
        for p in problems:
            print(f"  {p}", file=sys.stderr)
        return 13
    print(f"comm lab: {len(results)} measurement(s) clean on "
          f"{jax.default_backend()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
