#!/usr/bin/env python
"""Memory watch CLI: render, gate and replay the memory observatory's
HBM ledger (paddle_tpu/telemetry/mem_obs, kind=memsnap records).

The memory sibling of tools/compile_report.py / commlab.py: the compile observatory projects what a program SHOULD
hold (static ``memory_analysis()``), this tool reads what the process
ACTUALLY held — the live-array ledger bucketed into params / opt_state
/ kv / workspace / other, the KV-pool block census, and the OOM
postmortems the engine captures on allocation failure. Every record is
gated through tools/trace_check.py (bucket sums, headroom arithmetic,
KV census tiling recomputed from each record's own fields) and
replayed through the REAL in-flight rules (`hbm_pressure`,
`kv_thrash`, `mem_projection_drift` in telemetry/health.py) — what
pages in production is what this tool reports offline.

    JAX_PLATFORMS=cpu python tools/memwatch.py run.jsonl
    JAX_PLATFORMS=cpu python tools/memwatch.py run.jsonl --postmortem
    JAX_PLATFORMS=cpu python tools/memwatch.py --smoke \
        [--telemetry out.jsonl]
    JAX_PLATFORMS=cpu python tools/memwatch.py --selfcheck

Modes:
  (default)     render the ledger timeline of a JSONL file: per-sample
                bucket bytes, headroom, KV occupancy and rates; records
                gated through trace_check and the anomaly rules — any
                invalid record OR fired rule is a finding (exit 14)
  --postmortem  forensics mode: render the LAST event=postmortem record
                in the file — what killed the allocation, the top-K
                live suspects by bytes, the KV pool state and the
                compile-signature families resident at death; exit 14
                when the file holds no postmortem (nothing to diagnose)
  --smoke       the ci.sh leg: a real tiny serving engine (tagged
                weights + paged-KV arenas) plus a real Adam step
                (tagged optimizer state), sampled for a few steps
                against a declared budget and a shape-derived static
                projection; records gated, rules must stay SILENT, and
                the ledger total must reconcile with the projection
                within HealthConfig.mem_reconcile_tol
  --selfcheck   proof the watcher itself works: the checked-in
                pressure specimen (tools/specimens/
                memsnap_pressure.jsonl) must trip `hbm_pressure` AND
                `kv_thrash` BY NAME through the real AnomalyDetector;
                a clean smoke ledger must validate, reconcile and stay
                silent; a captured postmortem must round-trip through
                the sink and carry its suspects

Exit codes: 0 clean; 14 findings (invalid records, fired rules,
missing postmortem, failed reconciliation); 9 selfcheck miss (the
watcher itself is broken).
"""
import argparse
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SPECIMEN = os.path.join(REPO, "tools", "specimens",
                        "memsnap_pressure.jsonl")

MEM_RULES = ("hbm_pressure", "kv_thrash", "mem_projection_drift")


def _mb(v):
    return "-" if not isinstance(v, (int, float)) else f"{v / 2**20:.2f}"


def _read(path):
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                records.append(json.loads(line))
    return [r for r in records
            if isinstance(r, dict) and r.get("kind") == "memsnap"]


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
        if stats["n_memsnap"] != len(records):
            problems.append(
                f"{label}: wrote {len(records)} memsnap records, "
                f"trace_check counted {stats['n_memsnap']}")
    finally:
        os.unlink(path)
    return problems


def _rule_findings(records, detector=None):
    """Feed ledger records through the REAL in-flight rules — the
    watcher must agree with what would page in production."""
    from paddle_tpu.telemetry.health import AnomalyDetector

    det = detector or AnomalyDetector()
    found = []
    for rec in records:
        found.extend(det.observe(rec))
    return [a for a in found if a.kind in MEM_RULES]


def print_timeline(records):
    print(f"{'step':>6s} {'event':10s} {'total MB':>9s} {'params':>8s} "
          f"{'opt':>8s} {'kv':>8s} {'work':>8s} {'other':>8s} "
          f"{'headMB':>8s} {'kvocc':>6s} {'ev/s':>6s}")
    print("-" * 96)
    for r in records:
        occ = r.get("kv_occupancy")
        evr = r.get("kv_eviction_rate")
        occ = "-" if occ is None else f"{occ:.3f}"
        evr = "-" if evr is None else f"{evr:.2f}"
        print(f"{r.get('step', 0):>6d} {r.get('event', '?'):10s} "
              f"{_mb(r.get('total_bytes')):>9s} "
              f"{_mb(r.get('params_bytes')):>8s} "
              f"{_mb(r.get('opt_state_bytes')):>8s} "
              f"{_mb(r.get('kv_bytes')):>8s} "
              f"{_mb(r.get('workspace_bytes')):>8s} "
              f"{_mb(r.get('other_bytes')):>8s} "
              f"{_mb(r.get('headroom_bytes')):>8s} "
              f"{occ:>6s} {evr:>6s}")


def print_postmortem(rec):
    """Render one forensic record: the offline half of the engine's
    capture-on-failure."""
    print(f"POSTMORTEM at step {rec.get('step')} "
          f"(rank {rec.get('rank')}, engine {rec.get('engine')})")
    print(f"  error: {rec.get('error')}")
    total = rec.get("total_bytes")
    budget = rec.get("hbm_budget_bytes")
    print(f"  ledger: total {_mb(total)} MB"
          + (f" of {_mb(budget)} MB budget "
             f"(headroom {_mb(rec.get('headroom_bytes'))} MB)"
             if budget else " (no declared budget)"))
    for k in ("params_bytes", "opt_state_bytes", "kv_bytes",
              "workspace_bytes", "other_bytes"):
        print(f"    {k[:-6]:10s} {_mb(rec.get(k)):>10s} MB")
    nt = rec.get("kv_blocks_total")
    if nt is not None:
        print(f"  kv pool: {rec.get('kv_blocks_held')}/{nt} held, "
              f"{rec.get('kv_blocks_free')} free, "
              f"{rec.get('kv_blocks_cached')} cached; "
              f"evictions {rec.get('kv_evictions')}, "
              f"admissions {rec.get('kv_admissions')}")
    top = rec.get("top_arrays") or []
    print(f"  top {len(top)} live suspects by bytes:")
    for t in top:
        print(f"    {_mb(t.get('bytes')):>10s} MB  "
              f"{t.get('bucket', '?'):10s} "
              f"{t.get('dtype', '?'):10s} {t.get('shape', '')}")
    fams = rec.get("compile_families") or []
    if fams:
        print(f"  {len(fams)} compile-signature families resident:")
        for f in fams:
            print(f"    {f.get('family')}: {f.get('n_compiles')} "
                  f"compile(s), digest {f.get('digest', '?')}")


# ---------------------------------------------------------------------------
# smoke: a real tagged process sampled against a static projection
# ---------------------------------------------------------------------------

def _static_projection(model, opt, eng):
    """The compile-observatory stance applied by hand: what the process
    SHOULD hold, derived from shapes alone — model leaves, optimizer
    state leaves, and the paged-KV arena formula — never from the live
    arrays the ledger is about to be checked against."""
    import numpy as np
    import jax.numpy as jnp

    def leaf_bytes(shape, dtype):
        return int(np.prod(shape or (1,))) * jnp.dtype(dtype).itemsize

    params = sum(
        leaf_bytes(getattr(p._value, "shape", ()), p._value.dtype)
        for p in eng._bound if getattr(p, "_value", None) is not None)
    params += sum(
        leaf_bytes(getattr(p._value, "shape", ()), p._value.dtype)
        for p in opt._parameter_list or ()
        if getattr(p, "_value", None) is not None)
    opt_state = sum(
        leaf_bytes(getattr(v, "shape", ()), v.dtype)
        for st in opt._states.values() for v in st.values()
        if hasattr(v, "dtype"))
    kv = eng.cache.num_blocks * eng._block_bytes()
    return params + opt_state + kv


def run_smoke(telemetry=None, steps=6):
    """The ci.sh leg: every tagging hook exercised (engine weights,
    optimizer params + state, KV arenas), sampled against a declared
    budget and the shape-derived static projection. Returns
    (records, problems)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.serving import SamplingParams, ServingEngine
    from paddle_tpu.telemetry import sink as tsink
    from paddle_tpu.telemetry.health import HealthConfig
    from paddle_tpu.telemetry.mem_obs import MemoryObservatory

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_check

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=2, max_seq_len=64, dropout=0.0,
                    use_flash_attention=False)
    model = GPTForPretraining(cfg)
    eng = ServingEngine(model, max_slots=2, block_size=8,
                        prefill_chunk=8, max_model_len=64,
                        hbm_budget_mb=256)

    # a real Adam step so the optimizer's params AND state providers
    # have live arrays to tag (states materialize on first step)
    lin = nn.Linear(16, 16)
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=lin.parameters())
    x = paddle.to_tensor(np.ones((4, 16), np.float32))
    loss = (lin(x) ** 2).sum()
    loss.backward()
    opt.step()
    opt.clear_grad()

    proj = _static_projection(model, opt, eng)
    obs = MemoryObservatory(
        sink=tsink.JsonlSink(telemetry) if telemetry else None,
        hbm_budget_bytes=256 * 2 ** 20,
        kv_source=eng._kv_accounting,
        projection_bytes=proj, projection_family="memwatch_smoke",
        engine=eng.engine_id)

    h = eng.submit([1, 2, 3], SamplingParams(max_new_tokens=4))
    records = []
    for i in range(1, steps + 1):
        eng.step()
        records.append(obs.snapshot(i))
    # the engine keeps a decode step in flight: the sampled steps end
    # with the newest tokens still on the device
    eng.run_until_idle(max_steps=100)
    h.result(timeout=60)
    if obs.sink is not None:
        obs.sink.close()

    problems = _validate_records(records, trace_check, "smoke")
    fired = _rule_findings(records)
    problems += [f"smoke: {a.message}" for a in fired]

    last = records[-1]
    tol = HealthConfig().mem_reconcile_tol
    total = last["total_bytes"]
    if not proj or abs(total - proj) > tol * proj:
        problems.append(
            f"smoke: ledger total {total} does not reconcile with the "
            f"shape-derived static projection {proj} within "
            f"{tol:.0%} — the live walk and the static accounting "
            "disagree about what this process holds")
    for bucket in ("params_bytes", "opt_state_bytes", "kv_bytes"):
        if not last.get(bucket):
            problems.append(
                f"smoke: {bucket} is empty — the tagging hook for "
                "that bucket never fired")
    print_timeline(records)
    print(f"smoke: projection {proj} bytes vs ledger {total} bytes "
          f"({abs(total - proj) / proj:.1%} apart, tol {tol:.0%})")
    return records, problems


# ---------------------------------------------------------------------------
# selfcheck
# ---------------------------------------------------------------------------

def run_selfcheck():
    """Proof the watcher works: specimen pages BY NAME, clean ledger
    stays silent and reconciles, postmortem round-trips."""
    from paddle_tpu.telemetry.mem_obs import MemoryObservatory
    from paddle_tpu.telemetry.sink import validate_step_record

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_check

    ok = True
    report = {}

    # a) the pressure specimen: schema-valid records whose ledger left
    # the declared budget band AND whose eviction rate ran past the
    # admission rate — both rules must page BY NAME
    with open(SPECIMEN) as f:
        specimen = [json.loads(line) for line in f if line.strip()]
    spec_problems = _validate_records(specimen, trace_check, "specimen")
    if spec_problems:
        print("SELFCHECK FAILED: the pressure specimen must be SCHEMA-"
              "valid (pressure is a semantics finding, not a malformed "
              "record):", file=sys.stderr)
        for p in spec_problems:
            print(f"  {p}", file=sys.stderr)
        ok = False
    fired = _rule_findings(specimen)
    kinds = {a.kind for a in fired}
    report["specimen"] = {
        "n_records": len(specimen),
        "anomalies": [a.to_dict() for a in fired],
        "kinds": sorted(kinds)}
    for want in ("hbm_pressure", "kv_thrash"):
        if want not in kinds:
            print(f"SELFCHECK FAILED: tools/specimens/"
                  f"memsnap_pressure.jsonl did not trip {want} "
                  "through the AnomalyDetector", file=sys.stderr)
            ok = False

    # b) clean ledger: the smoke run must validate, reconcile against
    # its static projection, and keep every rule quiet
    records, clean_problems = run_smoke(telemetry=None, steps=4)
    report["clean"] = {"n_records": len(records),
                       "problems": clean_problems}
    if clean_problems:
        print("SELFCHECK FAILED: the clean smoke ledger did not come "
              "back clean:", file=sys.stderr)
        for p in clean_problems:
            print(f"  {p}", file=sys.stderr)
        ok = False

    # c) postmortem round-trip: capture-on-failure writes a record the
    # validator accepts and the forensics renderer can name suspects
    # from (error + top_arrays are REQUIRED by the validator)
    obs = MemoryObservatory(hbm_budget_bytes=256 * 2 ** 20)
    pm = obs.capture_postmortem(
        "RESOURCE_EXHAUSTED: Out of memory allocating 2.5G", step=4)
    pm2 = json.loads(json.dumps(pm))
    pm_problems = validate_step_record(pm2)
    report["postmortem"] = {"problems": pm_problems,
                            "n_suspects": len(pm2.get("top_arrays")
                                              or [])}
    if pm_problems:
        print("SELFCHECK FAILED: a captured postmortem did not "
              "round-trip through the validator:", file=sys.stderr)
        for p in pm_problems:
            print(f"  {p}", file=sys.stderr)
        ok = False
    if not pm2.get("error") or not pm2.get("top_arrays"):
        print("SELFCHECK FAILED: the postmortem names no cause or no "
              "suspects — forensics with nothing to say",
              file=sys.stderr)
        ok = False
    print_postmortem(pm2)
    return ok, report


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", nargs="?", default=None,
                    help="memsnap JSONL to render/replay")
    ap.add_argument("--postmortem", action="store_true",
                    help="render the last OOM postmortem in the file "
                         "(exit 14 when there is none)")
    ap.add_argument("--smoke", action="store_true",
                    help="the ci.sh leg: tagged engine + optimizer "
                         "sampled against budget and static "
                         "projection; exit 14 on any finding")
    ap.add_argument("--selfcheck", action="store_true",
                    help="specimen trips hbm_pressure + kv_thrash by "
                         "name, clean ledger silent + reconciled, "
                         "postmortem round-trips")
    ap.add_argument("--telemetry", default=None,
                    help="in --smoke, append the sampled memsnap "
                         "records to this JSONL")
    ap.add_argument("--report", default=None,
                    help="write the JSON report here")
    args = ap.parse_args(argv)

    if args.selfcheck:
        import jax
        ok, report = run_selfcheck()
        report["tool"] = "memwatch"
        report["platform"] = jax.default_backend()
        if args.report:
            with open(args.report, "w") as f:
                json.dump(report, f, indent=2, sort_keys=True)
        if ok:
            print("memwatch selfcheck OK: pressure specimen caught "
                  "hbm_pressure + kv_thrash by name, clean ledger "
                  "reconciled and silent, postmortem round-trips")
        return 0 if ok else 9

    if args.smoke:
        records, problems = run_smoke(telemetry=args.telemetry)
        if problems:
            print(f"memwatch: {len(problems)} finding(s)")
            for p in problems:
                print(f"  {p}", file=sys.stderr)
            return 14
        print(f"memwatch: {len(records)} ledger sample(s) clean")
        return 0

    if not args.path:
        ap.print_help()
        return 1

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_check

    records = _read(args.path)
    if args.postmortem:
        pms = [r for r in records if r.get("event") == "postmortem"]
        if not pms:
            print(f"memwatch: no postmortem record in {args.path} — "
                  "nothing to diagnose", file=sys.stderr)
            return 14
        print_postmortem(pms[-1])
        return 0

    problems = _validate_records(records, trace_check, args.path) \
        if records else [f"{args.path}: no memsnap records"]
    fired = _rule_findings(records)
    print_timeline(records)
    for a in fired:
        print(f"ANOMALY {a.kind}: {a.message}")
    problems += [f"{args.path}: {a.kind} fired" for a in fired]
    if problems:
        print(f"memwatch: {len(problems)} finding(s)")
        for p in problems:
            print(f"  {p}", file=sys.stderr)
        return 14
    print(f"memwatch: {len(records)} record(s) clean in {args.path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
