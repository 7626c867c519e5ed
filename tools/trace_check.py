#!/usr/bin/env python
"""Validate a telemetry metrics JSONL + Chrome trace pair.

CI gate for the flight-recorder schema (paddle_tpu/telemetry): checks
that every JSONL record parses and carries the required step/phase
fields with finite values, that the Chrome trace is valid trace JSON
(traceEvents with ph/ts/dur/pid), and — when both are given — that the
trace's step spans are consistent with the JSONL step count. Used by
tests/test_telemetry.py and runnable standalone:

    python tools/trace_check.py run.jsonl [trace.json]

Exit 0 when valid; exit 7 with a problem listing otherwise (distinct
from pytest's own codes so CI logs disambiguate).
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def check_metrics_jsonl(path):
    """Returns (n_records, n_step_records, n_compile_records,
    n_ckpt_records, n_plan_records, n_elastic_records,
    n_serving_records, n_kernel_records, n_reqtrace_records,
    n_kernelbench_records, n_thread_lint_records, n_commbench_records,
    n_memsnap_records, n_fleet_records, problems). Positional
    consumers should
    prefer check_pair's named stats dict — this tuple GROWS when a new
    record kind lands (kerneldoctor's selfcheck was silently broken by
    exactly such an append once).

    An empty or record-free metrics file is a FAILURE, not a vacuous
    pass: a validator that says OK about a file no step ever wrote
    would green-light a run whose telemetry silently broke."""
    from paddle_tpu.telemetry.sink import validate_step_record

    problems = []
    records = []
    try:
        if os.path.getsize(path) == 0:
            return 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, [
                f"{path}: empty metrics file (0 bytes): no step was "
                "ever recorded"]
        with open(path) as f:
            for i, line in enumerate(f):
                line = line.strip()
                if not line:
                    continue
                try:
                    records.append(json.loads(line))
                except json.JSONDecodeError as e:
                    problems.append(f"{path}:{i + 1}: not JSON: {e}")
    except OSError as e:
        return 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, [
            f"{path}: unreadable: {e}"]
    if not records:
        problems.append(f"{path}: no records")
    for i, rec in enumerate(records):
        for p in validate_step_record(rec):
            problems.append(f"{path}:{i + 1}: {p}")
    problems += check_compile_records(records, path)
    problems += check_ckpt_records(records, path)
    problems += check_plan_records(records, path)
    problems += check_elastic_records(records, path)
    problems += check_moe_records(records, path)
    problems += check_serving_records(records, path)
    problems += check_kernel_records(records, path)
    problems += check_reqtrace_records(records, path)
    problems += check_kernelbench_records(records, path)
    problems += check_thread_lint_records(records, path)
    problems += check_commbench_records(records, path)
    problems += check_memsnap_records(records, path)
    problems += check_fleet_records(records, path)
    n_steps = sum(1 for r in records
                  if isinstance(r, dict) and r.get("kind") == "step")
    n_compiles = sum(1 for r in records
                     if isinstance(r, dict) and r.get("kind") == "compile")
    n_ckpt = sum(1 for r in records
                 if isinstance(r, dict) and r.get("kind") == "ckpt")
    n_plan = sum(1 for r in records
                 if isinstance(r, dict) and r.get("kind") == "plan")
    n_elastic = sum(1 for r in records
                    if isinstance(r, dict) and r.get("kind") == "elastic")
    n_serving = sum(1 for r in records
                    if isinstance(r, dict) and r.get("kind") == "serving")
    n_kernel = sum(1 for r in records
                   if isinstance(r, dict)
                   and r.get("kind") == "kernel_lint")
    n_reqtrace = sum(1 for r in records
                     if isinstance(r, dict)
                     and r.get("kind") == "reqtrace")
    n_kernelbench = sum(1 for r in records
                        if isinstance(r, dict)
                        and r.get("kind") == "kernelbench")
    n_thread_lint = sum(1 for r in records
                        if isinstance(r, dict)
                        and r.get("kind") == "thread_lint")
    n_commbench = sum(1 for r in records
                      if isinstance(r, dict)
                      and r.get("kind") == "commbench")
    n_memsnap = sum(1 for r in records
                    if isinstance(r, dict)
                    and r.get("kind") == "memsnap")
    n_fleet = sum(1 for r in records
                  if isinstance(r, dict) and r.get("kind") == "fleet")
    return (len(records), n_steps, n_compiles, n_ckpt, n_plan,
            n_elastic, n_serving, n_kernel, n_reqtrace, n_kernelbench,
            n_thread_lint, n_commbench, n_memsnap, n_fleet, problems)


def check_compile_records(records, path):
    """Cross-record rules for compile events (telemetry.compile_obs):

    - per signature family AND rank (a merged multi-rank file carries
      every rank's independent clock), steps must be monotonic
      non-decreasing;
    - every RECOMPILE (n_compiles > 1) must carry a non-empty cause —
      a compile ledger that cannot say WHY it recompiled is exactly the
      black box the observatory exists to remove;
    - a family recompiling with zero causes anywhere fails even if the
      producer forgot the n_compiles ordinal.

    Untracked records (jax.monitoring stream — no signature, so no
    cause is derivable) are exempt from the cause rules AND from the
    monotonicity rule: their step counter is per-observatory-session,
    and a rolling telemetry file legitimately appends several sessions,
    each restarting the shared '(jax)' family at step 0.
    """
    problems = []
    last_step = {}
    fam_counts = {}
    fam_causes = {}
    for i, rec in enumerate(records):
        if not isinstance(rec, dict) or rec.get("kind") != "compile":
            continue
        fam = rec.get("fn", "?")
        step = rec.get("step")
        if isinstance(step, (int, float)) and not rec.get("untracked"):
            clock = (rec.get("rank", 0), fam)
            prev = last_step.get(clock)
            if prev is not None and step < prev:
                problems.append(
                    f"{path}:{i + 1}: compile record for {fam!r} "
                    f"(rank {clock[0]}) at step {step} after one at "
                    f"step {prev} (non-monotonic)")
            last_step[clock] = step
        if rec.get("untracked"):
            continue
        fam_counts[fam] = fam_counts.get(fam, 0) + 1
        if rec.get("cause"):
            fam_causes[fam] = fam_causes.get(fam, 0) + 1
        if rec.get("n_compiles", 1) > 1 and not rec.get("cause"):
            problems.append(
                f"{path}:{i + 1}: recompile of {fam!r} "
                f"(n_compiles={rec.get('n_compiles')}) carries no cause")
    for fam, n in fam_counts.items():
        if n > 1 and fam_causes.get(fam, 0) == 0:
            problems.append(
                f"{path}: {n} compile events for {fam!r} but no cause "
                "on any of them — the recompile diff is missing")
    return problems


def check_ckpt_records(records, path):
    """Cross-record rules for checkpoint events (paddle_tpu.resilience;
    per-record schema/vocabulary lives in sink.validate_step_record):

    - per rank, COMMIT steps must be monotonic non-decreasing — the
      atomic-commit protocol cannot legally land step 5 after step 9
      within one ledger;
    - every commit must be preceded by a save event for the same step
      and rank — a commit the ledger never saw started is a producer
      bug (or a doctored file);
    - a restore/fallback must reference a step some commit in the file
      landed, when any commits are present at all (a restore-only
      ledger — a resumed process reading an older run's checkpoints —
      is legitimate).
    """
    problems = []
    last_commit = {}
    saved = set()
    committed = set()
    any_commits = False
    for i, rec in enumerate(records):
        if not isinstance(rec, dict) or rec.get("kind") != "ckpt":
            continue
        rank = rec.get("rank", 0)
        step = rec.get("step")
        event = rec.get("event")
        if not isinstance(step, (int, float)):
            continue          # schema validation already flagged it
        if event == "save":
            saved.add((rank, step))
        elif event == "commit":
            any_commits = True
            committed.add((rank, step))
            if (rank, step) not in saved:
                problems.append(
                    f"{path}:{i + 1}: ckpt commit at step {step} "
                    f"(rank {rank}) with no preceding save event")
            prev = last_commit.get(rank)
            if prev is not None and step < prev:
                problems.append(
                    f"{path}:{i + 1}: ckpt commit at step {step} after "
                    f"one at step {prev} (rank {rank}, non-monotonic)")
            last_commit[rank] = step
        elif event in ("restore", "fallback") and any_commits and \
                (rank, step) not in committed:
            problems.append(
                f"{path}:{i + 1}: ckpt {event} references step {step} "
                f"(rank {rank}) that no commit in this ledger landed")
    return problems


# plan-record projection drift threshold — the same 15% bound the
# compile observatory's hbm_projection_drift rule uses (PR 4): past it
# the planner's feasibility decisions were made on fiction
PLAN_DRIFT_FRAC = 0.15


def check_plan_records(records, path):
    """Cross-record rules for auto-sharding plan records (kind=plan,
    paddle_tpu.planner; per-record schema lives in
    sink.validate_step_record):

    - the chosen layout's axis product must equal n_chips when both
      are present — a plan whose mesh does not multiply out to its
      chip count never factorized anything;
    - when both projected_hbm_bytes and measured_hbm_bytes are present
      (the compile observatory measured the chosen layout), they must
      agree within PLAN_DRIFT_FRAC — a plan whose projection drifted
      >15% from what XLA actually allocated chose its layout on
      numbers that were wrong, and the search must be re-run with the
      measured calibration.
    """
    problems = []
    for i, rec in enumerate(records):
        if not isinstance(rec, dict) or rec.get("kind") != "plan":
            continue
        chosen = rec.get("chosen")
        n_chips = rec.get("n_chips")
        if isinstance(chosen, dict) and isinstance(n_chips, int):
            prod = 1
            for axis in ("dp", "pp", "mp", "sp", "ep"):
                v = chosen.get(axis, 1)
                prod *= v if isinstance(v, int) and v > 0 else 1
            if prod != n_chips:
                problems.append(
                    f"{path}:{i + 1}: chosen layout multiplies to "
                    f"{prod} chips but the plan claims n_chips="
                    f"{n_chips}")
        projected = rec.get("projected_hbm_bytes")
        measured = rec.get("measured_hbm_bytes")
        if isinstance(projected, (int, float)) and \
                isinstance(measured, (int, float)) and measured > 0:
            drift = abs(measured - projected) / float(measured)
            if drift > PLAN_DRIFT_FRAC:
                problems.append(
                    f"{path}:{i + 1}: plan projection drift "
                    f"{drift * 100:.1f}% (projected "
                    f"{projected / 2**30:.2f} GiB vs measured "
                    f"{measured / 2**30:.2f} GiB) exceeds "
                    f"{PLAN_DRIFT_FRAC * 100:.0f}% — re-plan with "
                    "calibration from the compile observatory")
    return problems


def check_elastic_records(records, path):
    """Cross-record rules for elastic-membership events (kind=elastic,
    distributed.elastic ElasticCoordinator + resilience.reshard;
    per-record schema lives in sink.validate_step_record):

    - a declared_dead for host H requires a PRECEDING heartbeat_miss
      for the same host — the protocol declares nobody dead without
      recorded misses (an insta-declaration means the detector's
      threshold accounting is broken or the ledger was doctored);
    - a reshard_restore must reference a step some ckpt commit in the
      file landed, when any commits are present at all (a reshard from
      another run's directory is legitimate in a restore-only ledger)
      — restoring an uncommitted step would mean the drain protocol
      lost the atomic-commit guarantee; the both-layouts requirement
      is per-record (sink validation);
    - a relaunch requires a preceding replan — exiting 101 without a
      recorded plan for the surviving world is a coordinator that
      decided nothing yet relaunched anyway.
    """
    problems = []
    missed_hosts = set()
    committed = set()
    any_commits = False
    any_replan = False
    for i, rec in enumerate(records):
        if not isinstance(rec, dict):
            continue
        kind = rec.get("kind")
        if kind == "ckpt" and rec.get("event") == "commit" and \
                isinstance(rec.get("step"), (int, float)):
            any_commits = True
            committed.add(rec["step"])
            continue
        if kind != "elastic":
            continue
        event = rec.get("event")
        host = rec.get("host")
        if event == "heartbeat_miss":
            missed_hosts.add(host)
        elif event == "declared_dead":
            if host not in missed_hosts:
                problems.append(
                    f"{path}:{i + 1}: host {host!r} declared dead with "
                    "no preceding heartbeat_miss record")
        elif event == "replan":
            any_replan = True
        elif event == "relaunch":
            if not any_replan:
                problems.append(
                    f"{path}:{i + 1}: elastic relaunch with no "
                    "preceding replan record")
        elif event == "reshard_restore":
            step = rec.get("step")
            if any_commits and isinstance(step, (int, float)) and \
                    step not in committed:
                problems.append(
                    f"{path}:{i + 1}: reshard_restore references step "
                    f"{step} that no ckpt commit in this ledger landed")
    return problems


def check_moe_records(records, path):
    """Cross-record rules for MoE routing-health fields on step records
    (paddle_tpu.moe.stats; per-record bounds — dropped_frac in [0, 1],
    non-negativity — live in sink.validate_step_record):

    - moe_entropy must not exceed log(moe_num_experts): the expert-load
      entropy of an E-way categorical is bounded by log E, so a record
      above the bound means the producer's expert count and its entropy
      came from different distributions (or the ledger was doctored);
    - a record carrying any moe_* health field must also carry
      moe_num_experts — an entropy with no expert count can never be
      bounds-checked, which defeats the point of recording it.
    """
    import math

    problems = []
    for i, rec in enumerate(records):
        if not isinstance(rec, dict) or rec.get("kind") != "step":
            continue
        has_moe = any(rec.get(k) is not None
                      for k in ("moe_entropy", "moe_dropped_frac",
                                "moe_overflow", "moe_aux_loss"))
        if not has_moe:
            continue
        n_exp = rec.get("moe_num_experts")
        if not isinstance(n_exp, int) or n_exp < 1:
            problems.append(
                f"{path}:{i + 1}: step record carries moe.* health "
                "fields but no moe_num_experts — the entropy bound "
                "cannot be checked")
            continue
        ent = rec.get("moe_entropy")
        bound = math.log(n_exp)
        if isinstance(ent, (int, float)) and ent > bound + 1e-6:
            problems.append(
                f"{path}:{i + 1}: moe_entropy {ent} exceeds "
                f"log(num_experts={n_exp}) = {bound:.6f} — the "
                "expert-load distribution and the expert count disagree")
    return problems


# kernel_lint record thresholds — mirror analysis/kernel_lint.py's
# COST_DRIFT_FRAC/COST_FLOPS_FLOOR (the KN503 rule) the same way
# PLAN_DRIFT_FRAC mirrors the PR-4 hbm rule: the ledger validator must
# agree with the tool that wrote the ledger about what "drifted" means
KERNEL_DRIFT_FRAC = 0.25
KERNEL_FLOPS_FLOOR = 1_000_000


def check_kernel_records(records, path):
    """Cross-record rules for Kernel Doctor results (kind=kernel_lint,
    analysis/kernel_lint via tools/kerneldoctor.py; per-record schema —
    findings list shape, KN rule vocabulary, n_findings agreement —
    lives in sink.validate_step_record):

    - a record whose own numbers show a VMEM projection over its
      recorded budget must carry a KN502 finding — a ledger that
      writes down the overflow but claims the kernel is clean is
      doctored or the lint that produced it never looked;
    - a record whose declared-vs-counted FLOPs drift exceeds the KN503
      threshold must carry a KN503 finding, same reasoning;
    - the same kernel must not appear both clean and with findings in
      one ledger (rank-disambiguated): one of the two runs is stale.
    """
    problems = []
    verdicts = {}
    for i, rec in enumerate(records):
        if not isinstance(rec, dict) or rec.get("kind") != "kernel_lint":
            continue
        rules = {f.get("rule") for f in rec.get("findings", [])
                 if isinstance(f, dict)}
        vmem = rec.get("vmem_bytes")
        budget = rec.get("vmem_budget")
        if isinstance(vmem, (int, float)) and \
                isinstance(budget, (int, float)) and vmem > budget \
                and "KN502" not in rules:
            problems.append(
                f"{path}:{i + 1}: kernel {rec.get('kernel')!r} records "
                f"vmem_bytes {vmem} over its budget {budget} with no "
                "KN502 finding — the projection and the verdict "
                "disagree")
        d = rec.get("flops_declared")
        c = rec.get("flops_counted")
        if isinstance(d, (int, float)) and isinstance(c, (int, float)):
            drift = abs(d - c)
            if drift > max(KERNEL_DRIFT_FRAC * max(d, c),
                           KERNEL_FLOPS_FLOOR) and "KN503" not in rules:
                problems.append(
                    f"{path}:{i + 1}: kernel {rec.get('kernel')!r} "
                    f"records declared flops {d} vs counted {c} "
                    f"(drift past {KERNEL_DRIFT_FRAC * 100:.0f}%) with "
                    "no KN503 finding")
        key = (rec.get("rank", 0), rec.get("kernel"))
        clean = rec.get("n_findings") == 0
        if key in verdicts and verdicts[key][1] != clean:
            problems.append(
                f"{path}:{i + 1}: kernel {rec.get('kernel')!r} appears "
                f"both clean and with findings (line "
                f"{verdicts[key][0]}) — one verdict is stale")
        verdicts[key] = (i + 1, clean)
    return problems


def check_thread_lint_records(records, path):
    """Cross-record rules for Concurrency Doctor results
    (kind=thread_lint, analysis/threadlint + analysis/lockwatch via
    tools/threaddoctor.py; per-record schema — source vocabulary, TH
    rule vocabulary, n_findings/n_edges agreement, edge-triple shape —
    lives in sink.validate_step_record):

    - a source=lockwatch record whose OWN edge list contains a cycle
      must carry a TH602 finding — a witness that writes down the
      circular acquisition order but claims the run was clean is
      doctored or never looked at its own graph;
    - when the same file carries a source=static record (the analyzer's
      nested-acquisition graph), every observed lockwatch edge must be
      a subgraph edge of the static union: an observed edge the
      analyzer never derived means a real acquisition path it is blind
      to (un-annotated lock, manual .acquire(), reflection) and the
      static TH602 verdict cannot be trusted.
    """
    from paddle_tpu.analysis.lockwatch import find_cycles

    problems = []
    static_edges = set()
    has_static = False
    for rec in records:
        if not isinstance(rec, dict) or rec.get("kind") != "thread_lint":
            continue
        if rec.get("source") == "static":
            has_static = True
            for e in rec.get("edges", []):
                if isinstance(e, list) and len(e) == 3:
                    static_edges.add((e[0], e[1]))
    for i, rec in enumerate(records):
        if not isinstance(rec, dict) or rec.get("kind") != "thread_lint":
            continue
        if rec.get("source") != "lockwatch":
            continue
        edges = [e for e in rec.get("edges", [])
                 if isinstance(e, list) and len(e) == 3]
        adj = {}
        for a, b, _count in edges:
            adj.setdefault(a, set()).add(b)
        cycles = find_cycles(adj)
        rules = {f.get("rule") for f in rec.get("findings", [])
                 if isinstance(f, dict)}
        if cycles and "TH602" not in rules:
            loops = ["->".join(c) for c in cycles]
            problems.append(
                f"{path}:{i + 1}: lockwatch record's own edges contain "
                f"lock-order cycle(s) {loops} but carry no TH602 "
                "finding — the observed graph and the verdict disagree")
        if has_static:
            for a, b, _count in edges:
                if (a, b) not in static_edges:
                    problems.append(
                        f"{path}:{i + 1}: observed lock-order edge "
                        f"{a} -> {b} is absent from the static graph "
                        "in this file — the analyzer is blind to a "
                        "real acquisition path")
    return problems


# the serving-lifecycle event families (paddle_tpu.serving; per-record
# schema lives in sink.validate_step_record)
_SERVING_TERMINAL = ("finished", "failed", "cancelled", "expired")


def check_serving_records(records, path):
    """Cross-record rules for serving-lifecycle events (kind=serving,
    paddle_tpu.serving.ServingEngine + tools/serving_drill.py):

    - a SHED record must carry `queue_depth` — admission rejected a
      request, and a rejection the ledger cannot justify with the
      queue pressure it saw is unauditable;
    - a QUIESCE record must report zero `kv_blocks_used` — a quiesced
      engine (all requests terminal) holding blocks has LEAKED them
      (some terminal path dropped a request without releasing it);
    - quiesce `counts` must balance: admitted == finished + failed +
      cancelled + expired — a request that left the admission ledger
      without reaching exactly one terminal state is unaccounted work
      (a stream somewhere is hanging);
    - the quiesce counts must agree with the ledger's own per-event
      record tallies for that engine (when the ledger carries them) —
      a counts snapshot the records contradict is a doctored or
      half-written ledger;
    - a DEADLINE MISS is a failure of enforcement, not of the request:
      any admitted/finished record whose `queue_wait_ms` exceeds its
      recorded `queue_deadline_ms` means the scheduler ran a request
      it had promised to expire;
    - prefix-cache accounting (the copy-on-write sharing round) must
      be arithmetically possible: `prefix_hit_rate` in [0, 1] (it is
      tokens_saved / tokens_offered), `prefill_tokens_saved` never
      exceeding `prefill_tokens_offered` (the cache cannot save
      positions nobody asked to prefill), and a QUIESCE record must
      show ZERO `prefix_blocks_shared` — with every request terminal
      there is nobody left to share a block with, so a surviving
      shared reference is a dropped holder.
    """
    problems = []
    tallies = {}          # (rank, engine) -> {event: n}
    for i, rec in enumerate(records):
        if not isinstance(rec, dict) or rec.get("kind") != "serving":
            continue
        ev = rec.get("event")
        key = (rec.get("rank", 0), rec.get("engine"))
        if ev == "shed" and not isinstance(rec.get("queue_depth"),
                                           (int, float)):
            problems.append(
                f"{path}:{i + 1}: serving shed record carries no "
                "queue_depth — an admission rejection with no recorded "
                "queue pressure to justify it")
        if ev in ("admitted",) + _SERVING_TERMINAL:
            t = tallies.setdefault(key, {})
            t[ev] = t.get(ev, 0) + 1
        if ev in ("admitted", "finished"):
            qw = rec.get("queue_wait_ms")
            qd = rec.get("queue_deadline_ms")
            if isinstance(qw, (int, float)) and \
                    isinstance(qd, (int, float)) and qw > qd:
                what = "admitted" if ev == "admitted" \
                    else "run to completion"
                problems.append(
                    f"{path}:{i + 1}: deadline miss — request "
                    f"{rec.get('rid')} waited {qw}ms against a "
                    f"{qd}ms queue deadline yet was {what}: "
                    "queue-deadline enforcement is dead")
        ph = rec.get("prefix_hit_rate")
        if isinstance(ph, (int, float)) and not (0.0 <= ph <= 1.0):
            problems.append(
                f"{path}:{i + 1}: prefix_hit_rate {ph} outside [0, 1] "
                "— the hit accounting (tokens_saved / tokens_offered) "
                "is broken")
        saved = rec.get("prefill_tokens_saved")
        offered = rec.get("prefill_tokens_offered")
        if isinstance(saved, (int, float)) and \
                isinstance(offered, (int, float)) and saved > offered:
            problems.append(
                f"{path}:{i + 1}: prefill_tokens_saved {saved} > "
                f"prefill_tokens_offered {offered} — the prefix cache "
                "claims to have saved positions nobody offered")
        if ev == "quiesce":
            shared = rec.get("prefix_blocks_shared")
            if isinstance(shared, (int, float)) and shared > 0:
                problems.append(
                    f"{path}:{i + 1}: {int(shared)} KV block(s) still "
                    "SHARED (refs>1) at quiesce — every request is "
                    "terminal, so a surviving shared reference means a "
                    "holder was dropped without releasing it")
            kv = rec.get("kv_blocks_used")
            if isinstance(kv, (int, float)) and kv > 0:
                problems.append(
                    f"{path}:{i + 1}: {int(kv)} KV block(s) still "
                    "allocated at quiesce — the pool leaked (a "
                    "terminal path dropped a request without "
                    "releasing its blocks)")
            counts = rec.get("counts")
            if isinstance(counts, dict):
                adm = counts.get("admitted", 0)
                term = sum(counts.get(k, 0) for k in _SERVING_TERMINAL)
                if adm != term:
                    problems.append(
                        f"{path}:{i + 1}: quiesce counts don't "
                        f"balance: admitted {adm} != finished+failed+"
                        f"cancelled+expired {term} — requests "
                        "unaccounted for at quiesce")
                t = tallies.get(key, {})
                if t.get("admitted"):
                    for evname in ("admitted",) + _SERVING_TERMINAL:
                        if t.get(evname, 0) != counts.get(evname, 0):
                            problems.append(
                                f"{path}:{i + 1}: ledger carries "
                                f"{t.get(evname, 0)} {evname!r} "
                                f"record(s) but the quiesce counts "
                                f"claim {counts.get(evname, 0)} — the "
                                "records and the snapshot disagree")
    return problems


# request-trace decomposition tolerance: span durations must sum to
# the recorded end-to-end latency within 1% (plus a small absolute
# floor for the per-span 4-decimal ms rounding) — the spans TILE the
# request's wall-clock life by construction (telemetry.reqtrace), so
# any bigger gap means the producer dropped an event, appended out of
# order, or the record was doctored
TRACE_SUM_TOL_FRAC = 0.01
TRACE_SUM_TOL_ABS_MS = 0.5


def check_reqtrace_records(records, path):
    """Cross-record rules for per-request trace timelines
    (kind=reqtrace, telemetry.reqtrace RequestTracer; per-record schema
    — span-kind vocabulary, non-negative times, outcome vocabulary —
    lives in sink.validate_step_record):

    - the LATENCY-DECOMPOSITION invariant: span durations must sum to
      `e2e_ms` within TRACE_SUM_TOL_FRAC — a timeline that does not
      account for the latency it claims to explain attributes nothing;
    - span starts must be monotonic non-decreasing (the spans tile the
      wall clock; an out-of-order span means two clocks were mixed);
    - a trace that did ENGINE WORK (prefill_chunk/decode spans) or
      claims outcome 'finished' must carry an `admit` span — a request
      cannot be served out of a queue it was never admitted from
      (finalize-without-admit is a producer bug or a doctored ledger);
    - every non-shed trace must end in a `finalize` span — a trace
      with no terminal transition is a request the engine dropped.
    """
    problems = []
    for i, rec in enumerate(records):
        if not isinstance(rec, dict) or rec.get("kind") != "reqtrace":
            continue
        spans = rec.get("spans")
        if not isinstance(spans, list) or not spans:
            continue              # schema validation already flagged it
        kinds = {sp.get("kind") for sp in spans
                 if isinstance(sp, dict)}
        total = 0.0
        prev_t0 = None
        for j, sp in enumerate(spans):
            if not isinstance(sp, dict):
                continue
            d = sp.get("dur_ms")
            if isinstance(d, (int, float)) and d == d and d >= 0:
                total += float(d)
            t0 = sp.get("t0_ms")
            if isinstance(t0, (int, float)):
                if prev_t0 is not None and t0 < prev_t0 - 1e-6:
                    problems.append(
                        f"{path}:{i + 1}: reqtrace span {j} "
                        f"({sp.get('kind')}) starts at {t0}ms before "
                        f"the previous span's {prev_t0}ms — the "
                        "timeline is out of order")
                prev_t0 = t0
        e2e = rec.get("e2e_ms")
        if isinstance(e2e, (int, float)) and e2e >= 0:
            tol = max(TRACE_SUM_TOL_FRAC * e2e, TRACE_SUM_TOL_ABS_MS)
            if abs(total - e2e) > tol:
                problems.append(
                    f"{path}:{i + 1}: reqtrace decomposition broken — "
                    f"request {rec.get('rid')}'s spans sum to "
                    f"{total:.4f}ms but e2e_ms is {e2e}ms (tolerance "
                    f"{tol:.4f}ms): the timeline does not account for "
                    "the latency it claims to explain")
        outcome = rec.get("outcome")
        if ("admit" not in kinds
                and (kinds & {"prefill_chunk", "decode"}
                     or outcome == "finished")):
            problems.append(
                f"{path}:{i + 1}: reqtrace for request {rec.get('rid')} "
                f"({outcome}) did engine work with no admit span — a "
                "request cannot be served out of a queue it was never "
                "admitted from")
        if outcome != "shed" and "finalize" not in kinds:
            problems.append(
                f"{path}:{i + 1}: reqtrace for request {rec.get('rid')} "
                f"({outcome}) carries no finalize span — a trace with "
                "no terminal transition is a dropped request")
    return problems


# speedup must agree with the two timings it claims to summarize
KERNELBENCH_SPEEDUP_TOL = 0.05


def check_kernelbench_records(records, path):
    """Cross-rules over kernel-observatory measurement records
    (kind='kernelbench', telemetry/kernel_obs via tools/kernellab.py).
    The schema basics (non-negative ms, roofline fractions in [0, 1])
    live in sink.validate_step_record; here the claims that span
    fields or records:

    - a speedup claim requires BOTH timings (kernel_ms and
      fallback_ms) and must equal fallback_ms / kernel_ms within 5% —
      a ratio the ledger cannot reproduce is a doctored row;
    - a db_update event must reference, by db_key, a measured row
      (event measure/tune) present in the SAME file — the DB may only
      roll forward from measurements the ledger shows.
    """
    problems = []
    measured_keys = set()
    for r in records:
        if isinstance(r, dict) and r.get("kind") == "kernelbench" \
                and r.get("event") in (None, "measure", "tune") \
                and r.get("db_key"):
            measured_keys.add(r["db_key"])
    for i, rec in enumerate(records):
        if not isinstance(rec, dict) or rec.get("kind") != "kernelbench":
            continue
        sp = rec.get("speedup")
        km = rec.get("kernel_ms")
        fm = rec.get("fallback_ms")
        if sp is not None:
            if not isinstance(km, (int, float)) \
                    or not isinstance(fm, (int, float)):
                problems.append(
                    f"{path}:{i + 1}: kernelbench {rec.get('kernel')} "
                    f"claims speedup {sp} without both timings "
                    "(kernel_ms and fallback_ms) — a ratio with no "
                    "numerator or denominator on the ledger")
            elif km > 0 and isinstance(sp, (int, float)) and sp == sp:
                want = fm / km
                if abs(sp - want) > KERNELBENCH_SPEEDUP_TOL \
                        * max(abs(want), 1e-9):
                    problems.append(
                        f"{path}:{i + 1}: kernelbench "
                        f"{rec.get('kernel')} speedup {sp:.4f} does "
                        f"not match fallback_ms/kernel_ms = "
                        f"{want:.4f} — the ratio and its inputs "
                        "disagree")
        if rec.get("event") == "db_update":
            key = rec.get("db_key")
            if not key:
                problems.append(
                    f"{path}:{i + 1}: kernelbench db_update for "
                    f"{rec.get('kernel')} carries no db_key — an "
                    "update that references nothing")
            elif key not in measured_keys:
                problems.append(
                    f"{path}:{i + 1}: kernelbench db_update "
                    f"references db_key {key!r} but no measured "
                    "(measure/tune) record in this file carries it — "
                    "the DB may only roll forward from measurements "
                    "the ledger shows")
    return problems


# how far achieved_bw / bw_frac / predicted_ms may drift from the
# values recomputable from their own inputs on the same record
COMMBENCH_DERIVED_TOL = 0.05


def check_commbench_records(records, path):
    """Cross-rules over mesh-observatory measurement records
    (kind='commbench', telemetry/comm_obs via tools/commlab.py). The
    schema basics (non-negative ms, bw_frac in [0, 1], positive
    axis_size/payload) live in sink.validate_step_record; here the
    claims that must be recomputable from the record's own fields:

    - achieved_bw must equal wire_bytes / (time_ms / 1e3) within 5% —
      a bandwidth the ledger cannot reproduce is a doctored row;
    - bw_frac must equal min(1, achieved_bw / peak_bw) within 5%, and
      requires BOTH inputs on the record;
    - predicted_ms must equal wire_bytes / peak_bw * 1e3 within 5% —
      the analytic floor the calibration ratio divides by must match
      the peak the record claims to have been priced against;
    - wire_bytes must lie in (0, 2 x payload_bytes] — no wire-fraction
      convention (comm_audit: (n-1)/n, full, or ring 2(n-1)/n) moves
      more than twice the operand;
    - a db_update event must reference, by db_key, a measured row in
      the SAME file — the DB may only roll forward from measurements
      the ledger shows (the kernelbench rule).
    """
    problems = []
    measured_keys = set()
    for r in records:
        if isinstance(r, dict) and r.get("kind") == "commbench" \
                and r.get("event") in (None, "measure") \
                and r.get("db_key"):
            measured_keys.add(r["db_key"])

    def _num(v):
        return isinstance(v, (int, float)) and v == v

    for i, rec in enumerate(records):
        if not isinstance(rec, dict) or rec.get("kind") != "commbench":
            continue
        label = f"{rec.get('op')} over {rec.get('axis')!r}"
        tm, wb = rec.get("time_ms"), rec.get("wire_bytes")
        abw, pbw = rec.get("achieved_bw"), rec.get("peak_bw")
        frac, pm = rec.get("bw_frac"), rec.get("predicted_ms")
        payload = rec.get("payload_bytes")
        if _num(wb) and isinstance(payload, int) and payload > 0 \
                and not 0.0 < wb <= 2.0 * payload:
            problems.append(
                f"{path}:{i + 1}: commbench {label} claims wire_bytes "
                f"{wb} outside (0, 2 x payload_bytes {payload}] — no "
                "wire-fraction convention moves that")
        if _num(abw):
            if not _num(tm) or tm <= 0 or not _num(wb) or wb <= 0:
                problems.append(
                    f"{path}:{i + 1}: commbench {label} claims "
                    f"achieved_bw {abw} without positive time_ms and "
                    "wire_bytes — a bandwidth with no inputs on the "
                    "ledger")
            else:
                want = wb / (tm / 1e3)
                if abs(abw - want) > COMMBENCH_DERIVED_TOL * want:
                    problems.append(
                        f"{path}:{i + 1}: commbench {label} achieved_bw "
                        f"{abw:.4g} does not match wire_bytes/"
                        f"(time_ms/1e3) = {want:.4g} — the claim and "
                        "its inputs disagree")
        if _num(frac):
            if not _num(abw) or not _num(pbw) or pbw <= 0:
                problems.append(
                    f"{path}:{i + 1}: commbench {label} claims bw_frac "
                    f"{frac} without achieved_bw and peak_bw — a "
                    "fraction with no numerator or denominator")
            else:
                want = min(1.0, abw / pbw)
                if abs(frac - want) > COMMBENCH_DERIVED_TOL \
                        * max(want, 1e-9):
                    problems.append(
                        f"{path}:{i + 1}: commbench {label} bw_frac "
                        f"{frac:.4g} does not match min(1, achieved/"
                        f"peak) = {want:.4g}")
        if _num(pm) and _num(wb) and _num(pbw) and pbw > 0:
            want = wb / pbw * 1e3
            if want > 0 and abs(pm - want) > COMMBENCH_DERIVED_TOL * want:
                problems.append(
                    f"{path}:{i + 1}: commbench {label} predicted_ms "
                    f"{pm:.4g} does not match wire_bytes/peak_bw = "
                    f"{want:.4g} — the analytic floor and the peak it "
                    "claims disagree")
        if rec.get("event") == "db_update":
            key = rec.get("db_key")
            if not key:
                problems.append(
                    f"{path}:{i + 1}: commbench db_update for {label} "
                    "carries no db_key — an update that references "
                    "nothing")
            elif key not in measured_keys:
                problems.append(
                    f"{path}:{i + 1}: commbench db_update references "
                    f"db_key {key!r} but no measured record in this "
                    "file carries it — the DB may only roll forward "
                    "from measurements the ledger shows")
    return problems


# how far kv_occupancy / kv_cache_share may drift from the values
# recomputable from the block counts on the same record (the counts
# are exact ints; the fractions are rounded to 6 places on write)
MEMSNAP_DERIVED_TOL = 1e-4


def check_memsnap_records(records, path):
    """Cross-rules over memory-observatory ledger records
    (kind='memsnap', telemetry/mem_obs via tools/memwatch.py). The
    schema basics (non-negative bytes, fractions in [0, 1], postmortem
    forensics completeness) live in sink.validate_step_record; here
    the claims that must be recomputable from the record's own fields:

    - when every attribution bucket is present, the buckets must sum
      EXACTLY to total_bytes — the ledger walk assigns each live array
      to exactly one bucket, so a mismatch means bytes were invented
      or dropped after the walk;
    - headroom_bytes must equal max(0, hbm_budget_bytes - total_bytes)
      and requires the budget on the record — headroom against an
      undeclared budget is a claim with no denominator;
    - the KV block census must tile: held + free + cached ==
      blocks_total (every pool block is in exactly one of the three
      states — BlockPool's own invariant, re-proved per record);
    - kv_occupancy must equal (held + cached) / blocks_total and
      kv_cache_share must equal cached / blocks_total, each requiring
      its counts on the record;
    - the per-class eviction/admission breakdowns, when present, must
      sum to the cumulative kv_evictions / kv_admissions counters;
    - a postmortem's top_arrays bytes must each be <= total_bytes — a
      suspect larger than the whole ledger is a fabricated suspect.
    """
    problems = []

    def _num(v):
        return isinstance(v, (int, float)) and v == v

    buckets = ("params_bytes", "opt_state_bytes", "kv_bytes",
               "workspace_bytes", "other_bytes")
    for i, rec in enumerate(records):
        if not isinstance(rec, dict) or rec.get("kind") != "memsnap":
            continue
        label = f"memsnap step {rec.get('step')}"
        total = rec.get("total_bytes")
        vals = [rec.get(k) for k in buckets]
        if _num(total) and all(_num(v) for v in vals):
            bsum = sum(vals)
            if bsum != total:
                problems.append(
                    f"{path}:{i + 1}: {label} buckets sum to {bsum} "
                    f"but total_bytes claims {total} — the ledger walk "
                    "assigns every array to exactly one bucket, so "
                    "bytes were invented or dropped after the walk")
        head = rec.get("headroom_bytes")
        budget = rec.get("hbm_budget_bytes")
        if _num(head):
            if not _num(budget) or not _num(total):
                problems.append(
                    f"{path}:{i + 1}: {label} claims headroom_bytes "
                    f"{head} without hbm_budget_bytes and total_bytes "
                    "— headroom against an undeclared budget")
            elif head != max(0, budget - total):
                problems.append(
                    f"{path}:{i + 1}: {label} headroom_bytes {head} "
                    f"does not match max(0, budget {budget} - total "
                    f"{total}) = {max(0, budget - total)}")
        nt = rec.get("kv_blocks_total")
        nh, nf, nc = (rec.get("kv_blocks_held"),
                      rec.get("kv_blocks_free"),
                      rec.get("kv_blocks_cached"))
        counts_ok = all(isinstance(v, int) for v in (nt, nh, nf, nc))
        if counts_ok and nh + nf + nc != nt:
            problems.append(
                f"{path}:{i + 1}: {label} KV census does not tile: "
                f"held {nh} + free {nf} + cached {nc} != total {nt} — "
                "every pool block is in exactly one state")
        occ = rec.get("kv_occupancy")
        if _num(occ):
            if not counts_ok or nt <= 0:
                problems.append(
                    f"{path}:{i + 1}: {label} claims kv_occupancy "
                    f"{occ} without a positive block census — a "
                    "fraction with no counts behind it")
            else:
                want = min(1.0, (nh + nc) / nt)
                if abs(occ - want) > MEMSNAP_DERIVED_TOL:
                    problems.append(
                        f"{path}:{i + 1}: {label} kv_occupancy "
                        f"{occ:.6g} does not match (held + cached)/"
                        f"total = {want:.6g}")
        share = rec.get("kv_cache_share")
        if _num(share):
            if not counts_ok or nt <= 0:
                problems.append(
                    f"{path}:{i + 1}: {label} claims kv_cache_share "
                    f"{share} without a positive block census")
            else:
                want = min(1.0, nc / nt)
                if abs(share - want) > MEMSNAP_DERIVED_TOL:
                    problems.append(
                        f"{path}:{i + 1}: {label} kv_cache_share "
                        f"{share:.6g} does not match cached/total = "
                        f"{want:.6g}")
        for by_key, cum_key in (("evictions_by_class", "kv_evictions"),
                                ("admissions_by_class",
                                 "kv_admissions")):
            by = rec.get(by_key)
            cum = rec.get(cum_key)
            if isinstance(by, dict) and by and isinstance(cum, int):
                bsum = sum(v for v in by.values()
                           if isinstance(v, int))
                if bsum != cum:
                    problems.append(
                        f"{path}:{i + 1}: {label} {by_key} sums to "
                        f"{bsum} but {cum_key} claims {cum} — the "
                        "per-class breakdown and the cumulative "
                        "counter disagree")
        if rec.get("event") == "postmortem" and _num(total):
            for t in rec.get("top_arrays") or []:
                b = t.get("bytes") if isinstance(t, dict) else None
                if isinstance(b, int) and b > total:
                    problems.append(
                        f"{path}:{i + 1}: {label} postmortem names a "
                        f"suspect of {b} bytes, larger than the whole "
                        f"ledger ({total}) — a fabricated suspect")
    return problems


def check_fleet_records(records, path):
    """Cross-record rules for fleet-tier events (kind=fleet,
    paddle_tpu.fleet.FleetRouter + tools/fleet_drill.py). Ordered
    rules bind only WITHIN the fleet records (the router emits them
    from one process, so concatenating per-process ledgers preserves
    their relative order); rules that join fleet records to the
    replicas' own kind=serving records are presence-based, because a
    combined ledger gives no cross-process ordering.

    - a DECLARED_DEAD must be preceded by a failed probe (healthy
      false) for the same replica — a death the prober never
      witnessed is a verdict without evidence;
    - a FAILOVER must reference a replica previously DECLARED DEAD or
      carry a non-empty `error` — re-routing a live, unerrored
      replica's request is load-balancing wearing a failover's name,
      and it would hide real failover bugs in the noise;
    - a REPLAY_SPLICED record's arithmetic must balance: n_tokens ==
      streamed_before + streamed_after — the spliced stream claims to
      be token-identical to an uninterrupted run, and a count that
      doesn't add up means tokens were dropped or double-streamed at
      the splice point; it must also follow a FAILOVER for the same
      request_id (a splice with no failover to explain it);
    - a fleet QUIESCE's counts must balance: requests == (admitted -
      failover) + shed + rejected — every request terminates exactly
      once: a first admission (failovers are RE-admissions), a shed
      at the fleet door, or a permanent rejection;
    - the fleet quiesce's `admitted_by_engine` must agree with each
      engine's OWN serving-quiesce admitted count, for engines whose
      serving quiesce appears in the ledger (a SIGKILLed replica
      never quiesces, so it is exempt — its admissions are vouched
      for by its flushed per-request records instead);
    - when the ledger carries the replicas' serving admitted records,
      every failover's request_id must appear on at least TWO of them
      (the first admission and the replay), at least one marked
      `replayed` — the replayed request on replica B must reference
      the same id as its first admission on replica A.
    """
    problems = []
    fleet = [(i, r) for i, r in enumerate(records)
             if isinstance(r, dict) and r.get("kind") == "fleet"]
    if not fleet:
        return problems
    admitted_rids = {}    # request_id -> [n_admissions, n_replayed]
    serving_quiesce = {}  # str(engine) -> admitted count (last wins)
    any_serving_admitted = False
    for r in records:
        if not isinstance(r, dict) or r.get("kind") != "serving":
            continue
        if r.get("event") == "admitted":
            any_serving_admitted = True
            rid = r.get("request_id")
            if rid is not None:
                slot = admitted_rids.setdefault(str(rid), [0, 0])
                slot[0] += 1
                if r.get("replayed"):
                    slot[1] += 1
        elif r.get("event") == "quiesce":
            counts = r.get("counts")
            if isinstance(counts, dict) and r.get("engine") is not None:
                serving_quiesce[str(r.get("engine"))] = \
                    counts.get("admitted", 0)
    probe_failed = set()     # replicas with a witnessed failed probe
    dead = set()             # replicas declared dead so far
    failover_rids = set()    # request_ids with a failover so far
    for i, rec in fleet:
        ev = rec.get("event")
        replica = rec.get("replica")
        if ev == "probe" and rec.get("healthy") is False:
            probe_failed.add(replica)
        elif ev == "declared_dead":
            if replica not in probe_failed:
                problems.append(
                    f"{path}:{i + 1}: replica {replica!r} declared "
                    "dead with no preceding failed probe — a death "
                    "verdict the prober never witnessed")
            dead.add(replica)
        elif ev == "failover":
            rid = rec.get("request_id")
            if rid is not None:
                failover_rids.add(str(rid))
            if replica not in dead and not rec.get("error"):
                problems.append(
                    f"{path}:{i + 1}: failover away from replica "
                    f"{replica!r} which was neither declared dead nor "
                    "carries an error — a re-route wearing a "
                    "failover's name")
            if any_serving_admitted and rid is not None:
                n_adm, n_replayed = admitted_rids.get(str(rid), (0, 0))
                # a failover at streamed_before == 0 re-admits WITHOUT
                # replay tokens (there is nothing to replay), so the
                # replayed marker is only owed when tokens were already
                # on the wire
                need_replayed = bool(rec.get("streamed_before"))
                if n_adm < 2 or (need_replayed and n_replayed < 1):
                    problems.append(
                        f"{path}:{i + 1}: failover for request "
                        f"{rid!r} but the ledger shows {n_adm} "
                        f"admission(s) ({n_replayed} replayed) for "
                        "that id — the replay on the new replica must "
                        "reference the same request_id as its first "
                        "admission")
        elif ev == "replay_spliced":
            before = rec.get("streamed_before")
            after = rec.get("streamed_after")
            n = rec.get("n_tokens")
            if isinstance(before, int) and isinstance(after, int) and \
                    isinstance(n, int) and before + after != n:
                problems.append(
                    f"{path}:{i + 1}: spliced stream accounting "
                    f"broken: n_tokens {n} != streamed_before "
                    f"{before} + streamed_after {after} — tokens were "
                    "dropped or double-streamed at the splice point")
            rid = rec.get("request_id")
            if rid is not None and str(rid) not in failover_rids:
                problems.append(
                    f"{path}:{i + 1}: replay_spliced for request "
                    f"{rid!r} with no preceding failover for that "
                    "request — a splice nothing explains")
        elif ev == "quiesce":
            counts = rec.get("counts")
            if isinstance(counts, dict):
                req = counts.get("requests", 0)
                first = counts.get("admitted", 0) \
                    - counts.get("failover", 0)
                expect = first + counts.get("shed", 0) \
                    + counts.get("rejected", 0)
                if req != expect:
                    problems.append(
                        f"{path}:{i + 1}: fleet quiesce counts don't "
                        f"balance: requests {req} != (admitted - "
                        f"failover) + shed + rejected {expect} — a "
                        "request terminated zero or twice")
            by_engine = rec.get("admitted_by_engine")
            if isinstance(by_engine, dict):
                for eng, n_adm in by_engine.items():
                    have = serving_quiesce.get(str(eng))
                    if have is not None and have != n_adm:
                        problems.append(
                            f"{path}:{i + 1}: fleet routed {n_adm} "
                            f"admission(s) to engine {eng} but that "
                            f"engine's own quiesce counted {have} — "
                            "the router and the replica disagree "
                            "about what was admitted")
    return problems


def check_chrome_trace(path):
    """Returns (n_events, ranks, problems)."""
    problems = []
    try:
        with open(path) as f:
            trace = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return 0, set(), [f"{path}: not valid JSON: {e}"]
    events = trace.get("traceEvents") if isinstance(trace, dict) else trace
    if not isinstance(events, list):
        return 0, set(), [f"{path}: no traceEvents list"]
    ranks = set()
    n = 0
    for i, ev in enumerate(events):
        if not isinstance(ev, dict) or "ph" not in ev:
            problems.append(f"{path}: event {i} missing 'ph'")
            continue
        if ev["ph"] == "M":
            continue
        n += 1
        if ev["ph"] == "X":
            for key in ("name", "ts", "dur", "pid"):
                if key not in ev:
                    problems.append(
                        f"{path}: X event {i} ({ev.get('name')}) "
                        f"missing '{key}'")
            if "pid" in ev:
                ranks.add(ev["pid"])
    if n == 0:
        problems.append(f"{path}: no duration events")
    return n, ranks, problems


def check_pair(jsonl_path, trace_path=None):
    """Full validation. Returns (problems, stats): problems == [] means
    valid; stats carries the already-computed counts so callers don't
    re-parse the files."""
    (n_rec, n_steps, n_compiles, n_ckpt, n_plan, n_elastic,
     n_serving, n_kernel, n_reqtrace, n_kernelbench, n_thread_lint,
     n_commbench, n_memsnap, n_fleet, problems) = \
        check_metrics_jsonl(jsonl_path)
    stats = {"n_records": n_rec, "n_steps": n_steps,
             "n_compiles": n_compiles, "n_ckpt": n_ckpt,
             "n_plan": n_plan,
             "n_elastic": n_elastic, "n_serving": n_serving,
             "n_kernel": n_kernel, "n_reqtrace": n_reqtrace,
             "n_kernelbench": n_kernelbench,
             "n_thread_lint": n_thread_lint,
             "n_commbench": n_commbench,
             "n_memsnap": n_memsnap,
             "n_fleet": n_fleet,
             "n_events": 0, "ranks": set()}
    if trace_path is not None:
        n_ev, ranks, trace_problems = check_chrome_trace(trace_path)
        stats["n_events"], stats["ranks"] = n_ev, ranks
        problems += trace_problems
        if not trace_problems:
            with open(trace_path) as f:
                trace = json.load(f)
            events = trace.get("traceEvents", []) \
                if isinstance(trace, dict) else trace
            steps = [e for e in events if isinstance(e, dict)
                     and e.get("cat") == "step" and e.get("ph") == "X"]
            # cross-check against STEP records only: phase-only JSONL
            # next to a stepped trace used to vacuously pass (the phase
            # lines inflated the record count)
            if steps and n_steps == 0:
                problems.append(
                    f"{trace_path}: {len(steps)} step spans but "
                    f"{jsonl_path} has zero step records")
            elif steps and len(steps) > n_steps:
                problems.append(
                    f"{trace_path}: {len(steps)} step spans but only "
                    f"{n_steps} JSONL step records")
    return problems, stats


def main(argv):
    if len(argv) < 2:
        print(__doc__)
        return 1
    jsonl_path = argv[1]
    trace_path = argv[2] if len(argv) > 2 else None
    problems, stats = check_pair(jsonl_path, trace_path)
    if problems:
        for p in problems:
            print(f"INVALID: {p}")
        return 7
    msg = f"OK: {stats['n_records']} records in {jsonl_path}"
    if stats.get("n_compiles"):
        msg += f" ({stats['n_compiles']} compile events)"
    if stats.get("n_ckpt"):
        msg += f" ({stats['n_ckpt']} ckpt events)"
    if stats.get("n_plan"):
        msg += f" ({stats['n_plan']} plan records)"
    if stats.get("n_elastic"):
        msg += f" ({stats['n_elastic']} elastic events)"
    if stats.get("n_serving"):
        msg += f" ({stats['n_serving']} serving events)"
    if stats.get("n_kernel"):
        msg += f" ({stats['n_kernel']} kernel-lint records)"
    if stats.get("n_reqtrace"):
        msg += f" ({stats['n_reqtrace']} request traces)"
    if stats.get("n_kernelbench"):
        msg += f" ({stats['n_kernelbench']} kernel measurements)"
    if stats.get("n_thread_lint"):
        msg += f" ({stats['n_thread_lint']} thread-lint records)"
    if stats.get("n_commbench"):
        msg += f" ({stats['n_commbench']} collective measurements)"
    if stats.get("n_memsnap"):
        msg += f" ({stats['n_memsnap']} memory snapshots)"
    if stats.get("n_fleet"):
        msg += f" ({stats['n_fleet']} fleet events)"
    if trace_path:
        msg += (f"; {stats['n_events']} trace events over ranks "
                f"{sorted(stats['ranks'])} in {trace_path}")
    print(msg)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
