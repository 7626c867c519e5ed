"""Structured metrics sink: one JSONL record per step + Chrome trace export.

This is the serialization half of the flight recorder. Every consumer of
training metrics in the repo (TelemetryRecorder, TelemetryCallback,
tools/trace_check.py) speaks the same schema.

Reference analogs: the profiler's `profiler.proto` serialized output and
`tools/CrossStackProfiler`'s per-rank chrome-trace merge; JAX's
XPlane->TensorBoard path covers device-side detail, this covers the
host-side step ledger.
"""
import atexit
import json
import os
import weakref

from ..analysis import lockwatch

# one process-wide atexit hook over weak refs: sinks stay collectable
# (a per-instance atexit.register would pin every sink + its fd for the
# process lifetime) while anything still alive at exit gets flushed
_LIVE_SINKS = weakref.WeakSet()
_ATEXIT_INSTALLED = False


def _close_live_sinks():
    for sink in list(_LIVE_SINKS):
        sink.close()

SCHEMA_VERSION = 1

# required keys of a per-step record (validated by tools/trace_check.py)
STEP_RECORD_KEYS = ("schema", "kind", "rank", "step", "step_ms",
                    "compile_ms", "execute_ms")
# optional, present when the recorder has the inputs to compute them
STEP_OPTIONAL_KEYS = ("loss", "tokens_per_sec", "mfu", "mem_bytes",
                      "cache_hits", "cache_misses", "collectives",
                      "grad_norm", "update_ratio", "nan_count",
                      "inf_count", "input_wait_ms", "input_queue_depth",
                      "input_bound_frac", "moe_entropy",
                      "moe_dropped_frac", "moe_overflow", "moe_aux_loss",
                      "moe_num_experts", "comm_ms", "comm_frac", "extra")
# input-pipeline fields (io.prefetch loader health taps: how long the
# step blocked waiting for its batch, ready-queue depth at fetch, and
# the EMA input-bound fraction — host-bound vs chip-bound as a number)
INPUT_KEYS = ("input_wait_ms", "input_queue_depth", "input_bound_frac")
# health-tap fields (telemetry.health numerics taps; None until a fetch
# step lands them — they appear every k-th record when taps are on)
HEALTH_KEYS = ("grad_norm", "update_ratio", "nan_count", "inf_count")
# MoE routing-health fields (paddle_tpu.moe.stats; present on steps of
# models exposing collect_moe_stats): expert-load entropy (<= log E —
# cross-checked by tools/trace_check.py against moe_num_experts),
# dropped-token fraction in [0, 1], capacity-overflow ratio (>= 0,
# > 1 means some expert saw more assignments than capacity), and the
# load-balancing aux-loss value
MOE_KEYS = ("moe_entropy", "moe_dropped_frac", "moe_overflow",
            "moe_aux_loss", "moe_num_experts")
# communication-attribution fields (telemetry/comm_obs + recorder):
# wall-time collective.* span milliseconds summed over the step
# (trace-time spans tagged traced=true are excluded) and that sum as a
# fraction of step_ms in [0, 1] — compute-vs-communication step
# decomposition as a number; the per-op breakdown stays in
# 'collectives'
COMM_KEYS = ("comm_ms", "comm_frac")

# required keys of a compile-event record (telemetry.compile_obs); the
# optional attachments are hbm (memory_analysis breakdown), cost
# (XLA cost analysis), hlo_ops (top-K opcode table), cause (recompile
# diff strings), signature, hbm_projected_bytes, analytic_flops
COMPILE_RECORD_KEYS = ("schema", "kind", "rank", "fn", "step",
                      "compile_ms", "n_compiles")

# required keys of a checkpoint-event record (paddle_tpu.resilience);
# optional: save_ms, bytes, op, error, problems, removed, signal
CKPT_RECORD_KEYS = ("schema", "kind", "rank", "step", "event")
# the event vocabulary tools/trace_check.py accepts
CKPT_EVENTS = ("save", "commit", "restore", "fallback", "failed", "gc",
               "preempt")

# required keys of an elastic-membership record (distributed.elastic
# ElasticCoordinator + resilience.reshard); optional: host, step,
# miss_count, detect_s, world_from, world_to, layout_from, layout_to,
# dead_hosts
ELASTIC_RECORD_KEYS = ("schema", "kind", "rank", "event")
# the declared-dead protocol's event vocabulary: a host misses a
# heartbeat poll (per miss), is declared dead past the threshold, the
# survivors replan via the auto-sharding planner, the drained
# checkpoint reshards onto the new layout, the process relaunches.
# tools/trace_check.py enforces the cross-record ordering (a
# declared_dead needs a preceding heartbeat_miss for the same host; a
# reshard_restore must reference a committed step and carry BOTH
# layouts; a relaunch needs a preceding replan).
ELASTIC_EVENTS = ("heartbeat_miss", "declared_dead", "replan",
                  "reshard_restore", "relaunch")

# required keys of a serving-lifecycle record (paddle_tpu.serving
# ServingEngine); optional: rid, engine, queue_depth, queue_wait_ms,
# queue_deadline_ms, predicted_wait_ms, retry_after_s, n_tokens,
# priority, reason, error, attempt, requeued, running, completed,
# drained_ms, kv_blocks_used, counts
SERVING_RECORD_KEYS = ("schema", "kind", "rank", "event")
# the request-lifecycle vocabulary: admitted (passed admission control
# into the bounded queue), one of four TERMINAL outcomes (finished /
# failed / cancelled / expired), shed (rejected up front: queue full or
# predicted to blow its deadline — MUST carry queue_depth, the
# pressure that justified the rejection), restart (transient step
# fault -> arenas rebuilt, in-flight requeued for recompute-replay),
# drain_begin/drain_end (graceful drain protocol), quiesce (engine
# idle: counts must balance — admitted == finished+failed+cancelled+
# expired — and kv_blocks_used must be 0; tools/trace_check.py
# enforces both).
SERVING_EVENTS = ("admitted", "finished", "failed", "cancelled",
                  "expired", "shed", "restart", "drain_begin",
                  "drain_end", "quiesce")

# required keys of a per-request trace record (telemetry.reqtrace
# RequestTracer, the serving engine's Dapper-style span timeline);
# optional: engine, t0_s, ttft_ms, tpot_ms, queue_wait_ms, n_tokens,
# prompt_len, preemptions
REQTRACE_RECORD_KEYS = ("schema", "kind", "rank", "rid", "outcome",
                        "e2e_ms", "spans")
# the span vocabulary: queued (waiting; `reason` says why — submit /
# preempt / restart), admit (the admission decision with its prefix-hit
# info), shed (rejected up front), prefill_chunk (one chunked-prefill
# dispatch; `replay`+`replay_cause` mark chunks recomputing positions a
# preemption or warm restart threw away), decode (CONSECUTIVE decode
# steps coalesced into one segment at engine-step boundaries — one span
# per decode stretch, never one per token), preempt / restart_replay
# (the requeue markers), cow_fork (copy-on-write block fork), finalize
# (terminal transition + stream close). Spans TILE the request's
# [submit, finish] wall-clock interval — each begins where the previous
# ended — which is what makes the decomposition invariant (durations
# sum to e2e_ms) checkable by tools/trace_check.py.
# `collective` / `transfer` are the multi-chip vocabulary (ROADMAP
# multi-chip serving item): time inside a cross-chip collective or a
# host<->device / chip<->chip transfer. They tile like every other
# kind, so the decomposition invariant is unchanged — a trace carrying
# them still sums to e2e_ms.
REQTRACE_SPAN_KINDS = ("queued", "admit", "shed", "prefill_chunk",
                       "decode", "preempt", "cow_fork", "restart_replay",
                       "finalize", "collective", "transfer")
# trace outcomes: the four terminal request states plus `shed` (the
# request never entered the engine; its trace is the admission verdict)
REQTRACE_OUTCOMES = ("finished", "failed", "cancelled", "expired",
                     "shed")

# required keys of a fleet-tier record (paddle_tpu.fleet FleetRouter —
# the router/front tier over N engine replicas); optional: replica,
# to_replica, request_id, policy, healthy, miss_count, detect_s,
# breaker, streamed_before, streamed_after, n_tokens, queue_depth,
# retry_after_s, reason, error, counts
FLEET_RECORD_KEYS = ("schema", "kind", "rank", "event")
# the fleet lifecycle vocabulary: route (a routing decision — which
# replica and WHY: prefix_affinity / session / least_loaded), probe
# (one health-probe verdict; an unhealthy probe carries miss_count, the
# ElasticCoordinator consecutive-miss pattern one tier up),
# declared_dead (miss_count consecutive failed probes — must be
# preceded by at least one failed probe for the same replica, the
# elastic declared-dead rule), failover (a request resubmitted after
# replica death or a mid-stream error: must reference a preceding death
# OR carry the error that justified it), replay_spliced (the spliced
# stream's accounting: n_tokens MUST equal streamed_before +
# streamed_after — the recompute-replay invariant made auditable),
# restart (one rolling-restart step: drain -> quiesce -> restart ->
# re-admit for one replica), shed (cross-replica admission rejected the
# request at the fleet door: every replica full/unhealthy), quiesce
# (the fleet ledger snapshot: requests == admitted + shed, and the sum
# of per-replica serving admissions must equal fleet admitted +
# failover re-admissions; tools/trace_check.py enforces all of it).
FLEET_EVENTS = ("route", "probe", "declared_dead", "failover",
                "replay_spliced", "restart", "shed", "quiesce")


def make_step_record(step, step_ms, compile_ms, rank=0, loss=None,
                     tokens_per_sec=None, mfu=None, mem_bytes=None,
                     cache_hits=None, cache_misses=None, collectives=None,
                     grad_norm=None, update_ratio=None, nan_count=None,
                     inf_count=None, input_wait_ms=None,
                     input_queue_depth=None, input_bound_frac=None,
                     moe_entropy=None, moe_dropped_frac=None,
                     moe_overflow=None, moe_aux_loss=None,
                     moe_num_experts=None, comm_ms=None, comm_frac=None,
                     **extra):
    """Normalize one step's measurements into the schema dict."""
    rec = {
        "schema": SCHEMA_VERSION,
        "kind": "step",
        "rank": int(rank),
        "step": int(step),
        "step_ms": round(float(step_ms), 4),
        "compile_ms": round(float(compile_ms), 4),
        "execute_ms": round(max(0.0, float(step_ms) - float(compile_ms)), 4),
    }
    if loss is not None:
        rec["loss"] = float(loss)
    if tokens_per_sec is not None:
        rec["tokens_per_sec"] = round(float(tokens_per_sec), 2)
    if mfu is not None:
        rec["mfu"] = round(float(mfu), 6)
    if mem_bytes is not None:
        rec["mem_bytes"] = int(mem_bytes)
    if cache_hits is not None:
        rec["cache_hits"] = int(cache_hits)
    if cache_misses is not None:
        rec["cache_misses"] = int(cache_misses)
    # health taps: keep non-finite values AS IS (NaN round-trips through
    # json.loads) — a poisoned grad_norm is the signal, not noise; the
    # paired nan/inf counts make it machine-checkable regardless
    if grad_norm is not None:
        rec["grad_norm"] = float(grad_norm)
    if update_ratio is not None:
        rec["update_ratio"] = float(update_ratio)
    if nan_count is not None:
        rec["nan_count"] = int(nan_count)
    if inf_count is not None:
        rec["inf_count"] = int(inf_count)
    # input-pipeline taps (io.prefetch): numeric, wait/depth >= 0, the
    # bound fraction in [0, 1] — validated by tools/trace_check.py
    if input_wait_ms is not None:
        rec["input_wait_ms"] = round(float(input_wait_ms), 4)
    if input_queue_depth is not None:
        rec["input_queue_depth"] = int(input_queue_depth)
    if input_bound_frac is not None:
        rec["input_bound_frac"] = round(float(input_bound_frac), 4)
    # MoE routing-health taps (paddle_tpu.moe.stats): bounded fractions
    # + the expert count that anchors the entropy bound — validated
    # below and cross-checked by tools/trace_check.py
    if moe_entropy is not None:
        rec["moe_entropy"] = round(float(moe_entropy), 6)
    if moe_dropped_frac is not None:
        rec["moe_dropped_frac"] = round(float(moe_dropped_frac), 6)
    if moe_overflow is not None:
        rec["moe_overflow"] = round(float(moe_overflow), 6)
    if moe_aux_loss is not None:
        rec["moe_aux_loss"] = round(float(moe_aux_loss), 6)
    if moe_num_experts is not None:
        rec["moe_num_experts"] = int(moe_num_experts)
    # communication attribution (telemetry/comm_obs): wall-time
    # collective span sum + its fraction of the step — validated below
    # and bounded by tools/trace_check.py
    if comm_ms is not None:
        rec["comm_ms"] = round(float(comm_ms), 4)
    if comm_frac is not None:
        rec["comm_frac"] = round(float(comm_frac), 6)
    if collectives:
        rec["collectives"] = {
            str(k): {"ms": round(float(v[0]), 4), "calls": int(v[1])}
            if isinstance(v, (tuple, list)) else v
            for k, v in collectives.items()}
    if extra:
        rec["extra"] = extra
    return rec


def make_compile_record(fn, step, compile_ms, rank=0, n_compiles=1,
                        backend=None, cause=None, signature=None,
                        hbm=None, cost=None, hlo_ops=None,
                        hbm_projected_bytes=None, analytic_flops=None,
                        untracked=False, **extra):
    """One trace/compile event as a first-class record (kind='compile').

    `cause` is the recompile diff (list of human-readable strings) —
    None/absent on the FIRST compile of a signature family, required on
    every later one (tools/trace_check.py enforces this). `untracked`
    marks compiles seen only through the jax.monitoring event stream
    (no signature, so no cause is derivable)."""
    rec = {
        "schema": SCHEMA_VERSION,
        "kind": "compile",
        "rank": int(rank),
        "fn": str(fn),
        "step": int(step),
        "compile_ms": round(float(compile_ms), 4),
        "n_compiles": int(n_compiles),
    }
    if backend is not None:
        rec["backend"] = str(backend)
    if cause:
        rec["cause"] = [str(c) for c in cause]
    if signature is not None:
        rec["signature"] = signature
    if hbm:
        rec["hbm"] = {k: int(v) for k, v in hbm.items()
                      if isinstance(v, (int, float))}
    if cost:
        rec["cost"] = {k: float(v) for k, v in cost.items()
                       if isinstance(v, (int, float))}
    if hlo_ops:
        rec["hlo_ops"] = hlo_ops
    if hbm_projected_bytes is not None:
        rec["hbm_projected_bytes"] = int(hbm_projected_bytes)
    if analytic_flops is not None:
        rec["analytic_flops"] = float(analytic_flops)
    if untracked:
        rec["untracked"] = True
    if extra:
        rec["extra"] = extra
    return rec


def make_ckpt_record(event, step, rank=0, save_ms=None, bytes=None,  # noqa: A002
                     **extra):
    """One checkpoint-lifecycle event as a first-class record
    (kind='ckpt', paddle_tpu.resilience.ckpt). `event` is one of
    CKPT_EVENTS: save (async kickoff), commit (manifest + atomic
    rename landed), restore, fallback (a corrupt checkpoint was
    skipped), failed (retries exhausted), gc (retention sweep),
    preempt (graceful-shutdown checkpoint)."""
    if event not in CKPT_EVENTS:
        raise ValueError(f"ckpt event must be one of {CKPT_EVENTS}, "
                         f"got {event!r}")
    rec = {
        "schema": SCHEMA_VERSION,
        "kind": "ckpt",
        "rank": int(rank),
        "step": int(step),
        "event": str(event),
    }
    if save_ms is not None:
        rec["save_ms"] = round(float(save_ms), 4)
    if bytes is not None:
        rec["bytes"] = int(bytes)
    for k, v in extra.items():
        if v is not None:
            rec[k] = v
    return rec


def make_elastic_record(event, rank=0, host=None, step=None,
                        miss_count=None, detect_s=None, world_from=None,
                        world_to=None, layout_from=None, layout_to=None,
                        **extra):
    """One elastic-membership lifecycle event as a first-class record
    (kind='elastic'). `event` is one of ELASTIC_EVENTS; `layout_from`/
    `layout_to` are axis dicts (resilience.reshard.normalize_layout
    canonical form); `detect_s` is the detector's first-miss ->
    declared-dead latency on its own clock (the drill asserts it stays
    inside the configured threshold window)."""
    if event not in ELASTIC_EVENTS:
        raise ValueError(f"elastic event must be one of {ELASTIC_EVENTS}, "
                         f"got {event!r}")
    rec = {
        "schema": SCHEMA_VERSION,
        "kind": "elastic",
        "rank": int(rank),
        "event": str(event),
    }
    if host is not None:
        rec["host"] = str(host)
    if step is not None:
        rec["step"] = int(step)
    if miss_count is not None:
        rec["miss_count"] = int(miss_count)
    if detect_s is not None:
        rec["detect_s"] = float(detect_s)
    if world_from is not None:
        rec["world_from"] = int(world_from)
    if world_to is not None:
        rec["world_to"] = int(world_to)
    if layout_from is not None:
        rec["layout_from"] = dict(layout_from)
    if layout_to is not None:
        rec["layout_to"] = dict(layout_to)
    for k, v in extra.items():
        if v is not None:
            rec[k] = v
    return rec


def make_serving_record(event, rank=0, rid=None, engine=None,
                        queue_depth=None, queue_wait_ms=None,
                        queue_deadline_ms=None, predicted_wait_ms=None,
                        retry_after_s=None, n_tokens=None, priority=None,
                        reason=None, error=None, kv_blocks_used=None,
                        counts=None, **extra):
    """One serving-lifecycle event as a first-class record
    (kind='serving', paddle_tpu.serving.ServingEngine). `event` is one
    of SERVING_EVENTS; `engine` is the emitting engine instance id (so
    one ledger can carry several sequential engines and the quiesce
    accounting stays per-engine); `counts` is the quiesce snapshot of
    the engine's request accounting."""
    if event not in SERVING_EVENTS:
        raise ValueError(f"serving event must be one of {SERVING_EVENTS}, "
                         f"got {event!r}")
    rec = {
        "schema": SCHEMA_VERSION,
        "kind": "serving",
        "rank": int(rank),
        "event": str(event),
    }
    if rid is not None:
        rec["rid"] = int(rid)
    if engine is not None:
        rec["engine"] = int(engine)
    if queue_depth is not None:
        rec["queue_depth"] = int(queue_depth)
    if queue_wait_ms is not None:
        rec["queue_wait_ms"] = round(float(queue_wait_ms), 4)
    if queue_deadline_ms is not None:
        rec["queue_deadline_ms"] = round(float(queue_deadline_ms), 4)
    if predicted_wait_ms is not None:
        rec["predicted_wait_ms"] = round(float(predicted_wait_ms), 4)
    if retry_after_s is not None:
        rec["retry_after_s"] = round(float(retry_after_s), 4)
    if n_tokens is not None:
        rec["n_tokens"] = int(n_tokens)
    if priority is not None:
        rec["priority"] = str(priority)
    if reason is not None:
        rec["reason"] = str(reason)
    if error is not None:
        rec["error"] = str(error)
    if kv_blocks_used is not None:
        rec["kv_blocks_used"] = int(kv_blocks_used)
    if counts is not None:
        rec["counts"] = {str(k): int(v) for k, v in counts.items()}
    for k, v in extra.items():
        if v is not None:
            rec[k] = v
    return rec


def make_reqtrace_record(rid, outcome, spans, e2e_ms, rank=0, engine=None,
                         t0_s=None, ttft_ms=None, tpot_ms=None,
                         queue_wait_ms=None, n_tokens=None,
                         prompt_len=None, preemptions=None, **extra):
    """One request's complete span timeline as a first-class record
    (kind='reqtrace', telemetry.reqtrace.RequestTracer). `spans` is the
    ordered tiling of the request's wall-clock life — each span a dict
    {kind, t0_ms, dur_ms, ...attrs} with t0_ms relative to submit time —
    and `e2e_ms` the end-to-end latency the span durations must sum to
    (tools/trace_check.py enforces the decomposition within 1%).
    `t0_s` is the submit instant on the process monotonic clock, which
    is what lets offline tools order requests and the Chrome export
    place per-request lanes next to engine-step spans."""
    if outcome not in REQTRACE_OUTCOMES:
        raise ValueError(f"reqtrace outcome must be one of "
                         f"{REQTRACE_OUTCOMES}, got {outcome!r}")
    norm = []
    for sp in spans:
        s = {"kind": str(sp["kind"]),
             "t0_ms": round(float(sp["t0_ms"]), 4),
             "dur_ms": round(float(sp["dur_ms"]), 4)}
        for k, v in sp.items():
            if k not in ("kind", "t0_ms", "dur_ms") and v is not None:
                s[k] = v
        norm.append(s)
    rec = {
        "schema": SCHEMA_VERSION,
        "kind": "reqtrace",
        "rank": int(rank),
        "rid": int(rid),
        "outcome": str(outcome),
        "e2e_ms": round(float(e2e_ms), 4),
        "spans": norm,
    }
    if engine is not None:
        rec["engine"] = int(engine)
    if t0_s is not None:
        rec["t0_s"] = round(float(t0_s), 6)
    if ttft_ms is not None:
        rec["ttft_ms"] = round(float(ttft_ms), 4)
    if tpot_ms is not None:
        rec["tpot_ms"] = round(float(tpot_ms), 4)
    if queue_wait_ms is not None:
        rec["queue_wait_ms"] = round(float(queue_wait_ms), 4)
    if n_tokens is not None:
        rec["n_tokens"] = int(n_tokens)
    if prompt_len is not None:
        rec["prompt_len"] = int(prompt_len)
    if preemptions is not None:
        rec["preemptions"] = int(preemptions)
    for k, v in extra.items():
        if v is not None:
            rec[k] = v
    return rec


def make_fleet_record(event, rank=0, replica=None, to_replica=None,
                      request_id=None, policy=None, healthy=None,
                      miss_count=None, detect_s=None, breaker=None,
                      streamed_before=None, streamed_after=None,
                      n_tokens=None, queue_depth=None, retry_after_s=None,
                      reason=None, error=None, counts=None, **extra):
    """One fleet-tier event as a first-class record (kind='fleet',
    paddle_tpu.fleet.FleetRouter). `event` is one of FLEET_EVENTS;
    `replica` names the replica the event is ABOUT (for a failover,
    the one that failed — `to_replica` is where the request went);
    `request_id` is the stable client-visible id that joins fleet
    records to the per-replica kind=serving / kind=reqtrace records;
    `counts` is the quiesce snapshot of the router's accounting."""
    if event not in FLEET_EVENTS:
        raise ValueError(f"fleet event must be one of {FLEET_EVENTS}, "
                         f"got {event!r}")
    rec = {
        "schema": SCHEMA_VERSION,
        "kind": "fleet",
        "rank": int(rank),
        "event": str(event),
    }
    if replica is not None:
        rec["replica"] = str(replica)
    if to_replica is not None:
        rec["to_replica"] = str(to_replica)
    if request_id is not None:
        rec["request_id"] = str(request_id)
    if policy is not None:
        rec["policy"] = str(policy)
    if healthy is not None:
        rec["healthy"] = bool(healthy)
    if miss_count is not None:
        rec["miss_count"] = int(miss_count)
    if detect_s is not None:
        rec["detect_s"] = round(float(detect_s), 4)
    if breaker is not None:
        rec["breaker"] = str(breaker)
    if streamed_before is not None:
        rec["streamed_before"] = int(streamed_before)
    if streamed_after is not None:
        rec["streamed_after"] = int(streamed_after)
    if n_tokens is not None:
        rec["n_tokens"] = int(n_tokens)
    if queue_depth is not None:
        rec["queue_depth"] = int(queue_depth)
    if retry_after_s is not None:
        rec["retry_after_s"] = round(float(retry_after_s), 4)
    if reason is not None:
        rec["reason"] = str(reason)
    if error is not None:
        rec["error"] = str(error)
    if counts is not None:
        rec["counts"] = {str(k): int(v) for k, v in counts.items()}
    for k, v in extra.items():
        if v is not None:
            rec[k] = v
    return rec


# required keys of a Kernel Doctor result record (analysis/kernel_lint
# via tools/kerneldoctor.py); optional: module, fn, grid, vmem_bytes,
# vmem_budget, flops_declared, flops_counted, has_fallback
KERNEL_RECORD_KEYS = ("schema", "kind", "rank", "kernel", "n_findings",
                      "findings")

# the KN rule vocabulary (analysis/kernel_lint.RULES is the documented
# source; this tuple is what the record validator enforces)
KERNEL_LINT_RULES = ("KN501", "KN502", "KN503", "KN504", "KN505")


def make_kernel_record(kernel, findings=(), rank=0, module=None,
                       fn=None, grid=None, vmem_bytes=None,
                       vmem_budget=None, flops_declared=None,
                       flops_counted=None, has_fallback=None, **extra):
    """One kernel's Kernel Doctor verdict as a first-class record
    (kind='kernel_lint'). `findings` is a list of Finding objects or
    {rule, message} dicts; a clean kernel records n_findings == 0 with
    its derived numbers (grid, projected VMEM, declared-vs-counted
    FLOPs) so the ledger shows what was checked, not just that nothing
    fired. tools/trace_check.py cross-checks the numbers against the
    findings (a VMEM projection over budget with no KN502 finding is a
    doctored or half-written ledger)."""
    fs = []
    for f in findings:
        if isinstance(f, dict):
            fs.append({"rule": str(f.get("rule", "")),
                       "message": str(f.get("message", ""))})
        else:
            fs.append({"rule": str(getattr(f, "rule_id", "")),
                       "message": str(getattr(f, "message", ""))})
    rec = {
        "schema": SCHEMA_VERSION,
        "kind": "kernel_lint",
        "rank": int(rank),
        "kernel": str(kernel),
        "n_findings": len(fs),
        "findings": fs,
    }
    if module is not None:
        rec["module"] = str(module)
    if fn is not None:
        rec["fn"] = str(fn)
    if grid is not None:
        rec["grid"] = [int(g) for g in grid]
    if vmem_bytes is not None:
        rec["vmem_bytes"] = int(vmem_bytes)
    if vmem_budget is not None:
        rec["vmem_budget"] = int(vmem_budget)
    if flops_declared is not None:
        rec["flops_declared"] = int(flops_declared)
    if flops_counted is not None:
        rec["flops_counted"] = int(flops_counted)
    if has_fallback is not None:
        rec["has_fallback"] = bool(has_fallback)
    for k, v in extra.items():
        if v is not None:
            rec[k] = v
    return rec


# required keys of a Concurrency Doctor record (analysis/threadlint +
# analysis/lockwatch via tools/threaddoctor.py); optional: locks,
# n_locks, modules
THREAD_LINT_RECORD_KEYS = ("schema", "kind", "rank", "source",
                           "n_findings", "findings", "n_edges", "edges")

# the TH rule vocabulary (analysis/threadlint's docstring is the
# documented source; this tuple is what the record validator enforces)
THREAD_LINT_RULES = ("TH600", "TH601", "TH602", "TH603", "TH604")

# what a thread_lint record may claim to be: the static pass over the
# source, or the lockwatch runtime witness
THREAD_LINT_SOURCES = ("static", "lockwatch")


def make_thread_lint_record(source, findings=(), edges=(), rank=0,
                            locks=None, modules=None, **extra):
    """One Concurrency Doctor verdict as a first-class record
    (kind='thread_lint'). source='static' carries threadlint's findings
    plus the nested-acquisition graph edges ([held, acquired, site]);
    source='lockwatch' carries the runtime witness — observed
    acquisition-order edges ([held, acquired, count]) and the per-lock
    snapshot under 'locks' (the watchdog black-box section).
    tools/trace_check.py cross-rules a static/lockwatch pair in the
    same file: the observed edge set must be a SUBGRAPH of the static
    graph, and any observed cycle fails outright."""
    fs = []
    for f in findings:
        if isinstance(f, dict):
            fs.append({"rule": str(f.get("rule", "")),
                       "message": str(f.get("message", ""))})
        else:
            fs.append({"rule": str(getattr(f, "rule_id", "")),
                       "message": str(getattr(f, "message", ""))})
    es = [[e[0], e[1], e[2]] for e in edges]
    rec = {
        "schema": SCHEMA_VERSION,
        "kind": "thread_lint",
        "rank": int(rank),
        "source": str(source),
        "n_findings": len(fs),
        "findings": fs,
        "n_edges": len(es),
        "edges": es,
    }
    if locks is not None:
        rec["locks"] = [dict(row) for row in locks]
        rec["n_locks"] = len(rec["locks"])
    if modules is not None:
        rec["modules"] = [str(m) for m in modules]
    for k, v in extra.items():
        if v is not None:
            rec[k] = v
    return rec


# required keys of a mesh-observatory measurement record
# (telemetry/comm_obs via tools/commlab.py); optional: compile_ms,
# wire_bytes, achieved_bw, peak_bw, bw_frac, predicted_ms, db_ms,
# db_key, medium, n_samples, warmup, event, seed
COMMBENCH_RECORD_KEYS = ("schema", "kind", "rank", "op", "axis",
                         "axis_size", "payload_bytes", "backend",
                         "time_ms")

# the sweep's op vocabulary — the shard_map collectives
# distributed/collective.py issues (telemetry/comm_obs.SWEEP_OPS)
COMMBENCH_OPS = ("psum", "all_gather", "reduce_scatter", "all_to_all",
                 "ppermute")

# what one commbench record may claim to be (cross-checked by
# tools/trace_check.py: a db_update must reference a measured row)
COMMBENCH_EVENTS = ("measure", "db_update")


def make_commbench_record(op, axis, axis_size, payload_bytes, backend,
                          time_ms, rank=0, compile_ms=None,
                          wire_bytes=None, achieved_bw=None, peak_bw=None,
                          bw_frac=None, predicted_ms=None, db_ms=None,
                          db_key=None, medium=None, n_samples=None,
                          warmup=None, event=None, seed=None, **extra):
    """One measured collective data point as a first-class typed record
    (kind='commbench'): what the mesh moves. `op` + `axis_size` +
    `payload_bytes` + `backend` reproduce the DB key
    (telemetry/comm_obs.db_key); `time_ms` is the compile-excluded
    execute median (compile_ms rides separately); `achieved_bw` /
    `bw_frac` place it against the planner's `ICI_BW_BY_CHIP` /
    `DCN_BW_BYTES` peaks; `predicted_ms` is the analytic floor
    `calibration_from_comm_records` ratios against; `db_ms` is the
    best-known DB latency the comm_bw_degraded rule judges against
    (absent when the measurement was given no DB — no reference, no
    jurisdiction). Non-finite timings become None + an error note — a
    NaN never rides the ledger silently."""
    def _clean(v):
        if v is None:
            return None, False
        bad = isinstance(v, float) and (v != v or v in (float("inf"),
                                                        float("-inf")))
        return (None if bad else float(v)), bad

    time_ms, bad = _clean(time_ms)
    rec = {
        "schema": SCHEMA_VERSION,
        "kind": "commbench",
        "rank": int(rank),
        "op": str(op),
        "axis": str(axis),
        "axis_size": int(axis_size),
        "payload_bytes": int(payload_bytes),
        "backend": str(backend),
        "time_ms": time_ms,
    }
    if bad:
        rec["error"] = "non-finite time_ms"
    for key, v in (("compile_ms", compile_ms), ("wire_bytes", wire_bytes),
                   ("achieved_bw", achieved_bw), ("peak_bw", peak_bw),
                   ("bw_frac", bw_frac), ("predicted_ms", predicted_ms),
                   ("db_ms", db_ms)):
        v, bad = _clean(v)
        if v is not None:
            rec[key] = v
        elif bad:
            rec["error"] = f"non-finite {key}"
    if db_key is not None:
        rec["db_key"] = str(db_key)
    if medium is not None:
        rec["medium"] = str(medium)
    if n_samples is not None:
        rec["n_samples"] = int(n_samples)
    if warmup is not None:
        rec["warmup"] = int(warmup)
    if event is not None:
        rec["event"] = str(event)
    if seed is not None:
        rec["seed"] = int(seed)
    for k, v in extra.items():
        if v is not None:
            rec[k] = v
    return rec


# required keys of a memory-observatory ledger record
# (telemetry/mem_obs via tools/memwatch.py); optional: the attribution
# buckets, budget/headroom/projection anchors, KV-pool accounting, and
# the postmortem payload (top_arrays, compile_families)
MEMSNAP_RECORD_KEYS = ("schema", "kind", "rank", "event", "step",
                       "total_bytes")

# attribution buckets — every live byte lands in exactly ONE, so
# tools/trace_check.py can recompute total_bytes from the record's own
# fields (the reqtrace decomposition stance, applied to HBM)
MEMSNAP_BUCKETS = ("params_bytes", "opt_state_bytes", "kv_bytes",
                   "workspace_bytes", "other_bytes")

# what one memsnap record may claim to be: a step-cadence ledger
# snapshot, or the capture-on-failure POSTMORTEM written when an
# allocation failed (RESOURCE_EXHAUSTED) — a postmortem must carry an
# error note and the top-K array listing (validated below), so an OOM
# is diagnosable offline from the ledger alone
MEMSNAP_EVENTS = ("snapshot", "postmortem")


def make_memsnap_record(event, step, total_bytes, rank=0,
                        params_bytes=None, opt_state_bytes=None,
                        kv_bytes=None, workspace_bytes=None,
                        other_bytes=None, hbm_budget_bytes=None,
                        headroom_bytes=None, projected_bytes=None,
                        projection_family=None, n_arrays=None,
                        kv_blocks_total=None, kv_blocks_held=None,
                        kv_blocks_free=None, kv_blocks_cached=None,
                        kv_occupancy=None, kv_cache_share=None,
                        kv_evictions=None, kv_admissions=None,
                        kv_eviction_rate=None, kv_admission_rate=None,
                        evictions_by_class=None, admissions_by_class=None,
                        engine=None, error=None, top_arrays=None,
                        compile_families=None, **extra):
    """One live-HBM ledger snapshot as a first-class typed record
    (kind='memsnap') — the memory sibling of kind='commbench': the mesh
    observatory measures what the mesh moves, the memory observatory
    measures what the chip HOLDS. The bucket fields (MEMSNAP_BUCKETS)
    partition total_bytes — tools/trace_check.py recomputes the sum;
    `headroom_bytes` is max(0, hbm_budget_bytes - total_bytes), the
    admission signal the serving engine gauges; `projected_bytes` is
    the compile observatory's static memory_analysis() projection the
    reconcile-drift rule latches against; the kv_* fields snapshot the
    BlockPool/PrefixIndex accounting (held+free+cached must tile
    kv_blocks_total) plus the eviction/admission rates the kv_thrash
    rule judges — all riding ON the record, so healthwatch replay and
    the in-flight detector see identical numbers. A postmortem event
    additionally carries `error`, the top-K `top_arrays` by bytes, and
    the active `compile_families`. Non-finite measurements become None
    + an error note, like make_commbench_record — a NaN never rides
    the ledger silently."""
    def _clean(v):
        if v is None:
            return None, False
        bad = isinstance(v, float) and (v != v or v in (float("inf"),
                                                        float("-inf")))
        return (None if bad else float(v)), bad

    total_bytes, bad = _clean(total_bytes)
    rec = {
        "schema": SCHEMA_VERSION,
        "kind": "memsnap",
        "rank": int(rank),
        "event": str(event),
        "step": int(step),
        "total_bytes": None if total_bytes is None else int(total_bytes),
    }
    if bad:
        rec["error"] = "non-finite total_bytes"
    for key, v in (("params_bytes", params_bytes),
                   ("opt_state_bytes", opt_state_bytes),
                   ("kv_bytes", kv_bytes),
                   ("workspace_bytes", workspace_bytes),
                   ("other_bytes", other_bytes),
                   ("hbm_budget_bytes", hbm_budget_bytes),
                   ("headroom_bytes", headroom_bytes),
                   ("projected_bytes", projected_bytes)):
        v, bad = _clean(v)
        if v is not None:
            rec[key] = int(v)
        elif bad:
            rec["error"] = f"non-finite {key}"
    for key, v in (("kv_occupancy", kv_occupancy),
                   ("kv_cache_share", kv_cache_share),
                   ("kv_eviction_rate", kv_eviction_rate),
                   ("kv_admission_rate", kv_admission_rate)):
        v, bad = _clean(v)
        if v is not None:
            rec[key] = round(v, 6)
        elif bad:
            rec["error"] = f"non-finite {key}"
    for key, v in (("n_arrays", n_arrays),
                   ("kv_blocks_total", kv_blocks_total),
                   ("kv_blocks_held", kv_blocks_held),
                   ("kv_blocks_free", kv_blocks_free),
                   ("kv_blocks_cached", kv_blocks_cached),
                   ("kv_evictions", kv_evictions),
                   ("kv_admissions", kv_admissions),
                   ("engine", engine)):
        if v is not None:
            rec[key] = int(v)
    if projection_family is not None:
        rec["projection_family"] = str(projection_family)
    if evictions_by_class is not None:
        rec["evictions_by_class"] = {str(k): int(v) for k, v
                                     in evictions_by_class.items()}
    if admissions_by_class is not None:
        rec["admissions_by_class"] = {str(k): int(v) for k, v
                                      in admissions_by_class.items()}
    if error is not None:
        rec["error"] = str(error)
    if top_arrays is not None:
        rec["top_arrays"] = list(top_arrays)
    if compile_families is not None:
        rec["compile_families"] = list(compile_families)
    for k, v in extra.items():
        if v is not None:
            rec[k] = v
    return rec


# required keys of an auto-sharding plan record (paddle_tpu.planner);
# optional: chip, n_chips, projected_hbm_bytes, measured_hbm_bytes,
# hbm_budget_bytes, cost_step_s, calibration, verify
PLAN_RECORD_KEYS = ("schema", "kind", "rank", "model", "chosen",
                    "candidates_considered", "candidates_rejected")


def make_plan_record(model, chosen, candidates_considered,
                     candidates_rejected, rank=0, chip=None, n_chips=None,
                     projected_hbm_bytes=None, measured_hbm_bytes=None,
                     hbm_budget_bytes=None, cost_step_s=None,
                     calibration=None, verify=None, **extra):
    """One auto-sharding decision as a first-class record (kind='plan',
    paddle_tpu.planner.Plan.to_record). `chosen` is the layout dict
    (dp/pp/mp/sp/ep/zero_stage/...); `candidates_rejected` is the
    rejection ledger ([{layout, reason}] — every reason non-empty, the
    validator enforces it). `measured_hbm_bytes` is attached after the
    compile observatory measures the chosen layout's first compile;
    tools/trace_check.py fails the plan when measured drifts >15% from
    `projected_hbm_bytes` (the PR-4 hbm_projection_drift rule applied
    to the planner's own numbers)."""
    rec = {
        "schema": SCHEMA_VERSION,
        "kind": "plan",
        "rank": int(rank),
        "model": str(model),
        "chosen": dict(chosen),
        "candidates_considered": int(candidates_considered),
        "candidates_rejected": [dict(r) for r in candidates_rejected],
    }
    if chip is not None:
        rec["chip"] = str(chip)
    if n_chips is not None:
        rec["n_chips"] = int(n_chips)
    if projected_hbm_bytes is not None:
        rec["projected_hbm_bytes"] = int(projected_hbm_bytes)
    if measured_hbm_bytes is not None:
        rec["measured_hbm_bytes"] = int(measured_hbm_bytes)
    if hbm_budget_bytes is not None:
        rec["hbm_budget_bytes"] = int(hbm_budget_bytes)
    if cost_step_s is not None:
        rec["cost_step_s"] = float(cost_step_s)
    if calibration is not None:
        rec["calibration"] = float(calibration)
    if verify is not None:
        rec["verify"] = verify
    for k, v in extra.items():
        if v is not None:
            rec[k] = v
    return rec


class JsonlSink:
    """Append-only JSONL metrics file, one record per line. Thread-safe.

    Crash durability: the file handle is held open and every record is
    flushed to the OS as it is written, and live sinks are closed by a
    process-wide `atexit` hook (weak refs — a sink is still collectable
    the moment its owner drops it) — records buffered at the moment of
    an exception (or a SystemExit tearing the interpreter down) are on
    disk, not lost in a dead buffer. A write after close() transparently
    reopens (append), so a closed sink still works."""

    def __init__(self, path):
        global _ATEXIT_INSTALLED
        self.path = os.fspath(path)
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        self._mu = lockwatch.make_lock("JsonlSink._mu")
        self._n = 0     # guarded by: _mu
        self._f = open(self.path, "a")  # guarded by: _mu
        if not _ATEXIT_INSTALLED:
            atexit.register(_close_live_sinks)
            _ATEXIT_INSTALLED = True
        _LIVE_SINKS.add(self)

    def write(self, record):
        line = json.dumps(record, sort_keys=True)
        with self._mu:
            if self._f is None or self._f.closed:
                self._f = open(self.path, "a")
            self._f.write(line + "\n")
            self._f.flush()
            self._n += 1
        return record

    def flush(self):
        with self._mu:
            if self._f is not None and not self._f.closed:
                self._f.flush()
                try:
                    os.fsync(self._f.fileno())
                except OSError:
                    pass

    def close(self):
        with self._mu:
            if self._f is not None and not self._f.closed:
                self._f.flush()
                self._f.close()

    def __len__(self):  # threadlint: lock-free (racy record count is fine for progress/tests)
        return self._n


def emit_record(rec, *sinks):
    """Write one record through THE standard sink fallback chain —
    the first usable candidate wins, else the context-active
    recorder's sink, else the record is returned unwritten. Each
    candidate may be a sink object (anything with .write), a path
    string (opened append as a JsonlSink), or None. This is the single
    owner of the precedence rule the resilience/elastic emitters share
    (explicit sink > manager's sink > active recorder)."""
    out = None
    for s in sinks:
        if s is None:
            continue
        out = JsonlSink(s) if isinstance(s, str) else s
        break
    if out is None:
        from .recorder import current_recorder
        r = current_recorder()
        out = r.sink if r is not None else None
    if out is not None:
        out.write(rec)
    return rec


def read_jsonl(path):
    """Load a metrics JSONL back into a list of dicts (round-trip)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def validate_step_record(rec):
    """Return a list of problems with one record ([] == valid)."""
    problems = []
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, not dict"]
    kind = rec.get("kind")
    if kind == "compile":
        for key in COMPILE_RECORD_KEYS:
            if key not in rec:
                problems.append(f"compile record missing '{key}'")
        v = rec.get("compile_ms")
        if v is not None and (not isinstance(v, (int, float)) or v < 0):
            problems.append(f"'compile_ms' not a non-negative number: {v!r}")
        n = rec.get("n_compiles")
        if n is not None and (not isinstance(n, int) or n < 1):
            problems.append(f"'n_compiles' not a positive int: {n!r}")
        cause = rec.get("cause")
        if cause is not None and (not isinstance(cause, list) or
                                  not all(isinstance(c, str) for c in cause)):
            problems.append(f"'cause' not a list of strings: {cause!r}")
        return problems
    if kind == "kernel_lint":
        for key in KERNEL_RECORD_KEYS:
            if key not in rec:
                problems.append(f"kernel_lint record missing '{key}'")
        if not str(rec.get("kernel", "")).strip():
            problems.append("kernel_lint record names no kernel")
        n = rec.get("n_findings")
        fs = rec.get("findings")
        if n is not None and (not isinstance(n, int) or n < 0):
            problems.append(f"'n_findings' not a non-negative int: {n!r}")
        if fs is not None:
            if not isinstance(fs, list):
                problems.append("'findings' not a list")
            else:
                if isinstance(n, int) and n != len(fs):
                    problems.append(
                        f"n_findings {n} but {len(fs)} findings listed "
                        "— the count and the list disagree")
                for j, f in enumerate(fs):
                    if not isinstance(f, dict):
                        problems.append(f"finding {j} not a dict")
                        continue
                    if f.get("rule") not in KERNEL_LINT_RULES:
                        problems.append(
                            f"finding {j} rule {f.get('rule')!r} not in "
                            f"the KN vocabulary "
                            f"{list(KERNEL_LINT_RULES)}")
                    if not str(f.get("message", "")).strip():
                        problems.append(
                            f"finding {j} carries no message — a "
                            "finding the ledger cannot explain")
        for key in ("vmem_bytes", "vmem_budget", "flops_declared",
                    "flops_counted"):
            v = rec.get(key)
            if v is not None and (not isinstance(v, (int, float))
                                  or v < 0):
                problems.append(
                    f"'{key}' not a non-negative number: {v!r}")
        return problems
    if kind == "thread_lint":
        for key in THREAD_LINT_RECORD_KEYS:
            if key not in rec:
                problems.append(f"thread_lint record missing '{key}'")
        src = rec.get("source")
        if src is not None and src not in THREAD_LINT_SOURCES:
            problems.append(
                f"unknown thread_lint source {src!r} (expected one of "
                f"{list(THREAD_LINT_SOURCES)})")
        n = rec.get("n_findings")
        fs = rec.get("findings")
        if n is not None and (not isinstance(n, int) or n < 0):
            problems.append(f"'n_findings' not a non-negative int: {n!r}")
        if fs is not None:
            if not isinstance(fs, list):
                problems.append("'findings' not a list")
            else:
                if isinstance(n, int) and n != len(fs):
                    problems.append(
                        f"n_findings {n} but {len(fs)} findings listed "
                        "— the count and the list disagree")
                for j, f in enumerate(fs):
                    if not isinstance(f, dict):
                        problems.append(f"finding {j} not a dict")
                        continue
                    if f.get("rule") not in THREAD_LINT_RULES:
                        problems.append(
                            f"finding {j} rule {f.get('rule')!r} not in "
                            f"the TH vocabulary "
                            f"{list(THREAD_LINT_RULES)}")
                    if not str(f.get("message", "")).strip():
                        problems.append(
                            f"finding {j} carries no message — a "
                            "finding the ledger cannot explain")
        ne = rec.get("n_edges")
        es = rec.get("edges")
        if ne is not None and (not isinstance(ne, int) or ne < 0):
            problems.append(f"'n_edges' not a non-negative int: {ne!r}")
        if es is not None:
            if not isinstance(es, list):
                problems.append("'edges' not a list")
            else:
                if isinstance(ne, int) and ne != len(es):
                    problems.append(
                        f"n_edges {ne} but {len(es)} edges listed — "
                        "the count and the list disagree")
                for j, e in enumerate(es):
                    if (not isinstance(e, list) or len(e) != 3
                            or not isinstance(e[0], str)
                            or not isinstance(e[1], str)):
                        problems.append(
                            f"edge {j} not a [held, acquired, "
                            f"site-or-count] triple: {e!r}")
        locks = rec.get("locks")
        if locks is not None:
            if not isinstance(locks, list):
                problems.append("'locks' not a list")
            else:
                for j, row in enumerate(locks):
                    if not isinstance(row, dict) or \
                            not str(row.get("name", "")).strip():
                        problems.append(f"lock row {j} names no lock")
                    elif not isinstance(row.get("acquires"), int):
                        problems.append(
                            f"lock row {j} ({row.get('name')}) carries "
                            "no integer 'acquires' count")
        return problems
    if kind == "commbench":
        for key in COMMBENCH_RECORD_KEYS:
            if key not in rec:
                problems.append(f"commbench record missing '{key}'")
        op = rec.get("op")
        if op is not None and op not in COMMBENCH_OPS:
            problems.append(f"unknown commbench op {op!r} (expected one "
                            f"of {list(COMMBENCH_OPS)})")
        for key in ("time_ms", "compile_ms", "predicted_ms", "db_ms",
                    "wire_bytes", "achieved_bw", "peak_bw"):
            v = rec.get(key)
            if v is not None and (not isinstance(v, (int, float))
                                  or v != v or v < 0):
                problems.append(
                    f"'{key}' not a non-negative number: {v!r}")
        if rec.get("time_ms") is None and "error" not in rec:
            problems.append("commbench record with null time_ms "
                            "carries no 'error' note")
        v = rec.get("bw_frac")
        if v is not None and (not isinstance(v, (int, float))
                              or v != v or not 0.0 <= v <= 1.0):
            problems.append(
                f"'bw_frac' not a bandwidth fraction in [0, 1]: {v!r}")
        for key in ("axis_size", "payload_bytes"):
            v = rec.get(key)
            if v is not None and (not isinstance(v, int) or v < 1):
                problems.append(f"'{key}' not a positive int: {v!r}")
        for key in ("n_samples", "warmup"):
            v = rec.get(key)
            if v is not None and (not isinstance(v, int) or v < 0):
                problems.append(
                    f"'{key}' not a non-negative int: {v!r}")
        m = rec.get("medium")
        if m is not None and m not in ("ici", "dcn"):
            problems.append(f"'medium' not 'ici'/'dcn': {m!r}")
        ev = rec.get("event")
        if ev is not None and ev not in COMMBENCH_EVENTS:
            problems.append(f"unknown commbench event {ev!r} "
                            f"(expected one of "
                            f"{list(COMMBENCH_EVENTS)})")
        return problems
    if kind == "plan":
        for key in PLAN_RECORD_KEYS:
            if key not in rec:
                problems.append(f"plan record missing '{key}'")
        chosen = rec.get("chosen")
        if chosen is not None:
            if not isinstance(chosen, dict):
                problems.append(f"'chosen' not a layout dict: {chosen!r}")
            else:
                for axis in ("dp", "pp", "mp"):
                    v = chosen.get(axis)
                    if not isinstance(v, int) or v < 1:
                        problems.append(
                            f"chosen layout '{axis}' not a positive "
                            f"int: {v!r}")
        n = rec.get("candidates_considered")
        rejected = rec.get("candidates_rejected")
        if n is not None and (not isinstance(n, int) or n < 1):
            problems.append(
                f"'candidates_considered' not a positive int: {n!r}")
        if rejected is not None:
            if not isinstance(rejected, list):
                problems.append("'candidates_rejected' not a list")
            else:
                if isinstance(n, int) and len(rejected) >= n:
                    problems.append(
                        f"{len(rejected)} rejected candidates but only "
                        f"{n} considered — the chosen layout cannot be "
                        "among them")
                for j, r in enumerate(rejected):
                    if not isinstance(r, dict) or \
                            not str(r.get("reason", "")).strip():
                        problems.append(
                            f"rejected candidate {j} carries no reason "
                            "— a rejection the ledger cannot explain")
        for key in ("projected_hbm_bytes", "measured_hbm_bytes",
                    "hbm_budget_bytes"):
            v = rec.get(key)
            if v is not None and (not isinstance(v, (int, float))
                                  or v < 0):
                problems.append(
                    f"'{key}' not a non-negative number: {v!r}")
        return problems
    if kind == "elastic":
        for key in ELASTIC_RECORD_KEYS:
            if key not in rec:
                problems.append(f"elastic record missing '{key}'")
        ev = rec.get("event")
        if ev is not None and ev not in ELASTIC_EVENTS:
            problems.append(f"unknown elastic event {ev!r} "
                            f"(expected one of {list(ELASTIC_EVENTS)})")
        if ev in ("heartbeat_miss", "declared_dead"):
            if not str(rec.get("host", "")).strip():
                problems.append(f"elastic {ev} record names no host")
            mc = rec.get("miss_count")
            if mc is not None and (not isinstance(mc, int) or mc < 1):
                problems.append(
                    f"'miss_count' not a positive int: {mc!r}")
        for key in ("world_from", "world_to"):
            v = rec.get(key)
            if v is not None and (not isinstance(v, int) or v < 1):
                problems.append(f"'{key}' not a positive int: {v!r}")
        for key in ("layout_from", "layout_to"):
            v = rec.get(key)
            if v is None:
                continue
            if not isinstance(v, dict) or not v:
                problems.append(f"'{key}' not a non-empty layout "
                                f"dict: {v!r}")
            else:
                for a, s in v.items():
                    if not isinstance(s, int) or s < 1:
                        problems.append(
                            f"'{key}' axis {a!r} not a positive "
                            f"int: {s!r}")
        if ev == "reshard_restore":
            # the one event that must be fully anchored on its own:
            # which committed step moved, from which layout, to which
            if not isinstance(rec.get("step"), int):
                problems.append(
                    "elastic reshard_restore record references no step")
            for key in ("layout_from", "layout_to"):
                if not rec.get(key):
                    problems.append(
                        f"elastic reshard_restore record carries no "
                        f"'{key}'")
        v = rec.get("detect_s")
        if v is not None and (not isinstance(v, (int, float)) or v < 0):
            problems.append(f"'detect_s' not a non-negative number: {v!r}")
        return problems
    if kind == "serving":
        for key in SERVING_RECORD_KEYS:
            if key not in rec:
                problems.append(f"serving record missing '{key}'")
        ev = rec.get("event")
        if ev is not None and ev not in SERVING_EVENTS:
            problems.append(f"unknown serving event {ev!r} "
                            f"(expected one of {list(SERVING_EVENTS)})")
        for key in ("queue_depth", "queue_wait_ms", "queue_deadline_ms",
                    "predicted_wait_ms", "retry_after_s", "n_tokens",
                    "kv_blocks_used", "drained_ms",
                    "prefix_blocks_shared", "prefix_hit_rate",
                    "prefill_tokens_saved", "prefill_tokens_offered"):
            v = rec.get(key)
            if v is not None and (not isinstance(v, (int, float))
                                  or v != v or v < 0):
                problems.append(
                    f"'{key}' not a non-negative number: {v!r}")
        if ev == "quiesce":
            # quiesce must be auditable on its own: the accounting
            # snapshot and the pool state are WHAT it asserts
            if "kv_blocks_used" not in rec:
                problems.append(
                    "serving quiesce record carries no kv_blocks_used")
            counts = rec.get("counts")
            if not isinstance(counts, dict):
                problems.append(
                    "serving quiesce record carries no counts dict")
            else:
                for k, v in counts.items():
                    if not isinstance(v, int) or v < 0:
                        problems.append(
                            f"quiesce count {k!r} not a non-negative "
                            f"int: {v!r}")
        return problems
    if kind == "fleet":
        for key in FLEET_RECORD_KEYS:
            if key not in rec:
                problems.append(f"fleet record missing '{key}'")
        ev = rec.get("event")
        if ev is not None and ev not in FLEET_EVENTS:
            problems.append(f"unknown fleet event {ev!r} "
                            f"(expected one of {list(FLEET_EVENTS)})")
        if ev in ("route", "probe", "declared_dead", "failover",
                  "replay_spliced", "restart"):
            if not str(rec.get("replica", "")).strip():
                problems.append(f"fleet {ev} record names no replica")
        if ev == "declared_dead":
            mc = rec.get("miss_count")
            if not isinstance(mc, int) or mc < 1:
                problems.append(
                    f"fleet declared_dead 'miss_count' not a positive "
                    f"int: {mc!r}")
        if ev == "failover" and not str(rec.get("to_replica",
                                                "")).strip():
            problems.append("fleet failover record names no to_replica "
                            "— where did the request go?")
        if ev == "replay_spliced":
            # the splice must be auditable on its own: both halves and
            # the total are WHAT it asserts (the cross-rule checks the
            # arithmetic; the validator checks the fields exist)
            for key in ("streamed_before", "streamed_after", "n_tokens"):
                v = rec.get(key)
                if not isinstance(v, int) or v < 0:
                    problems.append(
                        f"fleet replay_spliced '{key}' not a "
                        f"non-negative int: {v!r}")
        if ev == "quiesce":
            counts = rec.get("counts")
            if not isinstance(counts, dict):
                problems.append(
                    "fleet quiesce record carries no counts dict")
            else:
                for k, v in counts.items():
                    if not isinstance(v, int) or v < 0:
                        problems.append(
                            f"fleet quiesce count {k!r} not a "
                            f"non-negative int: {v!r}")
        for key in ("miss_count", "detect_s", "streamed_before",
                    "streamed_after", "n_tokens", "queue_depth",
                    "retry_after_s"):
            v = rec.get(key)
            if v is not None and (not isinstance(v, (int, float))
                                  or v != v or v < 0):
                problems.append(
                    f"'{key}' not a non-negative number: {v!r}")
        return problems
    if kind == "reqtrace":
        for key in REQTRACE_RECORD_KEYS:
            if key not in rec:
                problems.append(f"reqtrace record missing '{key}'")
        outcome = rec.get("outcome")
        if outcome is not None and outcome not in REQTRACE_OUTCOMES:
            problems.append(f"unknown reqtrace outcome {outcome!r} "
                            f"(expected one of {list(REQTRACE_OUTCOMES)})")
        for key in ("e2e_ms", "t0_s", "ttft_ms", "tpot_ms",
                    "queue_wait_ms", "n_tokens", "prompt_len",
                    "preemptions"):
            v = rec.get(key)
            if v is not None and (not isinstance(v, (int, float))
                                  or v != v or v < 0):
                problems.append(
                    f"'{key}' not a non-negative number: {v!r}")
        spans = rec.get("spans")
        if spans is not None:
            if not isinstance(spans, list) or not spans:
                problems.append("'spans' not a non-empty list — a trace "
                                "with no timeline explains nothing")
            else:
                for j, sp in enumerate(spans):
                    if not isinstance(sp, dict):
                        problems.append(f"span {j} not a dict")
                        continue
                    if sp.get("kind") not in REQTRACE_SPAN_KINDS:
                        problems.append(
                            f"span {j} kind {sp.get('kind')!r} not in "
                            f"the vocabulary {list(REQTRACE_SPAN_KINDS)}")
                    for key in ("t0_ms", "dur_ms"):
                        v = sp.get(key)
                        if not isinstance(v, (int, float)) or v != v \
                                or v < 0:
                            problems.append(
                                f"span {j} '{key}' not a non-negative "
                                f"number: {v!r}")
        return problems
    if kind == "ckpt":
        for key in CKPT_RECORD_KEYS:
            if key not in rec:
                problems.append(f"ckpt record missing '{key}'")
        ev = rec.get("event")
        if ev is not None and ev not in CKPT_EVENTS:
            problems.append(f"unknown ckpt event {ev!r} "
                            f"(expected one of {list(CKPT_EVENTS)})")
        for key in ("save_ms", "bytes"):
            v = rec.get(key)
            if v is not None and (not isinstance(v, (int, float)) or v < 0):
                problems.append(f"'{key}' not a non-negative number: {v!r}")
        if ev == "commit" and "save_ms" not in rec:
            problems.append("ckpt commit record carries no save_ms")
        return problems
    if kind == "memsnap":
        for key in MEMSNAP_RECORD_KEYS:
            if key not in rec:
                problems.append(f"memsnap record missing '{key}'")
        ev = rec.get("event")
        if ev is not None and ev not in MEMSNAP_EVENTS:
            problems.append(f"unknown memsnap event {ev!r} "
                            f"(expected one of {list(MEMSNAP_EVENTS)})")
        for key in ("total_bytes",) + MEMSNAP_BUCKETS + (
                "hbm_budget_bytes", "headroom_bytes", "projected_bytes",
                "kv_eviction_rate", "kv_admission_rate"):
            v = rec.get(key)
            if v is not None and (not isinstance(v, (int, float))
                                  or v != v or v < 0):
                problems.append(
                    f"'{key}' not a non-negative number: {v!r}")
        if rec.get("total_bytes") is None and "error" not in rec:
            problems.append("memsnap record with null total_bytes "
                            "carries no 'error' note")
        for key in ("kv_occupancy", "kv_cache_share"):
            v = rec.get(key)
            if v is not None and (not isinstance(v, (int, float))
                                  or v != v or not 0.0 <= v <= 1.0):
                problems.append(
                    f"'{key}' not a fraction in [0, 1]: {v!r}")
        for key in ("n_arrays", "kv_blocks_total", "kv_blocks_held",
                    "kv_blocks_free", "kv_blocks_cached",
                    "kv_evictions", "kv_admissions"):
            v = rec.get(key)
            if v is not None and (not isinstance(v, int) or v < 0):
                problems.append(
                    f"'{key}' not a non-negative int: {v!r}")
        for key in ("evictions_by_class", "admissions_by_class"):
            v = rec.get(key)
            if v is None:
                continue
            if not isinstance(v, dict):
                problems.append(f"'{key}' not a dict: {v!r}")
            else:
                for cls, n in v.items():
                    if not isinstance(n, int) or n < 0:
                        problems.append(
                            f"'{key}' count for class {cls!r} not a "
                            f"non-negative int: {n!r}")
        if ev == "postmortem":
            # the forensic contract: an OOM record that cannot say
            # what failed, or show WHO held the bytes, diagnoses
            # nothing offline
            if not str(rec.get("error", "")).strip():
                problems.append(
                    "memsnap postmortem carries no error note — a "
                    "forensic record that cannot say what killed the "
                    "allocation")
            ta = rec.get("top_arrays")
            if not isinstance(ta, list) or not ta:
                problems.append(
                    "memsnap postmortem carries no top_arrays listing "
                    "— an OOM with no suspects named")
            else:
                for j, a in enumerate(ta):
                    if not isinstance(a, dict) or \
                            not isinstance(a.get("bytes"), int) or \
                            a["bytes"] < 0:
                        problems.append(
                            f"top_arrays[{j}] carries no non-negative "
                            "'bytes'")
        return problems
    for key in STEP_RECORD_KEYS:
        if key not in rec:
            problems.append(f"step record missing '{key}'")
    for key in ("step_ms", "compile_ms", "execute_ms"):
        v = rec.get(key)
        if v is not None and (not isinstance(v, (int, float)) or v < 0):
            problems.append(f"'{key}' not a non-negative number: {v!r}")
    for key in ("tokens_per_sec", "mfu", "loss"):
        v = rec.get(key)
        if v is not None and not isinstance(v, (int, float)):
            problems.append(f"'{key}' not numeric: {v!r}")
        if isinstance(v, float) and (v != v or v in (float("inf"),
                                                     float("-inf"))):
            problems.append(f"'{key}' non-finite: {v!r}")
    for key in HEALTH_KEYS:
        # numeric when present; non-finite is ALLOWED here — a NaN
        # grad_norm is the health taps reporting a poisoned step, and
        # the paired nan/inf counts stay machine-checkable integers
        v = rec.get(key)
        if v is not None and not isinstance(v, (int, float)):
            problems.append(f"'{key}' not numeric: {v!r}")
    for key in INPUT_KEYS:
        v = rec.get(key)
        if v is None:
            continue
        if not isinstance(v, (int, float)) or v != v or v < 0:
            problems.append(
                f"'{key}' not a non-negative number: {v!r}")
        elif key == "input_bound_frac" and v > 1.0:
            problems.append(f"'input_bound_frac' above 1.0: {v!r}")
    for key in MOE_KEYS:
        v = rec.get(key)
        if v is None:
            continue
        if key == "moe_num_experts":
            if not isinstance(v, int) or v < 1:
                problems.append(
                    f"'moe_num_experts' not a positive int: {v!r}")
            continue
        if not isinstance(v, (int, float)) or v != v:
            problems.append(f"'{key}' not a finite number: {v!r}")
            continue
        if key in ("moe_entropy", "moe_dropped_frac", "moe_overflow") \
                and v < 0:
            problems.append(f"'{key}' negative: {v!r}")
        if key == "moe_dropped_frac" and v > 1.0:
            problems.append(f"'moe_dropped_frac' above 1.0: {v!r}")
    for key in COMM_KEYS:
        v = rec.get(key)
        if v is None:
            continue
        if not isinstance(v, (int, float)) or v != v or v < 0:
            problems.append(
                f"'{key}' not a non-negative number: {v!r}")
        elif key == "comm_frac" and v > 1.0:
            problems.append(f"'comm_frac' above 1.0: {v!r}")
    return problems


# ---------------------------------------------------------------------------
# Chrome trace export (CrossStackProfiler analog, multi-rank)
# ---------------------------------------------------------------------------

def spans_to_trace_events(spans, default_rank=0):
    """spans: iterable of dicts {name, t0, dur, rank?, tid?, cat?} (seconds)
    -> chrome trace 'X' events in microseconds, pid == rank."""
    events = []
    ranks = set()
    for sp in spans:
        rank = int(sp.get("rank", default_rank))
        ranks.add(rank)
        ev = {
            "name": sp["name"], "ph": "X",
            "pid": rank, "tid": int(sp.get("tid", 0)),
            "ts": float(sp["t0"]) * 1e6, "dur": float(sp["dur"]) * 1e6,
            "cat": sp.get("cat", "host"),
        }
        if sp.get("args"):
            ev["args"] = sp["args"]
        events.append(ev)
    meta = [{"name": "process_name", "ph": "M", "pid": r,
             "args": {"name": f"rank {r}"}} for r in sorted(ranks)]
    return meta + events


def export_chrome_tracing(path, sources, align_on=None):
    """Write one Chrome-trace JSON merging host spans across ranks.

    `sources` is a list whose items are either TelemetryRecorder objects
    (their `.spans` and `.rank` are used) or plain span-dict lists. Each
    rank becomes its own trace pid so the merged timeline reads like the
    reference CrossStackProfiler output. `align_on`: optional span name
    whose start is declared t=0 per rank (the `__sync__`-marker recipe
    from tools/merge_profiles.py).

    Spans still OPEN at export time (a stuck collective, an aborted
    step) are closed at 'now' and tagged args={'open': True} rather
    than dropped — an export made from a crash handler must show what
    the program was inside, not pretend it was idle.

    Returns the number of spans written. Output loads in chrome://tracing
    or Perfetto.
    """
    all_spans = []
    for i, src in enumerate(sources):
        spans = getattr(src, "spans", src)
        open_fn = getattr(src, "open_span_dicts", None)
        if open_fn is not None:
            spans = list(spans) + list(open_fn())
        rank = getattr(src, "rank", None)
        for sp in spans:
            sp = dict(sp)
            if "rank" not in sp:
                sp["rank"] = i if rank is None else rank
            all_spans.append(sp)
    if align_on is not None:
        zero = {}
        for sp in all_spans:
            if sp["name"] == align_on:
                zero.setdefault(sp["rank"], sp["t0"])
        for sp in all_spans:
            sp["t0"] = sp["t0"] - zero.get(sp["rank"], 0.0)
    events = spans_to_trace_events(all_spans)
    path = os.fspath(path)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"traceEvents": events,
                   "displayTimeUnit": "ms"}, f)
    return len(all_spans)
