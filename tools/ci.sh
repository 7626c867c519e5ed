#!/usr/bin/env bash
# CI gate (reference analog: paddle_build.sh + check_api_compatible.py):
# native libs compile, the static and observatory selfchecks, the
# serving / fleet / resilience drills, and the full pytest suite on the
# 8-virtual-device CPU mesh. Correctness, counts and compile families
# only: nothing here is a speed. Speeds are read on the chip through
# BENCHMARK.json + benchmark/ (PERF.md).
set -euo pipefail
cd "$(dirname "$0")/.."

# per-stage wall-time ledger: stage() stamps the boundary between
# stages; the summary line at the bottom names where the minutes went
# (a CI run that slows down should say WHICH gate slowed it down)
STAGE_TIMES=""
_stage_name=""
_stage_t0=$SECONDS
stage() {
  local now=$SECONDS
  if [ -n "$_stage_name" ]; then
    STAGE_TIMES="${STAGE_TIMES}${STAGE_TIMES:+, }${_stage_name} $((now - _stage_t0))s"
  fi
  _stage_name="$1"
  _stage_t0=$now
  if [ -n "$1" ]; then echo "== $1 =="; fi
}

stage "[1/10] native build"
if command -v cmake >/dev/null && command -v ninja >/dev/null; then
  cmake -S csrc -B csrc/build/cmake -G Ninja >/dev/null
  cmake --build csrc/build/cmake >/dev/null
else
  mkdir -p csrc/build
  for lib in pskv kvstore ptio; do
    g++ -O3 -std=c++17 -shared -fPIC -pthread "csrc/${lib}.cc" \
        -o "csrc/build/lib${lib}.so"
  done
  g++ -O3 -std=c++17 -shared -fPIC -Icsrc/third_party \
      csrc/predictor.cc -ldl -o csrc/build/libptpredictor.so
  g++ -O3 -std=c++17 -shared -fPIC -Icsrc/third_party \
      csrc/pjrt_mock_plugin.cc -o csrc/build/libpjrt_mock.so
  g++ -O3 -std=c++17 -Icsrc/third_party csrc/predictor_main.cc \
      csrc/build/libptpredictor.so -ldl -o csrc/build/predictor_smoke
fi
echo "native libs OK"

# pure-C++ serving smoke: the standalone binary (no Python linked)
# serves a ZeroCopy run through the PJRT C ABI against the mock plugin
SMOKE_DIR=$(mktemp -d)
printf 'MOCK-IDENTITY' > "$SMOKE_DIR/m.mlir"
printf 'version 1\ninput x0 f32 2,2\noutput out0 f32 2,2\n' \
    > "$SMOKE_DIR/m.sig"
csrc/build/predictor_smoke "$SMOKE_DIR/m" csrc/build/libpjrt_mock.so \
    | grep -q "^OK" && echo "native serving smoke OK"
rm -rf "$SMOKE_DIR"

stage "[2/10] api-surface audit"
python tools/api_audit.py --out api_gap.json --strict
# signature-level diff (check_api_compatible.py analog): param names,
# relative order, and no new required params vs the reference
python tools/api_sig_audit.py --out api_sig_gap.json --strict

stage "[3/10] graph doctor + framework lint"
# pre-flight static analysis (paddle_tpu/analysis): the GPT config's
# traced step + sharding specs must lint clean, every rule family must
# demonstrably fire on its broken specimen, and a new framework-lint
# violation (tracer leak, traced impurity, bare pallas_call) anywhere
# in paddle_tpu/ fails the build. The standalone astlint run overlaps
# graphdoctor's framework pass on purpose: it is the cheap (~2s AST
# walk) gate that still fires when graphdoctor itself is broken, and
# the one developers run locally
JAX_PLATFORMS=cpu python tools/graphdoctor.py --model gpt \
    --report /tmp/graphdoctor_ci.json
# the MoE family (paddle_tpu/moe): the routed gpt_moe step must trace
# clean through the same battery over a dp x mp x ep mesh, including
# SH208 rule coverage of the expert partition rules (selfcheck already
# demonstrated above — skip repeating it)
JAX_PLATFORMS=cpu python tools/graphdoctor.py --model gpt_moe \
    --no-selfcheck
JAX_PLATFORMS=cpu python -m paddle_tpu.analysis.astlint paddle_tpu
# auto-sharding planner gate (tools/autoshard.py), same two-sided
# pattern: the checked-in infeasible specimen (HBM budget too small,
# tools/specimens/autoshard_infeasible.json) must be rejected with the
# binding constraint named, and a feasible GPT-125M config must
# produce a plan that passes the full graph-doctor battery clean —
# including re-linting the planner's tags on the live model — with a
# kind=plan record that validates under tools/trace_check.py
JAX_PLATFORMS=cpu python tools/autoshard.py --selfcheck
# kernel doctor gate (tools/kerneldoctor.py over paddle_tpu/analysis/
# kernel_lint.py), same two-sided pattern one level below the graph:
# the checked-in broken specimens must be caught BY NAME — the
# racy-grid kernel (tools/specimens/kernel_racy.py, parallel-marked
# accumulation axis -> KN501) and the over-VMEM BlockSpec
# (tools/specimens/kernel_overvmem.py -> KN502) — every in-tree
# registered Pallas kernel must lint clean (races, VMEM projection,
# CostEstimate honesty, fallback parity, grid-spec sanity), the AST
# sweep must prove no pallas_call site in paddle_tpu/ remains outside
# the kernel registry (the astlint FW405 rule, also enforced by the
# standalone astlint run above), and the emitted kind=kernel_lint
# records must validate under tools/trace_check.py
JAX_PLATFORMS=cpu python tools/kerneldoctor.py --selfcheck
# concurrency doctor gate (tools/threaddoctor.py over paddle_tpu/
# analysis/threadlint.py + lockwatch.py), the doctor pattern applied
# to the host-side threaded runtime: the checked-in broken specimens
# must be caught BY NAME — the unguarded-field class
# (tools/specimens/thread_unguarded.py -> TH601, incl. the silent
# lock-owner coverage half) and the ABBA / cross-object lock-order
# cycles (tools/specimens/thread_deadlock.py -> TH602 naming both
# edges) — every module in threadlint.MODULES must lint clean, the
# lockwatch witness must trace a real cross-thread nested acquisition
# and catch a reversed order as an observed cycle, and the emitted
# kind=thread_lint records must validate under tools/trace_check.py
# including the observed-subset-of-static cross-rule
JAX_PLATFORMS=cpu python tools/threaddoctor.py --selfcheck
# comm lab gate (tools/commlab.py over telemetry/comm_obs.py): the
# checked-in degraded specimen (tools/specimens/commbench_degraded.jsonl)
# must trip the comm_bw_degraded anomaly BY NAME through the real
# AnomalyDetector while its in-band and reference-free rows stay
# silent, a clean sweep over every (op, size>1 axis) of the dp=2,mp=4
# mesh must validate
# under trace_check AND pass the comm_audit wire-byte honesty leg
# (claimed bytes vs a re-trace of the same sweep program), and the
# comm DB must refuse non-finite rows and round-trip losslessly
JAX_PLATFORMS=cpu python tools/commlab.py --selfcheck
# memory watch gate (tools/memwatch.py over telemetry/mem_obs.py), the
# observatory selfcheck pattern applied to what the chip HOLDS: the
# checked-in pressure specimen (tools/specimens/memsnap_pressure.jsonl)
# must trip the hbm_pressure AND kv_thrash anomalies BY NAME through
# the real AnomalyDetector, a clean smoke ledger (tagged engine weights
# + optimizer state + paged-KV arenas sampled live) must validate under
# trace_check, reconcile against its shape-derived static projection
# within HealthConfig.mem_reconcile_tol and stay silent, and a captured
# OOM postmortem must round-trip with its suspects named
JAX_PLATFORMS=cpu python tools/memwatch.py --selfcheck

stage "[4/10] observatory smokes + health/compile specimens"
# comm-lab smoke (tools/commlab.py --smoke): every shard_map collective
# over every size>1 axis of the dp=2,mp=4 mesh, the kind=commbench
# records gated through trace_check AND the comm_audit wire-byte leg
# inside the tool (exit 13 on any finding)
JAX_PLATFORMS=cpu python tools/commlab.py --smoke
# memory-watch smoke (tools/memwatch.py --smoke): the live HBM ledger
# sampled over a real serving engine + optimizer step with every
# tagging hook exercised, gated through trace_check inside the tool
# (exit 14 on any finding — invalid record, fired rule, failed
# projection reconciliation)
JAX_PLATFORMS=cpu python tools/memwatch.py --smoke
# the health monitor's offline analyzer (tools/healthwatch.py) replays
# the SAME anomaly rules the in-flight monitor runs: the checked-in
# broken specimen must trip EVERY anomaly family (NaN step, loss
# spike, grad explosion, step-time regression) — proof the watcher can
# still see what it gates on (the graphdoctor selfcheck pattern). That
# a clean run stays clean is tier-1's (tests/test_health.py)
JAX_PLATFORMS=cpu python tools/healthwatch.py \
    tools/specimens/health_anomalous.jsonl \
    --expect nan,loss_spike,grad_explosion,step_time_regression
# compile observatory (tools/compile_report.py): the checked-in thrash
# specimen must trip the storm rule AND the causes must name the
# thrashing argument (clean runs: tests/test_compile_obs.py; the fork
# ban: tests/test_io_prefetch.py)
JAX_PLATFORMS=cpu python tools/compile_report.py --selfcheck \
    tools/specimens/compile_thrash.jsonl --expect-arg batch

stage "[5/10] serving engine smoke"
# continuous-batching serving gate (paddle_tpu/serving +
# tools/serving_smoke.py), the two-sided pattern:
#   a) N concurrent streamed requests through the real engine loop
#      (background thread + HTTP front) must be token-for-token
#      identical to single-request run_generate, with ZERO recompiles
#      across the whole run (compile-observatory-verified) and the
#      serving.* gauges live on /metrics;
#   b) --selfcheck: an over-admitted schedule (block pool smaller than
#      the offered load) must trip eviction and the
#      serving.preemptions counter while every recomputed stream stays
#      identical — proof the eviction path both exists and is safe.
# The default leg also gates the request tracer (telemetry.reqtrace):
# every finished request must yield a validated kind=reqtrace record
# whose spans sum to its end-to-end latency, /metrics must expose
# parseable Prometheus latency histograms tracking the legacy gauges,
# and /traces must serve the exemplar timelines.
JAX_PLATFORMS=cpu python tools/serving_smoke.py
JAX_PLATFORMS=cpu python tools/serving_smoke.py --selfcheck
# tail-latency attribution gate (tools/tail_report.py), two-sided:
#   a) the checked-in pathology specimen
#      (tools/specimens/reqtrace_tail.jsonl) must name queue_wait,
#      preemption AND restart as dominant causes and trip the
#      tail_latency rule for each, while the invalid specimen
#      (tools/specimens/reqtrace_invalid.jsonl) must be CAUGHT by
#      trace_check both ways (non-summing decomposition +
#      finished-without-admit);
#   b) a live mini-drill injects each pathology into a real engine
#      (overload -> queue_wait, over-admission -> preemption, transient
#      step fault -> restart) and the dominant cause must come out
#      right on the actual traces.
JAX_PLATFORMS=cpu python tools/tail_report.py --selfcheck

stage "[6/10] serving resilience drill"
# serving robustness gate (paddle_tpu/serving/resilience +
# tools/serving_drill.py), the two-sided pattern:
#   a) --selfcheck first proves the failures are VISIBLE: the
#      checked-in leak specimen (a quiesce record still holding KV
#      blocks) and deadline-miss specimen (a request run to completion
#      past its recorded queue deadline) must each be caught by
#      tools/trace_check.py, and BlockPool.assert_quiesced must catch
#      an in-process leak;
#   b) then the mini drill inside --selfcheck runs the real thing: an
#      overload wave (2x slots) + tight-deadline shed probes (429 +
#      Retry-After) + an expired-TTFT probe + a mid-stream HTTP client
#      disconnect (must cancel + release blocks) + an injected
#      .transient step fault (must warm-restart and REPLAY the
#      in-flight streams token-identically) + a graceful drain under
#      load (healthz 503-draining, livez 200, accepted work finishes),
#      ending with zero leaked KV blocks, balanced request accounting
#      (admitted == finished+failed+cancelled+expired), and a
#      kind=serving ledger that passes trace_check.
JAX_PLATFORMS=cpu python tools/serving_drill.py --selfcheck

stage "[7/10] fleet drill"
# fleet-tier robustness gate (paddle_tpu/fleet + tools/fleet_drill.py),
# the two-sided pattern one tier above the serving drill:
#   a) --selfcheck first proves the failures are VISIBLE: the
#      checked-in failover-without-death specimen (a failover record
#      no death or error justifies) and the splice-mismatch specimen
#      (a replayed stream whose n_tokens != streamed_before +
#      streamed_after) must each be CAUGHT by tools/trace_check.py's
#      kind=fleet cross-rules;
#   b) then a mini in-process drill runs the real thing: 2 engine
#      replicas behind a FleetRouter, an injected mid-stream replica
#      failure, failover replay — every stream token-identical to the
#      single-engine reference, the combined router+engine ledger
#      trace_check-clean including the fleet quiesce accounting
#      identity (requests == first-admissions + sheds + rejections)
#      and the per-engine admission agreement.
# Exit codes: 12 drill findings, 9 selfcheck miss — distinct from
# serving_drill 11 / chaos_drill 8 / trace_check 7 so logs
# disambiguate. (The full 3-process SIGKILL drill is the slow-tier
# run: tools/fleet_drill.py with no flags.)
JAX_PLATFORMS=cpu python tools/fleet_drill.py --selfcheck

stage "[8/10] resilience chaos drill"
# fault-tolerance gate (paddle_tpu.resilience + tools/chaos_drill.py):
#   a) the checked-in corrupt-checkpoint specimen
#      (tools/specimens/ckpt_corrupt) must be REJECTED by manifest
#      verification with the offending leaf named — proof the verifier
#      can still see the corruption it gates on — while a re-sealed
#      clean copy must pass;
#   b) a real mini train loop is SIGKILL'd right after step 3's async
#      save kicks off (leaving an uncommitted .tmp husk), auto-resumed
#      from the last committed step, and must finish with a loss
#      trajectory bit-identical to an uninterrupted baseline, with
#      ckpt.* metrics live on /metrics during the run and the kind=ckpt
#      telemetry ledger validating under tools/trace_check.py.
JAX_PLATFORMS=cpu python tools/chaos_drill.py --selfcheck

stage "[9/10] elastic mesh drill"
# host-loss gate (distributed.elastic + resilience.reshard +
# tools/elastic_drill.py), the two-sided pattern:
#   a) the checked-in cross-layout specimen
#      (tools/specimens/ckpt_cross_layout, saved under dp=2) must
#      reshard-restore under dp=1 AND under an mp=2 mesh with
#      digest-equal logical weights + live momentum slots, and a
#      tampered leaf must still be LEAF-NAMED across the reshard path;
#   b) a dp=2 two-process pod loses one host to SIGKILL: the survivor
#      must declare it dead within the miss threshold, replan via the
#      auto-sharding planner to the 1-host layout, drain a final
#      checkpoint and exit 101; the relaunch must resume THROUGH the
#      reshard path with digest-equal weights and a finite continued
#      loss — the whole sequence validated as kind=elastic telemetry
#      by tools/trace_check.py.
JAX_PLATFORMS=cpu python tools/elastic_drill.py --selfcheck

stage "[10/10] test suite"
# 4 xdist shards (reference `tools/parallel_UT_rule.py` CI sharding):
# each worker process builds its own 8-virtual-device CPU platform
python -m pytest tests/ -q -n auto --dist loadfile

stage ""   # close the last stage so the ledger covers all ten
echo "stage wall times: ${STAGE_TIMES} (total ${SECONDS}s)"
echo "CI OK"
