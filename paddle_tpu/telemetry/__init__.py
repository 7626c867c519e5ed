"""paddle_tpu.telemetry — the training flight recorder.

Unifies the three older observability stubs into one step-level layer:

- `profiler.py` host spans (RecordEvent)  -> `telemetry.span` /
  recorder span buffer + multi-rank Chrome-trace export;
- `monitor.py` counters                   -> advanced automatically per
  recorded step (`telemetry.steps`, `telemetry.compile_cache_*`);
- `distributed/metrics.py` eval stats     -> unchanged (eval-metric math),
  but per-step comm/step telemetry now lives here.

Reference analogs: `platform/profiler.h` RecordEvent + DeviceTracer and
`tools/CrossStackProfiler`'s per-rank merge; JAX-era device detail stays
on `jax.profiler` (XPlane/TensorBoard) — this layer owns the host-side
step ledger: wall time, compile vs. execute split, tokens/sec, MFU,
memory, per-collective time.

Entry points:
- span / scope — the two naming primitives: `span(name)` times the host
  while the program runs (recorder, chrome trace, and the profiler's
  own trace); `scope(name)` names the device work traced inside one
  model layer (`jax.named_scope("pt." + name)`, `SCOPES` the whole
  vocabulary; defined in core/scope.py) and costs a compiled program
  nothing.
- TelemetryRecorder — per-step JSONL records; context-activate it and
  `jit.TrainStep` / `distributed.ShardedTrainStep` record themselves.
- StepTimer — explicit jax.stages AOT compile-cache wrapper.
- hapi.callbacks.TelemetryCallback — Model.fit integration.
- sink.export_chrome_tracing / tools/trace_check.py — trace tooling.
- health.HealthConfig / HealthMonitor — jit-safe numerics taps +
  anomaly detection (`health=` on the train steps); watchdog.HangWatchdog
  — stall detection with black-box dumps; metrics_http.MetricsServer —
  live /healthz, /metrics (Prometheus), /steps scrape endpoint;
  tools/healthwatch.py replays the same anomaly rules offline.
- compile_obs.CompileObservatory — the compile observatory: context-
  activate it and every train-step (re)compile is recorded with a
  cause diff, compiled-HBM breakdown (`memory_analysis()`), cost-model
  cross-checks and a recompile-storm rule; tools/compile_report.py
  renders/replays the JSONL offline.
- mem_obs.MemoryObservatory — the memory observatory: a live HBM
  ledger over `jax.live_arrays()` with byte attribution into
  params/opt_state/kv/workspace/other buckets, KV-pool occupancy
  telemetry, reconciliation against the compile observatory's static
  projection, and capture-on-failure OOM postmortems;
  tools/memwatch.py renders/replays the JSONL offline.
"""
from . import compile_obs  # noqa: F401
from . import health  # noqa: F401
from . import mem_obs  # noqa: F401
from . import metrics_http  # noqa: F401
from . import mfu  # noqa: F401
from . import reqtrace  # noqa: F401
from . import sink  # noqa: F401
from . import watchdog  # noqa: F401
from .health import (  # noqa: F401
    Anomaly, AnomalyDetector, HealthConfig, HealthError, HealthMonitor)
from .compile_obs import (  # noqa: F401
    CompileObservatory, CompileSignature, RecompileTracker,
    current_observatory, diff_signatures, signature_of)
from .compile_obs import dispatch as observed_dispatch  # noqa: F401
from .mem_obs import (  # noqa: F401
    MemoryObservatory, is_oom, register_provider, snapshot_ledger)
from .metrics_http import MetricsServer  # noqa: F401
from .mfu import (  # noqa: F401
    device_peak_flops, model_flops_per_token, train_step_flops)
from .recorder import (  # noqa: F401
    SCOPES, StepTimer, TelemetryRecorder, auto_step, current_recorder,
    open_spans, scope, span)
from .reqtrace import (  # noqa: F401
    RequestTrace, RequestTracer, decompose, dominant_cause,
    trace_chrome_spans)
from .sink import (  # noqa: F401
    JsonlSink, export_chrome_tracing, make_ckpt_record,
    make_memsnap_record, make_reqtrace_record,
    make_serving_record, make_step_record, read_jsonl,
    validate_step_record)
from .watchdog import HangWatchdog, dump_black_box  # noqa: F401

__all__ = [
    "TelemetryRecorder", "StepTimer", "span", "scope", "SCOPES", "auto_step",
    "current_recorder", "open_spans", "JsonlSink", "read_jsonl",
    "make_step_record", "make_ckpt_record",
    "make_serving_record", "make_reqtrace_record",
    "make_memsnap_record",
    "MemoryObservatory", "is_oom", "register_provider", "snapshot_ledger",
    "RequestTrace", "RequestTracer", "decompose", "dominant_cause",
    "trace_chrome_spans",
    "validate_step_record", "export_chrome_tracing",
    "device_peak_flops", "model_flops_per_token", "train_step_flops",
    "HealthConfig", "HealthMonitor", "HealthError", "Anomaly",
    "AnomalyDetector", "HangWatchdog", "dump_black_box", "MetricsServer",
    "CompileObservatory", "CompileSignature", "RecompileTracker",
    "current_observatory", "diff_signatures", "signature_of",
    "observed_dispatch",
    "mfu", "sink", "health", "watchdog", "metrics_http", "compile_obs",
    "reqtrace", "mem_obs",
]
