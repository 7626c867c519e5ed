"""paddle_tpu.serving — continuous-batching LLM serving engine.

The "millions of users" layer (ROADMAP): a long-lived engine process
that serves many concurrent generation streams from ONE compiled
decode step over a paged KV cache, instead of one run_generate program
per request.

- `kv_cache` — refcounted block-pool allocator + paged K/V arenas
  ([num_blocks, block_size, hidden] per layer; PagedAttention layout)
  + `PrefixIndex`, the block-granular radix index that lets requests
  share cached prompt-prefix blocks copy-on-write (RadixAttention).
- `scheduler` — token-granular continuous batching: admit/evict at
  every step, chunked prefill interleaved with decode, preemption by
  recompute (Orca/vLLM scheduling).
- `engine` — `ServingEngine`: fixed-shape compiled prefill/decode
  steps (recompile-free steady state, compile-observatory-checkable),
  per-slot greedy/top-k/top-p sampling, streaming token handles,
  `serving.*` metrics on the monitor registry — latencies as true
  streaming histograms with the legacy p50/p99 gauges recomputed from
  them at scrape time — plus per-request span timelines
  (`telemetry.reqtrace`: every request a kind=reqtrace record whose
  spans tile its life, tail exemplars on `GET /traces`, offline
  attribution via `tools/tail_report.py`). `EngineConfig
  .from_inference_config` routes the `paddle_tpu.inference.Config`
  compat switches (device, memory pool, precision) into real engine
  behavior.
- `resilience` — the failure story: per-request server-side deadlines
  (queue-wait/TTFT/total, reaped at step boundaries), per-class
  priorities over a bounded waiting queue, SLO-aware load shedding
  (queue depth x measured TPOT -> 429 + Retry-After up front), typed
  terminal errors, and the warm-restart backoff schedule. Exercised by
  `tools/serving_drill.py` (overload + disconnects + injected step
  fault, leak-checked via `BlockPool.assert_quiesced`).
- `http` — stdlib streaming HTTP front (`POST /generate`, `/metrics`,
  `/healthz` readiness + `/livez` liveness), riding the PR-3
  MetricsServer pattern; detects client disconnects and cancels the
  abandoned request.

Measured on the chip by the serving cells of `BENCHMARK.json`
(`benchmark/`, read through `PERF.md`); smoked in CI by
`tools/serving_smoke.py` (token parity with run_generate + eviction
selfcheck).
"""
from .kv_cache import (  # noqa: F401
    BlockLeakError, BlockPool, CacheKind, PagedKVCache, PrefixIndex,
    RowPool, StaleIndexError, kv_kind, latent_kind, state_kind)
from .resilience import (  # noqa: F401
    AdmissionController, Deadlines, DeadlineExceededError,
    EngineDeadError, EngineDrainingError, EngineStoppedError,
    MemoryPressureError, QueueFullError, RequestCancelledError,
    ServingError, ShedError)
from .scheduler import (  # noqa: F401
    Request, RequestHandle, SamplingParams, Scheduler)
from .engine import EngineConfig, ServingEngine  # noqa: F401
from .http import ServingHTTPServer  # noqa: F401

__all__ = [
    "BlockPool", "BlockLeakError", "CacheKind", "kv_kind", "latent_kind",
    "state_kind", "RowPool",
    "PagedKVCache", "PrefixIndex",
    "StaleIndexError", "Request",
    "RequestHandle", "SamplingParams", "Scheduler", "EngineConfig",
    "ServingEngine", "ServingHTTPServer",
    "AdmissionController", "Deadlines", "ServingError", "ShedError",
    "QueueFullError", "MemoryPressureError", "EngineDrainingError",
    "EngineStoppedError",
    "EngineDeadError", "RequestCancelledError", "DeadlineExceededError",
]
