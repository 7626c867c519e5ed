"""Continuous-batching serving engine over the paged KV cache.

The decode loop run_generate compiles is perfect for ONE request; a
serving process needs the loop inverted: a long-lived engine holding
ONE compiled decode step over a fixed batch of SLOTS, with requests
flowing through the slots at token granularity (scheduler.py) and K/V
living in the shared block arena (kv_cache.py). Every engine step is
at most one chunked-prefill dispatch plus one decode dispatch, both at
FIXED shapes — after warmup the steady state is recompile-free, and the
PR-4 compile observatory can prove it (`telemetry.observed_dispatch`
routes both steps through the signature-keyed AOT cache when an
observatory is active).

The loop keeps ONE decode step in flight: `step()` dispatches decode
n+1 before it fetches decode n's tokens, and the continuing slots take
their input tokens from n's output on the device. Everything else n+1
needs (contexts, block tables, sampling counts, which slots end by
`max_new_tokens`) is host arithmetic, so the host's scheduling,
emitting and watching run under the device's time instead of between
two programs. The host waits only where it needs a value: a request
with an EOS runs one step past it and that step's token is discarded.

The engine knows no architecture: a model hands it the per-layer
protocol of `serving/served.py` (embed, layers that each declare a
cache kind and bring a `decode` and a `prefill` over it, the head), and
the arenas, the fork and the sizing follow the layers' cache kinds.
Where a layer keeps rows by REQUEST (a recurrent state, a window
layer's ring of its last keys and values) a request holds one row of
`[max_slots + 1, ...]` arenas from its admission to its release, the steps carry those arenas like the paged ones, and no prefix
index is built: a block of K/V without the state at its boundary cannot
resume a request.

Numerics contract (GPT): the step computes the EXACT math of
`generation.run_generate`'s composed decode path — `models.gpt.ServedGPT`
runs the same Layer objects, the same masked f32-softmax attention
(ops.pallas_decode.paged_decode_attention's gather+dense fallback
mirrors models/gpt._cached_attention), and the engine the same f32
argmax — so a greedy stream through the batched engine is
token-for-token identical to a single run_generate call
(tools/serving_smoke.py gates this in CI). Sampling slots use
per-REQUEST fold_in(token_index) keys, so a sampled stream is also
independent of what else shares the batch.

Metrics: `serving.*` gauges/counters on the process monitor registry —
scrape them from any `telemetry.MetricsServer` or the serving HTTP
front (serving/http.py): queue depth, KV-block utilization, preemption
count. TTFT/TPOT/queue-wait land in streaming log-bucketed HISTOGRAMS
(`serving.ttft_ms`/`tpot_ms`/`queue_wait_ms`, true Prometheus
histogram series — quantiles are computable at scrape time over any
window); the legacy p50/p99 gauges are recomputed from those
histograms at every step and at scrape time, age-stamped by
`serving.slo_gauge_age_s`. Per-request span timelines
(telemetry.reqtrace) ride the attached sink as kind=reqtrace records,
with the slowest-K exemplars on `GET /traces`.
"""
import collections
import contextlib
import functools
import logging
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp

from .. import monitor
from ..analysis import lockwatch
from ..core import autograd
from ..generation import _cast_params
from ..jit import bind_tensors
from ..ops.pallas_decode import (flash_prefill_kv_rows,
                                 paged_decode_kv_rows)
from ..resilience.retry import classify_failure
from ..telemetry.mem_obs import (MemoryObservatory, is_oom,
                                 register_provider)
from ..telemetry.recorder import scope as _scope
from ..telemetry.recorder import span as _telemetry_span
from ..telemetry.reqtrace import RequestTracer
from .kv_cache import (NULL_BLOCK, NULL_ROW, BlockPool, PagedKVCache,
                       PrefixIndex, RowPool)
from .resilience import (AdmissionController, DeadlineExceededError,
                         EngineDeadError, EngineDrainingError,
                         EngineStoppedError, MemoryPressureError,
                         RequestCancelledError, ShedError,
                         restart_backoff)
from .scheduler import (CANCELLED, EXPIRED, FAILED, FINISHED, PREFILL,
                        TERMINAL_STATES, RequestHandle, Request,
                        SamplingParams, Scheduler)
from .served import ChunkView, DecodeView, read_stats, sum_stats

__all__ = ["EngineConfig", "ServingEngine"]

_NEG_INF = -1e30

import itertools as _itertools

_ENGINE_IDS = _itertools.count()

# every span this module writes: `telemetry.span`, cat="serving"
_span = functools.partial(_telemetry_span, cat="serving")


# A decode step that was dispatched and not retired: its tokens (`tok`,
# `logp`) are still on the device. `entries` are the (slot, request)
# pairs it sampled for; `stats` what the layers counted in it and in the
# chunks dispatched before it.
_Flight = collections.namedtuple("_Flight", "tok logp stats entries")


class EngineConfig:
    """Engine shape/capacity knobs. Everything that feeds a compiled
    step shape is fixed here at construction — that is what keeps the
    steady state recompile-free."""

    def __init__(self, max_slots=4, block_size=16, num_blocks=None,
                 max_model_len=None, prefill_chunk=32, dtype="bfloat16",
                 weights="native", kv_memory_mb=None, device=None,
                 max_queue=None, max_restarts=3, restart_backoff_s=1.0,
                 enable_prefix_cache=True, enable_tracing=True,
                 trace_exemplars=32, hbm_budget_mb=None,
                 mem_sample_every=1, engine_id=None):
        if weights not in ("native", "wo8"):
            raise ValueError(f"weights must be 'native' or 'wo8', "
                             f"got {weights!r}")
        self.max_slots = int(max_slots)
        self.block_size = int(block_size)
        self.num_blocks = num_blocks
        self.max_model_len = max_model_len
        self.prefill_chunk = int(prefill_chunk)
        self.dtype = dtype
        self.weights = weights
        self.kv_memory_mb = kv_memory_mb
        self.device = device
        # prefix-sharing KV cache (copy-on-write block reuse across
        # requests). Default ON; off must bit-match the pre-sharing
        # engine — the index is simply never consulted
        self.enable_prefix_cache = bool(enable_prefix_cache)
        # per-request tracing (telemetry.reqtrace): pure host-side span
        # bookkeeping at event boundaries — no traced values, no new
        # compile families; `trace_exemplars` bounds the slowest-K ring
        # the /traces endpoint serves
        self.enable_tracing = bool(enable_tracing)
        self.trace_exemplars = int(trace_exemplars)
        # resilience knobs: bounded waiting queue (None -> 16x slots),
        # warm-restart cap + backoff base for transient step faults
        self.max_queue = 16 * self.max_slots if max_queue is None \
            else int(max_queue)
        self.max_restarts = int(max_restarts)
        self.restart_backoff_s = float(restart_backoff_s)
        # memory observatory: a declared HBM budget (None -> no budget,
        # the observatory still samples but hbm_pressure has no
        # jurisdiction) and the step cadence of ledger snapshots
        self.hbm_budget_mb = hbm_budget_mb
        self.mem_sample_every = max(1, int(mem_sample_every))
        # explicit engine identity for multi-process fleets: the
        # default per-process counter collides across replicas (every
        # child's first engine is 0), and the combined fleet ledger
        # tallies per (rank, engine)
        self.engine_id = None if engine_id is None else int(engine_id)

    @classmethod
    def from_inference_config(cls, config, **overrides):
        """Build from a `paddle_tpu.inference.Config` — the compat
        surface's device/precision switches select real engine
        behavior here (see inference/predictor.py):

        - `disable_gpu()` -> the engine and its KV arenas live on the
          host CPU device;
        - `enable_use_gpu(memory_pool_init_size_mb=N)` -> accelerator
          device, and N megabytes budget the paged-KV arena size;
        - `enable_tensorrt_engine(precision_mode=...)` -> decode
          compute dtype: Int8 -> weight-only-int8 weights with bf16
          activations (the W8A16 serving recipe), Half/Bfloat16 ->
          bf16, Float32 -> the parameters' own dtype;
        - `enable_prefix_cache(False)` -> disables prefix-sharing KV
          block reuse (the engine then bit-matches the cold-cache
          path).
        """
        kw = {}
        if not getattr(config, "_use_tpu", True):
            kw["device"] = jax.devices("cpu")[0]
        kw["enable_prefix_cache"] = bool(
            getattr(config, "_prefix_cache", True))
        pool_mb = getattr(config, "_memory_pool_mb", 0)
        if pool_mb:
            kw["kv_memory_mb"] = int(pool_mb)
        precision = getattr(config, "_serving_precision", None)
        if precision is not None:
            from ..inference.predictor import PrecisionType
            if precision == PrecisionType.Int8:
                kw["weights"] = "wo8"
                kw["dtype"] = "bfloat16"
            elif precision in (PrecisionType.Half, PrecisionType.Bfloat16):
                kw["dtype"] = "bfloat16"
            elif precision == PrecisionType.Float32:
                kw["dtype"] = None
        kw.update(overrides)
        return cls(**kw)


class ServingEngine:
    """submit(prompt, params) -> streaming RequestHandle; step() runs
    one scheduler iteration (one prefill chunk + one decode batch);
    start()/stop() run the loop on a background thread.

    `model.served()` must return the per-layer protocol of
    `serving/served.py`: GPTForPretraining (quantized or not),
    DeepseekV2ForCausalLM, GraniteHybridForCausalLM and
    ExaoneMoeForCausalLM implement it.
    """

    def __init__(self, model, config=None, sink=None, **overrides):
        self.cfg = config or EngineConfig(**overrides)
        cfg = self.cfg
        self.engine_id = next(_ENGINE_IDS) if cfg.engine_id is None \
            else cfg.engine_id
        self._sink = sink               # threadlint: type=JsonlSink
        self.model = model
        if cfg.weights == "wo8":
            from ..quant import quantize_for_decode
            quantize_for_decode(model)
        self.served = served = model.served()
        self.cache_kinds = tuple(l.cache_kind for l in served.layers)
        self.max_model_len = int(cfg.max_model_len or served.max_seq_len)
        self.block_size = cfg.block_size
        self.max_blocks_per_seq = PagedKVCache.blocks_for_tokens(
            self.max_model_len, self.block_size)
        self._compute_dtype = cfg.dtype or served.dtype
        # "kv", "latent", "kv+state", ..., for the dispatch spans
        self._cache_kind_names = "+".join(sorted(
            {k.name for k in self.cache_kinds}))

        if cfg.device is not None:
            # serve from the configured device: move the weights once
            # (the tools/serve_13b_w8a16.py recipe), arenas follow
            for p in model.parameters():
                p._value = jax.device_put(p._value, cfg.device)
            for b in model.buffers():
                if b is not None:
                    b._value = jax.device_put(b._value, cfg.device)

        num_blocks = self._resolve_num_blocks()
        self.pool = BlockPool(num_blocks)   # guarded by: _mu
        # some layer keeps rows by request: one row a request beside its
        # blocks, as many rows as slots (admission bounds running +
        # prefilling by max_slots)
        row_names = sorted({k.name for k in self.cache_kinds
                            if k.by_request})
        self.rows = RowPool(cfg.max_slots, row_names) \
            if row_names else None      # guarded by: _mu
        # positions a window layer's ring holds (0: the model has none)
        self._window = max(k.window for k in self.cache_kinds)
        with self._on_device():
            self.cache = PagedKVCache(   # guarded by: _mu
                self.cache_kinds, num_blocks, self.block_size,
                dtype=self._compute_dtype,
                request_rows=cfg.max_slots if self.rows else 0)
        # a block of K/V without the state at its boundary cannot resume
        # a request, and the index cannot snapshot a state: a model that
        # keeps rows by request gets no prefix match
        if self.rows and cfg.enable_prefix_cache:
            logging.getLogger(__name__).info(
                "engine %d: the model keeps per-request state; the prefix "
                "cache is off whatever enable_prefix_cache says",
                self.engine_id)
        # guarded by: none (immutable ref; entries mutate under _mu)
        self.prefix_index = (
            PrefixIndex(self.block_size, pool=self.pool)
            if cfg.enable_prefix_cache and not self.rows else None)
        # the Scheduler object carries no lock of its own: every one of
        # its methods runs under the engine lock (its class line says
        # `# guarded by: ServingEngine._mu`); the REFERENCE never moves
        self.sched = Scheduler(self.pool, self.block_size, cfg.max_slots,
                               self.max_model_len,
                               prefix_index=self.prefix_index,
                               row_pool=self.rows)

        named = list(model.named_parameters()) + [
            (n, b) for n, b in model.named_buffers() if b is not None]
        self._bound = [p for _, p in named]
        self._build_fns()

        # the engine lock IS the step serializer: one dispatch at a
        # time by design, so device calls under it are expected
        # (lockwatch proxies when armed; raw RLock otherwise)
        self._mu = lockwatch.make_rlock("ServingEngine._mu")  # threadlint: dispatch-lock
        self._cv = lockwatch.make_condition("ServingEngine._cv", self._mu)
        self._thread = None     # guarded by: none (start/stop confined; racy is_alive probes ok)
        self._stopping = False  # guarded by: none (one-way flag; loop re-reads each iteration)
        self._stopped = False   # guarded by: none (stop-path flag, set without the lock by design)
        self._draining = False  # guarded by: _mu
        self._dead = False      # guarded by: _mu
        self._restarts = 0      # guarded by: none (stepping-thread confined) — CONSECUTIVE failed-step restarts
        self._sleep = time.sleep        # injectable (tests pin backoff)
        self._join_timeout_s = 30.0     # stop(): loop-join bound
        self._stop_lock_timeout_s = 5.0  # stop(): wedged-lock bound
        self.admission = AdmissionController(  # guarded by: _mu
            cfg.max_queue, cfg.max_slots)
        self._counts = {"admitted": 0, "finished": 0, "failed": 0,  # guarded by: _mu
                        "cancelled": 0, "expired": 0, "shed": 0}
        # latency lives in streaming log-bucketed histograms on the
        # monitor registry (scraped as true Prometheus histograms);
        # the legacy p50/p99 gauges are recomputed from them — at every
        # step AND at scrape time (refresh_latency_gauges), so a
        # stalled engine can no longer serve percentiles frozen at the
        # last finished request. `_last_latency_obs` age-stamps them.
        self._last_latency_obs = None   # guarded by: _mu
        self.tracer = (  # threadlint: type=RequestTracer  # guarded by: none (immutable ref; tracer is self-locked)
            RequestTracer(engine_id=self.engine_id, sink=sink,
                          exemplar_k=cfg.trace_exemplars)
            if cfg.enable_tracing else None)
        self.kv_peak_utilization = 0.0  # guarded by: _mu
        # prefix-cache accounting: offered = positions each admission
        # would have to prefill cold, saved = positions a cache hit
        # covered instead (saved <= offered by construction — the
        # trace_check cross-rule pins it)
        self._prefix_stats = {"lookups": 0, "hits": 0,  # guarded by: _mu
                              "tokens_saved": 0, "tokens_offered": 0}
        # memory observatory: live HBM ledger + KV occupancy telemetry
        # sampled every `mem_sample_every` steps; its headroom gauge is
        # what submit()'s admission consult reads. Always constructed —
        # without a declared budget it still ledgers and reconciles,
        # it just has no hbm_pressure jurisdiction.
        self.mem_obs = MemoryObservatory(  # guarded by: _mu
            sink=sink,
            hbm_budget_bytes=(int(cfg.hbm_budget_mb) * 2 ** 20
                              if cfg.hbm_budget_mb else None),
            kv_source=self._kv_accounting,
            engine=self.engine_id)
        # a serving process has no optimizer to tag the weights, so
        # the engine tags its own bound leaves (params + buffers) —
        # queried fresh each snapshot, so a quantize/device_put swap
        # is re-attributed automatically
        register_provider(
            "engine.weights", "params", self,
            lambda eng: [p._value for p in eng._bound
                         if getattr(p, "_value", None) is not None])
        self._steps = 0                 # guarded by: _mu
        self._stats_pending = []        # guarded by: _mu
        self._in_flight = None          # guarded by: _mu — the _Flight not retired yet
        self._voided = []               # guarded by: _mu — requeued by _void_in_flight
        monitor.set_gauge("serving.kv_blocks_total", self.pool.capacity)
        monitor.set_gauge("serving.draining", 0)
        self._update_gauges()

    # ------------------------------------------------------------------
    # sizing
    # ------------------------------------------------------------------
    def _resolve_num_blocks(self):
        cfg = self.cfg
        if cfg.num_blocks is not None:
            return int(cfg.num_blocks)
        if cfg.kv_memory_mb:
            n = int(cfg.kv_memory_mb) * 2 ** 20 // self._block_bytes()
            return max(2, n)
        # default: every slot can hold a full-length sequence (+ null)
        return cfg.max_slots * self.max_blocks_per_seq + 1

    def _block_bytes(self):
        """Bytes a block costs over all layers, by their cache kinds."""
        return PagedKVCache.block_bytes(self.cache_kinds, self.block_size,
                                        self._compute_dtype)

    # ------------------------------------------------------------------
    # compiled step functions
    # ------------------------------------------------------------------
    def _build_fns(self):
        served = self.served
        bound = self._bound
        dtype = self.cfg.dtype
        bs_blk = self.block_size
        mb = self.max_blocks_per_seq
        C = self.cfg.prefill_chunk

        def run_layers(h, k_pages, v_pages, step, view):
            """Every layer's `step` (its decode or its prefill) over its
            own arenas: (h, new K arenas, new V arenas, summed stats)."""
            new_k, new_v, stats = [], [], []
            for li, layer in enumerate(served.layers):
                h, (kp, vp), st = getattr(layer, step)(
                    h, (k_pages[li], v_pages[li]), view)
                new_k.append(kp)
                new_v.append(vp)
                stats.append(st)
            return h, tuple(new_k), tuple(new_v), sum_stats(stats)

        def select(last, rngs, temp, top_k, top_p, greedy,
                   sampling=True):
            """Per-slot token selection: run_generate's _make_selector
            math with the knobs as ARRAYS (one compiled program serves
            every per-request sampling config). temperature division is
            exact for 1.0, dynamic top-k via the k-th order statistic,
            dynamic top-p via the same sorted-cumsum mask.

            sampling=False builds the GREEDY-ONLY program — no sorts,
            no rng: the sort/categorical machinery measures ~1/3 of the
            whole decode step on the CPU smoke, and a decode batch whose
            active slots are all greedy shouldn't pay it (the engine
            dispatches the variant per step; each compiles once)."""
            V = last.shape[-1]
            lg = last.astype(jnp.float32) / temp[:, None]
            greedy_tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            if not sampling:
                tok = greedy_tok
            else:
                sorted_desc = jnp.sort(lg, axis=-1)[:, ::-1]
                k_eff = jnp.where(top_k > 0, jnp.clip(top_k, 1, V), V)
                kth = jnp.take_along_axis(sorted_desc,
                                          (k_eff - 1)[:, None], 1)
                lg_s = jnp.where(lg < kth, _NEG_INF, lg)
                sort_idx = jnp.argsort(-lg_s, axis=-1)
                sorted_logits = jnp.take_along_axis(lg_s, sort_idx, axis=-1)
                probs = jax.nn.softmax(sorted_logits, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                keep = (cum - probs) < top_p[:, None]  # top tok always kept
                masked = jnp.where(keep, sorted_logits, _NEG_INF)
                inv = jnp.argsort(sort_idx, axis=-1)
                lg_s = jnp.take_along_axis(masked, inv, axis=-1)
                sampled = jax.vmap(jax.random.categorical)(rngs, lg_s) \
                    .astype(jnp.int32)
                tok = jnp.where(greedy, greedy_tok, sampled)
            logp = jax.nn.log_softmax(last.astype(jnp.float32), axis=-1)
            tok_logp = jnp.take_along_axis(logp, tok[:, None], 1)[:, 0]
            return tok, tok_logp

        def decode_step(param_vals, k_pages, v_pages, tokens, ctx,
                        tables, rows=None, use_kernel=None):
            """Last-position logits [S, V] of one decode step, the
            updated arenas and the layers' stats. rows [S]: the slots'
            request rows, where the model keeps any. use_kernel rides
            through to the layers' attention (None = its platform
            gate); only chip_smoke.py and the tests pass it, to hold
            the fused kernel against the gather+dense path on the same
            step."""
            with _scope("cast"):
                param_vals = _cast_params(param_vals, dtype)
            with autograd.fresh_tape(), autograd.no_grad(), \
                    bind_tensors(bound, param_vals):
                with _scope("embed"):
                    h = served.embed(tokens[:, None], ctx[:, None])
                blk = jnp.take_along_axis(
                    tables, (ctx // bs_blk)[:, None], axis=1)[:, 0]
                view = DecodeView(blk, ctx % bs_blk, tables, ctx, ctx > 0,
                                  use_kernel, rows)
                h, new_k, new_v, stats = run_layers(
                    h, k_pages, v_pages, "decode", view)
                with _scope("head"):
                    last = served.head(h)[:, -1]
            return last, new_k, new_v, stats

        def decode_logits(*args, **kw):
            return decode_step(*args, **kw)[:3]

        def decode_fn(param_vals, k_pages, v_pages, tokens, ctx, tables,
                      keys, counts, temp, top_k, top_p, greedy, rows=None,
                      sampling=True):
            last, new_k, new_v, stats = decode_step(
                param_vals, k_pages, v_pages, tokens, ctx, tables, rows)
            with _scope("sample"):
                rngs = jax.vmap(jax.random.fold_in)(keys, counts) \
                    if sampling else keys
                tok, logp = select(last, rngs, temp, top_k, top_p,
                                   greedy, sampling=sampling)
            return tok, logp, new_k, new_v, stats

        def prefill_step(param_vals, k_pages, v_pages, ids, p0, n_real,
                         table_row, row=None, use_kernel=None):
            """Logits [1, V] at the chunk's last REAL position, the
            updated arenas and the layers' stats, for one chunk of ONE
            request: ids [1, C] (tail past n_real is padding ->
            null-block writes), positions p0..p0+C-1; row: the request's
            row, where the model keeps any. use_kernel as in
            decode_step."""
            with _scope("cast"):
                param_vals = _cast_params(param_vals, dtype)
            with autograd.fresh_tape(), autograd.no_grad(), \
                    bind_tensors(bound, param_vals):
                positions = p0 + jnp.arange(C, dtype=jnp.int32)
                with _scope("embed"):
                    h = served.embed(ids, positions[None])
                tmask = jnp.arange(C, dtype=jnp.int32) < n_real
                blk = jnp.where(
                    tmask,
                    table_row[jnp.clip(positions // bs_blk, 0, mb - 1)],
                    NULL_BLOCK)
                view = ChunkView(blk, positions % bs_blk, table_row, p0,
                                 n_real, positions, tmask, use_kernel, row)
                h, new_k, new_v, stats = run_layers(
                    h, k_pages, v_pages, "prefill", view)
                with _scope("head"):
                    last = served.head(h, at=n_real - 1)[:, -1]
            return last, new_k, new_v, stats

        def prefill_logits(*args, **kw):
            return prefill_step(*args, **kw)[:3]

        def prefill_fn(param_vals, k_pages, v_pages, ids, p0, n_real,
                       table_row, key, count, temp, top_k, top_p, greedy,
                       row=None):
            """One prefill chunk; also samples the next token from the
            last REAL position — used only when the host knows this
            was the final chunk."""
            last, new_k, new_v, stats = prefill_step(
                param_vals, k_pages, v_pages, ids, p0, n_real, table_row,
                row)
            with _scope("sample"):
                rngs = jax.random.fold_in(key, count)[None]
                tok, logp = select(last, rngs, temp[None], top_k[None],
                                   top_p[None], greedy[None])
            return tok[0], logp[0], new_k, new_v, stats

        def fork_fn(k_pages, v_pages, src, dst):
            """Copy-on-write fork: duplicate physical block `src` into
            `dst` across every paged arena of every layer (all rows —
            positions the forking request has not covered yet stay
            masked by its context length until it overwrites them)."""
            def fork(pages):
                return tuple(
                    a if a is None or kind.by_request
                    else a.at[dst].set(a[src])
                    for a, kind in zip(pages, self.cache_kinds))
            return fork(k_pages), fork(v_pages)

        def merge_fn(prev_tok, host_tok, from_host):
            """The input tokens of a decode step whose batch changed:
            the continuing slots' are the output of the step in flight,
            still on the device; a slot placed or replayed since has
            its on the host."""
            return jnp.where(from_host, host_tok, prev_tok)

        self._decode_logits = decode_logits
        self._prefill_logits = prefill_logits
        # compiled here, ahead of time: the first batch to change
        # membership with a step in flight must not compile anything
        slot = functools.partial(jax.ShapeDtypeStruct,
                                 (self.cfg.max_slots,))
        with self._on_device():
            self._merge_tokens = jax.jit(merge_fn).lower(
                slot(jnp.int32), slot(jnp.int32), slot(jnp.bool_)).compile()
            # stands in for the output of a step when none is in flight
            self._no_tokens = jnp.zeros((self.cfg.max_slots,), jnp.int32)
        donate = (1, 2) if jax.default_backend() == "tpu" else ()
        def decode_greedy_fn(*args):
            return decode_fn(*args, sampling=False)

        # named functions, not partials: a device trace's `XLA Modules`
        # line reads jit_decode_fn / jit_decode_greedy_fn /
        # jit_prefill_fn
        self._decode_jit = jax.jit(decode_fn, donate_argnums=donate)
        self._decode_greedy_jit = jax.jit(decode_greedy_fn,
                                          donate_argnums=donate)
        self._prefill_jit = jax.jit(prefill_fn, donate_argnums=donate)
        self._fork_jit = jax.jit(
            fork_fn,
            donate_argnums=(0, 1) if jax.default_backend() == "tpu"
            else ())
        # one request's row of every request-row arena (`request_rows`)
        def rows_fn(arenas, row):
            return [a[row] for a in arenas]

        self._rows_jit = jax.jit(rows_fn)

    def _on_device(self):
        """Allocate and compile for the configured device, where one
        is configured."""
        return jax.default_device(self.cfg.device) \
            if self.cfg.device is not None else contextlib.nullcontext()

    def _dispatch(self, family, jitted, args):
        """Route through the PR-4 compile observatory when one is
        active: every (re)compile of the serving steps becomes a
        kind=compile record with a cause diff, and the recompile-free
        steady state is checkable from the telemetry alone."""
        from ..telemetry import observed_dispatch
        return observed_dispatch(family, jitted, args)

    # ------------------------------------------------------------------
    # submission / admission control
    # ------------------------------------------------------------------
    def submit(self, prompt_ids, params=None, deadlines=None,
               priority="normal", request_id=None, replay_tokens=None,
               **kw):
        """Queue one generation; returns a RequestHandle whose
        `.tokens()` stream yields ids as the engine emits them.

        `deadlines` (resilience.Deadlines) are server-side budgets the
        scheduler enforces at step boundaries; `priority` orders the
        bounded waiting queue ('interactive' | 'normal' | 'batch').
        `request_id` is the stable client-visible id echoed on every
        stream event and telemetry record (defaults to
        'e<engine>-r<rid>'); `replay_tokens` seeds a FAILOVER REPLAY —
        tokens another replica already streamed before dying. They are
        treated exactly like a preemption's kept tokens: prefill
        recomputes their K/V (riding the prefix cache) and decode
        resumes at fold_in(base, len(replay_tokens)), so the continued
        stream is token-identical to an uninterrupted run. The handle's
        stream yields only the NEW tokens (the replayed ones are
        already on the client's wire).
        Raises `ShedError`/`QueueFullError` (429 + Retry-After at the
        HTTP front) when admission control rejects the request up
        front, `EngineDrainingError` during a graceful drain, and
        `EngineStoppedError`/`EngineDeadError` when there is no engine
        left to serve it."""
        params = params or SamplingParams(**kw)
        if params.seed is not None:
            base = jax.random.PRNGKey(int(params.seed))
        elif params.greedy:
            base = jax.random.PRNGKey(0)    # unused by greedy slots
        else:
            from ..core.random import default_generator
            base = default_generator().split()
        req = Request(prompt_ids, params, np.asarray(base),
                      deadlines=deadlines, priority=priority,
                      request_id=request_id)
        if req.request_id is None:
            req.request_id = f"e{self.engine_id}-r{req.rid}"
        if replay_tokens:
            replay = [int(t) for t in replay_tokens]
            if len(replay) >= params.max_new_tokens:
                raise ValueError(
                    f"replay_tokens carries {len(replay)} token(s) but "
                    f"max_new_tokens is {params.max_new_tokens} — "
                    "nothing left to stream")
            if params.eos_token_id is not None and \
                    int(params.eos_token_id) in replay:
                raise ValueError(
                    "replay_tokens contains eos_token_id — the stream "
                    "already terminated")
            # direct assignment, NOT push_token: these tokens are
            # already on the client's wire — they must not enter this
            # handle's stream queue or stamp first_token_time
            req.out_tokens = replay
        with _span("serving_submit", rid=req.rid):
            wait = _span("serving_submit.lock_wait").begin()
            with self._cv:
                wait.end()
                if self._dead:
                    raise EngineDeadError(
                        "engine is dead (warm-restart attempts exhausted)")
                if self._stopping or self._stopped:
                    raise EngineStoppedError("engine is stopped")
                if self._draining:
                    raise EngineDrainingError(
                        "engine is draining (admission stopped)",
                        retry_after_s=5.0)
                self.sched.validate(req)        # client error, not load
                try:
                    self._check_mem_headroom()
                    self.admission.admit_or_raise(req, self.sched.waiting)
                except ShedError as e:
                    self._counts["shed"] += 1
                    monitor.incr("serving.shed")
                    self._record("shed", rid=req.rid,
                                 request_id=req.request_id,
                                 queue_depth=e.queue_depth,
                                 predicted_wait_ms=e.predicted_wait_ms,
                                 retry_after_s=e.retry_after_s,
                                 reason=type(e).reason,
                                 priority=req.priority_class)
                    if self.tracer is not None:
                        # the shed verdict IS this request's trace
                        self.tracer.record_shed(
                            req, time.monotonic(),
                            queue_depth=e.queue_depth,
                            reason=type(e).reason)
                    raise
                if self.tracer is not None:
                    req.trace = self.tracer.start(req.rid, req.submit_time)
                self.sched.enqueue(req)     # validated above, by design
                self._counts["admitted"] += 1
                monitor.incr("serving.requests")
                monitor.incr("serving.admitted")
                self._record("admitted", rid=req.rid,
                             request_id=req.request_id,
                             queue_depth=len(self.sched.waiting),
                             priority=req.priority_class,
                             queue_deadline_ms=self._queue_deadline_ms(req),
                             replayed=len(req.out_tokens) or None)
                self._update_gauges()
                self._cv.notify_all()
        return RequestHandle(req, engine=self)

    def cancel(self, req):
        """Cancel `req` (RequestHandle.cancel lands here): finalized
        immediately — the engine lock serializes against steps, so the
        slot and KV blocks go back to the pool right now, and the
        stream terminates with `RequestCancelledError`."""
        with self._cv:
            if req.state in TERMINAL_STATES:
                return False
            req.cancel_requested = True
            self._finalize(
                req, CANCELLED, "cancelled",
                exc=RequestCancelledError(
                    f"request {req.rid} cancelled after "
                    f"{len(req.out_tokens)} token(s)"),
                counter="serving.cancelled")
            self._update_gauges()
            self._cv.notify_all()
        return True

    def request_rows(self, handle):
        """What a live request keeps by row (`CacheKind.request_rows`: a
        recurrent layer's state): `(ids, rows)`, `ids` the tokens whose
        positions the rows have taken in and `rows` {layer: (array,
        ...)} over the layers that keep rows, each array the request's
        row sliced out of its arena on the device (the caller copies
        what it wants to the host). None where the model keeps no rows
        or the request holds none (waiting, preempted, finished). The
        step in flight is retired first, so the rows are those after
        `ids` and no token later. For inspection and for checks against
        a reference: it takes the engine's lock and `request_bytes` of
        device memory."""
        req = handle._req
        with self._mu:
            if req.row is None:
                return None
            self._flush()
            if req.row is None or not req.n_prefilled:
                return None     # its last token came with the flush
            ids = np.asarray(req.tokens_all[:req.n_prefilled], np.int32)
            at = [(layer, len(kind.request_rows))
                  for layer, kind in enumerate(self.cache.kinds)
                  if kind.by_request]
            got = iter(self._rows_jit(
                [a for layer, n in at for a in
                 (self.cache.k[layer], self.cache.v[layer])[:n]],
                np.int32(req.row)))
        return ids, {layer: tuple(next(got) for _ in range(n))
                     for layer, n in at}

    # ------------------------------------------------------------------
    # the engine loop
    # ------------------------------------------------------------------
    def step(self):
        """One scheduler iteration: reap (cancellations + deadlines),
        admit, at most one prefill chunk, one decode batch. Returns
        True when any work was done.

        The decode batch is dispatched BEFORE the previous call's is
        fetched: this call schedules, grows blocks, builds and
        dispatches decode n+1 while n runs, and only then waits for n's
        tokens, emits them, finalizes, and samples memory and gauges —
        all under n+1's time on the device. A final chunk's first token
        is fetched in the same late phase and its request takes a slot
        then, so no wait stands between two dispatches.

        The whole iteration, the wait for the engine's lock included,
        is one `serving_step` telemetry span whose children name its
        phases (`serving_step.lock_wait`, `.schedule`, `.blocks`,
        `.build`, `serving_dispatch`, `.fetch`, `.emit`,
        `.mem_snapshot`, `.gauges`): a lane next to the per-request
        lanes in the Chrome export, and under a running `jax.profiler`
        trace a line of the XPlane beside the device's ops, where they
        say what the host did while the device idled."""
        with _span("serving_step") as whole:
            wait = _span("serving_step.lock_wait").begin()
            with self._mu:
                wait.end()
                whole.set(step=self._steps)
                try:
                    self._schedule()
                    prev = self._in_flight
                    did, first = self._prefill_one()
                    self._in_flight = self._decode_dispatch(prev)
                    did = self._retire(prev, first) or did \
                        or self._in_flight is not None
                except BaseException:
                    self._void_in_flight()
                    raise
                self._steps += 1
                if self._steps % self.cfg.mem_sample_every == 0:
                    with _span("serving_step.mem_snapshot"):
                        try:
                            self.mem_obs.snapshot(self._steps,
                                                  device=self.cfg.device)
                        except Exception:
                            pass    # the ledger must never take a step down
                with _span("serving_step.gauges"):
                    self._update_gauges()
                return did

    def _schedule(self):     # requires: _mu
        """The step's host-only head: reap, admit, and the accounting
        of what was admitted (prefix hits, queue-wait samples)."""
        with _span("serving_step.schedule") as sp:
            now = time.monotonic()
            self._reap(now)
            admitted = self.sched.admit(now=now)
            if self.prefix_index is not None:
                ps = self._prefix_stats
                for req in admitted:
                    ps["lookups"] += 1
                    ps["tokens_offered"] += len(req.tokens_all)
                    if req.prefix_cached_tokens:
                        ps["hits"] += 1
                        ps["tokens_saved"] += req.prefix_cached_tokens
                        monitor.incr("serving.prefix_hits")
            depth = len(self.sched.waiting)
            for req in admitted:
                if req.trace is not None:
                    req.trace.note_admit(
                        now, queue_depth=depth,
                        prefix_cached_tokens=req.prefix_cached_tokens)
                # sample only FIRST admissions (admit stamped them with
                # this step's clock): a preempted/requeued request keeps
                # its original admit_time, and re-observing that frozen
                # wait would double-count it in the histogram
                if req.admit_time != now:
                    continue
                qw = req.queue_wait_ms()
                if qw is not None:
                    monitor.observe_hist("serving.queue_wait_ms", qw)
                    self._last_latency_obs = now
            sp.set(admitted=len(admitted), waiting=depth)

    def _reap(self, now=None):     # requires: _mu
        """Step-boundary enforcement of cancellation + server-side
        deadlines: every reaped request releases its slot and KV
        blocks to the pool IMMEDIATELY and its stream terminates with
        a typed error — never a hang."""
        for req, why in self.sched.reap(now):
            if why == "cancelled":
                self._finalize(
                    req, CANCELLED, "cancelled",
                    exc=RequestCancelledError(
                        f"request {req.rid} cancelled after "
                        f"{len(req.out_tokens)} token(s)"),
                    counter="serving.cancelled")
            else:
                self._finalize(
                    req, EXPIRED, "expired",
                    exc=DeadlineExceededError(
                        f"request {req.rid} blew its {why} deadline "
                        f"({req.deadlines!r})", which=why),
                    counter="serving.deadline_exceeded", reason=why)

    def _has_work(self):     # requires: _mu
        """Requests to serve, or a dispatched step whose tokens nobody
        fetched yet: what `sched.has_work()` cannot see."""
        return self._in_flight is not None or self.sched.has_work()

    def _void_in_flight(self):     # requires: _mu
        """A step raised. Errors surface a step late — at the fetch of
        step n with step n+1 already queued behind it — so the tokens of
        up to two decode steps and of a last chunk are on the device and
        will never be fetched. Drop them, and take every request back to
        the newest token the host holds: the positions past it are
        computed again, to the same K/V and (by `fold_in(key, count)`)
        the same samples, so whoever steps again — the serve loop after
        `_on_step_error`, or a caller that drives `step()` by hand —
        loses no token and repeats none."""
        self._in_flight = None
        self._stats_pending.clear()
        if self.rows:
            # a position computed twice moves a recurrent state twice,
            # and a ring that took in a dropped step's position has lost
            # the oldest key of the position to compute again: such a
            # model's requests give their rows back and replay from
            # position 0 (oldest first at the waiting front).
            # `_on_step_error` still counts them among the step's
            # requests: a permanent fault fails them
            self._voided = list(self.sched.admit_order)
            for req in reversed(self._voided):
                self.sched.requeue(req)
            return
        for req in self.sched.prefilling + \
                [r for r in self.sched.running if r is not None]:
            req.n_prefilled = min(req.n_prefilled, len(req.tokens_all) - 1)

    def _flush(self):     # requires: _mu
        """Retire the step in flight outside `step()`: the callers that
        hand the engine back (run_until_idle, stop, emit_quiesce) leave
        nothing on the device unfetched. Writes no `serving_step.*`
        span: those belong to the loop's thread."""
        flight, self._in_flight = self._in_flight, None
        if flight is not None:
            try:
                self._emit_flight(flight, *self._fetch_flight(flight))
            except BaseException:
                self._void_in_flight()
                raise

    def run_until_idle(self, max_steps=None):
        n = 0
        while max_steps is None or n < max_steps:
            with self._mu:
                if not self._has_work():
                    return n
            self.step()
            n += 1
        with self._mu:
            self._flush()   # cut short by max_steps: nothing stays in flight
            self._update_gauges()
        return n

    def start(self):    # threadlint: lock-free (caller-serialized lifecycle; flags are none-guarded)
        if self._thread is not None and self._thread.is_alive():
            return self
        if self._dead:
            raise EngineDeadError(
                "engine is dead (warm-restart attempts exhausted); "
                "build a fresh ServingEngine")
        self._stopping = False
        self._stopped = False
        self._thread = threading.Thread(
            target=self._serve_loop, name="paddle-tpu-serving-engine",
            daemon=True)
        self._thread.start()
        return self

    def stop(self):     # threadlint: lock-free (manual bounded acquires — see body comments)
        """Stop the serve loop, then FAIL every request still queued or
        in flight with `EngineStoppedError` — a submitter blocked on a
        handle must get a clean error, never hang forever on a stream
        no loop will ever feed again."""
        # the flag is set WITHOUT the engine lock (a wedged step could
        # hold it indefinitely; the loop re-reads the flag each
        # iteration, and an idle loop self-wakes from its 0.1s wait) —
        # the notify is best-effort within the bounded window
        self._stopping = True
        if self._mu.acquire(timeout=self._stop_lock_timeout_s):
            try:
                self._cv.notify_all()
            finally:
                self._mu.release()
        t = self._thread
        joined = True
        if t is not None:
            t.join(timeout=self._join_timeout_s)
            if t.is_alive():
                # join timed out (e.g. mid-compile): keep the reference
                # so a later start() cannot race a SECOND loop against
                # this one — the stale loop exits at its next _stopping
                # check, and start() stays a no-op until it has
                joined = False
            else:
                self._thread = None
        # the engine lock serializes against any stale loop's last
        # step. When the join timed out that step may be WEDGED holding
        # the lock, so only wait a bounded extra window for it — a
        # stop() that can hang forever is worse than leaving the
        # leftovers for a later stop() once the wedged step returns
        if not self._mu.acquire(
                timeout=-1 if joined else self._stop_lock_timeout_s):
            self._stopped = True
            return joined
        try:
            self._stopped = True
            try:
                self._flush()   # the loop's last step: its tokens are real
            except Exception:   # noqa: BLE001 — stop() must get to the end
                pass
            leftovers = (list(self.sched.waiting)
                         + list(self.sched.prefilling)
                         + [r for r in self.sched.running
                            if r is not None])
            for req in leftovers:
                self._finalize(
                    req, FAILED, "failed",
                    error="engine stopped before the request finished",
                    exc=EngineStoppedError(
                        f"request {req.rid}: engine stopped before the "
                        "request finished"),
                    counter="serving.failed")
            if leftovers:
                self._update_gauges()
        finally:
            self._mu.release()
        return joined

    # ------------------------------------------------------------------
    # graceful drain
    # ------------------------------------------------------------------
    @property
    def draining(self):     # threadlint: lock-free (racy scrape by design)
        return self._draining

    @property
    def dead(self):     # threadlint: lock-free (racy scrape by design)
        return self._dead

    def drain(self, timeout=None):
        """Graceful drain: stop admission (submit raises
        `EngineDrainingError`; the HTTP front answers 503-draining on
        /healthz while /livez stays green), finish every request
        already accepted — queued AND running — then emit the quiesce
        record. Returns True when fully drained, False on timeout
        (admission stays stopped either way; `resume_admission()`
        reopens it, e.g. after a warm restart completes)."""
        with self._cv:
            self._draining = True
            monitor.set_gauge("serving.draining", 1)
            self._record("drain_begin",
                         queue_depth=len(self.sched.waiting),
                         running=self.sched.num_running())
            self._cv.notify_all()
        t0 = time.monotonic()
        loop_alive = self._thread is not None and self._thread.is_alive()
        if loop_alive:
            while True:
                with self._cv:
                    if not self._has_work() or self._dead:
                        break
                    self._cv.wait(timeout=0.05)
                if timeout is not None and \
                        time.monotonic() - t0 > timeout:
                    self._record("drain_end", completed=False,
                                 drained_ms=(time.monotonic() - t0)
                                 * 1000.0)
                    return False
        else:
            self.run_until_idle()
        with self._mu:
            completed = not self._has_work()
        if completed and self.prefix_index is not None:
            # a drain precedes a restart or shutdown: the arenas (and
            # their physical ids) do not survive it, so the index must
            # not either — quiesce also proves zero retained blocks
            with self._mu:
                self.prefix_index.flush()
                self._update_gauges()
        self._record("drain_end", completed=bool(completed),
                     drained_ms=(time.monotonic() - t0) * 1000.0)
        self.emit_quiesce()
        return completed

    def resume_admission(self):
        """Reopen admission after a drain (warm-restart complete)."""
        with self._cv:
            self._draining = False
            monitor.set_gauge("serving.draining", 0)
            self._cv.notify_all()

    def emit_quiesce(self):
        """Emit the kind=serving quiesce record: the request-accounting
        ledger (admitted must equal finished+failed+cancelled+expired —
        tools/trace_check.py enforces it) plus the pool's allocation
        count (must be zero — a leak here is a dropped request)."""
        with self._mu:
            self._flush()
            ps = self._prefix_stats
            offered = ps["tokens_offered"]
            self._record("quiesce", kv_blocks_used=self.pool.num_used,
                         queue_depth=len(self.sched.waiting),
                         counts=dict(self._counts),
                         # prefix-cache audit: zero shared refs at
                         # quiesce (all requests terminal -> nobody
                         # references anything), hit-rate in [0, 1],
                         # saved <= offered — trace_check cross-rules
                         prefix_blocks_shared=self.pool.num_shared,
                         prefix_hit_rate=(
                             ps["tokens_saved"] / offered
                             if offered else 0.0),
                         prefill_tokens_saved=ps["tokens_saved"],
                         prefill_tokens_offered=offered)

    def _serve_loop(self):
        while True:
            with self._cv:
                if self._stopping:
                    return
                if not self._has_work():
                    self._cv.wait(timeout=0.1)
                    continue
            try:
                did = self.step()
            except Exception as e:      # noqa: BLE001 — long-lived loop
                # a dead serve thread strands every open stream forever;
                # classify the failure and warm-restart (transient) or
                # fail the in-flight work loudly (permanent)
                alive, backoff = self._on_step_error(e)
                if not alive:
                    return
                if backoff:
                    self._sleep(backoff)
                continue
            with self._cv:
                self._cv.notify_all()   # wake drain()/result() waiters
            if not did:
                # work exists but none runnable (prefill waiting on
                # blocks): don't spin the lock hot
                time.sleep(0.002)

    def _rebuild_arenas(self):     # requires: _mu
        """Fresh pool + fresh K/V arenas: after a failed step the
        donated buffers are suspect, and every surviving request holds
        zero blocks by construction (failed or requeued). The prefix
        index MUST flush and rebind here — its physical block ids name
        the old arenas' storage, and a stale entry surviving a rebuild
        would splice garbage K/V into a later request's attention
        (tools/serving_smoke.py --selfcheck proves the tripwire)."""
        if self.prefix_index is not None:
            self.prefix_index.flush()
        self.pool = BlockPool(self.pool.num_blocks)
        self.sched.pool = self.pool
        if self.rows:
            self.rows = self.sched.row_pool = RowPool(self.cfg.max_slots,
                                                      self.rows.names)
        if self.prefix_index is not None:
            self.prefix_index.bind(self.pool)
        with self._on_device():
            self.cache = self.cache.fresh()
        self._stats_pending.clear()     # counts of the failed step's arrays

    def _on_step_error(self, exc):
        """A compiled step raised mid-flight (device OOM, runtime
        error): the in-flight requests' KV state — and, under donation,
        the arenas themselves — are suspect. Rides
        `resilience.retry.classify_failure`:

        - PERMANENT (a programming error): recompute-replay would hit
          the identical bug, so fail every ACTIVE request with the
          error (their streams raise instead of hanging), rebuild the
          arenas clean, and keep serving the queued requests;
        - TRANSIENT / INFRA: warm restart — rebuild the arenas and
          REQUEUE the in-flight requests for recompute-replay (the
          eviction invariant guarantees their streams replay
          token-identically), with bounded attempts + backoff; past
          `max_restarts` consecutive failures the engine declares
          itself DEAD and fails everything outstanding.

        Returns (keep_serving, backoff_s). Manual step() callers see
        the exception raw — this path is the background loop's."""
        import traceback
        monitor.incr("serving.engine_errors")
        msg = f"{type(exc).__name__}: {exc}"
        kind = classify_failure(exc)
        traceback.print_exc()
        with self._mu:
            # `step()` voided what it had in flight when it raised (the
            # failed step n and the step n+1 queued behind it): no token
            # of either is fetched, and the requests below are failed,
            # or requeued and replay them
            if is_oom(exc):
                # capture-on-failure: write the postmortem BEFORE the
                # arena rebuild below frees the evidence (the ledger
                # walk itself allocates nothing on device)
                try:
                    self.mem_obs.capture_postmortem(
                        msg, step=self._steps, device=self.cfg.device)
                except Exception:
                    pass  # forensics must never mask the real failure
            active = [r for r in self.sched.admit_order + self._voided
                      if r.state not in TERMINAL_STATES]
            self._voided = []
            if kind == "permanent":
                for req in active:
                    self._finalize(req, FAILED, "failed", error=msg,
                                   counter="serving.failed")
                self._rebuild_arenas()
                self._update_gauges()
                with self._cv:
                    self._cv.notify_all()
                return True, 0.0
            self._restarts += 1
            attempt = self._restarts
            if attempt > self.cfg.max_restarts:
                self._dead = True
                monitor.set_gauge("serving.engine_dead", 1)
                doomed = active + list(self.sched.waiting)
                for req in doomed:
                    err = (f"engine dead after {attempt - 1} warm-"
                           f"restart attempt(s); last failure: {msg}")
                    self._finalize(req, FAILED, "failed", error=err,
                                   exc=EngineDeadError(
                                       f"request {req.rid}: {err}"),
                                   counter="serving.failed")
                self._update_gauges()
                with self._cv:
                    self._cv.notify_all()
                return False, 0.0
            monitor.incr("serving.restarts")
            # requeue oldest-first so the waiting FRONT preserves the
            # original admission order for the replay
            now = time.monotonic()
            for req in reversed(active):
                if req.trace is not None:
                    req.trace.note_requeue(now, "restart",
                                           n_prefilled=req.n_prefilled)
                self.sched.requeue(req)
            self._rebuild_arenas()
            self._record("restart", attempt=attempt, reason=kind,
                         error=msg, requeued=len(active))
            self._update_gauges()
        return True, restart_backoff(attempt, self.cfg.restart_backoff_s)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # ------------------------------------------------------------------
    # device-step drivers
    # ------------------------------------------------------------------
    def _cow_fork(self, req, bi, evict=True):     # requires: _mu
        """Copy-on-write: make `req.blocks[bi]` safe to write. A block
        another request (or the prefix index) can read must never be
        mutated — fork it into a fresh private block (device-side row
        copy), swap the table entry, and drop this request's reference
        to the shared original. Block acquisition follows the
        `ensure_blocks` reclaim ladder — index leaves first (re-tried
        every round: preemption itself parks victims' index-registered
        blocks at refcount 0, making them evictable), then preemption
        only when `evict` allows it (the prefill path passes its own
        no-evict-while-decoding policy through, so a fork can never
        thrash the decode batch where chunk growth could not). Returns
        False when the chunk must wait (or the request yielded its own
        place and will replay)."""
        pool = self.sched.pool
        old = req.blocks[bi]
        if pool.is_private(old, req.rid):
            return True
        while True:
            got = pool.alloc(1, owner=req.rid)
            if got is not None:
                break
            if self.prefix_index is not None and \
                    self.prefix_index.evict(1, pool):
                continue
            if not evict:
                return False                # wait for free blocks
            victim = self.sched._pick_victim(exclude=req)
            if victim is None:
                self.sched.preempt(req)     # yield; replay re-matches
                return False
            self.sched.preempt(victim)
        new = got[0]
        args = (self.cache.k, self.cache.v, np.int32(old), np.int32(new))
        with _span("serving_dispatch", family="serving_fork",
                   cache_kind=self._cache_kind_names,
                   in_flight=int(self._in_flight is not None)):
            new_k, new_v = self._dispatch("serving_fork", self._fork_jit,
                                          args)
        self.cache.swap(new_k, new_v)
        pool.free([old], owner=req.rid)
        req.blocks[bi] = new
        monitor.incr("serving.prefix_cow_forks")
        if req.trace is not None:
            req.trace.note_cow_fork(time.monotonic())
        return True

    def _row_attrs(self, live, behind):
        """What a dispatch span says of the rows by request: how many
        of the batch's requests hold one, under each kind the model
        keeps (`state_rows`, `window_rows`), and `window_kv_rows`, the
        ring rows a window layer attends over for this batch: of the
        `behind` positions each request has cached, at most `window`,
        whatever its length."""
        names = self.rows.names if self.rows else ()
        window = self._window
        return dict(state_rows=live if "state" in names else 0,
                    window_rows=live if window else 0,
                    window_kv_rows=int(np.minimum(behind, window).sum())
                    if window else 0)

    def _prefill_one(self):     # requires: _mu
        """Dispatch at most one chunk of one request. Returns (did,
        first): `first` is (request, token, logp) when the chunk was the
        request's last — its sampled token is the stream's next one and
        is still on the device; `_retire` fetches it."""
        sched = self.sched
        # prefill growth normally WAITS for blocks instead of evicting
        # (a not-yet-streaming request must never thrash the decode
        # batch) — but when NOTHING is decoding, waiting would deadlock
        # a pool fully held by fellow prefills, so the oldest prefill
        # may then evict its way forward
        allow_evict = sched.num_running() == 0
        for idx, req in enumerate(list(sched.prefilling)):
            gained = sched.rematch(req)
            if gained:
                # published by the requests prefilled ahead of this one
                # since it was admitted
                ps = self._prefix_stats
                ps["tokens_saved"] += gained
                if gained == req.prefix_cached_tokens:
                    ps["hits"] += 1
                    monitor.incr("serving.prefix_hits")
            seq = req.tokens_all
            p0 = req.n_prefilled
            c_real = min(self.cfg.prefill_chunk, len(seq) - p0)
            if c_real <= 0:                     # defensive; place it
                sched.place(req)
                continue
            with _span("serving_step.blocks", kind="prefill"):
                # a prefix hit may resume INSIDE a shared block (partial
                # tail): fork before the chunk writes into it. Blocks
                # past p0's are freshly allocated, so one check
                # suffices; the fork obeys the same
                # no-evict-while-decoding policy as the chunk's own
                # block growth
                bi = p0 // self.block_size
                ready = sched.ensure_blocks(
                    req, p0 + c_real, evict=allow_evict and idx == 0) \
                    and (bi >= len(req.blocks) or self._cow_fork(
                        req, bi, evict=allow_evict and idx == 0))
            if not ready:
                continue                # wait for free blocks / yielded
            with _span("serving_step.build", kind="prefill"):
                C = self.cfg.prefill_chunk
                ids = np.zeros((1, C), np.int32)
                ids[0, :c_real] = seq[p0:p0 + c_real]
                table_row = self._table_row(req)
                p = req.params
                g = len(req.out_tokens)
                args = (self._param_vals(), self.cache.k, self.cache.v,
                        ids,
                        np.int32(p0), np.int32(c_real),
                        table_row,
                        req.rng_key, np.int32(g),
                        np.float32(p.temperature), np.int32(p.top_k),
                        np.float32(p.top_p), np.bool_(p.greedy))
                if self.rows:
                    args += (np.int32(req.row),)
            with _span("serving_dispatch", family="serving_prefill",
                       rid=req.rid, p0=p0, n_real=c_real,
                       kv_rows=flash_prefill_kv_rows(
                           p0, c_real, self.block_size),
                       **self._row_attrs(int(req.row is not None),
                                         min(p0, self._window - 1)),
                       cache_kind=self._cache_kind_names,
                       in_flight=int(self._in_flight is not None)):
                tok, logp, new_k, new_v, stats = self._dispatch(
                    "serving_prefill", self._prefill_jit, args)
            last = p0 + c_real >= len(seq)
            with _span("serving_step.emit", kind="prefill", tokens=0):
                self.cache.swap(new_k, new_v)
                # the last reference to the arenas this chunk replaced:
                # dropped here, freeing them lies inside the span
                del args
                monitor.incr("serving.prefill_chunks")
                # how much of what the chunks' kernels were handed was
                # padding: they work on the real positions only
                monitor.incr("serving.prefill_positions_real", c_real)
                monitor.incr("serving.prefill_positions_padded", C - c_real)
                req.n_prefilled = p0 + c_real
                if stats:
                    # fetched with the next tokens that are, so the
                    # counts never cost a wait of their own
                    self._stats_pending.append(stats)
                if req.trace is not None:
                    req.trace.note_prefill_chunk(time.monotonic(), p0,
                                                 c_real)
                if last:
                    # full prompt K/V now lives in this request's
                    # blocks: publish the FULL prompt blocks to the
                    # prefix index so later requests with the same
                    # prefix skip recomputing (whoever reads them is
                    # dispatched after this chunk)
                    sched.note_prefill_done(req)
            return True, ((req, tok, logp) if last else None)
        return False, None

    def _decode_dispatch(self, prev):     # requires: _mu
        """Build and dispatch one decode batch; returns its `_Flight`,
        or None when no slot has a token to decode. `prev` is the
        flight dispatched by the previous call and not retired yet: a
        slot that continues from it takes its input token from `prev`'s
        output on the device, and everything else about its step
        (context, blocks, sampling count, whether `prev`'s token is its
        last by `max_new_tokens`) is known to the host without it."""
        sched = self.sched
        # the requests whose newest token is prev's, still unfetched
        carried = set() if prev is None else {
            req.rid for slot, req in prev.entries
            if sched.running[slot] is req}

        def leaving(req):
            return req.rid in carried and \
                len(req.out_tokens) + 1 >= req.params.max_new_tokens

        with _span("serving_step.blocks", kind="decode"):
            # grow blocks oldest-first so eviction lands on the youngest
            for req in list(sched.admit_order):
                if req.slot is None or leaving(req):
                    continue
                sched.ensure_blocks(req, req.n_prefilled + 1, evict=True)
                # decode writes position n_prefilled: defensively fork a
                # still-shared tail (normally prefill already forked it)
                bi = req.n_prefilled // self.block_size
                if req.slot is not None and bi < len(req.blocks):
                    self._cow_fork(req, bi)
        active = [(i, r) for i, r in enumerate(sched.running)
                  if r is not None and not leaving(r)]
        if not active:
            return None
        with _span("serving_step.build", kind="decode"):
            S = self.cfg.max_slots
            mb = self.max_blocks_per_seq
            tokens = np.zeros((S,), np.int32)
            from_host = np.zeros((S,), np.bool_)
            ctx = np.zeros((S,), np.int32)
            tables = np.full((S, mb), NULL_BLOCK, np.int32)
            keys = np.zeros((S, 2), np.uint32)
            counts = np.zeros((S,), np.int32)
            temp = np.ones((S,), np.float32)
            top_k = np.zeros((S,), np.int32)
            top_p = np.ones((S,), np.float32)
            greedy = np.ones((S,), np.bool_)
            rows = np.full((S,), NULL_ROW, np.int32)
            # what the batch attends to, for the dispatch span: slots
            # that hold a context, and their contexts with the token
            # this step adds
            slots = ctx_tokens = 0
            for i, req in active:
                p = req.params
                on_device = req.rid in carried
                if not on_device:
                    tokens[i] = req.token_at(req.n_prefilled)
                    from_host[i] = True
                ctx[i] = req.n_prefilled
                if req.n_prefilled > 0:
                    slots += 1
                    ctx_tokens += req.n_prefilled + 1
                tables[i, :len(req.blocks)] = req.blocks
                if req.row is not None:
                    rows[i] = req.row
                keys[i] = req.rng_key
                # the index of the token this step samples: prev's
                # token is counted although it has not arrived
                counts[i] = len(req.out_tokens) + on_device
                temp[i] = p.temperature
                top_k[i] = p.top_k
                top_p[i] = p.top_p
                greedy[i] = p.greedy
            # the cache rows the attention kernel fetches a layer; in
            # a latent layer each is one row that is key and value
            kv_rows = paged_decode_kv_rows(ctx, self.block_size)
            # the continuing slots' tokens are prev's output, still on
            # the device, and in a steady step that is all of them: the
            # array goes in as it is (a slot that holds no request reads
            # whatever prev sampled there: its context is 0, so the step
            # masks it). A slot placed or replayed since has its token
            # on the host, and the merge program puts the two together:
            # ~0.45 ms of host a call (PERF.md §6), paid where the
            # batch changed and not every step
            if from_host.any():
                tokens = self._merge_tokens(
                    self._no_tokens if prev is None else prev.tok,
                    tokens, from_host)
            else:
                tokens = prev.tok
            # the other numpy args go straight into the jitted call: the
            # C++ dispatch path transfers them, which profiles ~2x
            # cheaper per step than a python-level jnp.asarray round for
            # each
            args = (self._param_vals(), self.cache.k, self.cache.v,
                    tokens, ctx, tables, keys, counts, temp, top_k, top_p,
                    greedy)
            if self.rows:
                args += (rows,)
            # all-greedy batches take the sort-free program (distinct
            # compile FAMILY, not a recompile — each variant compiles
            # once)
            sampling = any(not r.params.greedy for _, r in active)
            family = "serving_decode_sampling" if sampling \
                else "serving_decode"
        with _span("serving_dispatch", family=family, slots=slots,
                   ctx_tokens=ctx_tokens, kv_rows=kv_rows,
                   # a slot attends over its own position too
                   **self._row_attrs(int(np.count_nonzero(rows)),
                                     ctx[rows > 0] + 1),
                   cache_kind=self._cache_kind_names,
                   in_flight=int(prev is not None)):
            tok, logp, new_k, new_v, stats = self._dispatch(
                family,
                self._decode_jit if sampling else self._decode_greedy_jit,
                args)
        with _span("serving_step.emit", kind="decode", tokens=0):
            self.cache.swap(new_k, new_v)
            # the last reference to the arenas this step replaced:
            # dropped here, freeing them lies inside the span
            del args
            monitor.incr("serving.decode_steps")
            if prev is not None:
                monitor.incr("serving.decode_steps_overlapped")
            for _, req in active:
                # position n_prefilled is written by a program that is
                # dispatched: whatever reads it is dispatched later
                req.n_prefilled += 1
            if stats:
                self._stats_pending.append(stats)
            stats, self._stats_pending = self._stats_pending, []
        return _Flight(tok, logp, stats, active)

    def _fetch_flight(self, flight):     # requires: _mu
        """Host sync: the engine is the API boundary — the sampled
        tokens must land on the host to stream/route; logp's buffer is
        ready once tok's fetch has waited, and so are the counts of the
        step and of every chunk dispatched before it."""
        tok = np.asarray(flight.tok)
        logp = np.asarray(flight.logp)
        self._count_stats(flight.stats)
        return tok, logp

    def _emit_flight(self, flight, tok, logp):     # requires: _mu
        """Hand a fetched step's tokens to its requests; returns how
        many. A request that ended while the step was in flight — by
        the EOS the previous fetch brought, a cancel, a deadline — gets
        nothing: its token is discarded, never emitted, never counted
        as generated. One that was preempted meanwhile keeps its token
        (computed before its blocks went) and replays from it."""
        now = time.monotonic()
        emitted = 0
        for slot, req in flight.entries:
            if req.state in TERMINAL_STATES:
                monitor.incr("serving.tokens_discarded")
                continue
            if req.slot == slot and req.trace is not None:
                # O(1) per request per step: extends the coalesced
                # decode segment (one span per stretch, never per
                # token)
                req.trace.note_decode(now)
            self._emit(req, int(tok[slot]), float(logp[slot]), now=now)
            emitted += 1
        return emitted

    def _retire(self, flight, first):     # requires: _mu
        """The late phase of a step, run while the decode batch just
        dispatched is on the device: fetch and emit the previous
        batch's tokens, then the first token of the request whose last
        chunk this step dispatched, which takes a slot now and decodes
        from the next step on. Returns True when there was anything."""
        if flight is not None:
            with _span("serving_step.emit", kind="decode") as sp:
                with _span("serving_step.fetch", kind="decode"):
                    tok, logp = self._fetch_flight(flight)
                # a decode step ran to its end: that, or a request
                # finished (`_emit`), resets the restart cap. A step
                # that only dispatched proves nothing — errors surface
                # at the fetch — and a replay's prefill alone must not
                # keep an engine whose decode always fails alive
                self._restarts = 0
                sp.set(tokens=self._emit_flight(flight, tok, logp))
        if first is not None:
            req, tok, logp = first
            with _span("serving_step.emit", kind="prefill", tokens=1):
                with _span("serving_step.fetch", kind="prefill"):
                    tok = int(np.asarray(tok))
                    logp = float(np.asarray(logp))
                    # nothing was dispatched after the chunk, or a
                    # decode batch took the counts with it
                    self._count_stats(self._stats_pending)
                    self._stats_pending = []
                # a request the decode batch's block growth preempted
                # since keeps its first token and waits again
                self._emit(req, tok, logp)
                if req.state == PREFILL:    # _emit finishes done ones
                    self.sched.place(req)
        return flight is not None or first is not None

    # ------------------------------------------------------------------
    # helpers
    # ------------------------------------------------------------------
    def _param_vals(self):
        return [p._value for p in self._bound]

    def _count_stats(self, pending):     # requires: _mu
        """What the layers counted in the steps of `pending` (routed
        tokens, held expert pairs, ...) goes onto the `serving.<name>`
        counters. Called where tokens dispatched no earlier were just
        fetched, so reading the counts waits for nothing."""
        for st in pending:
            for name, value in read_stats(st).items():
                monitor.incr("serving." + name, value)

    def _table_row(self, req):
        row = np.full((self.max_blocks_per_seq,), NULL_BLOCK, np.int32)
        row[:len(req.blocks)] = req.blocks
        return row

    def _queue_deadline_ms(self, req):
        d = req.deadlines
        if d is None or d.queue_wait_s is None:
            return None
        return d.queue_wait_s * 1000.0

    def _record(self, event, **fields):
        """Emit one kind=serving lifecycle record to the attached sink
        (no-op without one); counters/gauges are updated by the callers
        regardless, so telemetry is optional but never partial."""
        if self._sink is None:
            return
        from ..telemetry.sink import make_serving_record
        self._sink.write(make_serving_record(
            event, engine=self.engine_id, **fields))

    def _finalize(self, req, status, event,  # requires: _mu
                  error=None, exc=None,
                  counter=None, **fields):
        """The single terminal transition: release slot + blocks via
        the scheduler, account the outcome, emit the typed record.
        Idempotent (a cancel racing a natural finish is a no-op)."""
        if req.state in TERMINAL_STATES:
            return
        self.sched.finish(req, error=error, status=status, failure=exc)
        self._counts[event] += 1
        if counter is not None:
            monitor.incr(counter)
        self._record(event, rid=req.rid,
                     request_id=getattr(req, "request_id", None),
                     n_tokens=len(req.out_tokens),
                     queue_wait_ms=req.queue_wait_ms(),
                     queue_deadline_ms=self._queue_deadline_ms(req),
                     priority=req.priority_class, error=error, **fields)
        if self.tracer is not None:
            # the single terminal transition closes the trace too: the
            # finalize span ends at the scheduler-stamped finish_time,
            # so the decomposition invariant (spans sum to e2e) holds
            # for every outcome, not just clean finishes
            self.tracer.finish(req, req.finish_time)

    def _emit(self, req, tok, logp, now=None):     # requires: _mu
        req.push_token(tok, now=now)
        monitor.incr("serving.tokens_generated")
        if req.done:
            self._restarts = 0
            monitor.incr("serving.finished")
            t = req.ttft_ms()
            if t is not None:
                monitor.observe_hist("serving.ttft_ms", t)
                self._last_latency_obs = time.monotonic()
            self._finalize(req, FINISHED, "finished")
            t = req.tpot_ms()
            if t is not None:
                monitor.observe_hist("serving.tpot_ms", t)
                self._last_latency_obs = time.monotonic()
                self.admission.note_tpot_ms(t)  # feeds shed prediction

    def _update_gauges(self):     # requires: _mu
        monitor.set_gauge("serving.queue_depth", len(self.sched.waiting))
        monitor.set_gauge("serving.running", self.sched.num_running())
        monitor.set_gauge("serving.prefilling", len(self.sched.prefilling))
        monitor.set_gauge("serving.kv_blocks_used", self.pool.num_used)
        if self.rows:
            for kind in self.rows.names:
                monitor.set_gauge(f"serving.{kind}_rows_live",
                                  self.rows.num_live)
        ps = self._prefix_stats
        offered = ps["tokens_offered"]
        monitor.set_gauge("serving.prefix_hit_rate",
                          ps["tokens_saved"] / offered if offered
                          else 0.0)
        monitor.set_gauge("serving.prefix_blocks_shared",
                          self.pool.num_shared)
        monitor.set_gauge("serving.prefix_blocks_cached",
                          self.pool.num_cached)
        monitor.set_gauge("serving.prefill_tokens_saved",
                          ps["tokens_saved"])
        monitor.set_gauge("serving.prefill_tokens_offered", offered)
        util = self.pool.utilization()
        monitor.set_gauge("serving.kv_block_utilization", util)
        self.kv_peak_utilization = max(self.kv_peak_utilization, util)
        headroom = self._mem_headroom_bytes()
        if headroom is not None:
            monitor.set_gauge("serving.mem_headroom_bytes", headroom)
        self.refresh_latency_gauges()

    def _kv_accounting(self):     # requires: _mu (called from snapshot)
        """The memory observatory's `kv_source`: the paged-pool block
        census (total/held/free/cached — held + free + cached tile the
        pool's capacity, the trace_check cross-rule pins it) plus the
        scheduler's cumulative per-priority-class eviction/admission
        counters the kv_thrash rule turns into windowed rates."""
        pool, sched = self.pool, self.sched
        ev = dict(sched.evictions_by_class)
        adm = dict(sched.admissions_by_class)
        return {
            "blocks_total": pool.capacity,
            "blocks_held": pool.num_used,
            "blocks_free": pool.num_free,
            "blocks_cached": pool.num_cached,
            "evictions": sum(ev.values()),
            "admissions": sum(adm.values()),
            "evictions_by_class": ev,
            "admissions_by_class": adm,
        }

    def _mem_headroom_bytes(self):     # requires: _mu
        """Bytes the engine believes it can still allocate. Ledger
        headroom (declared budget minus measured live total) when the
        observatory has both; otherwise the KV pool's free capacity in
        bytes — an always-available floor, so the gauge exists even
        without a declared budget."""
        h = self.mem_obs.headroom_bytes()
        if h is not None:
            return h
        return self.pool.num_free * self._block_bytes()

    def _check_mem_headroom(self):     # requires: _mu
        """submit()'s admission consult: with a declared HBM budget and
        a measured ledger showing it fully consumed, shed at the door
        (MemoryPressureError -> 429 + Retry-After) instead of admitting
        work into an allocation failure mid-decode. Without a budget or
        before the first snapshot there is no verdict to give —
        admission proceeds."""
        if self.mem_obs.hbm_budget_bytes is None:
            return
        h = self.mem_obs.headroom_bytes()
        if h is None or h > 0:
            return
        monitor.incr("serving.mem_shed")
        raise MemoryPressureError(
            f"HBM budget exhausted: ledger shows 0 headroom bytes "
            f"against the declared "
            f"{self.mem_obs.hbm_budget_bytes} byte budget",
            retry_after_s=1.0, queue_depth=len(self.sched.waiting))

    # the legacy-gauge <- histogram mapping (compat names kept: every
    # dashboard scraping serving.*_p50/_p99 keeps working; the scrape
    # can now ALSO compute its own quantiles from the histogram series)
    _LATENCY_GAUGES = (
        ("serving.ttft_ms", "serving.ttft_p50_ms",
         "serving.ttft_p99_ms"),
        ("serving.tpot_ms", "serving.tpot_p50_ms",
         "serving.tpot_p99_ms"),
        ("serving.queue_wait_ms", "serving.queue_wait_ms_p50",
         "serving.queue_wait_ms_p99"),
    )

    def refresh_latency_gauges(self):
        """Recompute the legacy p50/p99 SLO gauges from the streaming
        histograms NOW (over the histograms' bounded RECENT window, so
        a regression moves the p99 within ~a window of slow requests
        rather than after 1% of lifetime traffic), and age-stamp them.
        Called on every engine step AND from the HTTP front's /metrics
        + /healthz handlers —
        previously the percentiles refreshed only when a request
        happened to finish, so a stalled or wedged engine served
        exactly-frozen p50/p99 during the incidents they exist to
        expose. `serving.slo_gauge_age_s` says how stale the underlying
        samples are; a prober can alarm on the age even when the
        quantiles look healthy.

        Like every other serving.* stat on the registry (counters,
        tokens_generated, preemptions...), the histograms are
        PROCESS-global: several engines in one process merge their
        samples, by the registry's design (production serves one
        engine per process; bench/test harnesses that build control
        engines report percentiles from their own request handles, not
        these gauges). Reads go through `monitor.hist_quantile` — the
        registry lock makes them consistent against a concurrent
        observe()'s half-window rotation (an unlocked read torn across
        the rotation could publish the histogram's top bound as p99)."""
        for hist_name, p50_name, p99_name in self._LATENCY_GAUGES:
            p50 = monitor.hist_quantile(hist_name, 0.50)
            p99 = monitor.hist_quantile(hist_name, 0.99)
            if p50 is None or p99 is None:
                continue
            monitor.set_gauge(p50_name, float(p50))
            monitor.set_gauge(p99_name, float(p99))
        # `_last_latency_obs` is a step-loop field: take the engine
        # lock for the read — HTTP scrape threads land here directly,
        # and an unlocked read raced the step loop's store (the RLock
        # makes the _update_gauges re-entry free)
        with self._mu:
            last = self._last_latency_obs
        if last is not None:
            monitor.set_gauge(
                "serving.slo_gauge_age_s",
                round(time.monotonic() - last, 3))

    def prefix_stats(self):
        """Snapshot of the prefix-cache accounting: lookups, hits,
        tokens saved/offered, hit_rate (saved / offered), and the
        pool's current shared/cached block counts."""
        with self._mu:
            ps = dict(self._prefix_stats)
            offered = ps["tokens_offered"]
            ps["hit_rate"] = ps["tokens_saved"] / offered \
                if offered else 0.0
            ps["blocks_shared"] = self.pool.num_shared
            ps["blocks_cached"] = self.pool.num_cached
            return ps

    def metrics_snapshot(self):
        """Point-in-time serving stats (the /metrics serving.* family,
        as a dict)."""
        snap = monitor.snapshot()
        return {k: v for k, v in snap.items()
                if k.startswith("serving.")}
