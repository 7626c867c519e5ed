"""Training flight recorder: per-step telemetry with compile/execute split.

The reference stack spreads this over RecordEvent/DeviceTracer
(`platform/profiler.h`), the Monitor StatRegistry (`platform/monitor.h`)
and ad-hoc trainer logging; here one recorder unifies them for the
TPU-native regime, where the interesting split is *XLA compile time vs.
execute time*, not per-op kernels (XLA owns those — `jax.profiler`'s
XPlane trace covers device detail).

Mechanics:

- compile time is observed through `jax.monitoring`'s event-duration
  stream (jaxpr trace + MLIR lowering + backend_compile — the same
  events `jax.stages` lowering/compilation emit), accumulated into
  whichever step window is open. Step 0 of a jitted loop therefore shows
  nonzero compile_ms; steady-state steps show 0.0 and advance the
  compile-cache hit counter.
- spans (`telemetry.span("name")`) are host intervals tagged with the
  recorder's rank; `distributed/collective.py` tags each eager
  collective, so per-step comm time is attributable. Spans export to a
  multi-rank Chrome trace (sink.export_chrome_tracing), and every span
  is also a `jax.profiler.TraceAnnotation`: while a profile runs it
  lies in the XPlane on the device trace's clock, with or without a
  recorder.
- every closed step writes one JSONL record (sink.make_step_record):
  step, loss, step_ms, compile_ms, execute_ms, tokens/sec, MFU,
  mem_bytes, per-collective ms, cache hit/miss counters.
- `paddle_tpu.monitor` counters (`telemetry.steps`,
  `telemetry.compile_cache_hits/misses`) advance with every step so a
  stuck job is still triagable from `monitor.snapshot()` alone.
"""
import contextlib
import threading
import time

import jax

from .. import monitor
from .. import profiler as _profiler
# `telemetry.scope`, the device's counterpart of `span`: it lives in
# core/ because the model packages, which may not import this one
# (tests/test_layering.py), are where it is opened
from ..core.scope import SCOPES, scope  # noqa: F401
from . import mfu as _mfu
from .sink import JsonlSink, make_step_record

_LOCK = threading.Lock()
_RECORDER_STACK = []          # guarded by: _LOCK — active (context-entered) recorders
_OPEN_STEPS = []              # guarded by: _LOCK — open _StepWindow objects (compile sink)
_OPEN_SPANS = []              # guarded by: _LOCK — spans entered but not yet exited (any thread)
_LISTENER_INSTALLED = False   # guarded by: none (idempotent install; main-thread hook)

# jax.monitoring events that constitute "compile" for the split; all
# three fire on a jit cache miss and none on a hit
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def _compile_listener(event, duration, **kwargs):
    if event not in _COMPILE_EVENTS:
        return
    with _LOCK:
        for win in _OPEN_STEPS:
            win.compile_secs += duration


def _install_listener():
    """Idempotently hook jax's compile-event stream. The listener stays
    registered for the process lifetime (it is a no-op with no open step
    windows — a dict lookup and a lock-free len check)."""
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return
    jax.monitoring.register_event_duration_secs_listener(_compile_listener)
    _LISTENER_INSTALLED = True


def current_recorder():
    """The innermost context-active TelemetryRecorder, or None."""
    with _LOCK:
        return _RECORDER_STACK[-1] if _RECORDER_STACK else None


class _StepWindow:
    """One open step measurement: wall clock + compile accumulation +
    span capture start index."""

    def __init__(self, recorder):
        self.recorder = recorder
        self.compile_secs = 0.0
        self.loss = None
        self.extra = {}
        self.span_start = len(recorder.spans)
        self.t0 = time.perf_counter()

    def note(self, loss=None, **extra):
        """Attach the step's loss (Tensor/array/float — fetched lazily at
        close, which also syncs the device) and any extra record fields."""
        if loss is not None:
            self.loss = loss
        self.extra.update(extra)
        return self


@contextlib.contextmanager
def auto_step(**extra):
    """Bracket a train-step body with the active recorder, if any.

    Used by TrainStep/ShardedTrainStep so any step executed while a
    recorder is context-active gets recorded with zero call-site changes.
    Re-entrant calls (a recorder-managed wrapper around an instrumented
    step) record only the OUTERMOST window. Yields a _StepWindow (or an
    inert one when no recorder is active) whose .note(loss=...) feeds the
    record.
    """
    rec = current_recorder()
    if rec is None or rec._open:
        yield _InertWindow()
        return
    win = rec.start_step()
    if extra:
        win.extra.update(extra)
    try:
        yield win
    finally:
        rec.end_step()


class _InertWindow:
    def note(self, loss=None, **extra):
        return self


class _OpenSpan:
    """One row of the open-span table: what `open_spans()` and the
    chrome export read of a span that has not ended yet."""
    __slots__ = ("name", "cat", "rank", "attrs", "t0", "thread", "rec")


def _push_open_span(name, cat, t0, rec=None, rank=None, attrs=None):
    """Register a just-entered span in the module-wide open-span table.
    The hang watchdog reads this table to NAME what a stalled step is
    stuck inside (e.g. `collective.all_reduce`), and chrome export
    closes these instead of dropping them. Returns the entry (identity
    is the removal token)."""
    entry = _OpenSpan()
    entry.name, entry.cat, entry.t0 = name, cat, t0
    entry.thread = threading.current_thread()
    entry.rec, entry.rank, entry.attrs = rec, rank, dict(attrs or {})
    with _LOCK:
        _OPEN_SPANS.append(entry)
    return entry


def _pop_open_span(entry):
    with _LOCK:
        # spans nest, so the one that ends is nearly always the newest
        if _OPEN_SPANS and _OPEN_SPANS[-1] is entry:
            _OPEN_SPANS.pop()
            return
        try:
            _OPEN_SPANS.remove(entry)
        except ValueError:
            pass


def open_spans():
    """Snapshot of every currently-open telemetry span (all threads):
    [{name, cat, age_s, thread, rank, attrs}], oldest first. This is
    what the watchdog black-box dump records, so a hang inside an
    instrumented region is attributable without a debugger."""
    now = time.perf_counter()
    with _LOCK:
        entries = list(_OPEN_SPANS)
    return [{"name": e.name, "cat": e.cat,
             "age_s": round(now - e.t0, 4), "thread": e.thread.name,
             "rank": e.rank, "attrs": dict(e.attrs)} for e in entries]


_PLAIN = (int, float, str, bool)


def _plain(attrs):
    """Attribute values as the profiler and the chrome trace take them:
    int, float, str or bool; anything else by its repr."""
    return {k: (v if isinstance(v, _PLAIN) else repr(v))
            for k, v in attrs.items()}


class span(_OpenSpan):
    """A named host span: `with telemetry.span("name", cat, **attrs):`,
    or `begin()` / `end()` where the region is not a block.

    It is written to three places. `jax.profiler.TraceAnnotation` puts it
    on the profiler's clock, so whenever a profile is running the span
    lies in the XPlane on its own thread's line, beside the device's
    ops, with `attrs` as the event's stats; with no profile running
    that is one near-empty native call. The context-active
    TelemetryRecorder, if any, gets it when it ends (chrome-trace
    export, per-step collective time), as does paddle_tpu.profiler's
    table when that profiler is enabled. While the body runs the span
    sits in the open-span table, so a hang inside it is named in the
    watchdog's black-box dumps. `set(**attrs)` adds attributes known
    only once the body has run. Attribute values are int, float, str
    or bool (anything else is kept by its repr)."""
    __slots__ = ("_ev", "_ann")

    def __init__(self, name, cat="host", rank=None, **attrs):
        self.name, self.cat, self.rank, self.attrs = name, cat, rank, attrs

    def begin(self):
        # the annotation starts first and ends last, so the span's own
        # bookkeeping (encoding the attributes among it) lies inside it
        ann = self._ann = jax.profiler.TraceAnnotation(self.name)
        ann.__enter__()
        if self.attrs:
            ann.set_metadata(**_plain(self.attrs))
        self.thread = threading.current_thread()
        t0 = self.t0 = time.perf_counter()
        if _profiler._GLOBAL["enabled"]:
            ev = self._ev = _profiler.RecordEvent(self.name)
            ev._t0 = t0
            ev._from_telemetry = True   # the span owns recorder routing
        else:
            self._ev = None
        with _LOCK:
            rec = self.rec = _RECORDER_STACK[-1] if _RECORDER_STACK \
                else None
            _OPEN_SPANS.append(self)
        if self.rank is None and rec is not None:
            self.rank = rec.rank
        return self

    def set(self, **attrs):
        """Attributes known only after the span began (how many
        requests a scheduling pass admitted, say)."""
        self.attrs.update(attrs)
        self._ann.set_metadata(**_plain(attrs))

    def end(self):
        dur = time.perf_counter() - self.t0
        _pop_open_span(self)
        if self._ev is not None:
            self._ev.end()
        if self.rec is not None:
            self.rec.add_span(self.name, self.t0, dur, cat=self.cat,
                              rank=self.rank, args=self.attrs)
        self._ann.__exit__(None, None, None)

    __enter__ = begin

    def __exit__(self, exc_type, exc, tb):
        self.end()
        return False


class StepTimer:
    """Explicit compile/execute split for a plain jittable function via
    `jax.stages`: an AOT cache keyed on input avals. A key miss runs
    lower()+compile() under the clock (compile_ms); a hit dispatches the
    cached executable (execute only). The deterministic-counter
    counterpart to the listener-based split in TelemetryRecorder.

    timer = StepTimer(fn); out = timer(*args)
    timer.cache_hits / timer.cache_misses / timer.last_compile_ms
    """

    def __init__(self, fn, recorder=None):
        self._fn = fn
        self._cache = {}
        self._last_compiled = None
        self.recorder = recorder
        self.cache_hits = 0
        self.cache_misses = 0
        self.last_compile_ms = 0.0
        self.last_execute_ms = 0.0

    @staticmethod
    def _key(args):
        leaves = jax.tree_util.tree_leaves(args)
        return tuple(
            (tuple(getattr(x, "shape", ())), str(getattr(x, "dtype", type(x))))
            for x in leaves)

    def __call__(self, *args):
        from . import compile_obs
        key = self._key(args)
        compiled = self._cache.get(key)
        obs = compile_obs.current_observatory()
        if compiled is None:
            with (obs.compiling() if obs is not None
                  else contextlib.nullcontext()):
                t0 = time.perf_counter()
                compiled = jax.jit(self._fn).lower(*args).compile()
                self.last_compile_ms = (time.perf_counter() - t0) * 1000.0
            self._cache[key] = compiled
            self._last_compiled = compiled
            self.cache_misses += 1
            monitor.incr("telemetry.aot_cache_misses")
            if obs is not None:
                # attribute this compile to the observatory's ledger
                # (cause diffs, memory/cost, storm rule) instead of the
                # unattributed jax-event stream; the timer's own call
                # count is the step clock for its records
                obs.observe(
                    f"StepTimer:{getattr(self._fn, '__name__', 'fn')}",
                    compile_obs.signature_of(args), self.last_compile_ms,
                    compiled=compiled,
                    step=self.cache_hits + self.cache_misses - 1)
        else:
            self.last_compile_ms = 0.0
            self.cache_hits += 1
            monitor.incr("telemetry.aot_cache_hits")
        t0 = time.perf_counter()
        out = compiled(*args)
        jax.block_until_ready(out)
        self.last_execute_ms = (time.perf_counter() - t0) * 1000.0
        if self.recorder is not None:
            extra = {}
            mem = self.memory_analysis_dict()
            if mem is not None:
                # last-compiled HBM breakdown rides the step record, so
                # AOT-cache behaviour is visible in the JSONL, not just
                # in in-process counters
                extra["hbm"] = mem
            self.recorder.record_external_step(
                step_ms=self.last_compile_ms + self.last_execute_ms,
                compile_ms=self.last_compile_ms,
                cache_hits=self.cache_hits,
                cache_misses=self.cache_misses, **extra)
        return out

    def memory_analysis(self):
        """Compiled memory analysis of the last-compiled executable (HBM
        argument/output/temp bytes), None when unavailable."""
        if self._last_compiled is None:
            return None
        try:
            return self._last_compiled.memory_analysis()
        except Exception:
            return None

    def memory_analysis_dict(self):
        """Same, flattened to plain byte counts (the form the step
        record and compile observatory carry), None when unavailable."""
        if self._last_compiled is None:
            return None
        from .compile_obs import memory_analysis_dict
        return memory_analysis_dict(self._last_compiled)


class TelemetryRecorder:
    """Flight recorder for a training loop.

    rec = TelemetryRecorder(sink="run.jsonl", tokens_per_step=B*S,
                            flops_per_token=mfu.model_flops_per_token(...))
    with rec:                      # recorder active: TrainStep auto-records
        for batch in loader:
            loss = train_step(*batch)

    or wrap an arbitrary step callable:  step = rec.wrap(train_step).

    Per closed step, one schema record (sink.make_step_record) goes to the
    JSONL sink and to `rec.records`. MFU inputs: flops_per_step (exact,
    e.g. mfu.train_step_flops) OR flops_per_token (analytic) combined with
    tokens_per_step; peak_flops defaults from the device kind
    (mfu.device_peak_flops — None on CPU => no MFU in the record).
    """

    def __init__(self, sink=None, rank=0, tokens_per_step=None,
                 flops_per_step=None, flops_per_token=None,
                 peak_flops=None, n_devices=None, track_memory=True):
        self._owns_sink = isinstance(sink, str)
        self.sink = JsonlSink(sink) if self._owns_sink else sink
        self.rank = int(rank)
        self.tokens_per_step = tokens_per_step
        self.flops_per_step = flops_per_step
        self.flops_per_token = flops_per_token
        if peak_flops is None:
            peak_flops = _mfu.device_peak_flops()
        self.peak_flops = peak_flops
        self.n_devices = n_devices or 1
        self.track_memory = track_memory
        self.records = []
        self.spans = []
        self.cache_hits = 0
        self.cache_misses = 0
        self._step_idx = 0
        self._win = None
        _install_listener()

    # -- span API ----------------------------------------------------------
    def add_span(self, name, t0, dur, cat="host", rank=None, tid=None,
                 args=None):
        sp = {
            "name": name, "t0": float(t0), "dur": float(dur),
            "cat": cat, "rank": self.rank if rank is None else int(rank),
            "tid": threading.get_ident() % 1000 if tid is None else tid}
        if args:
            sp["args"] = _plain(args)
        self.spans.append(sp)

    def open_span_dicts(self):
        """Spans currently open under this recorder, synthesized as
        closed span dicts ending 'now' and tagged args={'open': True} —
        chrome export includes them instead of dropping them."""
        now = time.perf_counter()
        with _LOCK:
            entries = [e for e in _OPEN_SPANS if e.rec is self]
        return [{"name": e.name, "t0": float(e.t0),
                 "dur": float(now - e.t0), "cat": e.cat,
                 "rank": self.rank if e.rank is None else e.rank,
                 "tid": e.thread.ident % 1000,
                 "args": {"open": True, **{k: repr(v) for k, v
                                           in e.attrs.items()}}}
                for e in entries]

    # -- step lifecycle ----------------------------------------------------
    @property
    def _open(self):
        return self._win is not None

    def start_step(self):
        if self._win is not None:
            raise RuntimeError("TelemetryRecorder: step already open")
        self._win = _StepWindow(self)
        with _LOCK:
            _OPEN_STEPS.append(self._win)
        return self._win

    def end_step(self, loss=None, **extra):
        win = self._win
        if win is None:
            raise RuntimeError("TelemetryRecorder: no open step")
        if loss is not None:
            win.loss = loss
        win.extra.update(extra)
        loss_val = None
        if win.loss is not None:
            # fetching the scalar double-duties as the device sync, so
            # step_ms covers the full computation, not just dispatch
            try:
                v = win.loss
                v = v.item() if hasattr(v, "item") else v
                loss_val = float(v)
            except Exception:
                loss_val = None
        t1 = time.perf_counter()
        with _LOCK:
            _OPEN_STEPS.remove(win)
        self._win = None
        step_s = t1 - win.t0
        compile_ms = win.compile_secs * 1000.0
        if compile_ms > 0:
            self.cache_misses += 1
            monitor.incr("telemetry.compile_cache_misses")
        else:
            self.cache_hits += 1
            monitor.incr("telemetry.compile_cache_hits")
        monitor.incr("telemetry.steps")

        execute_s = max(1e-9, step_s - win.compile_secs)
        tokens_per_sec = None
        if self.tokens_per_step:
            tokens_per_sec = self.tokens_per_step / execute_s
        flops_per_step = self.flops_per_step
        if flops_per_step is None and self.flops_per_token \
                and self.tokens_per_step:
            flops_per_step = self.flops_per_token * self.tokens_per_step
        mfu_val = None
        if flops_per_step is not None:
            mfu_val = _mfu.mfu(flops_per_step, execute_s,
                               peak_flops=self.peak_flops,
                               n_devices=self.n_devices)
        mem_bytes = self._live_bytes() if self.track_memory else None
        coll, comm_ms = self._collect_collectives(win.span_start)
        # compute-vs-communication decomposition: the wall-time
        # collective total and its bounded share of the step
        # (telemetry/comm_obs — validated by sink + trace_check)
        step_ms_total = step_s * 1000.0
        comm_frac = min(1.0, comm_ms / step_ms_total) \
            if step_ms_total > 0 else 0.0

        # an external step source (StepTimer) reports its OWN AOT cache
        # counters; they override the recorder's listener-derived ones
        extra = dict(win.extra)
        cache_hits = extra.pop("cache_hits", self.cache_hits)
        cache_misses = extra.pop("cache_misses", self.cache_misses)
        # input-pipeline taps (io.prefetch): the loader stashed the
        # fetch-wait stats of the batch this step consumed; pop them
        # one-shot so they land in THIS step's record only
        try:
            from ..io.prefetch import consume_step_input_stats
            istats = consume_step_input_stats()
        except Exception:
            istats = None
        if istats:
            for k, v in istats.items():
                extra.setdefault(k, v)
        rec = make_step_record(
            step=self._step_idx, step_ms=step_s * 1000.0,
            compile_ms=compile_ms, rank=self.rank, loss=loss_val,
            tokens_per_sec=tokens_per_sec, mfu=mfu_val, mem_bytes=mem_bytes,
            cache_hits=cache_hits, cache_misses=cache_misses,
            collectives=coll,
            comm_ms=comm_ms if coll else None,
            comm_frac=comm_frac if coll else None, **extra)
        # the whole step is also a span, so the JSONL ledger and the
        # chrome trace describe the same intervals
        self.add_span(f"step {self._step_idx}", win.t0, step_s, cat="step")
        self._step_idx += 1
        self.records.append(rec)
        if self.sink is not None:
            self.sink.write(rec)
        return rec

    def record_external_step(self, step_ms, compile_ms, **kwargs):
        """Record a step measured elsewhere (StepTimer, bench phases)."""
        win = self.start_step()
        win.t0 = time.perf_counter() - step_ms / 1000.0
        win.compile_secs = compile_ms / 1000.0
        return self.end_step(**kwargs)

    @contextlib.contextmanager
    def step(self, **extra):
        win = self.start_step()
        win.extra.update(extra)
        try:
            yield win
        finally:
            self.end_step()

    def wrap(self, step_fn):
        """Wrap a train-step callable: every invocation becomes one
        recorded step, the (scalar) return value its loss."""
        def wrapped(*args, **kwargs):
            win = self.start_step()
            try:
                out = step_fn(*args, **kwargs)
                win.note(loss=out)
                return out
            finally:
                self.end_step()
        wrapped.__name__ = getattr(step_fn, "__name__", "step")
        return wrapped

    # -- context activation (TrainStep auto-record) ------------------------
    def __enter__(self):
        # under _LOCK: `current_recorder()` is consulted from other
        # threads (emit_record's fallback chain, span()), and an
        # unlocked append/remove raced those reads
        with _LOCK:
            _RECORDER_STACK.append(self)
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        if self._win is not None:
            # abandoned window (the step raised): close it as an aborted
            # record instead of dropping the measurements — the crash
            # file and the JSONL then agree on when the run died
            try:
                self._win.loss = None   # likely poisoned; don't fetch
                self.end_step(aborted=True,
                              abort_reason=(exc_type.__name__
                                            if exc_type else "unknown"))
            except Exception:
                with _LOCK:
                    if self._win in _OPEN_STEPS:
                        _OPEN_STEPS.remove(self._win)
                self._win = None
        with _LOCK:
            _RECORDER_STACK.remove(self)
        if self.sink is not None:
            if self._owns_sink:
                # we opened this file handle; release it (a later write
                # through this recorder transparently reopens append)
                self.sink.close()
            elif hasattr(self.sink, "flush"):
                self.sink.flush()
        return False

    # -- helpers -----------------------------------------------------------
    @staticmethod
    def _live_bytes():
        try:
            return int(sum(getattr(a, "nbytes", 0)
                           for a in jax.live_arrays()))
        except Exception:
            return None

    def _collect_collectives(self, span_start):
        """Aggregate this step's wall-time collective spans into the
        per-op breakdown + their total, (coll_or_None, comm_ms). Spans
        tagged traced=true (distributed/collective.py's shard_map
        primitives) cover TRACE time, not communication wall time —
        they stay in the chrome trace but never enter the step record's
        comm attribution."""
        coll, comm_ms = {}, 0.0
        for sp in self.spans[span_start:]:
            if sp.get("cat") != "collective":
                continue
            if (sp.get("args") or {}).get("traced"):
                continue
            ms, calls = coll.get(sp["name"], (0.0, 0))
            dur_ms = sp["dur"] * 1000.0
            coll[sp["name"]] = (ms + dur_ms, calls + 1)
            comm_ms += dur_ms
        return coll or None, comm_ms

    def export_chrome_tracing(self, path, extra_sources=(), align_on=None):
        """Export this recorder's spans (plus any peer ranks') as one
        Chrome trace. See sink.export_chrome_tracing."""
        from .sink import export_chrome_tracing as _export
        return _export(path, [self, *extra_sources], align_on=align_on)
