"""Compile observatory: recompile tracking with cause diffs, compiled-HBM
accounting, and cost-model cross-checks.

The flight recorder (recorder.py) measures what a step COST and the
health monitor (health.py) watches a job running WRONG; this module
watches the COMPILER — the third silent failure mode of a jit-and-trace
stack:

- a **retrace storm**: a shape/dtype/weak-type/static-arg thrash that
  recompiles the train step every few batches. The recorder shows
  nonzero compile_ms; only a signature DIFF says *why* ("arg `batch[0]`
  axis 0: 32→48"), and only a storm rule says it is pathological.
- an **HBM surprise**: the executable XLA actually built carries temp /
  generated-code buffers the static `analysis/sharding_lint.project_hbm`
  (SH206) projection never saw. `compiled.memory_analysis()` has the
  real number — computed on every compile, recorded nowhere, until now.
- **cost-model drift**: MFU claims divide measured time by an analytic
  FLOPs number (`telemetry/mfu.py`); when the compiled program's own
  cost analysis (`cost_model._cost_analysis`) disagrees, every MFU
  in the run is quietly wrong.

Mechanics — three layers, same pattern as the rest of telemetry
(context-activated, zero call-site changes):

- **CompileSignature / diff_signatures** — per-leaf aval descriptors
  (name from the arg tree path, shape, dtype, weak_type, sharding) plus
  static values and the donate set; diffing two signatures yields the
  human-readable recompile causes.
- **CompileObservatory** — a context manager (module stack, like
  TelemetryRecorder). While active, `jit.TrainStep`,
  `distributed.ShardedTrainStep` and `PipelineParallel.train_batch`
  dispatch through `observatory.call(family, jitted, *args)`: an AOT
  `lower().compile()` cache keyed on the signature. A miss IS a
  (re)compile — measured under the clock, diffed against the family's
  prior signature, enriched with `memory_analysis()`, XLA cost
  analysis, and a top-K optimized-HLO opcode profile
  (`cost_model.profile_hlo_text`), written as one JSONL record
  (sink.make_compile_record) and judged by the PR-3 AnomalyDetector
  (recompile_storm / hbm_projection_drift / flops_drift). A hit
  dispatches the cached executable — steady-state overhead is building
  the signature (one Python pass over the arg leaves) plus a dict
  lookup; the observatory is an opt-in context, not an always-on tax.
- **jax.monitoring bridge** — compiles that happen OUTSIDE the wrapped
  steps (a stray `jax.jit` in the loss, eval graphs, bench phases)
  still surface: the event-duration listener records each
  backend_compile as an `untracked` compile record and advances
  `compile.unattributed`, so the JSONL accounts for every compile the
  process paid for, attributed or not.

Monitor surface (scraped by telemetry.metrics_http `/metrics`):
counters `compile.count`, `compile.recompiles`, `compile.storms`,
`compile.unattributed`, `compile.aot_hits`; gauges
`compile.hbm_total_bytes`, `compile.hbm_arg_bytes`,
`compile.hbm_temp_bytes`, `compile.hbm_out_bytes`,
`compile.hbm_code_bytes`, `compile.last_ms`, `compile.flops`.

Offline, `tools/compile_report.py` replays the same detector rules over
the JSONL and renders the report (causes timeline, HBM breakdown,
roofline, top-K ops); `tools/trace_check.py` validates the records.

Reference analogs: JAX's own compile-cache miss explanations
(`jax_explain_cache_misses`) and Xprof compile-time attribution;
MegaScale-style per-job compilation accounting.
"""
import contextlib
import hashlib
import time
import warnings

import jax

from .. import monitor
from .sink import make_compile_record

__all__ = ["CompileObservatory", "CompileSignature", "RecompileTracker",
           "current_observatory", "diff_signatures", "signature_of",
           "memory_analysis_dict"]

_OBS_STACK = []                 # active (context-entered) observatories
_LISTENER_INSTALLED = False

# only the backend compile event counts as "a compile" for the
# unattributed stream: the trace/MLIR events of the same miss would
# triple-count it (recorder.py sums all three for the compile_ms SPLIT;
# here each record must be one program)
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def current_observatory():
    """The innermost context-active CompileObservatory, or None."""
    return _OBS_STACK[-1] if _OBS_STACK else None


def dispatch(family, jitted, args, arg_names=None, static=None,
             donate=None):
    """The one-line train-step integration point: route `jitted(*args)`
    through the active observatory's recorded AOT cache, or call it
    plainly (one stack peek) when none is active. All four wired
    dispatch sites (TrainStep, ShardedTrainStep, both
    PipelineParallel.train_batch branches) go through here, so the
    observatory contract has a single place to change."""
    obs = current_observatory()
    if obs is None:
        return jitted(*args)
    return obs.call(family, jitted, *args, arg_names=arg_names,
                    static=static, donate=donate)


def _jax_compile_listener(event, duration, **kwargs):
    if event != _BACKEND_COMPILE_EVENT:
        return
    obs = current_observatory()
    if obs is not None:
        obs._on_jax_compile_event(duration)


def _install_listener():
    """Idempotently hook jax's compile-event stream (stays registered
    for the process lifetime; a no-op while no observatory is active)."""
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return
    jax.monitoring.register_event_duration_secs_listener(
        _jax_compile_listener)
    _LISTENER_INSTALLED = True


# ---------------------------------------------------------------------------
# signatures + cause diffs
# ---------------------------------------------------------------------------

def _leaf_desc(x):
    """(shape, dtype, weak_type, sharding) of one argument leaf."""
    try:
        from jax.api_util import shaped_abstractify
        aval = shaped_abstractify(x)
        shape = tuple(aval.shape)
        dtype = str(aval.dtype)
        weak = bool(getattr(aval, "weak_type", False))
    except Exception:
        shape = tuple(getattr(x, "shape", ()))
        dtype = str(getattr(x, "dtype", type(x).__name__))
        weak = False
    sh = getattr(x, "sharding", None)
    return shape, dtype, weak, (str(sh) if sh is not None else None)


class CompileSignature:
    """What a jit cache key is MADE OF, kept human-addressable: one
    descriptor per argument leaf (name derived from the arg tree path,
    e.g. `batch[0]` or `opt_states[1]['m']`), the static values the
    caller declares, and the donate set. Equality of `.key` means the
    jit cache would hit; a changed key plus `diff_signatures` names the
    recompile cause."""

    def __init__(self, leaves, static=None, donate=None):
        self.leaves = tuple(leaves)          # [(name, shape, dtype, wt, sh)]
        self.static = dict(static or {})
        self.donate = tuple(donate or ())
        self.key = (self.leaves,
                    tuple(sorted((k, repr(v))
                                 for k, v in self.static.items())),
                    self.donate)

    def summary(self):
        """Compact JSONL form (the full leaf list would bloat every
        record; the diff is precomputed into `cause` instead). The
        digest is a stable content hash — NOT Python hash(), which is
        per-process randomized — so identical programs digest equal
        across ranks and runs (multi-rank merge / replay correlation)."""
        digest = hashlib.sha1(repr(self.key).encode()).hexdigest()[:8]
        return {"n_leaves": len(self.leaves), "digest": digest}

    def __eq__(self, other):
        return isinstance(other, CompileSignature) and self.key == other.key

    def __hash__(self):
        return hash(self.key)

    def __repr__(self):
        return (f"CompileSignature({len(self.leaves)} leaves, "
                f"static={self.static}, donate={self.donate})")


def signature_of(args, arg_names=None, static=None, donate=None):
    """Build the signature of a positional-args tuple. `arg_names` (one
    per top-level arg) roots the leaf paths — causes then read
    "arg `batch[0]` ..." instead of "arg `[5][0]` ..."."""
    from jax.tree_util import keystr, tree_flatten_with_path
    leaves = []
    for i, arg in enumerate(args):
        root = arg_names[i] if arg_names and i < len(arg_names) else f"[{i}]"
        paths, _ = tree_flatten_with_path(arg)
        for path, leaf in paths:
            leaves.append((root + keystr(path), *_leaf_desc(leaf)))
    return CompileSignature(leaves, static=static, donate=donate)


def _shape_cause(name, old_shape, new_shape):
    if len(old_shape) == len(new_shape):
        changed = [i for i, (a, b) in enumerate(zip(old_shape, new_shape))
                   if a != b]
        axes = ", ".join(f"axis {i}: {old_shape[i]}→{new_shape[i]}"
                         for i in changed)
        return (f"arg `{name}` {axes} "
                f"(shape {old_shape}→{new_shape})")
    return (f"arg `{name}` rank {len(old_shape)}→{len(new_shape)} "
            f"(shape {old_shape}→{new_shape})")


def diff_signatures(old, new):
    """Human-readable causes for why `new` missed where `old` compiled.
    Returns a list of strings, one per changed facet; empty only when
    the signatures are equal (a recompile with an empty diff means the
    jit key involves something the signature cannot see — reported as
    such rather than silently)."""
    if old is None:
        return []
    causes = []
    olds = {name: rest for name, *rest in old.leaves}
    news = {name: rest for name, *rest in new.leaves}
    added = [n for n in news if n not in olds]
    removed = [n for n in olds if n not in news]
    if added or removed:
        causes.append(
            f"arg set changed: {len(old.leaves)}→{len(new.leaves)} "
            f"leaves"
            + (f", added {added[:4]}" if added else "")
            + (f", removed {removed[:4]}" if removed else ""))
    for name in news:
        if name not in olds:
            continue
        (oshape, odt, owt, osh) = olds[name]
        (nshape, ndt, nwt, nsh) = news[name]
        if oshape != nshape:
            causes.append(_shape_cause(name, oshape, nshape))
        if odt != ndt:
            causes.append(f"arg `{name}` dtype {odt}→{ndt}")
        if owt != nwt:
            causes.append(f"weak_type flip on `{name}` ({owt}→{nwt})")
        if osh != nsh and oshape == nshape:
            causes.append(f"arg `{name}` sharding {osh}→{nsh}")
    for k in sorted(set(old.static) | set(new.static)):
        ov, nv = old.static.get(k), new.static.get(k)
        if repr(ov) != repr(nv):
            causes.append(f"static `{k}` {ov!r}→{nv!r}")
    if old.donate != new.donate:
        causes.append(f"new donate set {old.donate}→{new.donate}")
    if not causes:
        causes.append("signature unchanged (cache miss from outside the "
                      "observed facets — e.g. a fresh jit object)")
    return causes


# ---------------------------------------------------------------------------
# compiled-executable introspection
# ---------------------------------------------------------------------------

def memory_analysis_dict(compiled):
    """`compiled.memory_analysis()` flattened to plain per-device byte
    counts ({arg,out,temp,code,alias,total}_bytes), None when the
    backend refuses.
    total excludes generated code: it is the HBM the program's DATA
    needs, the number SH206 projects."""
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return None
        d = {
            "arg_bytes": int(ma.argument_size_in_bytes),
            "out_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "code_bytes": int(ma.generated_code_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
        # aliased (donated) buffers are counted in arg_bytes but their
        # output side is not a second allocation
        d["total_bytes"] = (d["arg_bytes"] + d["out_bytes"]
                            + d["temp_bytes"] - d["alias_bytes"])
        return d
    except Exception:
        return None


def _cost_dict(compiled):
    from ..cost_model import _cost_analysis
    ca = _cost_analysis(compiled)
    flops = float(ca.get("flops", 0.0) or 0.0)
    byts = float(ca.get("bytes accessed", 0.0) or 0.0)
    if flops <= 0 and byts <= 0:
        return None
    return {"flops": flops, "bytes_accessed": byts}


def _hlo_ops(compiled, top_k):
    try:
        from ..cost_model import profile_hlo_text
        prof = profile_hlo_text(compiled.as_text(), top_k=top_k)
        return prof["by_op"] or None
    except Exception:
        return None


# ---------------------------------------------------------------------------
# the tracker (record-keeping half — usable offline/standalone)
# ---------------------------------------------------------------------------

class RecompileTracker:
    """Per-family compile ledger: remembers each family's last
    signature, assigns the per-family ordinal (n_compiles), produces
    the cause diff, and builds the JSONL record. Pure bookkeeping — the
    observatory owns dispatch, counters and judgment, so this half is
    reusable anywhere a compile is observed (StepTimer, tests)."""

    def __init__(self, rank=0, backend=None):
        self.rank = int(rank)
        self.backend = backend
        self.families = {}           # family -> (last signature, count)
        self._last_step = {}         # family -> last recorded step
        self.records = []

    def observe(self, family, signature, compile_ms, step, hbm=None,
                cost=None, hlo_ops=None, hbm_projected_bytes=None,
                analytic_flops=None, untracked=False):
        """Account one compile; returns the record dict (kind='compile').

        The step clock is clamped non-decreasing PER FAMILY: sources
        with instance-local clocks (a fresh StepTimer restarting at 0
        under a family name an earlier instance used) must not make the
        ledger run backwards — trace_check validates monotonicity."""
        step = max(int(step), self._last_step.get(family, 0))
        self._last_step[family] = step
        prev, count = self.families.get(family, (None, 0))
        cause = diff_signatures(prev, signature) \
            if signature is not None else None
        if signature is not None:
            self.families[family] = (signature, count + 1)
        else:
            self.families[family] = (prev, count + 1)
        backend = self.backend
        if backend is None:
            try:
                backend = jax.default_backend()
            except Exception:
                backend = None
        rec = make_compile_record(
            fn=family, step=step, compile_ms=compile_ms, rank=self.rank,
            n_compiles=count + 1, backend=backend,
            cause=cause or None,
            signature=signature.summary() if signature is not None else None,
            hbm=hbm, cost=cost, hlo_ops=hlo_ops,
            hbm_projected_bytes=hbm_projected_bytes,
            analytic_flops=analytic_flops, untracked=untracked)
        self.records.append(rec)
        return rec


# ---------------------------------------------------------------------------
# the observatory
# ---------------------------------------------------------------------------

class CompileObservatory:
    """Context-active compile watcher + AOT dispatch cache.

    obs = CompileObservatory(sink="run.jsonl",
                             hbm_projection=report,      # project_hbm()
                             analytic_flops=fpt * B * S) # MFU's number
    with rec, obs:                      # recorder optional but natural
        for batch in loader:
            loss = train_step(*batch)   # steps dispatch THROUGH obs

    hbm_projection: int bytes or the report dict `project_hbm` returns
    (its per_device.total_bytes is used) — every compile record then
    carries the projection and the detector cross-checks >15% drift.
    analytic_flops: the per-step analytic FLOPs MFU accounting uses —
    compiled cost-analysis FLOPs are cross-checked against it.
    health: an existing HealthMonitor to route anomalies through
    (shares its action/counters/ring); None uses `action` directly
    ('warn' default, 'record', 'raise' HealthError).
    """

    def __init__(self, sink=None, rank=0, health=None, action="warn",
                 config=None, hbm_projection=None, analytic_flops=None,
                 hlo_top_k=8, track_hlo=True, aot_cache_size=32):
        import collections
        from .health import AnomalyDetector, HealthConfig
        from .sink import JsonlSink
        self._owns_sink = isinstance(sink, str)
        self.sink = JsonlSink(sink) if self._owns_sink else sink
        self.rank = int(rank)
        self.health = health
        if isinstance(config, dict):
            config = HealthConfig(**config)
        self.config = config or (health.config if health is not None
                                 else HealthConfig(action=action))
        self.detector = (health.detector if health is not None
                         else AnomalyDetector(self.config))
        self.tracker = RecompileTracker(rank=rank)
        self.analytic_flops = analytic_flops
        self.hbm_projection = self._projection_bytes(hbm_projection)
        self.hlo_top_k = int(hlo_top_k)
        self.track_hlo = bool(track_hlo)
        # bounded LRU: during the exact pathology this tool diagnoses
        # (a signature thrash) an unbounded cache would pin every stale
        # executable — and its jitted object — for the process lifetime
        self._aot = collections.OrderedDict()   # key -> (jitted, compiled)
        self._aot_cap = int(aot_cache_size)
        self._calls = 0
        self._compiling = 0           # listener suppression depth
        _install_listener()

    @staticmethod
    def _projection_bytes(proj):
        if proj is None:
            return None
        if isinstance(proj, dict):
            per_dev = proj.get("per_device", proj)
            return int(per_dev.get("total_bytes"))
        return int(proj)

    # -- context activation -------------------------------------------------
    def __enter__(self):
        _OBS_STACK.append(self)
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        _OBS_STACK.remove(self)
        if self.sink is not None:
            if self._owns_sink:
                self.sink.close()
            elif hasattr(self.sink, "flush"):
                self.sink.flush()
        return False

    # -- dispatch path ------------------------------------------------------
    def call(self, family, jitted, *args, arg_names=None, static=None,
             donate=None):
        """Dispatch `jitted(*args)` through the observatory: an AOT
        cache keyed on the args' signature AND the jitted object's
        identity (a rebuilt jit is a new program even when the
        signature cannot see why — e.g. a trainer re-jitting for a new
        optimizer). A miss lowers+compiles under the clock and
        records/judges the compile; a hit calls the cached executable.
        The jit's own params (shardings, donation) ride through
        lower(), so the executed program is the one the plain dispatch
        would have built."""
        sig = signature_of(args, arg_names=arg_names, static=static,
                           donate=donate)
        key = (family, id(jitted), sig.key)
        entry = self._aot.get(key)
        if entry is None:
            with self.compiling():
                t0 = time.perf_counter()
                compiled = jitted.lower(*args).compile()
                compile_ms = (time.perf_counter() - t0) * 1000.0
            # the entry pins the jitted object so its id() cannot be
            # recycled while the cache would still answer for it;
            # past the cap the least-recently-used executable goes (a
            # re-use after eviction re-lowers and is recorded again)
            self._aot[key] = (jitted, compiled)
            while len(self._aot) > self._aot_cap:
                self._aot.popitem(last=False)
            self.observe(family, sig, compile_ms, compiled=compiled,
                         cross_check=True)
        else:
            self._aot.move_to_end(key)
            compiled = entry[1]
            monitor.incr("compile.aot_hits")
        self._calls += 1
        return compiled(*args)

    @contextlib.contextmanager
    def compiling(self):
        """Suppress the jax.monitoring bridge for a compile this
        observatory is about to attribute itself (also used by
        StepTimer around its own lower/compile)."""
        self._compiling += 1
        try:
            yield
        finally:
            self._compiling -= 1

    # -- observation (also the StepTimer entry point) -----------------------
    def observe(self, family, signature, compile_ms, compiled=None,
                hbm=None, cost=None, untracked=False, step=None,
                cross_check=False):
        """Account one compile: enrich (memory/cost/HLO from the
        compiled executable when given), record, gauge, judge.

        cross_check: attach the observatory's hbm_projection /
        analytic_flops to this record (and so run the drift rules).
        Only the wrapped TRAIN-STEP dispatch sets it — those are the
        programs the projection/analytic numbers describe; a StepTimer
        helper or stray jit must not be judged against them.
        step: explicit step clock for the record (StepTimer passes its
        call count); defaults to the active recorder's step index, else
        this observatory's dispatch count."""
        hlo_ops = None
        if compiled is not None:
            if hbm is None:
                hbm = memory_analysis_dict(compiled)
            if cost is None:
                cost = _cost_dict(compiled)
            if self.track_hlo:
                hlo_ops = _hlo_ops(compiled, self.hlo_top_k)
        rec = self.tracker.observe(
            family, signature, compile_ms,
            step=self._current_step() if step is None else int(step),
            hbm=hbm, cost=cost, hlo_ops=hlo_ops,
            hbm_projected_bytes=(self.hbm_projection
                                 if hbm and cross_check else None),
            analytic_flops=(self.analytic_flops
                            if cost and cross_check else None),
            untracked=untracked)

        monitor.incr("compile.count")
        if untracked:
            monitor.incr("compile.unattributed")
        elif rec["n_compiles"] > 1:
            monitor.incr("compile.recompiles")
        monitor.set_gauge("compile.last_ms", rec["compile_ms"])
        if hbm:
            for k in ("total", "arg", "temp", "out", "code"):
                v = hbm.get(f"{k}_bytes")
                if v is not None:
                    monitor.set_gauge(f"compile.hbm_{k}_bytes", float(v))
        if cost:
            monitor.set_gauge("compile.flops", cost["flops"])

        if self.sink is not None:
            self.sink.write(rec)
        found = self.detector.observe(rec)
        if found:
            self._act(found)
        return rec

    # -- internals ----------------------------------------------------------
    def _current_step(self):
        from .recorder import current_recorder
        rec = current_recorder()
        if rec is not None:
            return rec._step_idx
        return self._calls

    def _on_jax_compile_event(self, duration):
        if self._compiling > 0:
            return        # an attributed compile is mid-flight on some
            # thread; its own observe() accounts it. (Cross-thread races
            # would at worst mis-file one event as attributed.)
        self.observe("(jax)", None, duration * 1000.0, untracked=True)

    def _act(self, anomalies):
        from .health import HealthError
        storms = sum(1 for a in anomalies if a.kind == "recompile_storm")
        if storms:
            monitor.incr("compile.storms", storms)
        if self.health is not None:
            # shared monitor: its action/counters own the response
            self.health._act(anomalies)
            return
        monitor.incr("health.anomalies", len(anomalies))
        if self.config.action == "record":
            return
        if self.config.action == "warn":
            for a in anomalies:
                warnings.warn(f"[compile] {a.message}", RuntimeWarning,
                              stacklevel=4)
            return
        raise HealthError(anomalies)

    def compiled_programs(self):
        """[(family, compiled executable)] for every program the AOT
        cache holds — what a caller reads `as_text()` from to check
        what was really compiled (chip_smoke.py looks for the Mosaic
        kernels and their per-device operand shapes there)."""
        return [(key[0], compiled)
                for key, (_, compiled) in self._aot.items()]

    @property
    def anomalies(self):
        return self.detector.anomalies

    @property
    def records(self):
        return self.tracker.records
