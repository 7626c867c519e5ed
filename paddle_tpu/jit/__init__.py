"""paddle_tpu.jit — trace-compile eager code into XLA programs.

TPU-native replacement for the reference's dynamic-to-static subsystem
(`python/paddle/fluid/dygraph/dygraph_to_static/program_translator.py:768`,
15+ AST transformers, `partial_program.py` run_program_op). The eager
Tensor ops *are* traceable jax computations, so `to_static` binds Layer
parameters/buffers as traced inputs and runs the Python function under
`jax.jit`; the autograd tape records at trace time, so a whole train step
(forward+backward+optimizer) compiles into ONE fused XLA program —
`TrainStep` packages that pattern. One AST pass remains
(`dy2static.convert_dynamic`): tensor-dependent Python `if`/`while`/`for`
and bool-ops are rewritten to dispatch into `static.control_flow`, which
lowers to native XLA control flow instead of the reference's sub-block
programs.
"""
import contextlib
import functools

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, Parameter
from ..core import autograd
from ..core.random import rng_guard, default_generator
from ..core.dtype import convert_dtype
from ..core.scope import scope


class InputSpec:
    """Shape/dtype spec for traced inputs (paddle.static.InputSpec analog)."""

    def __init__(self, shape, dtype="float32", name=None):
        self.shape = tuple(shape)
        self.dtype = convert_dtype(dtype)
        self.name = name

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype})"


@contextlib.contextmanager
def bind_tensors(tensors, values):
    """Temporarily swap raw values (possibly tracers) into Tensors; always
    restores, even on trace error."""
    olds = [t._value for t in tensors]
    grads = [t.grad for t in tensors]
    for t, v in zip(tensors, values):
        t._value = v
        t.grad = None
    try:
        yield
    finally:
        for t, o, g in zip(tensors, olds, grads):
            t._value = o
            t.grad = g


def _split_args(args):
    """Flatten args into (tensor values, rebuild fn, static cache key)."""
    leaves, treedef = jax.tree_util.tree_flatten(
        args, is_leaf=lambda x: isinstance(x, Tensor))
    dyn_idx, dyn_vals, static = [], [], []
    for i, leaf in enumerate(leaves):
        if isinstance(leaf, Tensor):
            dyn_idx.append(i)
            dyn_vals.append(leaf._value)
            static.append(None)
        elif isinstance(leaf, (jax.Array, np.ndarray)):
            dyn_idx.append(i)
            dyn_vals.append(jnp.asarray(leaf))
            static.append(None)
        else:
            static.append(leaf)

    def rebuild(values):
        out = list(static)
        for i, v in zip(dyn_idx, values):
            out[i] = Tensor(v)
        return jax.tree_util.tree_unflatten(treedef, out)

    key = (treedef, tuple(s if _hashable(s) else repr(s) for s in static))
    return dyn_vals, rebuild, key


def _hashable(x):
    try:
        hash(x)
        return True
    except TypeError:
        return False


def _unwrap_out(out):
    return jax.tree_util.tree_map(
        lambda x: x._value if isinstance(x, Tensor) else x, out,
        is_leaf=lambda x: isinstance(x, Tensor))


def _wrap_out(out):
    return jax.tree_util.tree_map(
        lambda x: Tensor(x) if isinstance(x, jax.Array) else x, out)


class StaticFunction:
    """Compiled wrapper of a python function / Layer forward."""

    def __init__(self, function, layer=None, input_spec=None):
        self._orig_fn = function
        self._fn = None     # AST-converted lazily at first call: by then
        # late-defined module globals and closure cells (e.g. super()'s
        # __class__, filled only after the class body completes) exist
        self._layer = layer if layer is not None else getattr(
            function, "__self__", None)
        from ..nn.layer.layers import Layer
        if not isinstance(self._layer, Layer):
            self._layer = None
        self._input_spec = input_spec
        self._jit_cache = {}
        try:
            functools.update_wrapper(self, function,
                                     assigned=("__name__", "__doc__"))
        except Exception:
            pass

    def _collect_state(self):
        if self._layer is None:
            return [], []
        params = [p for _, p in self._layer.named_parameters()]
        buffers = [b for _, b in self._layer.named_buffers() if b is not None]
        return params, buffers

    def __call__(self, *args, **kwargs):
        if not ProgramTranslator.enable_to_static:
            # the reference's global kill-switch: run the original
            # eager Python, no conversion, no jit
            return self._orig_fn(*args, **kwargs)
        if self._fn is None:
            # reference ProgramTranslator order: AST transform, then
            # trace — tensor-dependent if/while/for/bool-ops dispatch
            # into static.control_flow; plain Python keeps its semantics
            from .dy2static import convert_dynamic
            self._fn = convert_dynamic(self._orig_fn)
        params, buffers = self._collect_state()
        # args AND kwargs flatten together: kwarg tensor values become
        # traced inputs and non-tensor kwarg values are part of the cache
        # key (same keys with different values must not replay a stale
        # trace)
        dyn_vals, rebuild, key = _split_args((args, kwargs))
        # amp state is read at trace time; a toggled auto_cast context must
        # not silently reuse a trace made under the other policy
        from ..amp import amp_state
        st = amp_state()
        cache_key = (key, st.enabled, str(st.dtype) if st.enabled else "")

        jitted = self._jit_cache.get(cache_key)
        if jitted is None:
            fn = self._fn

            def traced(param_vals, buffer_vals, rng, arg_vals):
                with autograd.fresh_tape(), autograd.no_grad(), \
                        bind_tensors(params, param_vals), \
                        bind_tensors(buffers, buffer_vals), rng_guard(rng):
                    rb_args, rb_kwargs = rebuild(arg_vals)
                    out = fn(*rb_args, **rb_kwargs)
                    new_buf = [b._value for b in buffers]
                    return _unwrap_out(out), new_buf

            jitted = jax.jit(traced)
            self._jit_cache[cache_key] = jitted

        rng = default_generator().split()
        try:
            out_vals, new_buf = jitted([p._value for p in params],
                                       [b._value for b in buffers], rng,
                                       dyn_vals)
        except Exception as e:
            from .dy2static import friendly_trace_error
            friendly = friendly_trace_error(
                e, getattr(self._fn, "__name__", "function"))
            if friendly is not None:
                raise friendly from e
            raise
        for b, v in zip(buffers, new_buf):
            b._value = v
        return _wrap_out(out_vals)


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """Decorator/wrapper: compile a function or a Layer.

    paddle.jit.to_static analog. Accepts a Layer (compiles its forward) or a
    function (possibly a bound Layer method).
    """
    from ..nn.layer.layers import Layer

    def decorate(obj):
        if isinstance(obj, Layer):
            static = StaticFunction(obj.forward, layer=obj,
                                    input_spec=input_spec)
            obj.forward = static
            return obj
        return StaticFunction(obj, input_spec=input_spec)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(func):
    func._not_to_static = True
    return func


class TrainStep:
    """One fused-XLA training step: forward + backward + clip + optimizer.

    The TPU-native answer to the reference's static-graph training path
    (program + `append_backward` `python/paddle/fluid/backward.py:1390` +
    optimizer ops run by `framework/executor.cc:485`): the same eager code is
    traced once and jitted, with params/opt-state donated so updates happen
    in-place in HBM.

    loss_fn(*batch_tensors) -> scalar loss Tensor, computed with the model
    (closed over). Buffers (e.g. BN running stats) are threaded functionally.

    lint: False (default) | True (run the graph-doctor jaxpr lint at
    trace time and warn on findings) | "strict" (raise GraphDoctorError
    on error-severity findings) — see paddle_tpu.analysis.

    health: None (default) | True | dict | telemetry.HealthConfig |
    telemetry.HealthMonitor — in-flight numerics monitoring. When on,
    the traced step also computes global grad-norm, update/param ratio
    and NaN/Inf counts as DEVICE-SIDE auxiliary outputs (no host sync;
    one small fetch every `every_k` steps), feeds them through the
    anomaly detector (loss spikes, grad explosions, step-time
    regressions, hard NaN/Inf) with the configured warn/record/raise
    action, arms the hang watchdog around each step, and lands the
    fields in the step's JSONL record — see paddle_tpu.telemetry.health.

    resilience: None (default) | resilience.ResilienceManager |
    CheckpointManager | checkpoint-dir str | kwargs dict — fault
    tolerance. When on, every completed step calls the manager's
    step_boundary: periodic atomic step checkpoints (async, at most one
    in flight), and on an armed SIGTERM/preemption request a final
    synchronous checkpoint + black-box dump + SystemExit with the
    resumable exit code — see paddle_tpu.resilience.
    """

    def __init__(self, model, loss_fn, optimizer, donate=True, lint=False,
                 health=None, resilience=None):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        named = [(n, p) for n, p in model.named_parameters()
                 if not p.stop_gradient]
        self.param_names = [n for n, _ in named]
        self.params = [p for _, p in named]
        self.buffers = [b for _, b in model.named_buffers() if b is not None]
        for p in self.params:
            self.optimizer._get_state(p)
        self._jitted = None
        self._donate = donate
        self._lint = lint
        self.lint_findings = None
        from ..telemetry import health as _health
        self.health = _health.as_monitor(health)
        self._last_health = None
        from ..resilience.preempt import as_resilience
        self.resilience = as_resilience(resilience)
        if self.resilience is not None:
            self.resilience.attach(model, optimizer)

    def _maybe_lint(self, batch):
        """Pre-flight static analysis of the step (one extra trace, no
        execution) the first time a program is built with lint on."""
        if not self._lint or self.lint_findings is not None:
            return
        from ..analysis import emit
        from ..analysis.jaxpr_lint import lint_train_step
        self.lint_findings = emit(
            lint_train_step(self, *batch), mode=self._lint,
            title=f"graph doctor [{type(self).__name__}]")

    def _build_step_fn(self, check_nan_inf=False, health_taps=False):
        params, buffers, opt = self.params, self.buffers, self.optimizer
        loss_fn = self.loss_fn
        model = self.model

        def step(param_vals, opt_states, buffer_vals, lr, rng, batch_vals):
            with autograd.fresh_tape(), \
                    bind_tensors(params, param_vals), \
                    bind_tensors(buffers, buffer_vals), rng_guard(rng):
                batch = [Tensor(v) for v in batch_vals]
                loss = loss_fn(*batch)
                # MoE routing-health taps (paddle_tpu.moe): collected
                # as a device-side aux output like the health taps
                collect = getattr(model, "collect_moe_stats", None)
                mstats = collect() if collect is not None else None
                autograd.backward(loss)
                grads = []
                for p in params:
                    grads.append(p.grad._value if p.grad is not None
                                 else jnp.zeros_like(p._value))
                # compiled FLAGS_check_nan_inf analog: the per-op eager scan
                # can't see inside a fused step, so check loss + every grad
                # here (costs one tiny all-reduce per tensor, flag-gated)
                checks = None
                if check_nan_inf:
                    checks = (jnp.isfinite(loss._value).all(),
                              jnp.stack([jnp.all(jnp.isfinite(g))
                                         for g in grads])
                              if grads else jnp.ones((0,), jnp.bool_))
                # health taps judge the RAW grads (an explosion the clip
                # would mask is exactly what the detector must see)
                raw_grads = grads if health_taps else None
                with autograd.no_grad(), scope("optimizer"):
                    if opt._grad_clip is not None:
                        pg = opt._grad_clip(
                            [(p, Tensor(g)) for p, g in zip(params, grads)])
                        grads = [g._value for _, g in pg]
                    new_vals, new_states = opt._functional_apply(
                        params, param_vals, grads, opt_states, lr)
                if check_nan_inf:
                    # a poisoned step must not be applied: keep the old
                    # params/opt-state when anything was non-finite (the old
                    # buffers are donated, so the select must happen on
                    # device inside this program)
                    ok = jnp.logical_and(checks[0], jnp.all(checks[1]))
                    new_vals = [jnp.where(ok, n, o)
                                for n, o in zip(new_vals, param_vals)]
                    new_states = jax.tree_util.tree_map(
                        lambda n, o: jnp.where(ok, n, o),
                        new_states, opt_states)
                hstats = None
                if health_taps:
                    from ..telemetry.health import device_health_stats
                    hstats = device_health_stats(
                        loss._value, raw_grads, new_vals, param_vals)
                new_buf = [b._value for b in buffers]
                return (loss._value, new_vals, new_states, new_buf,
                        checks, hstats, mstats)

        return step

    def _make_step(self, check_nan_inf=False, health_taps=False):
        donate = (0, 1, 2) if self._donate else ()
        return jax.jit(self._build_step_fn(check_nan_inf=check_nan_inf,
                                           health_taps=health_taps),
                       donate_argnums=donate)

    def __call__(self, *batch):
        # flight-recorder integration: a context-active TelemetryRecorder
        # sees every step (wall time + the compile/execute split via the
        # jax.monitoring compile events this dispatch may emit) with no
        # call-site changes; inert (one stack peek) when no recorder is on
        from .. import telemetry
        with telemetry.auto_step() as _tw:
            if self.health is not None:
                # guard: watchdog armed around the step, black-box dump
                # on an escaping exception, taps fetched every k and
                # noted into the step record
                with self.health.guard(_tw) as g:
                    out = self._run_step(*batch)
                    g.stage(self._last_health)
            else:
                out = self._run_step(*batch)
            if getattr(self, "_last_moe", None) is not None:
                from ..moe.stats import note_step_stats
                note_step_stats(_tw, self._last_moe,
                                getattr(self.model, "moe_num_experts",
                                        None))
            _tw.note(loss=out)
        # resilience boundary AFTER the step record closes: periodic
        # checkpoint, and an armed preemption request drains + commits
        # + exits resumable here — never mid-step
        if self.resilience is not None:
            self.resilience.step_boundary(loss=out)
        return out

    def _run_step(self, *batch):
        from ..amp import amp_state
        from .. import flags
        st = amp_state()
        check = flags.get_flag("check_nan_inf")
        taps = self.health is not None
        amp_key = (st.enabled, str(st.dtype) if st.enabled else "", check,
                   taps)
        if self._jitted is None or getattr(self, "_amp_key", None) != amp_key:
            self._maybe_lint(batch)
            self._jitted = self._make_step(check_nan_inf=check,
                                           health_taps=taps)
            self._amp_key = amp_key
        from .. import monitor
        monitor.incr("jit.train_steps")
        batch_vals = [b._value if isinstance(b, Tensor) else jnp.asarray(b)
                      for b in batch]
        param_vals = [p._value for p in self.params]
        opt_states = [self.optimizer._states[id(p)] for p in self.params]
        buffer_vals = [b._value for b in self.buffers]
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        rng = default_generator().split()
        # compile observatory: while one is context-active, dispatch
        # goes through its signature-keyed AOT cache, so every
        # (re)compile is recorded with a cause diff + memory/cost
        # analysis; inert (one stack peek) otherwise. The family
        # carries the model class: two TrainSteps over different
        # models are different programs, not recompiles.
        from ..telemetry import compile_obs
        loss, new_vals, new_states, new_buf, checks, hstats, mstats = \
            compile_obs.dispatch(
                f"{type(self).__name__}[{type(self.model).__name__}]",
                self._jitted,
                (param_vals, opt_states, buffer_vals, lr, rng, batch_vals),
                arg_names=("params", "opt_states", "buffers", "lr", "rng",
                           "batch"),
                static={"check_nan_inf": check, "amp": st.enabled,
                        "amp_dtype": str(st.dtype) if st.enabled else "",
                        "health_taps": taps},
                donate=(0, 1, 2) if self._donate else ())
        self._last_health = hstats
        self._last_moe = mstats
        # reassign state FIRST: the inputs were donated, so the tensors must
        # point at the fresh buffers even when the finite check fires (the
        # step itself was skipped on device in that case)
        for p, v in zip(self.params, new_vals):
            p._value = v
            p.grad = None
        for p, s in zip(self.params, new_states):
            self.optimizer._states[id(p)] = s
        for b, v in zip(self.buffers, new_buf):
            b._value = v
        if checks is not None:
            self._report_non_finite(checks)
        return Tensor(loss)

    def _report_non_finite(self, checks):
        loss_ok, grads_ok = checks
        grads_ok = np.asarray(grads_ok)
        if bool(loss_ok) and bool(grads_ok.all()):
            return
        bad = [n for n, ok in zip(self.param_names, grads_ok) if not ok]
        msg = ("check_nan_inf: train step produced non-finite "
               + " and ".join(
                   (["loss"] if not bool(loss_ok) else [])
                   + ([f"grads for {bad[:8]}"
                       + (f" (+{len(bad) - 8} more)" if len(bad) > 8 else "")]
                      if bad else []))
               + "; the update was skipped")
        from ..flags import get_flag
        if get_flag("check_nan_inf_level") >= 1:
            import warnings
            warnings.warn(msg)
        else:
            raise FloatingPointError(msg)


class TracedLayer:
    """Trace a dygraph Layer into a reusable compiled program.

    Reference surface: `fluid/dygraph/jit.py:1157` (`TracedLayer.trace`
    returns (outputs, traced); traced(inputs) replays;
    `save_inference_model` exports).  Here "trace" is a jit-compiled
    StaticFunction over the layer's forward with its parameters captured —
    no Program recording, the jaxpr IS the program.
    """

    def __init__(self, layer, static_fn, example_inputs):
        self._layer = layer
        self._static = static_fn
        self._example_inputs = example_inputs

    @staticmethod
    def trace(layer, inputs):
        inputs = list(inputs) if isinstance(inputs, (tuple, list)) \
            else [inputs]
        static_fn = StaticFunction(layer.forward, layer=layer)
        out = static_fn(*inputs)
        traced = TracedLayer(layer, static_fn, inputs)
        outs = out if isinstance(out, (tuple, list)) else [out]
        return list(outs), traced

    def __call__(self, inputs):
        inputs = list(inputs) if isinstance(inputs, (tuple, list)) \
            else [inputs]
        out = self._static(*inputs)
        return list(out) if isinstance(out, (tuple, list)) else [out]

    def set_strategy(self, build_strategy=None, exec_strategy=None):
        # XLA owns scheduling/fusion; the reference's knobs have no analog
        return None

    def save_inference_model(self, path, feed=None, fetch=None, **configs):
        if feed is not None or fetch is not None:
            import warnings
            warnings.warn(
                "TracedLayer.save_inference_model: feed/fetch slot "
                "selection is not supported; exporting ALL traced "
                "inputs/outputs", stacklevel=2)
        from ..inference.export import save_inference_model
        save_inference_model(path, self._layer,
                             example_inputs=self._example_inputs)


def save(layer, path, input_spec=None, **configs):
    """Export for inference: StableHLO via jax.export + params
    (paddle.jit.save analog — see paddle_tpu.inference)."""
    from ..inference.export import save_inference_model
    save_inference_model(path, layer, input_spec=input_spec)


def load(path, **configs):
    from ..inference.export import load_inference_model
    return load_inference_model(path)


from .dy2static import (  # noqa: E402,F401  (public dy2static surface)
    Dy2StaticError, convert_dynamic, max_loop_iterations)


# ---- dy2static management surface (reference `program_translator.py`,
# `logging_utils.py`) --------------------------------------------------

_dy2stat_verbosity = 0
_dy2stat_code_level = -1


def set_verbosity(level=0, also_to_stdout=False):
    """Transcription logging verbosity (reference logging_utils.py:81).
    Conversion here is a single AST pass, so levels just gate whether the
    converted source is reported via warnings."""
    global _dy2stat_verbosity
    _dy2stat_verbosity = int(level)


def set_code_level(level=100, also_to_stdout=False):
    """Report converted code (reference logging_utils.py:51)."""
    global _dy2stat_code_level
    _dy2stat_code_level = int(level)


class ProgramTranslator:
    """Singleton switch for dy2static conversion (reference
    `program_translator.py:768`). enable(False) makes to_static run the
    original Python (tracing still compiles straight-line code)."""
    _instance = None
    enable_to_static = True

    @classmethod
    def get_instance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def enable(self, enable_to_static=True):
        type(self).enable_to_static = bool(enable_to_static)

    def get_program_cache(self):
        return {}


def enable_to_static(flag=True):
    ProgramTranslator.get_instance().enable(flag)


class TranslatedLayer:
    """Loaded-inference-artifact Layer face (reference
    `translated_layer.py`: the Layer returned by paddle.jit.load). Here
    jit.load returns the ExportedModel; this subclass-compatible alias
    exists so isinstance checks and type hints port."""

    def __init__(self, exported):
        self._exported = exported

    def __call__(self, *args):
        return self._exported(*args)

    def eval(self):
        return self

    def train(self):
        raise RuntimeError(
            "TranslatedLayer wraps a serving artifact (params baked as "
            "constants); re-train from the source Layer instead")
