"""Jaxpr lint: static rules over a traced-but-not-executed program.

The trace IS the program (the reference's ProgramDesc analog), so the
whole fused train step can be vetted before a single byte moves to a
device: `jax.make_jaxpr` costs one trace, no compile, no execution.

Rules (family JX, reported as `analysis.Finding`):

- JX101 undonated-state   — param/opt-state/buffer inputs that flow to
                            same-shaped outputs without donation: the
                            update allocates a second copy of every
                            buffer, doubling state HBM for the step.
- JX102 host-callback     — `pure_callback` / `io_callback` /
                            `debug_callback` (jax.debug.print) inside
                            the hot step: each call syncs device->host
                            and caps step throughput.
- JX103 silent-upcast     — a large bf16/fp16 tensor converted to
                            f32/f64 mid-graph: usually an accidental
                            promotion (a f32 literal, a forgotten
                            astype) that doubles the tensor's HBM and
                            bandwidth.
- JX104 x64-hazard        — int64/uint64/float64 values in the graph:
                            TPUs emulate 64-bit (and jax_enable_x64
                            leaks it everywhere); almost never intended
                            in a train step.
- JX105 degenerate-collective — psum/all_gather/... over axes that are
                            all size 1 on the given mesh: a no-op that
                            still pays collective latency per step.
- JX106 reduce-then-broadcast — psum_scatter (reduce-scatter) whose
                            result is immediately all_gather'd over the
                            same axis: that pair IS an all-reduce; the
                            fused form halves launch count.
"""
import numpy as np

import jax

from . import Finding, SEV_ERROR, SEV_INFO, SEV_WARNING

# primitives that indicate a host round-trip inside the step
_CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                   "debug_print", "callback")

# psum_invariant / all_gather_invariant: what psum / all_gather trace to
# under shard_map's varying-axes tracking when the result is replicated
_COLLECTIVE_PRIMS = ("psum", "psum_invariant", "pmax", "pmin",
                     "all_gather", "all_gather_invariant", "all_to_all",
                     "ppermute", "psum_scatter", "reduce_scatter")

# JX103 floor: below this many elements an upcast is noise, not a
# bandwidth problem (biases, norms, scalars)
UPCAST_MIN_ELEMENTS = 65536


def _iter_jaxprs(jaxpr, path="step"):
    """Yield (jaxpr, path) for the top jaxpr and every sub-jaxpr reachable
    through eqn params (pjit/scan/while/cond/custom_vjp/shard_map/remat),
    duck-typed so it tracks jax versions without private imports."""
    yield jaxpr, path
    for eqn in jaxpr.eqns:
        for key, val in eqn.params.items():
            vals = val if isinstance(val, (tuple, list)) else (val,)
            for i, v in enumerate(vals):
                inner = getattr(v, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    v = inner           # ClosedJaxpr -> Jaxpr
                if hasattr(v, "eqns") and hasattr(v, "invars"):
                    sub = f"{path}/{eqn.primitive.name}"
                    if len(vals) > 1:
                        sub += f"[{i}]"
                    yield from _iter_jaxprs(v, sub)


def _dtype_name(dt):
    """Dtype name tolerant of extended dtypes (PRNG keys have no numpy
    equivalent — np.dtype() raises on them)."""
    try:
        return np.dtype(dt).name
    except TypeError:
        return str(dt)


def _eqn_site(eqn):
    """Best-effort user call-site of an eqn from its source_info."""
    try:
        tb = eqn.source_info.traceback
        frame = tb.frames[0] if tb is not None and tb.frames else None
        if frame is not None:
            import os
            return f"{os.path.basename(frame.file_name)}:{frame.start_line}"
    except Exception:
        pass
    return eqn.primitive.name


def _axis_names(val):
    """Normalize an eqn's axis spec (name, tuple, frozenset) to a tuple."""
    if val is None:
        return ()
    if isinstance(val, (tuple, list, set, frozenset)):
        out = []
        for a in val:
            out.extend(_axis_names(a))
        return tuple(out)
    return (val,)


def lint_jaxpr(closed, *, donated=(), mesh_axis_sizes=None, fn_name="step",
               state_invars=None, param_names=None):
    """Run all JX rules over one ClosedJaxpr.

    donated:         iterable of flat-invar indices that are donated.
    mesh_axis_sizes: {axis_name: size} for JX105 (unknown axes skipped).
    state_invars:    flat-invar indices holding persistent train state
                     (params / opt states / buffers) — the JX101 set;
                     when None, JX101 is skipped (plain function lint).
    param_names:     optional names parallel to state_invars for
                     readable locations.
    """
    findings = []
    jaxpr = closed.jaxpr
    donated = set(donated)
    axis_sizes = dict(mesh_axis_sizes or {})

    # ---- JX101: persistent state that is not donated -------------------
    if state_invars is not None:
        undonated, bytes_undonated = [], 0
        for j, idx in enumerate(state_invars):
            if idx in donated or idx >= len(jaxpr.invars):
                continue
            aval = jaxpr.invars[idx].aval
            n = int(np.prod(aval.shape)) if aval.shape else 1
            undonated.append(param_names[j] if param_names
                             and j < len(param_names) else f"arg{idx}")
            bytes_undonated += n * aval.dtype.itemsize
        if undonated:
            head = ", ".join(undonated[:4])
            if len(undonated) > 4:
                head += f", +{len(undonated) - 4} more"
            findings.append(Finding(
                "JX101", SEV_WARNING, f"{fn_name}({head})",
                f"{len(undonated)} persistent state buffer(s) "
                f"({bytes_undonated / 1e6:.1f} MB) enter the step without "
                "donation: the updated copies allocate fresh HBM next to "
                "the old ones every step",
                suggestion="pass donate=True / donate_argnums for "
                           "params, optimizer states and buffers"))

    # ---- per-eqn rules (recursive over sub-jaxprs) ---------------------
    prev_prim = {}   # outvar id -> (primitive name, axes) for JX106
    for sub, path in _iter_jaxprs(jaxpr, fn_name):
        for eqn in sub.eqns:
            prim = eqn.primitive.name
            site = _eqn_site(eqn)

            if prim in _CALLBACK_PRIMS or prim.endswith("_callback"):
                what = eqn.params.get("callback", prim)
                findings.append(Finding(
                    "JX102", SEV_ERROR, f"{path} @ {site}",
                    f"host callback `{prim}` ({what!r}) inside the "
                    "compiled step: every invocation stalls the device "
                    "on a host round-trip",
                    suggestion="move debugging out of the hot step or "
                               "gate it behind a flag that is off in "
                               "production"))

            if prim == "convert_element_type":
                src = eqn.invars[0].aval
                dst = eqn.params.get("new_dtype")
                n = int(np.prod(src.shape)) if src.shape else 1
                # name-based: ml_dtypes' bfloat16 reports dtype.kind 'V'
                if (dst is not None
                        and _dtype_name(src.dtype) in ("bfloat16",
                                                       "float16")
                        and _dtype_name(dst) in ("float32", "float64")
                        and n >= UPCAST_MIN_ELEMENTS):
                    findings.append(Finding(
                        "JX103", SEV_WARNING, f"{path} @ {site}",
                        f"large tensor {tuple(src.shape)} silently upcast "
                        f"{_dtype_name(src.dtype)} -> "
                        f"{_dtype_name(dst)}: doubles its HBM footprint "
                        "and bandwidth mid-graph",
                        suggestion="keep the compute dtype, or make the "
                                   "accumulation explicit via "
                                   "preferred_element_type"))

            for v in list(eqn.outvars) + list(eqn.invars):
                aval = getattr(v, "aval", None)
                dt = getattr(aval, "dtype", None)
                if dt is not None and _dtype_name(dt) in (
                        "int64", "uint64", "float64"):
                    findings.append(Finding(
                        "JX104", SEV_WARNING, f"{path} @ {site}",
                        f"64-bit value ({_dtype_name(dt)} "
                        f"{tuple(aval.shape)}) in the step: TPUs emulate "
                        "64-bit arithmetic and it usually means "
                        "jax_enable_x64 leaked into the hot path",
                        suggestion="cast indices/labels to int32 and "
                                   "accumulators to float32"))
                    break   # one per eqn is enough

            if prim in _COLLECTIVE_PRIMS:
                axes = _axis_names(
                    eqn.params.get("axes", eqn.params.get(
                        "axis_name", eqn.params.get("axis_index_groups"))))
                named = [a for a in axes if isinstance(a, str)]
                known = [a for a in named if a in axis_sizes]
                if known and all(axis_sizes[a] == 1 for a in known) \
                        and len(known) == len(named):
                    findings.append(Finding(
                        "JX105", SEV_WARNING, f"{path} @ {site}",
                        f"collective `{prim}` over axis "
                        f"{tuple(named)} of size 1: a no-op that still "
                        "pays a collective launch every step",
                        suggestion="drop the collective or gate it on "
                                   "the mesh axis size"))
                # JX106: reduce-scatter immediately re-gathered
                if prim in ("all_gather", "all_gather_invariant") \
                        and eqn.invars:
                    src_info = prev_prim.get(id(eqn.invars[0]))
                    if src_info is not None:
                        sprim, saxes = src_info
                        if sprim in ("psum_scatter", "reduce_scatter") \
                                and set(named) & set(saxes):
                            findings.append(Finding(
                                "JX106", SEV_INFO, f"{path} @ {site}",
                                "reduce-scatter followed by all_gather "
                                f"over axis {tuple(named)}: the pair is "
                                "an all-reduce issued as two "
                                "collectives",
                                suggestion="replace the "
                                           "psum_scatter+all_gather pair "
                                           "with a single psum"))
                for ov in eqn.outvars:
                    prev_prim[id(ov)] = (prim, named)
    return findings


# ---------------------------------------------------------------------------
# convenience entry points over the framework's step objects
# ---------------------------------------------------------------------------

def flat_argnum_indices(args, argnums):
    """Map positional argnums to flat-invar index lists, matching how
    make_jaxpr flattens its arguments left-to-right (dict leaves in
    sorted-key order). THE single place this rule lives — trace hooks
    must not re-derive it."""
    sizes = [len(jax.tree_util.tree_leaves(a)) for a in args]
    starts = np.cumsum([0] + sizes)
    out = []
    for argnum in argnums:
        out.extend(range(int(starts[argnum]), int(starts[argnum + 1])))
    return out

def trace_train_step(train_step, *batch):
    """Trace a jit.TrainStep / distributed.ShardedTrainStep into
    (ClosedJaxpr, donated indices, state indices, names) WITHOUT
    executing it. `batch` entries may be Tensors, arrays, or
    ShapeDtypeStructs."""
    import jax.numpy as jnp
    from ..core.tensor import Tensor
    from ..core.random import default_generator

    ts = train_step
    step_fn = ts._build_step_fn(check_nan_inf=False)
    param_vals = [jax.ShapeDtypeStruct(p._value.shape, p._value.dtype)
                  for p in ts.params]
    opt_states = [
        {k: jax.ShapeDtypeStruct(np.shape(v), getattr(v, "dtype",
                                                      np.float32))
         for k, v in ts.optimizer._states[id(p)].items()}
        for p in ts.params]
    buffer_vals = [jax.ShapeDtypeStruct(b._value.shape, b._value.dtype)
                   for b in ts.buffers]
    batch_vals = []
    for b in batch:
        if isinstance(b, Tensor):
            b = b._value
        if not isinstance(b, jax.ShapeDtypeStruct):
            b = jax.ShapeDtypeStruct(np.shape(b), jnp.asarray(b).dtype)
        batch_vals.append(b)
    lr = jax.ShapeDtypeStruct((), jnp.float32)
    # get_state does NOT advance the stream (split would): linting a
    # step must not change the run's subsequent dropout masks/draws
    key = default_generator().get_state()
    rng = jax.ShapeDtypeStruct(key.shape, key.dtype)

    args = (param_vals, opt_states, buffer_vals, lr, rng, batch_vals)
    closed = jax.make_jaxpr(step_fn)(*args)

    donated = flat_argnum_indices(args, (0, 1, 2)) if ts._donate else []
    state_idx = flat_argnum_indices(args, (0, 1, 2))

    names = list(getattr(ts, "param_names", []))
    state_names = [f"param:{n}" for n in names]
    for n, p in zip(names, ts.params):
        # tree_flatten visits dict keys sorted — mirror that order
        state_names.extend(
            f"opt:{n}.{k}" for k in sorted(ts.optimizer._states[id(p)]))
    state_names.extend(f"buffer:{i}" for i in range(len(ts.buffers)))
    return closed, donated, state_idx, state_names


def lint_train_step(train_step, *batch, mesh=None):
    """Trace + lint a TrainStep/ShardedTrainStep against an example (or
    abstract) batch. Returns findings; never executes the step."""
    closed, donated, state_idx, names = trace_train_step(train_step, *batch)
    axis_sizes = None
    mesh = mesh or getattr(train_step, "mesh", None)
    if mesh is not None:
        axis_sizes = {a: int(mesh.shape[a]) for a in mesh.axis_names}
    return lint_jaxpr(
        closed, donated=donated, state_invars=state_idx,
        param_names=names, mesh_axis_sizes=axis_sizes,
        fn_name=type(train_step).__name__)


def lint_callable(fn, *args, mesh_axis_sizes=None, fn_name=None):
    """Lint an arbitrary jittable callable (no donation/state rules)."""
    closed = jax.make_jaxpr(fn)(*args)
    return lint_jaxpr(closed, mesh_axis_sizes=mesh_axis_sizes,
                      fn_name=fn_name or getattr(fn, "__name__", "fn"))
