"""Kernel Doctor: static race / VMEM / cost verification of Pallas kernels.

The jaxpr/sharding level has the Graph Doctor; this is the same
pre-flight discipline one level down, where a wrongly-parallel grid
axis or an over-VMEM block silently corrupts results or fails Mosaic
only at scale. Every `pallas_call` site registers itself
(`ops/kernel_registry.register_kernel`) with canonical example inputs;
the doctor captures each site's grid, BlockSpecs and operands by
intercepting `pallas_call` while the example runs, traces the kernel
body via `jax.make_jaxpr`, and derives — WITHOUT a TPU:

- KN501 grid-race     — evaluate every output BlockSpec `index_map`
                        over the whole grid; two grid points that write
                        the same output block while differing in an
                        axis marked `parallel` (dimension_semantics)
                        race: their DMA flush order is undefined. This
                        is the sequential-flush invariant of the
                        triangle-grid attention kernels generalized
                        from a comment into a checked property —
                        Mosaic's default sequential ("arbitrary") order
                        makes revisits legal; marking the axis parallel
                        does not.
- KN502 VMEM footprint— blocks x dtypes x double-buffering (+ scratch)
                        vs the per-core budget, the single projection
                        `moe_kernel_supported` / `paged_decode_supported`
                        delegate to (ops/kernel_registry.vmem_footprint).
- KN503 cost honesty  — declared CostEstimate FLOPs/transcendentals vs
                        values counted from the traced kernel jaxpr
                        (x grid steps), 25% drift threshold like the
                        PR-4 `flops_drift` rule; declared bytes vs a
                        revisit-aware DMA count of the block stream,
                        order-of-magnitude band (the in-tree estimates
                        quote streaming-convention bytes, so the byte
                        check is honesty, not exactness).
- KN504 fallback parity— seeded differential harness: each registered
                        kernel runs against its declared exact fallback
                        on randomized in-support shapes (interpret mode
                        off-TPU), outputs compared within the
                        registration's tolerance.
- KN505 grid-spec sanity— scalar-prefetch operands actually scalar
                        metadata (small, <= 2-D, SMEM-sized), index_maps
                        pure (re-evaluation stable) and in-bounds, and
                        the grid covers every output block (no window
                        left unwritten).

Entry points: `lint_kernel(reg)` / `lint_registry()` (used by
`tools/kerneldoctor.py`, the ci.sh stage-3 gate) and `capture_kernels`
/ `check_grid_races` for targeted tests (tests/test_io_prefetch.py
pins the triangle-grid invariant through KN501).
"""
import contextlib
import itertools
import os

import numpy as np

from . import Finding, SEV_ERROR, SEV_WARNING
from ..ops import kernel_registry
from ..ops.kernel_registry import VMEM_BUDGET, block_bytes, vmem_footprint

# KN503 thresholds: relative drift like the PR-4 flops_drift rule, with
# absolute floors so kernels whose whole work is below the floor (pure
# data movers) aren't failed over rounding-level disagreements; bytes
# use a band because declared estimates quote the streaming convention
# (each array crosses HBM once) while the per-step block walk counts
# re-fetches — same order of magnitude or the estimate is fiction.
COST_DRIFT_FRAC = 0.25
COST_FLOPS_FLOOR = 1_000_000
COST_TRANS_FLOOR = 100_000
COST_BYTES_BAND = 8.0
COST_BYTES_FLOOR = 1 << 20

# KN505 scalar-prefetch bounds: the prefetch channel is SMEM-resident
# index metadata, not tensor data
PREFETCH_MAX_BYTES = 256 * 1024
PREFETCH_MAX_NDIM = 2

# KN501/KN505 grid enumeration cap — registered examples must stay
# small enough to walk exhaustively (the point of a canonical example)
MAX_GRID_POINTS = 65536

RULES = {
    "KN501": "grid race: parallel axis writes overlapping output blocks",
    "KN502": "VMEM footprint exceeds the per-core budget",
    "KN503": "CostEstimate drifts from the traced kernel's counted cost",
    "KN504": "kernel output diverges from its declared exact fallback",
    "KN505": "grid-spec sanity: prefetch/index_map/coverage",
}


# ---------------------------------------------------------------------------
# capture: intercept pallas_call while a registered example runs
# ---------------------------------------------------------------------------

class SpecInfo:
    """One in/out BlockSpec as captured: block shape, the original
    Python index_map (evaluable with concrete ints + prefetch arrays),
    and the backing array's shape/dtype."""

    __slots__ = ("block_shape", "index_map", "array_shape", "dtype",
                 "is_output", "_blocks")

    def __init__(self, block_shape, index_map, array_shape, dtype,
                 is_output):
        self.block_shape = tuple(block_shape) if block_shape else None
        self.index_map = index_map
        self.array_shape = tuple(array_shape)
        self.dtype = np.dtype(dtype)
        self.is_output = bool(is_output)
        self._blocks = None


class KernelCapture:
    """Everything one intercepted pallas_call exposes statically."""

    def __init__(self, name, kernel_fn, grid, in_specs, out_specs,
                 scratch, num_scalar_prefetch, prefetch_values,
                 dimension_semantics, cost_estimate, interpret):
        self.name = name
        self.kernel_fn = kernel_fn
        self.grid = tuple(int(g) for g in grid)
        self.in_specs = in_specs          # [SpecInfo]
        self.out_specs = out_specs        # [SpecInfo]
        self.scratch = scratch            # [(shape, dtype)]
        self.num_scalar_prefetch = num_scalar_prefetch
        self.prefetch_values = prefetch_values
        self.dimension_semantics = dimension_semantics
        self.cost_estimate = cost_estimate
        self.interpret = interpret

    @property
    def n_steps(self):
        n = 1
        for g in self.grid:
            n *= g
        return n

    def grid_points(self):
        return itertools.product(*[range(g) for g in self.grid])

    def semantics(self):
        """Per-axis semantics: explicit dimension_semantics or the TPU
        default 'arbitrary' (sequential, revisit-legal)."""
        sem = self.dimension_semantics
        if sem is None:
            return ("arbitrary",) * len(self.grid)
        sem = tuple(str(s) for s in sem)
        if len(sem) < len(self.grid):
            sem = sem + ("arbitrary",) * (len(self.grid) - len(sem))
        return sem

    def eval_spec(self, spec):
        """Evaluate one spec's index_map over the whole grid (cached).
        Returns the list of block-index tuples in grid walk order."""
        if spec._blocks is None:
            out = []
            for idx in self.grid_points():
                out.append(_eval_index_map(
                    spec.index_map, idx, self.prefetch_values,
                    len(spec.block_shape or ())))
            spec._blocks = out
        return spec._blocks


def _eval_index_map(index_map, idx, prefetch_values, rank):
    if index_map is None:
        return (0,) * rank
    # np.int32 grid indices: index decodes written in jnp (the
    # triangle-grid sqrt decodes call .astype) evaluate eagerly
    raw = index_map(*(np.int32(v) for v in idx), *prefetch_values)
    if not isinstance(raw, tuple):
        raw = (raw,)
    return tuple(int(v) for v in raw)


def _dim_semantics(kwargs):
    """dimension_semantics from a pallas_call's compiler_params: a
    `pltpu.CompilerParams`, alone or keyed by backend
    ({'mosaic_tpu': CompilerParams(...)})."""
    cp = kwargs.get("compiler_params")
    if isinstance(cp, dict):
        cp = cp.get("mosaic_tpu")
    return getattr(cp, "dimension_semantics", None)


def _normalize_specs(kwargs):
    """(grid, in_specs, out_specs, scratch_shapes, num_scalar_prefetch)
    from pallas_call kwargs, whichever of grid=/grid_spec= was used."""
    gs = kwargs.get("grid_spec")
    if gs is not None:
        nsp = int(getattr(gs, "num_scalar_prefetch", 0) or 0)
        return (gs.grid, list(gs.in_specs), gs.out_specs,
                list(getattr(gs, "scratch_shapes", ()) or ()), nsp)
    grid = kwargs.get("grid", ())
    if isinstance(grid, int):
        grid = (grid,)
    return (grid, list(kwargs.get("in_specs", ()) or ()),
            kwargs.get("out_specs"),
            list(kwargs.get("scratch_shapes", ()) or ()), 0)


def _as_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


@contextlib.contextmanager
def _patched_pallas_call(records):
    """Monkeypatch jax.experimental.pallas.pallas_call so every call
    made underneath records (kernel, specs, concrete operands)."""
    from jax.experimental import pallas as pl
    real = pl.pallas_call

    def spy(kernel, *pa, **kwargs):
        wrapped = real(kernel, *pa, **kwargs)

        def runner(*operands):
            records.append((kernel, kwargs, operands))
            return wrapped(*operands)
        return runner

    pl.pallas_call = spy
    try:
        yield
    finally:
        pl.pallas_call = real


def capture_kernels(fn, args, kwargs=None, name="kernel"):
    """Run `fn(*args, **kwargs)` eagerly with pallas_call intercepted.
    Returns (captures, result): one KernelCapture per pallas_call the
    run made (>= 1), in call order."""
    import jax

    records = []
    # eagerly, through an entry that is itself jitted too: the spy
    # wants concrete operands, and a cached trace would never reach it
    with _patched_pallas_call(records), jax.disable_jit():
        result = fn(*args, **(kwargs or {}))
    if not records:
        raise ValueError(
            f"{name}: the registered example made no pallas_call — the "
            "example does not drive the kernel it claims to cover")
    captures = []
    for ordinal, (kernel, kw, operands) in enumerate(records):
        grid, in_specs, out_specs, scratch, nsp = _normalize_specs(kw)
        prefetch = [np.asarray(operands[i]) for i in range(nsp)]
        data_ops = operands[nsp:]
        out_shapes = _as_list(kw.get("out_shape"))
        out_spec_list = _as_list(out_specs)
        # no specs means pallas defaults every operand to a whole-array
        # block; a partial spec list is a capture we cannot account
        # (dropping operands would under-project VMEM), so refuse loudly
        if not in_specs and data_ops:
            in_specs = [None] * len(data_ops)
        if len(in_specs) != len(data_ops):
            raise ValueError(
                f"{name}: {len(data_ops)} data operands but "
                f"{len(in_specs)} in_specs — cannot account the "
                "unmatched operands")
        if not out_spec_list and out_shapes:
            out_spec_list = [None] * len(out_shapes)
        if len(out_spec_list) != len(out_shapes):
            raise ValueError(
                f"{name}: {len(out_shapes)} outputs but "
                f"{len(out_spec_list)} out_specs")
        in_infos = []
        for spec, op in zip(in_specs, data_ops):
            op = np.asarray(op)
            in_infos.append(SpecInfo(
                getattr(spec, "block_shape", None),
                getattr(spec, "index_map", None), op.shape, op.dtype,
                is_output=False))
        out_infos = []
        for spec, sds in zip(out_spec_list, out_shapes):
            out_infos.append(SpecInfo(
                getattr(spec, "block_shape", None),
                getattr(spec, "index_map", None), sds.shape, sds.dtype,
                is_output=True))
        # VMEM scratch only: semaphores and SMEM scalars take none of
        # the budget KN502 projects
        scratch_info = [(tuple(s.shape), np.dtype(s.dtype))
                        for s in scratch if hasattr(s, "shape")
                        and str(getattr(s, "memory_space", "vmem"))
                        == "vmem"]
        cname = name if len(records) == 1 else f"{name}#{ordinal}"
        captures.append(KernelCapture(
            cname, kernel, grid, in_infos, out_infos, scratch_info, nsp,
            prefetch, _dim_semantics(kw), kw.get("cost_estimate"),
            kw.get("interpret")))
    return captures, result


# ---------------------------------------------------------------------------
# KN501: grid-race detection
# ---------------------------------------------------------------------------

def check_grid_races(capture, semantics=None):
    """Flag output blocks written by grid points that differ in a
    parallel axis. `semantics` overrides the captured
    dimension_semantics (how tests parallelize a copy of a sequential
    kernel without touching the kernel)."""
    findings = []
    sem = (tuple(semantics) if semantics is not None
           else capture.semantics())
    par_axes = [d for d, s in enumerate(sem) if s == "parallel"]
    if not par_axes:
        return findings
    if capture.n_steps > MAX_GRID_POINTS:
        # parallel axes whose races we cannot enumerate: fail loud
        # rather than silently passing (check_gridspec warns once for
        # the merely-oversized sequential case)
        return [Finding(
            "KN501", SEV_ERROR, capture.name,
            f"grid {capture.grid} marks axes {par_axes} parallel but "
            f"is too large to enumerate ({capture.n_steps} > "
            f"{MAX_GRID_POINTS}) — races cannot be ruled out; shrink "
            "the registered example")]
    points = list(capture.grid_points())
    for oi, spec in enumerate(capture.out_specs):
        writers = {}
        for p, blk in zip(points, capture.eval_spec(spec)):
            writers.setdefault(blk, []).append(p)
        for blk, ps in writers.items():
            if len(ps) < 2:
                continue
            for axis in par_axes:
                vals = {p[axis] for p in ps}
                if len(vals) > 1:
                    findings.append(Finding(
                        "KN501", SEV_ERROR, capture.name,
                        f"output {oi} block {blk} is written by "
                        f"{len(ps)} grid points (e.g. {ps[0]} and "
                        f"{ps[1]}) that differ in grid axis {axis} "
                        f"marked 'parallel' — the flush order of those "
                        "writes is undefined (a grid race)",
                        suggestion="leave the axis sequential "
                                   "('arbitrary'): the revisit order is "
                                   "load-bearing, exactly like the "
                                   "triangle-grid flush invariant"))
                    break
            else:
                continue
            break       # one finding per output is enough to fail
    return findings


# ---------------------------------------------------------------------------
# KN502: VMEM footprint projection
# ---------------------------------------------------------------------------

def project_vmem(capture):
    """(total_bytes, moving, resident, scratch) of one grid program
    under the shared kernel_registry model: blocks whose index_map
    moves across the grid are double-buffered, constant blocks are
    fetched once, scratch is allocated once."""
    moving, resident = [], []
    for spec in capture.in_specs + capture.out_specs:
        if spec.block_shape is None:
            entry = (spec.array_shape, spec.dtype)
            resident.append(entry)
            continue
        blocks = capture.eval_spec(spec)
        entry = (spec.block_shape, spec.dtype)
        (resident if len(set(blocks)) <= 1 else moving).append(entry)
    total = vmem_footprint(moving=moving, resident=resident,
                           scratch=capture.scratch)
    return total, moving, resident, capture.scratch


def check_vmem(capture, budget=VMEM_BUDGET):
    total, moving, resident, scratch = project_vmem(capture)
    if total <= budget:
        return []
    worst = max(
        [(2 * block_bytes(s, d), s) for s, d in moving] +
        [(block_bytes(s, d), s) for s, d in resident + scratch],
        default=(0, ()))
    return [Finding(
        "KN502", SEV_ERROR, capture.name,
        f"projected VMEM footprint {total} bytes "
        f"({total / 2**20:.2f} MiB) exceeds the per-core budget "
        f"{budget} bytes ({budget / 2**20:.2f} MiB); largest "
        f"contributor: block {worst[1]} at {worst[0]} bytes "
        "(double-buffered)",
        suggestion="shrink the block (or make the big operand "
                   "grid-partitioned instead of resident) until the "
                   "kernel_registry.vmem_footprint projection fits")]


# ---------------------------------------------------------------------------
# KN503: CostEstimate honesty (declared vs counted from the jaxpr)
# ---------------------------------------------------------------------------

_TRANSCENDENTAL = frozenset((
    "exp", "exp2", "log", "log2", "log1p", "tanh", "logistic", "erf",
    "erf_inv", "erfc", "sin", "cos", "rsqrt", "sqrt", "pow", "cbrt",
))
_ELEMENTWISE = frozenset((
    "add", "sub", "mul", "div", "max", "min", "neg", "abs", "rem",
    "floor", "ceil", "round", "sign", "nextafter", "atan2",
    "integer_pow", "square",
))
_REDUCE = frozenset((
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "argmax", "argmin", "cumsum", "cummax",
))


def _aval_size(var):
    n = 1
    for d in getattr(var.aval, "shape", ()):
        n *= int(d)
    return n


def _is_float(var):
    return np.issubdtype(np.dtype(getattr(var.aval, "dtype", np.int32)),
                         np.floating)


def _sub_jaxprs(params):
    for v in params.values():
        if hasattr(v, "eqns"):
            yield v
        elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
            yield v.jaxpr
        elif isinstance(v, (list, tuple)):
            for x in v:
                if hasattr(x, "eqns"):
                    yield x
                elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                    yield x.jaxpr


def count_body_cost(jaxpr):
    """(flops, transcendentals) of ONE execution of a kernel jaxpr.

    dot_general counts 2*M*N*K; float elementwise/reduce ops count
    their element count; transcendentals count separately (the
    CostEstimate convention). `cond` eqns — what `pl.when` lowers to —
    are mutually-exclusive phases of a grid step (init / masked /
    unmasked / finalize), so the LARGEST cond branch in the body is
    taken rather than their sum: summing would double-count the
    masked-vs-unmasked pair every flash kernel dispatches between.
    `scan` (fori_loop) multiplies its body by the trip count; `while`
    trip counts are unknowable statically and count as one iteration.
    """
    flops = 0
    trans = 0
    cond_flops, cond_trans = [], []
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "cond":
            bf = bt = 0
            for br in eqn.params["branches"]:
                f, t = count_body_cost(br.jaxpr)
                bf, bt = max(bf, f), max(bt, t)
            cond_flops.append(bf)
            cond_trans.append(bt)
        elif prim == "scan":
            f, t = count_body_cost(eqn.params["jaxpr"].jaxpr)
            length = int(eqn.params.get("length", 1))
            flops += f * length
            trans += t * length
        elif prim == "while":
            f, t = 0, 0
            for sub in _sub_jaxprs(eqn.params):
                sf, st = count_body_cost(sub)
                f, t = f + sf, t + st
            flops += f
            trans += t
        elif prim == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            k = 1
            lhs_shape = eqn.invars[0].aval.shape
            for d in lc:
                k *= int(lhs_shape[d])
            flops += 2 * _aval_size(eqn.outvars[0]) * k
        elif prim in _TRANSCENDENTAL:
            if _is_float(eqn.outvars[0]):
                trans += _aval_size(eqn.outvars[0])
        elif prim in _ELEMENTWISE:
            if _is_float(eqn.outvars[0]):
                flops += _aval_size(eqn.outvars[0])
        elif prim in _REDUCE:
            flops += _aval_size(eqn.invars[0])
        else:
            for sub in _sub_jaxprs(eqn.params):
                f, t = count_body_cost(sub)
                flops += f
                trans += t
    flops += max(cond_flops, default=0)
    trans += max(cond_trans, default=0)
    return flops, trans


def trace_kernel_jaxprs(fn, args, kwargs=None):
    """Trace `fn` and return the kernel jaxpr of every pallas_call eqn
    inside, in call order. Only ndarray arguments are traced; python
    ints/bools/floats (block sizes, causal flags, eps) stay static —
    they steer grid construction, exactly as at a real call site."""
    import jax

    arr_idx = [i for i, a in enumerate(args)
               if isinstance(a, (np.ndarray, jax.Array))]

    def wrapper(*arrs):
        full = list(args)
        for i, a in zip(arr_idx, arrs):
            full[i] = a
        return fn(*full, **(kwargs or {}))

    closed = jax.make_jaxpr(wrapper)(*[args[i] for i in arr_idx])
    out = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn.params["jaxpr"])
            else:
                for sub in _sub_jaxprs(eqn.params):
                    walk(sub)
    walk(closed.jaxpr)
    return out


def counted_dma_bytes(capture):
    """Revisit-aware block traffic: a block is DMA'd when its index
    differs from the previous grid step's (Mosaic skips the copy when
    the window holds still), outputs flush on the same rule."""
    total = 0
    for spec in capture.in_specs + capture.out_specs:
        if spec.block_shape is None:
            total += block_bytes(spec.array_shape, spec.dtype)
            continue
        per_block = block_bytes(spec.block_shape, spec.dtype)
        prev, fetches = None, 0
        for blk in capture.eval_spec(spec):
            if blk != prev:
                fetches += 1
                prev = blk
        total += fetches * per_block
    return total


def check_cost(capture, kernel_jaxpr):
    """KN503: declared CostEstimate vs counted cost. Kernels that
    declare nothing are skipped (no declaration, no dishonesty)."""
    ce = capture.cost_estimate
    if ce is None:
        return [], {}
    step_flops, step_trans = count_body_cost(kernel_jaxpr)
    counted = {
        "flops": step_flops * capture.n_steps,
        "transcendentals": step_trans * capture.n_steps,
        "bytes_accessed": counted_dma_bytes(capture),
    }
    findings = []
    for field, floor in (("flops", COST_FLOPS_FLOOR),
                         ("transcendentals", COST_TRANS_FLOOR)):
        declared = int(getattr(ce, field, 0) or 0)
        c = counted[field]
        drift = abs(declared - c)
        if drift > max(COST_DRIFT_FRAC * max(declared, c), floor):
            findings.append(Finding(
                "KN503", SEV_ERROR, capture.name,
                f"declared {field} {declared} vs {c} counted from the "
                f"traced kernel body x {capture.n_steps} grid steps "
                f"(drift {drift / max(declared, c, 1) * 100:.0f}% > "
                f"{COST_DRIFT_FRAC * 100:.0f}%)",
                suggestion="recompute the CostEstimate from the actual "
                           "per-tile work (the scheduler plans DMA "
                           "overlap with these numbers)"))
    declared_b = int(getattr(ce, "bytes_accessed", 0) or 0)
    cb = counted["bytes_accessed"]
    if abs(declared_b - cb) > COST_BYTES_FLOOR and (
            declared_b > cb * COST_BYTES_BAND
            or declared_b * COST_BYTES_BAND < cb):
        findings.append(Finding(
            "KN503", SEV_ERROR, capture.name,
            f"declared bytes_accessed {declared_b} is more than "
            f"{COST_BYTES_BAND:.0f}x away from the revisit-aware block "
            f"stream ({cb} bytes) — the estimate is not within an "
            "order of magnitude of the DMA traffic",
            suggestion="count each block DMA the grid actually issues "
                       "(kernel_lint.counted_dma_bytes)"))
    return findings, counted


# ---------------------------------------------------------------------------
# KN504: fallback-parity fuzzing
# ---------------------------------------------------------------------------

def check_fallback_parity(reg, seeds=(0, 1, 2), errors=None):
    """Seeded differential harness: run the registered kernel and its
    declared exact fallback on randomized in-support inputs, compare
    within the registration's tolerance. Deterministic per seed (the
    example derives shapes AND values from the rng), so a failure
    reproduces bit-for-bit. The fallback is the reference, so its
    matmuls run at full precision (on a TPU, XLA's default is one bf16
    pass; the CPU is unaffected); the kernel runs as it does in
    production. `errors`, when given a list, collects each seed's
    largest absolute error for the caller's report."""
    if reg.fallback is None:
        return []
    import jax

    rtol, atol = reg.tol
    findings = []
    for seed in seeds:
        rng = np.random.default_rng(seed)
        args, kwargs = reg.example(rng)
        got = reg.fn(*args, **kwargs)
        with jax.default_matmul_precision("highest"):
            want = reg.fallback(*args, **kwargs)
        got_leaves = jax.tree_util.tree_leaves(got)
        want_leaves = jax.tree_util.tree_leaves(want)
        if len(got_leaves) != len(want_leaves):
            findings.append(Finding(
                "KN504", SEV_ERROR, reg.name,
                f"seed {seed}: kernel returned {len(got_leaves)} "
                f"arrays, fallback {len(want_leaves)}"))
            continue
        worst = 0.0
        for li, (g, w) in enumerate(zip(got_leaves, want_leaves)):
            g = np.asarray(g, dtype=np.float64)
            w = np.asarray(w, dtype=np.float64)
            if g.shape != w.shape:
                findings.append(Finding(
                    "KN504", SEV_ERROR, reg.name,
                    f"seed {seed}: output {li} shape {g.shape} vs "
                    f"fallback {w.shape}"))
                continue
            err = float(np.max(np.abs(g - w))) if g.size else 0.0
            worst = max(worst, err)
            if not np.allclose(g, w, rtol=rtol, atol=atol,
                               equal_nan=True):
                findings.append(Finding(
                    "KN504", SEV_ERROR, reg.name,
                    f"seed {seed}: output {li} diverges from the "
                    f"declared exact fallback (max abs err {err:.3e} "
                    f"at rtol={rtol}, atol={atol})",
                    suggestion="the kernel and fallback must share one "
                               "index/accumulation contract; rerun "
                               f"with np.random.default_rng({seed}) to "
                               "reproduce"))
                break
        if errors is not None:
            errors.append(worst)
    return findings


# ---------------------------------------------------------------------------
# KN505: scalar-prefetch / grid-spec sanity
# ---------------------------------------------------------------------------

def check_gridspec(capture):
    findings = []
    if any(g <= 0 for g in capture.grid):
        findings.append(Finding(
            "KN505", SEV_ERROR, capture.name,
            f"grid {capture.grid} has a non-positive dimension"))
        return findings
    if capture.n_steps > MAX_GRID_POINTS:
        return [Finding(
            "KN505", SEV_WARNING, capture.name,
            f"grid {capture.grid} too large to enumerate; shrink the "
            "registered example")]
    # scalar-prefetch operands: SMEM-sized index metadata
    for pi, val in enumerate(capture.prefetch_values):
        arr = np.asarray(val)
        if arr.ndim > PREFETCH_MAX_NDIM or arr.nbytes > PREFETCH_MAX_BYTES:
            findings.append(Finding(
                "KN505", SEV_ERROR, capture.name,
                f"scalar-prefetch operand {pi} is {arr.ndim}-D / "
                f"{arr.nbytes} bytes — the prefetch channel is SMEM "
                f"index metadata (<= {PREFETCH_MAX_NDIM}-D, "
                f"<= {PREFETCH_MAX_BYTES} bytes), not tensor data",
                suggestion="move tensor-sized operands to in_specs so "
                           "they stream through VMEM blocks"))
        if arr.dtype.kind not in "iuf":
            findings.append(Finding(
                "KN505", SEV_ERROR, capture.name,
                f"scalar-prefetch operand {pi} has non-scalar dtype "
                f"{arr.dtype}"))
    # index_maps: right rank and in-bounds over the WHOLE grid (the
    # per-point block lists are cached by eval_spec, so an exhaustive
    # bounds sweep costs nothing extra — a tail-of-grid off-by-one
    # must not hide past a sample), plus purity (stable under
    # re-evaluation) probed on a small sample
    points = list(capture.grid_points())
    sample = points[:8] + points[-2:]
    for kind, specs in (("input", capture.in_specs),
                        ("output", capture.out_specs)):
        for si, spec in enumerate(specs):
            if spec.block_shape is None:
                continue
            rank = len(spec.block_shape)
            nblocks = tuple(
                -(-int(a) // int(b))
                for a, b in zip(spec.array_shape, spec.block_shape))
            for p, one in zip(points, capture.eval_spec(spec)):
                if len(one) != rank:
                    findings.append(Finding(
                        "KN505", SEV_ERROR, capture.name,
                        f"{kind} {si} index_map returns {len(one)} "
                        f"indices for a rank-{rank} block"))
                    break
                if any(v < 0 or v >= nb for v, nb in zip(one, nblocks)):
                    findings.append(Finding(
                        "KN505", SEV_ERROR, capture.name,
                        f"{kind} {si} index_map maps grid point {p} to "
                        f"block {one}, outside the {nblocks} blocks of "
                        f"array {spec.array_shape}"))
                    break
            for p in sample:
                again = _eval_index_map(spec.index_map, p,
                                        capture.prefetch_values, rank)
                cached = capture.eval_spec(spec)[points.index(p)]
                if again != cached:
                    findings.append(Finding(
                        "KN505", SEV_ERROR, capture.name,
                        f"{kind} {si} index_map is impure: grid point "
                        f"{p} mapped to {cached} then {again}",
                        suggestion="index_maps must be pure functions "
                                   "of the grid indices and prefetch "
                                   "scalars"))
                    break
    # every output block must be written at least once
    for oi, spec in enumerate(capture.out_specs):
        if spec.block_shape is None:
            continue
        nblocks = tuple(
            -(-int(a) // int(b))
            for a, b in zip(spec.array_shape, spec.block_shape))
        visited = set(capture.eval_spec(spec))
        expected = 1
        for nb in nblocks:
            expected *= nb
        if len(visited) < expected:
            missing = next(idx for idx in itertools.product(
                *[range(nb) for nb in nblocks]) if idx not in visited)
            findings.append(Finding(
                "KN505", SEV_ERROR, capture.name,
                f"grid does not cover output {oi}: only "
                f"{len(visited)} of {expected} blocks are written "
                f"(e.g. block {missing} is never visited) — the "
                "unwritten windows ship whatever HBM held",
                suggestion="extend the grid (or fix the index_map) so "
                           "every output block is produced"))
    return findings


# ---------------------------------------------------------------------------
# per-kernel + whole-registry drivers
# ---------------------------------------------------------------------------

def lint_kernel(reg, budget=VMEM_BUDGET, seeds=(0,), example_seed=1234):
    """All five KN rules over one registered kernel. Returns
    (findings, info): info carries the derived numbers for the typed
    kernel_lint record (grid, vmem bytes, declared/counted cost)."""
    rng = np.random.default_rng(example_seed)
    args, kwargs = reg.example(rng)
    captures, _ = capture_kernels(reg.fn, args, kwargs, name=reg.name)
    bodies = trace_kernel_jaxprs(reg.fn, args, kwargs)
    findings = []
    info = {"kernel": reg.name, "module": reg.module,
            "fn": reg.fn_name, "n_calls": len(captures), "calls": []}
    for cap, body in zip(captures, bodies):
        findings += check_grid_races(cap)
        findings += check_vmem(cap, budget=budget)
        cost_findings, counted = check_cost(cap, body)
        findings += cost_findings
        findings += check_gridspec(cap)
        vmem_total = project_vmem(cap)[0]
        call = {"grid": list(cap.grid), "vmem_bytes": int(vmem_total),
                "semantics": list(cap.semantics())}
        if cap.cost_estimate is not None:
            call["flops_declared"] = int(cap.cost_estimate.flops or 0)
            call["flops_counted"] = int(counted.get("flops", 0))
            call["bytes_declared"] = int(
                cap.cost_estimate.bytes_accessed or 0)
            call["bytes_counted"] = int(
                counted.get("bytes_accessed", 0))
        info["calls"].append(call)
    findings += check_fallback_parity(reg, seeds=seeds)
    info["vmem_bytes"] = max(
        (c["vmem_bytes"] for c in info["calls"]), default=0)
    info["has_fallback"] = reg.fallback is not None
    return findings, info


def lint_registry(registry=None, budget=VMEM_BUDGET, seeds=(0,)):
    """Lint every kernel in `registry` (default: the fully-populated
    in-tree registry). Returns (findings, [info dicts])."""
    if registry is None:
        registry = kernel_registry.registered_kernels()
    findings, infos = [], []
    for reg in registry:
        try:
            f, info = lint_kernel(reg, budget=budget, seeds=seeds)
        except Exception as e:  # noqa: BLE001 — a crash IS a finding
            f = [Finding("KN505", SEV_ERROR, reg.name,
                         f"kernel doctor could not evaluate the "
                         f"registered example: {type(e).__name__}: {e}")]
            info = {"kernel": reg.name, "module": reg.module,
                    "fn": reg.fn_name, "n_calls": 0, "calls": [],
                    "vmem_bytes": 0, "has_fallback": False}
        findings += f
        info["n_findings"] = len(f)
        infos.append(info)
    return findings, infos


def unregistered_pallas_sites(root):
    """AST sweep closing the 'new kernel dodges all checks' hole: every
    function under `root` containing a pallas_call must carry the
    @register_kernel decorator. Returns the FW405 findings (empty ==
    full registry coverage — the machine-checked version of the
    acceptance grep)."""
    from . import astlint
    return [f for f in astlint.lint_tree(root) if f.rule_id == "FW405"]


def pallas_site_functions(root):
    """{top-level function name -> [file paths]} for every function
    under `root` whose body (including nested defs) contains a
    pallas_call site. The registry cross-check: these names and the
    registered entries' fn names must cover each other — a site in an
    unregistered function is FW405's job, while a REGISTERED entry
    whose function no longer contains any pallas_call (the call moved
    out in a refactor) is a stale registration only this sweep sees."""
    import ast as _ast

    out = {}
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames
                       if d not in ("__pycache__", ".git")]
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            with open(path, encoding="utf-8") as f:
                try:
                    tree = _ast.parse(f.read())
                except SyntaxError:
                    continue
            stack = []

            def walk(node):
                is_fn = isinstance(
                    node, (_ast.FunctionDef, _ast.AsyncFunctionDef))
                if is_fn:
                    stack.append(node.name)
                if isinstance(node, _ast.Call):
                    fn_node = node.func
                    callee = getattr(fn_node, "attr", None) or \
                        getattr(fn_node, "id", None)
                    if callee == "pallas_call" and stack:
                        out.setdefault(stack[0], []).append(path)
                for child in _ast.iter_child_nodes(node):
                    walk(child)
                if is_fn:
                    stack.pop()

            walk(tree)
    return out
