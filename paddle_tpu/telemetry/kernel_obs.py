"""Kernel observatory: measured rooflines, a persistent timing DB, and
autotune-ready config search over the registered Pallas kernels.

The dynamic half of the kernel level. The Kernel Doctor
(analysis/kernel_lint.py, tools/kerneldoctor.py) proves every kernel in
`ops/kernel_registry.registered_kernels()` *statically* honest (KN501
races, KN502 VMEM, KN503 cost, KN504 parity, KN505 grid sanity); this
module *measures* them:

- **measure_kernel** — run a registration's seeded canonical example
  under warmup + median-of-k timing (`block_until_ready`; the program is
  AOT `lower().compile()`d first, the PR-4 compile-observatory
  discipline, so compile_ms is recorded separately and never pollutes
  the execute median), time the declared exact fallback on the same
  inputs, and report the kernel-vs-fallback speedup.
- **roofline** — combine measured time with the KN503 traced counts
  (`kernel_lint.count_body_cost` x grid steps for FLOPs,
  `kernel_lint.counted_dma_bytes` for the revisit-aware DMA stream) and
  the shared peak tables in `telemetry/mfu.py` (PEAK_FLOPS_BY_KIND +
  PEAK_HBM_BW_BY_KIND) into achieved-FLOP/s and achieved-bandwidth
  fractions, a compute- vs memory-bound verdict, and the
  roofline-predicted time the `kernel_time_drift` rule
  (telemetry/health.py) judges measured time against.
- **KernelDB** — a JSON file at a path the caller names: best-known
  timing + chosen config per (kernel, shape-signature, dtype, backend)
  key. Rolled forward only by `kernellab --db PATH --update-db`, which
  refuses non-finite rows.
- **tune_flash_fwd** — the config search: enumerate the
  (block_q, block_k) candidate space (ATTN_SWEEP_BQ x ATTN_SWEEP_BK)
  with `kernel_registry.vmem_footprint` (KN502) as the feasibility
  predicate and measured time as the objective; the winner is KN504
  parity-re-fuzzed (`kernel_lint.check_fallback_parity`) before it may
  be persisted. The kernels never read the DB: their block policy is
  a function of the shape alone, and a tuned config reaches them as an
  edit to that policy.

Every measurement is emitted as a typed ``kind=kernelbench`` record
(telemetry/sink.make_kernelbench_record, validated by
tools/trace_check.py) and mirrored as ``kernel.*`` gauges on /metrics.
CLI: tools/kernellab.py (--smoke / --selfcheck / --tune / --update-db).
"""
import json
import math
import os
import statistics
import time

import numpy as np

from .. import monitor
from .mfu import device_peak_flops, device_peak_hbm_bw
from .sink import make_kernelbench_record

__all__ = [
    "ATTN_SWEEP_BQ", "ATTN_SWEEP_BK", "KernelDB", "MeasureResult",
    "db_key", "measure_kernel", "measure_registry", "roofline",
    "shape_signature", "traced_cost", "tune_flash_fwd",
]

# the flash-attention sweep space tune_flash_fwd searches
ATTN_SWEEP_BQ = (256, 512, 1024, 2048)
ATTN_SWEEP_BK = (512, 1024, 2048)

DB_SCHEMA = 1


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------

_SHORT_DTYPE = {
    "float32": "f32", "float64": "f64", "bfloat16": "bf16",
    "float16": "f16", "int32": "i32", "int64": "i64", "int8": "i8",
    "uint8": "u8", "bool": "b1",
}


def _dt_short(dtype):
    name = np.dtype(dtype).name if str(dtype) != "bfloat16" else "bfloat16"
    return _SHORT_DTYPE.get(str(name), str(name))


def shape_signature(args, kwargs=None):
    """Stable shape/dtype signature of one example's inputs:
    ``f32[4,128],i32[40]`` — array leaves only, python scalars (block
    sizes, flags) excluded, in positional order. The DB's shape axis."""
    import jax

    parts = []
    leaves = list(args) + [v for _, v in sorted((kwargs or {}).items())]
    for a in leaves:
        if isinstance(a, (np.ndarray, jax.Array)):
            dt = _dt_short(a.dtype)
            parts.append(f"{dt}[{','.join(str(d) for d in a.shape)}]")
    return ",".join(parts)


def dominant_dtype(args, kwargs=None):
    """The record's dtype axis: the first array argument's dtype (the
    streamed operand dtype, which sets tiling and bandwidth)."""
    import jax

    leaves = list(args) + [v for _, v in sorted((kwargs or {}).items())]
    for a in leaves:
        if isinstance(a, (np.ndarray, jax.Array)):
            return _dt_short(a.dtype)
    return "?"


def db_key(kernel, sig, dtype, backend):
    """``kernel|sig|dtype|backend`` — the DB's primary key, mirroring
    how the registry keys canonical examples by kernel name."""
    return f"{kernel}|{sig}|{dtype}|{backend}"


# ---------------------------------------------------------------------------
# measurement harness
# ---------------------------------------------------------------------------

class MeasureResult:
    """One measured (kernel, inputs) point, roofline-attributed."""

    __slots__ = ("kernel", "sig", "dtype", "backend", "kernel_ms",
                 "fallback_ms", "speedup", "compile_ms", "flops",
                 "bytes_accessed", "roof", "n_samples", "warmup",
                 "config", "seed")

    def __init__(self, **kw):
        for s in self.__slots__:
            setattr(self, s, kw.get(s))

    def to_record(self, rank=0, event="measure"):
        roof = self.roof or {}
        return make_kernelbench_record(
            kernel=self.kernel, sig=self.sig, backend=self.backend,
            kernel_ms=self.kernel_ms, rank=rank, dtype=self.dtype,
            fallback_ms=self.fallback_ms, speedup=self.speedup,
            compile_ms=self.compile_ms, flops=self.flops,
            bytes_accessed=self.bytes_accessed,
            flops_frac=roof.get("flops_frac"),
            bw_frac=roof.get("bw_frac"),
            predicted_ms=roof.get("predicted_ms"),
            bound=roof.get("bound"), config=self.config,
            db_key=db_key(self.kernel, self.sig, self.dtype,
                          self.backend),
            n_samples=self.n_samples, warmup=self.warmup,
            event=event, seed=self.seed)


def _timed_call(fn, args, kwargs, warmup, k, clock):
    """AOT-compile `fn` over the ARRAY arguments (python scalars stay
    static, exactly as kernel_lint.trace_kernel_jaxprs binds them), then
    run warmup + k timed iterations and return
    (median_ms, compile_ms, samples). compile_ms is measured around
    lower().compile() — the compile-observatory discipline — so it can
    never leak into the execute median."""
    import jax

    kwargs = kwargs or {}
    arr_idx = [i for i, a in enumerate(args)
               if isinstance(a, (np.ndarray, jax.Array))]

    def wrapper(*arrs):
        full = list(args)
        for i, a in zip(arr_idx, arrs):
            full[i] = a
        return fn(*full, **kwargs)

    arrs = [args[i] for i in arr_idx]
    t0 = clock()
    compiled = jax.jit(wrapper).lower(*arrs).compile()
    compile_ms = (clock() - t0) * 1e3

    for _ in range(max(0, warmup)):
        jax.block_until_ready(compiled(*arrs))
    samples = []
    for _ in range(max(1, k)):
        t0 = clock()
        jax.block_until_ready(compiled(*arrs))
        samples.append((clock() - t0) * 1e3)
    return statistics.median(samples), compile_ms, samples


def traced_cost(reg, args, kwargs=None):
    """KN503-traced (flops, bytes_accessed) of one example run: the
    kernel-body jaxpr cost x grid steps summed over every pallas_call
    the run makes, and the revisit-aware block DMA stream. Returns
    (None, None) when capture fails (an example that cannot trace is a
    Kernel Doctor finding, not ours)."""
    from ..analysis import kernel_lint

    try:
        captures, _ = kernel_lint.capture_kernels(
            reg.fn, args, kwargs, name=reg.name)
        jaxprs = kernel_lint.trace_kernel_jaxprs(reg.fn, args, kwargs)
    except Exception:
        return None, None
    flops = 0
    bytes_accessed = 0
    for cap, jx in zip(captures, jaxprs):
        step_flops, _ = kernel_lint.count_body_cost(jx)
        flops += step_flops * cap.n_steps
        bytes_accessed += kernel_lint.counted_dma_bytes(cap)
    return int(flops), int(bytes_accessed)


def roofline(flops, bytes_accessed, time_ms, peak_flops=None,
             peak_bw=None, device_kind=None):
    """Place one measured point on the device roofline. Returns a dict:

    - achieved_flops / achieved_bw — measured rates (None without the
      corresponding count or a positive time);
    - flops_frac / bw_frac — achieved over peak, clamped to [0, 1]
      (None on CPU backends, where the peak tables answer None);
    - predicted_ms — the roofline floor max(flops/peak_flops,
      bytes/peak_bw), what `kernel_time_drift` judges measured time
      against;
    - bound — 'compute' | 'memory' by arithmetic intensity vs the
      machine balance (None when either peak is unknown).
    """
    if peak_flops is None:
        peak_flops = device_peak_flops(device_kind)
    if peak_bw is None:
        peak_bw = device_peak_hbm_bw(device_kind)
    t_s = time_ms / 1e3 if time_ms and time_ms > 0 else None
    out = {"achieved_flops": None, "achieved_bw": None,
           "flops_frac": None, "bw_frac": None,
           "predicted_ms": None, "bound": None,
           "peak_flops": peak_flops, "peak_hbm_bw": peak_bw}
    if t_s and flops:
        out["achieved_flops"] = flops / t_s
        if peak_flops:
            out["flops_frac"] = min(1.0, out["achieved_flops"]
                                    / peak_flops)
    if t_s and bytes_accessed:
        out["achieved_bw"] = bytes_accessed / t_s
        if peak_bw:
            out["bw_frac"] = min(1.0, out["achieved_bw"] / peak_bw)
    if peak_flops and peak_bw and (flops or bytes_accessed):
        t_compute = (flops or 0) / peak_flops
        t_memory = (bytes_accessed or 0) / peak_bw
        out["predicted_ms"] = max(t_compute, t_memory) * 1e3
        out["bound"] = "compute" if t_compute >= t_memory else "memory"
    return out


def measure_kernel(reg, seed=1234, warmup=2, k=5, clock=None,
                   time_fallback=True, args=None, kwargs=None,
                   config=None):
    """Measure one registration on its seeded canonical example (or on
    explicit `args`/`kwargs`): kernel median-of-k, fallback median on
    the SAME inputs, traced-cost roofline. Deterministic given `clock`
    (tests inject a fake) and `seed` (the example derives shapes AND
    values from it, the KN504 discipline)."""
    import jax

    clock = clock or time.perf_counter
    if args is None:
        rng = np.random.default_rng(seed)
        args, kwargs = reg.example(rng)
    kernel_ms, compile_ms, _ = _timed_call(
        reg.fn, args, kwargs, warmup, k, clock)
    fallback_ms = None
    speedup = None
    if time_fallback and reg.fallback is not None:
        fallback_ms, _, _ = _timed_call(
            reg.fallback, args, kwargs, warmup, k, clock)
        if kernel_ms > 0:
            speedup = fallback_ms / kernel_ms
    flops, bytes_accessed = traced_cost(reg, args, kwargs)
    backend = jax.default_backend()
    roof = roofline(flops, bytes_accessed, kernel_ms)
    res = MeasureResult(
        kernel=reg.name, sig=shape_signature(args, kwargs),
        dtype=dominant_dtype(args, kwargs), backend=backend,
        kernel_ms=kernel_ms, fallback_ms=fallback_ms, speedup=speedup,
        compile_ms=compile_ms, flops=flops,
        bytes_accessed=bytes_accessed, roof=roof, n_samples=max(1, k),
        warmup=max(0, warmup), config=config, seed=seed)
    _export_gauges(res)
    return res


def _export_gauges(res):
    """Mirror one measurement onto /metrics (telemetry.metrics_http
    scrapes monitor.snapshot_typed verbatim)."""
    name = res.kernel
    monitor.set_gauge(f"kernel.{name}.ms", float(res.kernel_ms))
    if res.speedup is not None:
        monitor.set_gauge(f"kernel.{name}.speedup", float(res.speedup))
    roof = res.roof or {}
    if roof.get("flops_frac") is not None:
        monitor.set_gauge(f"kernel.{name}.flops_frac",
                          float(roof["flops_frac"]))
    if roof.get("bw_frac") is not None:
        monitor.set_gauge(f"kernel.{name}.bw_frac",
                          float(roof["bw_frac"]))
    monitor.incr("kernel.measured")


def measure_registry(registry=None, seeds=(1234,), warmup=2, k=5,
                     clock=None):
    """Measure every registered kernel once per seed (the canonical
    example at seeds[0], the per-kernel shape/dtype sweep at the rest —
    the examples derive shapes and dtypes from the rng, so extra seeds
    ARE the sweep). Returns [MeasureResult, ...] in registry order."""
    from ..ops.kernel_registry import registered_kernels

    regs = registered_kernels() if registry is None \
        else list(registry.values())
    out = []
    for reg in regs:
        for seed in seeds:
            out.append(measure_kernel(reg, seed=seed, warmup=warmup,
                                      k=k, clock=clock))
    return out


# ---------------------------------------------------------------------------
# persistent measurement DB
# ---------------------------------------------------------------------------

def _finite(v):
    return isinstance(v, (int, float)) and math.isfinite(v)


class KernelDB:
    """A JSON file at `path`: best-known timing + chosen config per
    (kernel, shape-signature, dtype, backend) key. `update` REFUSES
    non-finite rows: a NaN that slips into the baseline would silently
    disarm every future comparison against it."""

    def __init__(self, path):
        self.path = path
        self.entries = {}
        self.comment = ""
        if path and os.path.exists(path):
            with open(path) as f:
                data = json.load(f)
            self.entries = dict(data.get("entries", {}))
            self.comment = data.get("comment", "")

    def lookup(self, kernel, sig=None, dtype=None, backend=None):
        """Entries for one kernel, narrowed by whatever axes the caller
        knows. Returns [(key, entry), ...]."""
        out = []
        for key, e in self.entries.items():
            if e.get("kernel") != kernel:
                continue
            if sig is not None and e.get("sig") != sig:
                continue
            if dtype is not None and e.get("dtype") != dtype:
                continue
            if backend is not None and e.get("backend") != backend:
                continue
            out.append((key, e))
        return out

    def best_ms(self, kernel, sig, dtype, backend):
        e = self.entries.get(db_key(kernel, sig, dtype, backend))
        return e.get("best_ms") if e else None

    def update(self, results, keep_best=True):
        """Roll measured rows in. `results` is [MeasureResult] or
        [(key, entry_dict)]. Returns (updated_keys, refused) where
        refused is [(key, reason)] — non-finite timings never land, and
        with keep_best a slower row than the incumbent is skipped (not
        refused: losing a race is not an error)."""
        updated, refused = [], []
        for item in results:
            if isinstance(item, MeasureResult):
                key = db_key(item.kernel, item.sig, item.dtype,
                             item.backend)
                entry = {
                    "kernel": item.kernel, "sig": item.sig,
                    "dtype": item.dtype, "backend": item.backend,
                    "best_ms": item.kernel_ms,
                    "fallback_ms": item.fallback_ms,
                    "flops": item.flops,
                    "bytes_accessed": item.bytes_accessed,
                }
                if item.config:
                    entry["config"] = dict(item.config)
            else:
                key, entry = item
                entry = dict(entry)
                # the key IS the identity — backfill the lookup axes
                # from it so a hand-built (key, entry) pair can't ship
                # an entry lookup() would never find
                parts = key.split("|")
                if len(parts) == 4:
                    for axis, val in zip(
                            ("kernel", "sig", "dtype", "backend"), parts):
                        entry.setdefault(axis, val)
            ms = entry.get("best_ms")
            if not _finite(ms) or ms < 0:
                refused.append(
                    (key, f"REFUSED: non-finite best_ms {ms!r}"))
                continue
            bad = [k for k, v in entry.items()
                   if isinstance(v, float) and not math.isfinite(v)]
            if bad:
                refused.append(
                    (key, f"REFUSED: non-finite value(s) in {bad}"))
                continue
            old = self.entries.get(key)
            if keep_best and old and _finite(old.get("best_ms")) \
                    and old["best_ms"] <= ms:
                continue
            self.entries[key] = entry
            updated.append(key)
        return updated, refused

    def save(self, path=None):
        path = path or self.path
        data = {"schema": DB_SCHEMA, "comment": self.comment,
                "entries": {k: self.entries[k]
                            for k in sorted(self.entries)}}
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
        return path


# ---------------------------------------------------------------------------
# config search (the autotune hook)
# ---------------------------------------------------------------------------

def _flash_fwd_vmem_feasible(bq, bk, h, budget=None):
    """KN502 feasibility for a flash-forward candidate, through the
    SAME kernel_registry.vmem_footprint model the Kernel Doctor
    projects with: q/k/v/out/lse blocks move (double-buffered), the
    acc/m/l accumulators are scratch."""
    from ..ops.kernel_registry import VMEM_BUDGET, vmem_footprint

    lanes = 128
    sub = 8
    f32 = 4
    itemsize = 4   # tune measures in f32; bf16 halves the moving set
    used = vmem_footprint(
        moving=[((1, bq, h), itemsize), ((1, bk, h), itemsize),
                ((1, bk, h), itemsize), ((1, bq, h), itemsize),
                ((1, sub, bq), f32)],
        scratch=[((bq, h), f32), ((bq, lanes), f32),
                 ((bq, lanes), f32)])
    return used <= (budget or VMEM_BUDGET)


def tune_flash_fwd(seq=1024, batch=1, heads=2, head_dim=64,
                   warmup=1, k=3, seeds=(0, 1), clock=None,
                   candidates=None):
    """Search the flash-forward (block_q, block_k) space at one shape:
    KN502 vmem_footprint as the feasibility predicate, measured
    median-of-k time as the objective, KN504 parity re-fuzz on the
    winner so tuning can never trade correctness. Returns
    (winner dict | None, [MeasureResult per feasible candidate],
    skipped list)."""
    import jax

    from ..analysis.kernel_lint import check_fallback_parity
    from ..ops import pallas_attention as pa
    from ..ops.kernel_registry import PallasKernel, get_kernel

    clock = clock or time.perf_counter
    reg = get_kernel("flash_fwd_rect")
    rng = np.random.default_rng(1234)
    q = rng.standard_normal(
        (batch, seq, heads, head_dim)).astype(np.float32)
    if candidates is None:
        candidates = [(bq, bk) for bq in ATTN_SWEEP_BQ
                      for bk in ATTN_SWEEP_BK]

    results, skipped = [], []
    for bq, bk in candidates:
        if bq > seq or bk > seq:
            skipped.append(((bq, bk), "blocks exceed seq"))
            continue
        if not _flash_fwd_vmem_feasible(bq, bk, head_dim):
            skipped.append(((bq, bk), "KN502: over the VMEM budget"))
            continue
        args = (q, q, q, True, 1.0, bq, bk)
        res = measure_kernel(
            reg, warmup=warmup, k=k, clock=clock, time_fallback=False,
            args=args, kwargs={},
            config={"sq": int(seq), "block_q": int(bq),
                    "block_k": int(bk)})
        results.append(res)
    if not results:
        return None, results, skipped

    best = min(results, key=lambda r: r.kernel_ms)
    bq, bk = best.config["block_q"], best.config["block_k"]

    # KN504 re-fuzz: the registered example with the TUNED blocks bound
    # in place of its defaults, against the registered exact fallback
    def tuned_fn(q_, k_, v_, causal, scale, block_q, block_k):
        return reg.fn(q_, k_, v_, causal, scale, bq, bk)

    def tuned_example(rng_):
        args_, kwargs_ = reg.example(rng_)
        return args_, kwargs_

    tuned_reg = PallasKernel(
        name=f"{reg.name}@bq{bq}bk{bk}", fn=tuned_fn,
        example=tuned_example, fallback=reg.fallback, tol=reg.tol,
        notes="tuned-config parity re-fuzz (kernellab --tune)")
    parity = check_fallback_parity(tuned_reg, seeds=seeds)
    winner = {
        "kernel": "flash_fwd", "sig": best.sig, "dtype": best.dtype,
        "backend": jax.default_backend(), "best_ms": best.kernel_ms,
        "config": dict(best.config),
        "parity_findings": [f.to_dict() for f in parity],
        "vmem_feasible": True,
    }
    return winner, results, skipped
