"""Mesh observatory: measured collective latencies vs the planner's
ICI/DCN peaks, a persistent comm DB, and per-step comm attribution.

The communication sibling of the kernel observatory
(telemetry/kernel_obs.py). Every comm number the planner prices today
is analytic: `cost_model.estimate_layout_cost` divides wire bytes by
the static `ICI_BW_BY_CHIP` / `DCN_BW_BYTES` tables and nothing ever
measures them. This module closes that loop:

- **measure_collective / sweep_mesh** — run each mesh collective
  (psum / all_gather / reduce_scatter / all_to_all / ppermute, per mesh
  axis, payloads swept log2 from 256 KiB to 256 MiB) under the
  kernel_obs discipline: AOT ``lower().compile()`` timed separately,
  warmup, then median-of-k ``block_until_ready`` samples against an
  injectable clock.
- **attribution** — place each measurement as an achieved-bandwidth
  fraction against the SAME peak tables the planner prices with
  (`cost_model.ICI_BW_BY_CHIP` / `DCN_BW_BYTES` — one source for
  claims and predictions, like mfu.py's shared FLOPs peaks), with
  wire bytes from `analysis/comm_audit`'s fraction convention so the
  harness and the jaxpr auditor can never disagree about what a
  collective moves. CPU backends have no entry in the peak tables, so
  bw_frac / predicted_ms are None there — no roofline, no drift to
  judge (the kernel_obs exemption rule).
- **CommDB** — a JSON file at a path the caller names: best-known
  latency per (op, axis-size, payload, backend) key, rolled forward
  only by ``commlab --db PATH --update-db`` with the KernelDB
  keep-best / refuse-non-finite semantics. A measured collective
  drifting a multiplicative band BELOW its DB row fires the
  `comm_bw_degraded` rule (telemetry/health.py); the DB reference rides
  ON the record (db_ms) so in-flight and offline replays judge
  identically.
- per-step attribution lands through TelemetryRecorder: wall-time
  ``collective.*`` spans aggregate into the step record's ``comm_ms``
  / ``comm_frac`` fields (spans tagged ``traced=True`` by
  distributed/collective.py cover trace time and are excluded), and
  per-rank step-boundary skew feeds the `straggler` rule.

A measurement attaches its DB reference (db_ms) only when the caller
hands `measure_collective` / `sweep_mesh` a CommDB; without one it
carries no reference and the rule has no jurisdiction.

Every measurement is emitted as a typed ``kind=commbench`` record
(telemetry/sink.make_commbench_record, cross-checked by
tools/trace_check.py) and mirrored as ``comm.*`` gauges on /metrics.
CLI: tools/commlab.py (--smoke / --selfcheck / --update-db).
"""
import json
import math
import os
import statistics
import time

import numpy as np

from .. import monitor
from .sink import make_commbench_record

__all__ = [
    "CommDB", "CommMeasureResult", "PAYLOAD_MAX_BYTES",
    "PAYLOAD_MIN_BYTES", "SWEEP_OPS", "attribution", "db_key",
    "device_peak_ici_bw", "measure_collective", "payload_sweep",
    "rank_step_skew", "sweep_axes", "sweep_mesh", "sweep_program",
    "wire_bytes",
]

# the sweep matrix: every shard_map collective the training stack issues
# (distributed/collective.py primitives; pmean/pmax lower to psum)
SWEEP_OPS = ("psum", "all_gather", "reduce_scatter", "all_to_all",
             "ppermute")

# log2 payload sweep bounds — 256 KiB (latency-dominated) to 256 MiB
# (bandwidth-saturated); commlab --smoke scales these down for the
# 8-virtual-device CPU mesh, where a MiB-scale sweep buys nothing
PAYLOAD_MIN_BYTES = 256 * 1024
PAYLOAD_MAX_BYTES = 256 * 1024 * 1024

DB_SCHEMA = 1

# second dim of every swept operand: one full lane register, so payload
# rounding only ever moves along the first (sharded) dim
_SWEEP_COLS = 128


def payload_sweep(min_bytes=PAYLOAD_MIN_BYTES, max_bytes=PAYLOAD_MAX_BYTES):
    """The log2 payload ladder [min, 2*min, ..., <= max], in bytes."""
    out = []
    b = int(min_bytes)
    while b <= int(max_bytes):
        out.append(b)
        b *= 2
    return out


def db_key(op, axis_size, payload_bytes, backend):
    """``op|ax<n>|<payload_bytes>|<backend>`` — the DB's primary key:
    the (op, axis-size, payload, backend) identity of one measurement,
    mirroring kernel_obs.db_key's kernel|sig|dtype|backend."""
    return f"{op}|ax{int(axis_size)}|{int(payload_bytes)}|{backend}"


# ---------------------------------------------------------------------------
# peaks + attribution (the planner's own tables — one source of truth)
# ---------------------------------------------------------------------------

def device_peak_ici_bw(kind=None):
    """Aggregate per-chip ICI bandwidth (bytes/s) for a device-kind
    string, from the SAME `cost_model.ICI_BW_BY_CHIP` table the planner
    prices layouts with (plus the 'v5 lite'/'v6 lite' device_kind
    aliases mfu.py's tables use). None when unknown (CPU backends) —
    the bandwidth fraction is then not computable and the drift rules
    have no jurisdiction."""
    from ..cost_model import ICI_BW_BY_CHIP
    from .mfu import _match_kind
    table = dict(ICI_BW_BY_CHIP)
    table.setdefault("v5 lite", ICI_BW_BY_CHIP["v5e"])
    table.setdefault("v6 lite", ICI_BW_BY_CHIP["v6e"])
    return _match_kind(table, kind)


def wire_bytes(op, payload_bytes, axis_size):
    """Wire traffic per participant for `op` moving a `payload_bytes`
    operand over an axis of `axis_size` — delegating to
    `analysis/comm_audit`'s fraction convention (all_gather /
    reduce_scatter / all_to_all (n-1)/n, psum 2(n-1)/n ring all-reduce,
    ppermute full operand) so the measurement harness and the jaxpr
    auditor share ONE rule and the third honesty leg is a real check,
    not a tautology over two copies of the same table."""
    from ..analysis.comm_audit import _wire_bytes
    return float(_wire_bytes(op, float(payload_bytes), int(axis_size)))


def attribution(op, payload_bytes, axis_size, time_ms, peak_bw=None,
                device_kind=None, over_dcn=False):
    """Attribute one measured collective against the planner's peaks:

    - wire_bytes — comm_audit-convention wire traffic of the operand;
    - achieved_bw — wire_bytes / measured seconds (None without a
      positive time);
    - bw_frac — achieved over peak, clamped to [0, 1] (None on CPU
      backends, where `device_peak_ici_bw` answers None);
    - predicted_ms — wire_bytes / peak * 1e3, the analytic floor
      `calibration_from_comm_records` ratios measured time against;
    - medium — 'dcn' when over_dcn, 'ici' when an ICI peak resolved,
      None otherwise (CPU).
    """
    from ..cost_model import DCN_BW_BYTES
    wb = wire_bytes(op, payload_bytes, axis_size)
    if peak_bw is None:
        peak_bw = float(DCN_BW_BYTES) if over_dcn \
            else device_peak_ici_bw(device_kind)
    t_s = time_ms / 1e3 if time_ms and time_ms > 0 else None
    out = {"wire_bytes": wb, "achieved_bw": None, "bw_frac": None,
           "predicted_ms": None, "peak_bw": peak_bw,
           "medium": ("dcn" if over_dcn
                      else ("ici" if peak_bw else None))}
    if t_s and wb:
        out["achieved_bw"] = wb / t_s
        if peak_bw:
            out["bw_frac"] = min(1.0, out["achieved_bw"] / peak_bw)
    if peak_bw and wb:
        out["predicted_ms"] = wb / peak_bw * 1e3
    return out


# ---------------------------------------------------------------------------
# the sweep programs
# ---------------------------------------------------------------------------

def sweep_program(op, axis, mesh, payload_bytes, dtype=np.float32):
    """Build one swept collective as a global-view callable.

    Returns (fn, global_sds, in_spec, actual_payload_bytes): `fn` takes
    ONE global array of `global_sds`'s shape placed with
    NamedSharding(mesh, in_spec); inside, shard_map runs `op` over
    `axis`. Shapes are chosen so the PER-DEVICE operand is
    `actual_payload_bytes` (payload rounded to the lane/divisibility
    grid) — exactly the per-device accounting
    `analysis/comm_audit.collective_wire_bytes` applies to shard_map
    bodies, which is what makes the third honesty leg's comparison
    meaningful."""
    import jax
    from jax.sharding import PartitionSpec as P

    if op not in SWEEP_OPS:
        raise ValueError(f"unknown sweep op {op!r} "
                         f"(expected one of {SWEEP_OPS})")
    n = int(mesh.shape[axis])
    itemsize = np.dtype(dtype).itemsize
    rows = max(1, int(payload_bytes) // (_SWEEP_COLS * itemsize))
    if op == "all_to_all":
        # per-device rows must split evenly over the axis
        rows = max(n, rows // n * n)
    if op == "reduce_scatter":
        # operand is the FULL (replicated) array; output rows must
        # divide over the axis
        rows = max(n, rows // n * n)
        global_shape = (rows, _SWEEP_COLS)
        in_spec, out_spec = P(), P(axis)
        body = lambda v: jax.lax.psum_scatter(   # noqa: E731
            v, axis, scatter_dimension=0, tiled=True)
    else:
        global_shape = (n * rows, _SWEEP_COLS)
        in_spec = P(axis)
        if op == "psum":
            out_spec = P()
            body = lambda v: jax.lax.psum(v, axis)           # noqa: E731
        elif op == "all_gather":
            out_spec = P()
            body = lambda v: jax.lax.all_gather(             # noqa: E731
                v, axis, axis=0, tiled=True)
        elif op == "ppermute":
            out_spec = P(axis)
            perm = [(i, (i + 1) % n) for i in range(n)]
            body = lambda v: jax.lax.ppermute(v, axis, perm)  # noqa: E731
        else:   # all_to_all
            out_spec = P(axis)
            body = lambda v: jax.lax.all_to_all(             # noqa: E731
                v, axis, split_axis=0, concat_axis=0, tiled=True)
    # check_vma off: a tiled all_gather's result is typed as varying, so
    # the replicated out_spec of that sweep would be rejected
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_spec,
                       out_specs=out_spec, check_vma=False)
    sds = jax.ShapeDtypeStruct(global_shape, np.dtype(dtype))
    actual = rows * _SWEEP_COLS * itemsize
    return fn, sds, in_spec, actual


# ---------------------------------------------------------------------------
# measurement harness (the kernel_obs timing discipline)
# ---------------------------------------------------------------------------

class CommMeasureResult:
    """One measured (op, axis, payload) point, bandwidth-attributed."""

    __slots__ = ("op", "axis", "axis_size", "payload_bytes", "backend",
                 "time_ms", "compile_ms", "wire_bytes", "achieved_bw",
                 "bw_frac", "predicted_ms", "peak_bw", "medium",
                 "n_samples", "warmup", "db_ms")

    def __init__(self, **kw):
        for s in self.__slots__:
            setattr(self, s, kw.get(s))

    def key(self):
        return db_key(self.op, self.axis_size, self.payload_bytes,
                      self.backend)

    def to_record(self, rank=0, event="measure"):
        return make_commbench_record(
            op=self.op, axis=self.axis, axis_size=self.axis_size,
            payload_bytes=self.payload_bytes, backend=self.backend,
            time_ms=self.time_ms, rank=rank, compile_ms=self.compile_ms,
            wire_bytes=self.wire_bytes, achieved_bw=self.achieved_bw,
            peak_bw=self.peak_bw, bw_frac=self.bw_frac,
            predicted_ms=self.predicted_ms, medium=self.medium,
            db_key=self.key(), db_ms=self.db_ms,
            n_samples=self.n_samples, warmup=self.warmup, event=event)


def _timed_call(fn, arr, warmup, k, clock):
    """AOT-compile `fn` over `arr`, then warmup + k timed
    ``block_until_ready`` iterations; returns
    (median_ms, compile_ms, samples). compile_ms is measured around
    lower().compile() — the compile-observatory discipline — so it can
    never leak into the execute median."""
    import jax

    t0 = clock()
    compiled = jax.jit(fn).lower(arr).compile()
    compile_ms = (clock() - t0) * 1e3
    for _ in range(max(0, warmup)):
        jax.block_until_ready(compiled(arr))
    samples = []
    for _ in range(max(1, k)):
        t0 = clock()
        jax.block_until_ready(compiled(arr))
        samples.append((clock() - t0) * 1e3)
    return statistics.median(samples), compile_ms, samples


def measure_collective(op, axis, mesh=None, payload_bytes=PAYLOAD_MIN_BYTES,
                       dtype=np.float32, warmup=2, k=5, clock=None,
                       over_dcn=False, db=None):
    """Measure one (op, axis, payload) point on the live mesh:
    median-of-k wall time of the AOT-compiled collective, attributed
    against the planner's peak tables. Deterministic given `clock`
    (tests inject a fake counter). When a CommDB is passed as `db`,
    its best-known latency for this key is attached as `db_ms` — the
    reference the `comm_bw_degraded` rule judges against."""
    import jax
    from jax.sharding import NamedSharding

    from ..distributed import env

    clock = clock or time.perf_counter
    mesh = mesh if mesh is not None else env.current_mesh()
    if mesh is None:
        raise RuntimeError("measure_collective: no mesh — pass mesh= or "
                           "env.build_mesh(...) first")
    fn, sds, in_spec, actual = sweep_program(op, axis, mesh,
                                             payload_bytes, dtype)
    host = np.arange(int(np.prod(sds.shape)),
                     dtype=np.dtype(dtype)).reshape(sds.shape)
    arr = jax.device_put(host, NamedSharding(mesh, in_spec))
    time_ms, compile_ms, _ = _timed_call(fn, arr, warmup, k, clock)
    backend = jax.default_backend()
    n = int(mesh.shape[axis])
    attr = attribution(op, actual, n, time_ms, over_dcn=over_dcn)
    db_ms = None
    if db is not None:
        db_ms = db.best_ms(op, n, actual, backend)
    res = CommMeasureResult(
        op=op, axis=str(axis), axis_size=n, payload_bytes=actual,
        backend=backend, time_ms=time_ms, compile_ms=compile_ms,
        wire_bytes=attr["wire_bytes"], achieved_bw=attr["achieved_bw"],
        bw_frac=attr["bw_frac"], predicted_ms=attr["predicted_ms"],
        peak_bw=attr["peak_bw"], medium=attr["medium"],
        n_samples=max(1, k), warmup=max(0, warmup), db_ms=db_ms)
    _export_gauges(res)
    return res


def _export_gauges(res):
    """Mirror one measurement onto /metrics (telemetry.metrics_http
    scrapes monitor.snapshot_typed verbatim)."""
    monitor.set_gauge(f"comm.{res.op}.ms", float(res.time_ms))
    if res.achieved_bw is not None:
        monitor.set_gauge(f"comm.{res.op}.achieved_bw",
                          float(res.achieved_bw))
    if res.bw_frac is not None:
        monitor.set_gauge(f"comm.{res.op}.bw_frac", float(res.bw_frac))
    monitor.incr("comm.measured")


def sweep_axes(mesh):
    """The mesh axes worth sweeping: size > 1 (a 1-axis collective
    moves nothing), in mesh axis order."""
    return [a for a in mesh.axis_names if int(mesh.shape[a]) > 1]


def sweep_mesh(mesh=None, ops=SWEEP_OPS, payloads=None, dtype=np.float32,
               warmup=2, k=5, clock=None, over_dcn_axes=(), db=None):
    """The full sweep: every op x every size>1 mesh axis x every
    payload rung. Returns [CommMeasureResult, ...] in deterministic
    (op, axis, payload) order. `over_dcn_axes` marks axes priced
    against DCN (the outer axis of a two-level plan)."""
    from ..distributed import env

    mesh = mesh if mesh is not None else env.current_mesh()
    if mesh is None:
        raise RuntimeError("sweep_mesh: no mesh — pass mesh= or "
                           "env.build_mesh(...) first")
    payloads = list(payloads) if payloads is not None else payload_sweep()
    out = []
    for op in ops:
        for axis in sweep_axes(mesh):
            for payload in payloads:
                out.append(measure_collective(
                    op, axis, mesh=mesh, payload_bytes=payload,
                    dtype=dtype, warmup=warmup, k=k, clock=clock,
                    over_dcn=axis in over_dcn_axes, db=db))
    return out


# ---------------------------------------------------------------------------
# per-rank step-boundary skew (the straggler measurement)
# ---------------------------------------------------------------------------

def rank_step_skew(records):
    """Per-step, per-rank step-boundary skew over kind=step records
    from MULTIPLE ranks: for each step index seen on >= 2 ranks,
    skew_ms[rank] = that rank's step_ms minus the fastest rank's. The
    offline view of what the `straggler` rule (telemetry/health.py)
    judges in flight — a rank persistently above the band is holding
    every collective barrier open for the whole mesh. Returns
    {step: {rank: skew_ms}}, only steps with >= 2 ranks."""
    by_step = {}
    for rec in records or ():
        if not isinstance(rec, dict) or rec.get("kind", "step") != "step":
            continue
        step, rank, ms = rec.get("step"), rec.get("rank"), rec.get("step_ms")
        if step is None or rank is None \
                or not isinstance(ms, (int, float)) or not math.isfinite(ms):
            continue
        by_step.setdefault(int(step), {})[int(rank)] = float(ms)
    out = {}
    for step, ranks in sorted(by_step.items()):
        if len(ranks) < 2:
            continue
        fastest = min(ranks.values())
        out[step] = {r: round(ms - fastest, 4)
                     for r, ms in sorted(ranks.items())}
    return out


# ---------------------------------------------------------------------------
# persistent measurement DB (the KernelDB contract)
# ---------------------------------------------------------------------------

def _finite(v):
    return isinstance(v, (int, float)) and math.isfinite(v)


class CommDB:
    """A JSON file at `path`: best-known latency per (op, axis-size,
    payload, backend) key. `update` REFUSES non-finite rows (a NaN in
    the reference would silently disarm every later comparison) and
    with keep_best skips rows slower than the incumbent — losing a
    race is not an error."""

    def __init__(self, path):
        self.path = path
        self.entries = {}
        self.comment = ""
        if path and os.path.exists(path):
            with open(path) as f:
                data = json.load(f)
            self.entries = dict(data.get("entries", {}))
            self.comment = data.get("comment", "")

    def lookup(self, op, axis_size=None, payload_bytes=None, backend=None):
        """Entries for one op, narrowed by whatever axes the caller
        knows. Returns [(key, entry), ...]."""
        out = []
        for key, e in self.entries.items():
            if e.get("op") != op:
                continue
            if axis_size is not None and e.get("axis_size") != int(axis_size):
                continue
            if payload_bytes is not None \
                    and e.get("payload_bytes") != int(payload_bytes):
                continue
            if backend is not None and e.get("backend") != backend:
                continue
            out.append((key, e))
        return out

    def best_ms(self, op, axis_size, payload_bytes, backend):
        e = self.entries.get(db_key(op, axis_size, payload_bytes, backend))
        return e.get("best_ms") if e else None

    def update(self, results, keep_best=True):
        """Roll measured rows in. `results` is [CommMeasureResult] or
        [(key, entry_dict)]. Returns (updated_keys, refused) where
        refused is [(key, reason)] — non-finite timings never land."""
        updated, refused = [], []
        for item in results:
            if isinstance(item, CommMeasureResult):
                key = item.key()
                entry = {
                    "op": item.op, "axis_size": int(item.axis_size),
                    "payload_bytes": int(item.payload_bytes),
                    "backend": item.backend, "best_ms": item.time_ms,
                    "wire_bytes": item.wire_bytes,
                    "predicted_ms": item.predicted_ms,
                }
            else:
                key, entry = item
                entry = dict(entry)
                # the key IS the identity — backfill the lookup axes
                # from it so a hand-built (key, entry) pair can't ship
                # an entry lookup() would never find
                parts = key.split("|")
                if len(parts) == 4 and parts[1].startswith("ax"):
                    entry.setdefault("op", parts[0])
                    try:
                        entry.setdefault("axis_size", int(parts[1][2:]))
                        entry.setdefault("payload_bytes", int(parts[2]))
                    except ValueError:
                        pass
                    entry.setdefault("backend", parts[3])
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
