"""Memory observatory: live HBM ledger, KV occupancy telemetry, OOM
forensics, and the admission-headroom gauge.

The memory sibling of the compile (compile_obs) and mesh (comm_obs)
observatories. Those close the loop on what the chip COMPILES and
MOVES; until now nothing closed it on what the chip HOLDS:
`compile_obs` captures only the static ``memory_analysis()``
projection, the serving BlockPool's occupancy never reaches /metrics,
and an allocation failure kills the process with no forensic record. This module is the live side:

- **ledger** — `snapshot_ledger` walks ``jax.live_arrays()`` and
  attributes every live byte into exactly one bucket (params /
  opt_state / kv / workspace / other) via the provider registry below;
  `other` absorbs allocator bytes the live-array walk cannot see
  (``device.memory_stats()['bytes_in_use']`` minus the live sum, when
  the backend reports stats at all — CPU does not, so there `other`
  is 0 and total IS the live sum). The buckets PARTITION the total by
  construction, which is what lets tools/trace_check.py recompute the
  sum from each record's own fields.
- **provider registry** — `register_provider(name, bucket, owner,
  fn)`: the optimizer tags its per-param state (and masters), the
  paged KV cache tags its block arenas. Providers are queried FRESH at
  snapshot time (arrays are replaced every step, so tagging
  identities once would rot) and hold their owner only by weakref — a
  dead owner silently drops out of the ledger instead of pinning its
  arrays live.
- **MemoryObservatory** — samples the ledger on a step cadence into
  typed ``kind=memsnap`` records (telemetry/sink.make_memsnap_record)
  through the existing sink/validator, mirrors ``mem.*`` gauges on
  /metrics, reconciles each snapshot against the compile observatory's
  static projection (the `mem_projection_drift` rule, latched per
  family), and feeds the `hbm_pressure` / `kv_thrash` rules
  (telemetry/health.py). Every reference a rule judges against —
  budget, projection, eviction/admission rates — rides ON the record,
  so healthwatch replay and the in-flight detector see identical
  numbers (the commbench db_ms stance).
- **OOM forensics** — `is_oom` recognizes an allocation failure
  (RESOURCE_EXHAUSTED / XlaRuntimeError OOM / MemoryError);
  `capture_postmortem` writes an ``event=postmortem`` record carrying
  the last ledger, the top-K live arrays by bytes, the KV pool state
  and the active compile-signature families — so a dead run is
  diagnosable offline via ``memwatch --postmortem``.

The serving engine attaches an observatory when `EngineConfig` declares
an HBM budget, samples it in `step()`, exposes the
``serving.mem_headroom_bytes`` gauge its admission path consults, and
captures a postmortem before its restart protocol tears the arenas
down. CLI: tools/memwatch.py (--smoke / --selfcheck / --postmortem).
"""
import threading
import weakref

from .. import monitor
from .sink import make_memsnap_record

__all__ = [
    "BUCKETS", "MemoryObservatory", "capture_postmortem",
    "device_bytes_in_use", "is_oom", "register_provider",
    "registered_providers", "snapshot_ledger", "unregister_provider",
]

# the attribution buckets, in ledger order (sink.MEMSNAP_BUCKETS minus
# the _bytes suffix); every live array lands in exactly one — untagged
# arrays are workspace (activations, donated temps, host staging)
BUCKETS = ("params", "opt_state", "kv", "workspace", "other")

# ---------------------------------------------------------------------------
# provider registry (the tagging hooks)
# ---------------------------------------------------------------------------

_PROVIDERS = {}          # name -> (bucket, weakref-to-owner, fn)
_PROVIDER_LOCK = threading.Lock()
_PROVIDER_SEQ = [0]


def register_provider(name, bucket, owner, fn):
    """Register a byte-bucket provider: `fn(owner)` returns the
    CURRENT arrays belonging to `bucket` (params / opt_state / kv).
    The owner is held by weakref only — when it dies the provider
    drops out of the next snapshot and is garbage-collected from the
    registry, so tagging can never extend an arena's lifetime (the
    engine rebuilds its KV cache on restart; the old one must stay
    collectible). Returns the unique registry name (`name#<n>`)."""
    if bucket not in BUCKETS:
        raise ValueError(f"unknown bucket {bucket!r} "
                         f"(expected one of {BUCKETS})")
    with _PROVIDER_LOCK:
        _PROVIDER_SEQ[0] += 1
        key = f"{name}#{_PROVIDER_SEQ[0]}"
        _PROVIDERS[key] = (bucket, weakref.ref(owner), fn)
    return key


def unregister_provider(key):
    with _PROVIDER_LOCK:
        _PROVIDERS.pop(key, None)


def registered_providers():
    """[(name, bucket), ...] of providers whose owner is still alive."""
    with _PROVIDER_LOCK:
        items = list(_PROVIDERS.items())
    return [(k, bucket) for k, (bucket, ref, _fn) in items
            if ref() is not None]


def _query_providers():
    """Yield (bucket, arrays) per live provider; reap dead owners."""
    with _PROVIDER_LOCK:
        items = list(_PROVIDERS.items())
    dead = []
    out = []
    for key, (bucket, ref, fn) in items:
        owner = ref()
        if owner is None:
            dead.append(key)
            continue
        try:
            arrs = fn(owner)
        except Exception:
            continue          # a broken provider must not kill sampling
        if arrs:
            out.append((bucket, arrs))
    if dead:
        with _PROVIDER_LOCK:
            for key in dead:
                _PROVIDERS.pop(key, None)
    return out


# ---------------------------------------------------------------------------
# the ledger walk
# ---------------------------------------------------------------------------

def device_bytes_in_use(device=None):
    """Allocator bytes_in_use from ``device.memory_stats()``, or None
    when the backend keeps no stats (CPU) — the ledger then has no
    'other' slack and the live-array sum IS the total."""
    import jax
    try:
        dev = device if device is not None else jax.devices()[0]
        stats = dev.memory_stats()
        if isinstance(stats, dict):
            v = stats.get("bytes_in_use")
            if isinstance(v, (int, float)) and v >= 0:
                return int(v)
    except Exception:
        pass
    return None


def snapshot_ledger(top_k=8, device=None):
    """Walk the live arrays once and attribute every byte.

    Returns a plain dict: per-bucket byte sums (`<bucket>_bytes`),
    `total_bytes`, `n_arrays`, and the `top_arrays` listing
    ([{bytes, bucket, shape, dtype}, ...] descending by bytes, length
    <= top_k) the postmortem record ships. Tag membership is queried
    FRESH from the provider registry — a step's functional updates
    replace the underlying arrays, so identity tags would be stale by
    the next sample."""
    import jax
    try:
        live = [a for a in jax.live_arrays()
                if getattr(a, "nbytes", None) is not None]
    except Exception:
        live = []
    tagged = {}
    for bucket, arrs in _query_providers():
        for a in arrs:
            tagged[id(a)] = bucket
    sums = {b: 0 for b in BUCKETS}
    rows = []
    for a in live:
        nb = int(a.nbytes)
        bucket = tagged.get(id(a), "workspace")
        sums[bucket] += nb
        rows.append((nb, bucket, a))
    live_sum = sum(sums.values())
    in_use = device_bytes_in_use(device)
    if in_use is not None and in_use > live_sum:
        # allocator bytes the live-array walk cannot see: fragmentation,
        # donated-but-unreclaimed buffers, runtime scratch
        sums["other"] = in_use - live_sum
    rows.sort(key=lambda r: r[0], reverse=True)
    top = [{"bytes": nb, "bucket": bucket,
            "shape": list(getattr(a, "shape", ()) or ()),
            "dtype": str(getattr(a, "dtype", "?"))}
           for nb, bucket, a in rows[:max(0, int(top_k))]]
    led = {f"{b}_bytes": sums[b] for b in BUCKETS}
    led["total_bytes"] = sum(sums.values())
    led["n_arrays"] = len(live)
    led["top_arrays"] = top
    return led


# ---------------------------------------------------------------------------
# OOM recognition
# ---------------------------------------------------------------------------

def is_oom(exc):
    """True when `exc` is an allocation failure: XLA surfaces HBM
    exhaustion as RESOURCE_EXHAUSTED (XlaRuntimeError), host allocators
    as MemoryError. String-matched, not type-matched — the concrete
    exception class moved across jaxlib versions and forensics must
    not depend on which one this build ships."""
    if isinstance(exc, MemoryError):
        return True
    text = f"{type(exc).__name__}: {exc}"
    return "RESOURCE_EXHAUSTED" in text or "Out of memory" in text \
        or "out of memory" in text


# ---------------------------------------------------------------------------
# the observatory
# ---------------------------------------------------------------------------

class MemoryObservatory:
    """Step-cadence HBM sampler -> typed memsnap records.

    `sink` takes the records (None -> in-memory only; `.records` keeps
    the tail either way); `health` is an AnomalyDetector fed each
    record in flight (the same rules healthwatch replays offline);
    `hbm_budget_bytes` anchors the `hbm_pressure` rule and the
    headroom gauge — None means no budget was declared, so the rule
    has no jurisdiction and headroom is None (the comm_obs no-DB
    stance); `kv_source` is a zero-arg callable returning the serving
    engine's pool/scheduler accounting dict (blocks_total/held/free/
    cached, cumulative evictions/admissions + per-class dicts);
    `projection_bytes` is the compile observatory's static HBM
    projection (resolved from `compile_obs.current_observatory()` when
    not given), latched per `projection_family`."""

    def __init__(self, sink=None, rank=0, health=None,
                 hbm_budget_bytes=None, kv_source=None,
                 projection_bytes=None, projection_family="default",
                 engine=None, top_k=8, keep=64):
        self.sink = sink
        self.rank = int(rank)
        self.health = health
        self.hbm_budget_bytes = None if hbm_budget_bytes is None \
            else int(hbm_budget_bytes)
        self.kv_source = kv_source
        self.projection_bytes = None if projection_bytes is None \
            else int(projection_bytes)
        self.projection_family = str(projection_family)
        self.engine = engine
        self.top_k = int(top_k)
        self.keep = int(keep)
        self.records = []
        self.last = None
        self._prev_kv = None      # (step, evictions, admissions)

    # -- projection -------------------------------------------------------

    def _projection(self):
        if self.projection_bytes is not None:
            return self.projection_bytes
        from . import compile_obs
        obs = compile_obs.current_observatory()
        proj = getattr(obs, "hbm_projection", None) if obs else None
        return int(proj) if isinstance(proj, (int, float)) and proj > 0 \
            else None

    # -- KV accounting ----------------------------------------------------

    def _kv_fields(self, step):
        if self.kv_source is None:
            return {}
        try:
            kv = self.kv_source()
        except Exception:
            return {}
        if not isinstance(kv, dict):
            return {}
        total = kv.get("blocks_total")
        held = kv.get("blocks_held")
        cached = kv.get("blocks_cached")
        fields = {
            "kv_blocks_total": total,
            "kv_blocks_held": held,
            "kv_blocks_free": kv.get("blocks_free"),
            "kv_blocks_cached": cached,
            "kv_evictions": kv.get("evictions"),
            "kv_admissions": kv.get("admissions"),
            "evictions_by_class": kv.get("evictions_by_class"),
            "admissions_by_class": kv.get("admissions_by_class"),
        }
        if isinstance(total, int) and total > 0:
            if isinstance(held, int) and isinstance(cached, int):
                fields["kv_occupancy"] = min(
                    1.0, (held + cached) / float(total))
            if isinstance(cached, int):
                fields["kv_cache_share"] = min(1.0, cached / float(total))
        # windowed per-step rates from the cumulative counters — written
        # ON the record so offline replay judges the identical numbers.
        # No previous sample -> no window -> no rate (first snapshot is
        # exempt from kv_thrash, not silently rated 0)
        ev, adm = kv.get("evictions"), kv.get("admissions")
        if isinstance(ev, int) and isinstance(adm, int):
            prev = self._prev_kv
            if prev is not None and step > prev[0]:
                dstep = float(step - prev[0])
                fields["kv_eviction_rate"] = max(0, ev - prev[1]) / dstep
                fields["kv_admission_rate"] = max(0, adm - prev[2]) / dstep
            self._prev_kv = (step, ev, adm)
        return {k: v for k, v in fields.items() if v is not None}

    # -- sampling ---------------------------------------------------------

    def snapshot(self, step, device=None):
        """Sample the ledger once into a kind=memsnap record: emit to
        the sink, mirror the mem.* gauges, feed the health detector.
        Returns the record."""
        led = snapshot_ledger(top_k=self.top_k, device=device)
        total = led["total_bytes"]
        budget = self.hbm_budget_bytes
        headroom = max(0, budget - total) if budget else None
        proj = self._projection()
        rec = make_memsnap_record(
            "snapshot", step, total, rank=self.rank,
            params_bytes=led["params_bytes"],
            opt_state_bytes=led["opt_state_bytes"],
            kv_bytes=led["kv_bytes"],
            workspace_bytes=led["workspace_bytes"],
            other_bytes=led["other_bytes"],
            hbm_budget_bytes=budget, headroom_bytes=headroom,
            projected_bytes=proj,
            projection_family=self.projection_family if proj else None,
            n_arrays=led["n_arrays"], engine=self.engine,
            **self._kv_fields(step))
        self._commit(rec)
        monitor.incr("mem.snapshots")
        return rec

    def capture_postmortem(self, error, step=None, device=None):
        """Capture-on-failure: write the forensic record an OOM leaves
        behind — last-known ledger buckets, a FRESH top-K array listing
        (the allocator state at death, not at the last cadence tick),
        the KV pool state, and the active compile-signature families.
        Returns the record."""
        led = snapshot_ledger(top_k=self.top_k, device=device)
        if step is None:
            step = (self.last or {}).get("step", 0) or 0
        total = led["total_bytes"]
        budget = self.hbm_budget_bytes
        top = led["top_arrays"] or [
            {"bytes": 0, "bucket": "other", "note": "no live arrays"}]
        rec = make_memsnap_record(
            "postmortem", step, total, rank=self.rank,
            params_bytes=led["params_bytes"],
            opt_state_bytes=led["opt_state_bytes"],
            kv_bytes=led["kv_bytes"],
            workspace_bytes=led["workspace_bytes"],
            other_bytes=led["other_bytes"],
            hbm_budget_bytes=budget,
            headroom_bytes=max(0, budget - total) if budget else None,
            projected_bytes=self._projection(),
            n_arrays=led["n_arrays"], engine=self.engine,
            error=str(error) or "allocation failure",
            top_arrays=top,
            compile_families=_active_compile_families(),
            **self._kv_fields(step))
        self._commit(rec)
        monitor.incr("mem.postmortems")
        return rec

    def _commit(self, rec):
        self.last = rec
        self.records.append(rec)
        del self.records[:-self.keep]
        if self.sink is not None:
            try:
                self.sink.write(rec)
            except Exception:
                pass
        _export_gauges(rec)
        if self.health is not None:
            try:
                self.health.observe(rec)
            except Exception:
                pass

    # -- the admission signal --------------------------------------------

    def headroom_bytes(self):
        """Bytes between the last sampled total and the declared
        budget (clamped at 0), or None when no budget was declared or
        nothing has been sampled — the serving admission path treats
        None as 'no memory opinion'."""
        if self.last is None:
            return None
        return self.last.get("headroom_bytes")


def _active_compile_families():
    """Summaries of the compile observatory's tracked signature
    families — WHICH compiled programs were resident when the
    allocator failed. [] when no observatory is active."""
    from . import compile_obs
    obs = compile_obs.current_observatory()
    if obs is None:
        return []
    out = []
    try:
        for fam, (sig, count) in sorted(obs.tracker.families.items()):
            row = {"family": str(fam), "n_compiles": int(count)}
            try:
                row.update(sig.summary())
            except Exception:
                pass
            out.append(row)
    except Exception:
        return []
    return out


def _export_gauges(rec):
    """Mirror one ledger record onto /metrics (telemetry.metrics_http
    scrapes monitor.snapshot_typed verbatim)."""
    for key in ("total_bytes", "params_bytes", "opt_state_bytes",
                "kv_bytes", "workspace_bytes", "other_bytes",
                "headroom_bytes", "n_arrays", "kv_occupancy",
                "kv_cache_share"):
        v = rec.get(key)
        if isinstance(v, (int, float)):
            monitor.set_gauge(f"mem.{key}", float(v))


# module-level capture hook: the engine's error path calls this even
# when it never built an observatory — forensics must not depend on a
# budget having been declared
def capture_postmortem(error, sink=None, step=0, rank=0, **kw):
    """One-shot postmortem without a standing observatory."""
    obs = MemoryObservatory(sink=sink, rank=rank, **kw)
    return obs.capture_postmortem(error, step=step)
