"""What every run of every cell shares: finding a cell's files by name,
the device look, the traced sub-window, the per-layer readers, and the
result line. Drivers (`benchmark/drivers/<driver>.py`) own the rest.

A run is: set-up (build, hand over seeded weights, warm the cell's
shapes) -> measured window -> read the memory peak -> free the program
-> compare what the window's own path produced with the plain reference
-> print. `execute` drives all of it after the device look, so the tests
can drive it on the CPU at a tiny size; only `run.py` prints a result
line, and only on a TPU.
"""
import importlib
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Spec:
    """One cell with everything found by its name."""

    def __init__(self, name, bench=None, root=ROOT):
        self.bench = bench or load_json(root, "BENCHMARK.json")
        self.root = root
        rows = [w for w in self.bench["workloads"] if w["name"] == name]
        if not rows:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.workload = rows[0]
        self.name = name
        self.chips = int(self.workload["chips"])
        cfg_row = [c for c in self.bench["configs"]
                   if c["name"] == self.workload["config"]][0]
        self.config = load_json(root, cfg_row["file"])
        here = os.path.join(root, self.bench["paths"][0])
        self.traffic = load_json(here, "traffic",
                                 self.workload["traffic"] + ".json")
        self.cell = load_json(here, "cells", name + ".json")
        # the yardstick is the benchmark's own, whatever holds the cell
        self.peaks = load_json(HERE, "peaks.json")
        self.metrics_dir = os.path.join(HERE, "metrics")

    def metric_rows(self, group):
        """The rows of `end_to_end` / `per_layer` this cell reports."""
        out = []
        for row in self.bench[group]:
            cells = row.get("workloads")
            if cells is None or self.name in cells:
                out.append(row)
        return out


def reference_of(config):
    """The plain reference that stands beside a configuration."""
    return importlib.import_module(
        f"benchmark.reference.{config['reference']}")


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def device_look(spec):
    """The devices of this machine as JAX reports them, or exit 2 where
    they are not the TPU chips the cell asks for."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu" or jax.default_backend() != "tpu":
        print(f"benchmark: platform is {d0.platform!r}, not 'tpu'; "
              "nothing was run", file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < spec.chips:
        print(f"benchmark: {len(devs)} chip(s), the cell needs "
              f"{spec.chips}; nothing was run", file=sys.stderr)
        raise SystemExit(2)
    if d0.device_kind not in spec.peaks["devices"]:
        print(f"benchmark: device kind {d0.device_kind!r} is not in "
              "peaks.json; nothing was run", file=sys.stderr)
        raise SystemExit(2)
    return devs[:spec.chips]


def device_block(devices):
    """The device as JAX reports it. The TPU runtime keeps two pools: the
    buffers (`peak_bytes_in_use`) and what it reserves for the scratch
    of compiled programs (`peak_bytes_reserved`: 10.6 GB of a training
    step's activations are there, and nowhere in `bytes_in_use`). The
    peak on a chip is the sum of the two."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class CompileCounter:
    """Backend compiles and persistent-cache hits/misses, from JAX's own
    monitoring events (the events `compile_cache.CacheCounter` reads)."""

    def __init__(self):
        import jax
        self.compiles = self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def snapshot(self):
        return {"compiles": self.compiles, "hits": self.hits,
                "misses": self.misses}


# ---------------------------------------------------------------------------
# the traced sub-window
# ---------------------------------------------------------------------------

class Tracer:
    """Traces `length_s` seconds starting `start_s` into the window.
    The driver calls `poll(elapsed)` between steps (or from the thread
    that only waits); the time spent starting and stopping the profiler
    is kept in `stall_s`."""

    def __init__(self, out_dir, start_s, length_s):
        self.out_dir, self.start_s, self.length_s = out_dir, start_s, length_s
        self.state = "before"
        self.t_on = self.t_off = None       # perf_counter, profiler running
        self.stall_s = 0.0

    def poll(self, elapsed, force_stop=False):
        import jax
        if self.state == "before" and elapsed >= self.start_s \
                and not force_stop:
            t = time.perf_counter()
            # the Python tracer is off: it records every call of the
            # interpreter (a million events in three seconds of serving)
            # and slows the host loop that the trace is there to show
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.out_dir, profiler_options=options)
            self.t_on = time.perf_counter()
            self.stall_s += self.t_on - t
            self.state = "on"
        elif self.state == "on" and (
                force_stop or
                time.perf_counter() - self.t_on >= self.length_s):
            self.t_off = time.perf_counter()
            jax.profiler.stop_trace()
            self.stall_s += time.perf_counter() - self.t_off
            self.state = "done"

    @property
    def window_s(self):
        return (self.t_off - self.t_on) if self.state == "done" else 0.0


def annotate(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# statistics on the harness clock
# ---------------------------------------------------------------------------

def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between
    order statistics; `values` non-empty."""
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def median(values):
    return float(statistics.median(values))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def read_per_layer(spec, run):
    """{metric: value} from the readers of the cell's per-layer rows.
    A reader that finds nothing returns None and the metric is left
    out."""
    out = {}
    for row in spec.metric_rows("per_layer"):
        meta = load_json(spec.metrics_dir, row["name"] + ".json")
        reader = importlib.import_module(
            f"benchmark.readers.{meta['reader']}")
        value = reader.read(meta.get("args", {}), run)
        if value is not None:
            out[row["name"]] = {"value": float(value), "unit": row["unit"]}
    return out


def execute(spec, seed, seconds, trace, t_start, devices, log=print,
            trace_dir=None):
    """Drive one run of a cell after the device look. Returns the result
    object (the last line of a run, as a dict)."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    driver_mod = importlib.import_module(
        f"benchmark.drivers.{spec.traffic['driver']}")
    counter = CompileCounter()
    driver = driver_mod.Driver(spec, seed, seconds, devices, log=log,
                               trace=trace)
    split = driver.setup()
    warm = counter.snapshot()
    tracer = None
    if trace:
        tspec = spec.traffic.get("trace", {})
        length = min(float(tspec.get("seconds", 3.0)), 0.5 * seconds)
        tracer = Tracer(trace_dir or os.path.join(spec.root, ".bench_trace"),
                        float(tspec.get("start_share", 0.4)) * seconds,
                        length)
    setup_s = time.time() - t_start
    log(f"set-up {setup_s:.3f} s; split {json.dumps(split)}; compile "
        f"cache in set-up {json.dumps(warm)}")
    measured = driver.window(tracer)
    after = counter.snapshot()
    device = device_block(devices)
    stats = devices[0].memory_stats() or {}
    log(f"memory peak {device['memory_peak_bytes']} bytes = "
        f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} + "
        f"peak_bytes_reserved {stats.get('peak_bytes_reserved')} of "
        f"bytes_limit {stats.get('bytes_limit')}")
    driver.release()
    log(f"bytes_in_use after the program was freed: "
        f"{(devices[0].memory_stats() or {}).get('bytes_in_use')}")
    t_check = time.perf_counter()
    checks = driver.check()
    log(f"output check {time.perf_counter() - t_check:.3f} s")

    e2e = dict(measured["end_to_end"])
    e2e["setup_s"] = setup_s
    result = {"correct": all(c["value"] <= c["limit"] for c in checks),
              "attempted": measured["attempted"],
              "failed": measured["failed"]}
    if trace:
        from benchmark import trace_reduce
        run = {"spec": spec, "seconds": measured["seconds"],
               "records": measured["records"], "end_to_end": e2e,
               "tracer": tracer, "trace": None,
               "device_kind": device["kind"],
               "compile": {"compiles_in_window":
                           after["compiles"] - warm["compiles"],
                           "cache_misses_warm":
                           after["misses"] - warm["misses"],
                           "cache_misses_setup": warm["misses"],
                           "cache_hits_setup": warm["hits"]}}
        breakdown = None
        if tracer.state == "done":
            tr = trace_reduce.load(
                trace_reduce.find_xplane(tracer.out_dir),
                host_names=measured["records"].get("host_spans", ()))
            run["trace"] = tr
            device["busy_s"] = trace_reduce.busy_seconds(tr)
            device["window_s"] = tracer.window_s
            breakdown = {
                "device_ops": trace_reduce.top_ops(tr),
                "idle_gaps": trace_reduce.idle_gaps(
                    tr, trace_reduce.window_of(tr))}
        result["metrics"] = read_per_layer(spec, run)
        result["device"] = device
        if breakdown:
            result["breakdown"] = breakdown
    else:
        result["metrics"] = {
            row["name"]: {"value": float(e2e[row["name"]]),
                          "unit": row["unit"]}
            for row in spec.metric_rows("end_to_end")}
        result["device"] = device
    # the numbers compared, each beside its limit: last on stderr and
    # last in the result line
    result["compared"] = {c["name"]: {k: v for k, v in c.items()
                                      if k != "name"} for c in checks}
    return result


def print_compared(result, file=sys.stderr):
    for name, c in result["compared"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"compared {name}: {c['value']:.6g} limit {c['limit']:.6g} "
              f"{verdict}" + (f" (worst leaf {c['leaf']})"
                              if c.get("leaf") else ""), file=file)
    print(f"correct: {result['correct']}", file=file, flush=True)
