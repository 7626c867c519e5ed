"""Training health monitor (paddle_tpu.telemetry.health/watchdog/
metrics_http): jit-safe numerics taps on the train steps, anomaly
detection rules, hang watchdog black-box dumps, the live HTTP scrape
surface, and the tools/healthwatch.py offline analyzer."""
import json
import math
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor, optimizer, telemetry
from paddle_tpu.telemetry.health import (
    Anomaly, AnomalyDetector, HealthConfig, HealthError, HealthMonitor,
    as_monitor)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _linear_step(health=None, lr=0.05):
    net = paddle.nn.Linear(8, 4)
    opt = optimizer.SGD(learning_rate=lr, parameters=net.parameters())

    def loss_fn(x, y):
        return ((net(x) - y) ** 2).mean()

    step = paddle.jit.TrainStep(net, loss_fn, opt, health=health)
    rs = np.random.RandomState(0)
    x = paddle.to_tensor(rs.randn(4, 8).astype(np.float32))
    y = paddle.to_tensor(rs.randn(4, 4).astype(np.float32))
    return step, x, y


# ---------------------------------------------------------------------------
# numerics taps
# ---------------------------------------------------------------------------

def test_train_step_health_taps_every_k(tmp_path):
    """Acceptance: with every_k=2 the taps land grad_norm/update_ratio/
    nan_count in every 2nd JSONL record, values sane, and exactly
    n_steps/k device fetches happen (no per-step host transfer)."""
    fetches0 = monitor.get("health.fetches")
    step, x, y = _linear_step(
        health=HealthConfig(every_k=2, action="record"))
    path = str(tmp_path / "run.jsonl")
    rec = telemetry.TelemetryRecorder(sink=path, track_memory=False)
    with rec:
        for _ in range(6):
            step(x, y)
    assert monitor.get("health.fetches") == fetches0 + 3
    tapped = [r for r in rec.records if "grad_norm" in r]
    assert len(tapped) == 3
    for r in tapped:
        assert r["grad_norm"] > 0
        assert 0 < r["update_ratio"] < 1
        assert r["nan_count"] == 0 and r["inf_count"] == 0
        assert telemetry.validate_step_record(r) == []
    # round-trip: the health fields survive the JSONL
    loaded = telemetry.read_jsonl(path)
    assert [r for r in loaded if "grad_norm" in r] == tapped
    # last-seen taps exported as gauges for /metrics
    assert monitor.get_gauge("health.grad_norm") > 0


def test_taps_raise_on_nan(tmp_path):
    """A poisoned batch (inf inputs -> non-finite loss/grads) trips the
    hard NaN/Inf rule; action='raise' surfaces HealthError and the
    monitor counters advance."""
    nan0 = monitor.get("health.nan_steps")
    step, x, y = _linear_step(health=HealthConfig(
        every_k=1, action="raise", dump_on_exception=False))
    bad = paddle.to_tensor(np.full((4, 8), np.inf, np.float32))
    with pytest.raises(HealthError) as ei:
        step(bad, y)
    assert "NaN" in str(ei.value) or "Inf" in str(ei.value)
    assert monitor.get("health.nan_steps") == nan0 + 1
    assert any(a.kind == "nan" for a in step.health.anomalies)


def test_taps_warn_action():
    step, x, y = _linear_step(health=HealthConfig(
        every_k=1, action="warn", dump_on_exception=False))
    bad = paddle.to_tensor(np.full((4, 8), np.nan, np.float32))
    with pytest.warns(RuntimeWarning, match=r"\[health\]"):
        step(bad, paddle.to_tensor(np.zeros((4, 4), np.float32)))


def test_sharded_train_step_health_taps():
    """ShardedTrainStep taps: device-side stats over the GSPMD mesh."""
    import jax
    from paddle_tpu.distributed import env, sharded_train
    mesh = env.build_mesh(dp=2, devices=jax.devices()[:2])
    try:
        net = paddle.nn.Linear(8, 4)
        opt = optimizer.SGD(learning_rate=0.05,
                            parameters=net.parameters())

        def loss_fn(x, y):
            return ((net(x) - y) ** 2).mean()

        step = sharded_train.ShardedTrainStep(
            net, loss_fn, opt, mesh=mesh,
            health=HealthConfig(every_k=2, action="record"))
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.randn(4, 8).astype(np.float32))
        y = paddle.to_tensor(rs.randn(4, 4).astype(np.float32))
        rec = telemetry.TelemetryRecorder(track_memory=False)
        with rec:
            for _ in range(4):
                step(x, y)
        tapped = [r for r in rec.records if "grad_norm" in r]
        assert len(tapped) == 2
        assert all(r["grad_norm"] > 0 and r["nan_count"] == 0
                   for r in tapped)
    finally:
        env.clear_mesh()


def test_no_host_transfer_inside_traced_step():
    """Acceptance: the taps add no host sync inside the traced step —
    the FW403 astlint rule (device_get) stays silent over the tap/step
    modules, and fetch count stays at n/k (checked above)."""
    from paddle_tpu.analysis import astlint
    for mod in ("paddle_tpu/telemetry/health.py",
                "paddle_tpu/jit/__init__.py",
                "paddle_tpu/distributed/sharded_train.py"):
        findings = astlint.lint_file(os.path.join(REPO, mod))
        fw403 = [f for f in findings if f.rule == "FW403"]
        assert fw403 == [], f"{mod}: hidden host sync: {fw403}"


def test_health_arg_normalization():
    assert as_monitor(None) is None
    assert as_monitor(False) is None
    m = as_monitor(True)
    assert isinstance(m, HealthMonitor)
    assert as_monitor(m) is m
    m2 = as_monitor({"every_k": 3, "action": "record"})
    assert m2.config.every_k == 3
    with pytest.raises(TypeError):
        as_monitor(42)
    with pytest.raises(ValueError):
        HealthConfig(action="explode")


# ---------------------------------------------------------------------------
# anomaly detector rules
# ---------------------------------------------------------------------------

def _steps(losses=None, grads=None, times=None):
    n = max(len(x) for x in (losses or [], grads or [], times or [0]))
    out = []
    for i in range(n):
        r = {"kind": "step", "step": i, "compile_ms": 0.0}
        if losses is not None:
            r["loss"] = losses[i]
        if grads is not None:
            r["grad_norm"] = grads[i]
        if times is not None:
            r["execute_ms"] = times[i]
        out.append(r)
    return out


def _detect(recs, **kw):
    det = AnomalyDetector(HealthConfig(action="record", min_points=8,
                                       **kw))
    for r in recs:
        det.observe(r)
    return det


def test_detector_clean_run_no_false_positives():
    """A realistic noisy-but-healthy run must not flag anything."""
    rs = np.random.RandomState(7)
    losses = list(5.0 * np.exp(-0.01 * np.arange(200))
                  + rs.randn(200) * 0.05)
    grads = list(1.0 + rs.randn(200) * 0.08)
    times = list(100.0 + rs.randn(200) * 3.0)
    det = _detect(_steps(losses, grads, times))
    assert det.anomalies == [], [a.message for a in det.anomalies]


def test_detector_loss_spike():
    losses = [3.0 + 0.01 * (i % 5) for i in range(30)] + [40.0]
    det = _detect(_steps(losses=losses))
    kinds = det.kinds()
    assert kinds == ["loss_spike"]
    a = det.anomalies[0]
    assert a.step == 30 and a.value == 40.0 and a.z > 8


def test_detector_grad_explosion():
    grads = [1.0 + 0.02 * (i % 7) for i in range(30)] + [5e4]
    det = _detect(_steps(grads=grads))
    assert det.kinds() == ["grad_explosion"]


def test_detector_step_time_regression_and_compile_exemption():
    times = [100.0 + (i % 3) for i in range(30)] + [900.0]
    recs = _steps(times=times)
    # a recompile step is slow for a LEGITIMATE reason: exempt
    recs[15]["compile_ms"] = 5000.0
    recs[15]["execute_ms"] = 100.0
    det = _detect(recs)
    assert det.kinds() == ["step_time_regression"]
    assert det.anomalies[0].step == 30


def test_detector_nan_hard_rule_and_window_isolation():
    """NaN steps flag immediately (no window warmup) and do NOT poison
    the rolling windows — the next clean step is judged normally."""
    recs = _steps(losses=[3.0, 2.9, float("nan"), 2.8, 2.9])
    recs[2]["nan_count"] = 4
    det = _detect(recs)
    assert det.kinds() == ["nan"]
    assert det.anomalies[0].step == 2
    # detector counted only finite losses into its window
    assert len(det._loss) == 4


def test_anomaly_to_dict_json_safe():
    a = Anomaly("nan", 3, float("nan"), "boom")
    json.dumps(a.to_dict())   # non-finite value must not break dumps


# ---------------------------------------------------------------------------
# hang watchdog + black-box dumps
# ---------------------------------------------------------------------------

def test_watchdog_fires_on_stalled_step(tmp_path):
    """Acceptance: a sub-second deadline watchdog fires on an
    artificially stalled step; the black box names the open collective
    span, carries all-thread stacks, the monitor snapshot, and the
    step-record ring."""
    fires0 = monitor.get("health.watchdog_fires")
    wd = telemetry.HangWatchdog(deadline_s=0.25, dump_dir=str(tmp_path),
                                poll_s=0.05)
    wd.ring.append({"step": 41, "loss": 2.5})
    wd.start()
    try:
        wd.step_opened()
        with telemetry.span("collective.all_reduce", cat="collective",
                            axis="dp", shape="(1024,)"):
            deadline = time.time() + 5
            while not wd.dumps and time.time() < deadline:
                time.sleep(0.05)        # the artificial stall
        wd.step_closed()
    finally:
        wd.stop()
    assert wd.fires == 1 and len(wd.dumps) == 1
    assert monitor.get("health.watchdog_fires") == fires0 + 1
    box = json.load(open(wd.dumps[0]))
    assert box["kind"] == "health_blackbox"
    assert "stalled" in box["reason"]
    # the stuck collective is NAMED, with its axis attr
    names = [s["name"] for s in box["open_spans"]]
    assert "collective.all_reduce" in names
    sp = box["open_spans"][names.index("collective.all_reduce")]
    assert sp["attrs"]["axis"] == "dp" and sp["age_s"] > 0.2
    # all-thread stacks: at least main + watchdog threads visible
    assert any("MainThread" in k for k in box["threads"])
    assert any("watchdog" in k for k in box["threads"])
    for stack in box["threads"].values():
        assert isinstance(stack, list) and stack
    # monitor snapshot + ring ride along
    assert "process.uptime_s" in box["monitor"]
    assert box["ring"] == [{"step": 41, "loss": 2.5}]


def test_watchdog_single_dump_per_window(tmp_path):
    """A 10x-deadline hang produces ONE dump, and a new step re-arms."""
    wd = telemetry.HangWatchdog(deadline_s=0.1, dump_dir=str(tmp_path),
                                poll_s=0.02)
    wd.start()
    try:
        wd.step_opened()
        deadline = time.time() + 5
        while not wd.dumps and time.time() < deadline:
            time.sleep(0.02)
        time.sleep(0.3)      # several more polls past the deadline...
        assert len(wd.dumps) == 1   # ...still ONE dump for the window
        wd.step_closed()
        time.sleep(0.15)     # disarmed: no new dumps
        assert len(wd.dumps) == 1
    finally:
        wd.stop()


def test_exception_escaping_step_dumps_black_box(tmp_path):
    """The same black box fires when an exception escapes a train step
    with health enabled."""
    step, x, y = _linear_step(health=HealthConfig(
        every_k=1, action="record", dump_dir=str(tmp_path)))
    with pytest.raises(Exception):
        step("not a tensor", y)
    dumps = [f for f in os.listdir(str(tmp_path))
             if f.startswith("health_blackbox_")]
    assert len(dumps) == 1
    box = json.load(open(os.path.join(str(tmp_path), dumps[0])))
    assert "exception escaped train step" in box["reason"]
    assert any("MainThread" in k for k in box["threads"])


def test_health_error_still_disarms_watchdog(tmp_path):
    """action='raise' escalating an anomaly out of step_close must NOT
    leave the watchdog armed — the documented recover-from-spike flow
    (catch HealthError, roll back, resume) would otherwise produce a
    false 'stalled' dump and a 503 /healthz during recovery."""
    step, x, y = _linear_step(health=HealthConfig(
        every_k=1, action="raise", hang_deadline_s=30.0,
        dump_dir=str(tmp_path), dump_on_exception=False))
    bad = paddle.to_tensor(np.full((4, 8), np.inf, np.float32))
    with pytest.raises(HealthError):
        step(bad, y)
    wd = step.health.watchdog
    assert wd is not None and not wd.armed
    step.health.close()


def test_train_step_watchdog_integration(tmp_path):
    """hang_deadline_s on the health config arms a watchdog per step;
    fast steps never fire it and the thread shuts down clean."""
    step, x, y = _linear_step(health=HealthConfig(
        every_k=1, action="record", hang_deadline_s=30.0,
        dump_dir=str(tmp_path)))
    for _ in range(2):
        step(x, y)
    wd = step.health.watchdog
    assert wd is not None and not wd.armed and wd.fires == 0
    step.health.close()
    assert [f for f in os.listdir(str(tmp_path))
            if f.startswith("health_blackbox_")] == []


# ---------------------------------------------------------------------------
# /metrics scrape surface
# ---------------------------------------------------------------------------

def test_metrics_endpoint_end_to_end(tmp_path):
    """Acceptance: a live job is scrapeable — /metrics serves Prometheus
    text with counter/gauge types, /healthz answers JSON, /steps tails
    the ring."""
    step, x, y = _linear_step(health=HealthConfig(every_k=1,
                                                  action="record"))
    for _ in range(3):
        step(x, y)
    srv = telemetry.MetricsServer(health=step.health).start()
    try:
        body = urllib.request.urlopen(
            srv.url + "/metrics", timeout=10).read().decode()
        assert "# TYPE paddle_tpu_jit_train_steps counter" in body
        assert "# TYPE paddle_tpu_health_grad_norm gauge" in body
        assert "# TYPE paddle_tpu_process_uptime_s gauge" in body
        assert "paddle_tpu_last_step_grad_norm" in body
        for line in body.splitlines():
            assert line.startswith("#") or len(line.split()) == 2, line

        hz = urllib.request.urlopen(srv.url + "/healthz", timeout=10)
        assert hz.status == 200
        h = json.loads(hz.read())
        assert h["status"] == "ok"
        assert h["train_steps"] >= 3 and h["nan_steps"] >= 0
        assert "last_step" in h

        tail = json.loads(urllib.request.urlopen(
            srv.url + "/steps?n=2", timeout=10).read())
        assert len(tail) == 2 and all("grad_norm" in r for r in tail)

        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/nope", timeout=10)
        assert ei.value.code == 404
    finally:
        srv.stop()


def test_healthz_reports_stalled(tmp_path):
    """A watchdog past its deadline flips /healthz to 'stalled' + 503."""
    mon = HealthMonitor(HealthConfig(every_k=1, action="record",
                                     hang_deadline_s=0.05,
                                     dump_dir=str(tmp_path),
                                     dump_on_exception=False))
    mon.step_open()          # arm and never close
    time.sleep(0.1)
    srv = telemetry.MetricsServer(health=mon).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/healthz", timeout=10)
        assert ei.value.code == 503
        h = json.loads(ei.value.read())
        assert h["status"] == "stalled"
        assert h["watchdog"]["armed"] and h["watchdog"]["overdue_s"] > 0
    finally:
        srv.stop()
        mon.close()


# ---------------------------------------------------------------------------
# monitor registry extensions
# ---------------------------------------------------------------------------

def test_monitor_gauges_and_snapshot_identity():
    monitor.set_gauge("test.depth", 3.5)
    monitor.set_gauge("test.depth", 1.5)      # gauges move both ways
    assert monitor.get_gauge("test.depth") == 1.5
    snap = monitor.snapshot()
    assert snap["test.depth"] == 1.5
    assert snap["process.uptime_s"] > 0
    assert isinstance(snap["process.rank"], int)
    typed = monitor.snapshot_typed()
    assert "test.depth" in typed["gauge"]
    assert "test.depth" not in typed["counter"]
    with pytest.raises(ValueError):
        monitor.incr("test.ctr", -1)          # counters are monotonic
    monitor.reset("test.depth")
    assert monitor.get_gauge("test.depth", -1.0) == -1.0


# ---------------------------------------------------------------------------
# satellites: sink durability, open-span export, profiler bridge
# ---------------------------------------------------------------------------

def test_sink_flush_survives_exception(tmp_path):
    """Records written before an exception are on disk at the moment it
    propagates (no buffering loss), and the aborted step is closed as a
    record instead of dropped."""
    path = str(tmp_path / "crash.jsonl")
    rec = telemetry.TelemetryRecorder(sink=path, track_memory=False)
    with pytest.raises(RuntimeError):
        with rec:
            with rec.step():
                pass
            rec.start_step()           # left open when the crash hits
            raise RuntimeError("boom")
    loaded = telemetry.read_jsonl(path)
    assert len(loaded) == 2
    assert loaded[1]["extra"]["aborted"] is True
    assert loaded[1]["extra"]["abort_reason"] == "RuntimeError"


def test_chrome_export_closes_open_spans(tmp_path):
    """A span still open at export time lands in the trace tagged
    open=True instead of being dropped."""
    rec = telemetry.TelemetryRecorder(track_memory=False)
    path = str(tmp_path / "trace.json")
    with rec:
        cm = telemetry.span("collective.stuck_all_gather",
                            cat="collective", axis="mp")
        cm.__enter__()
        try:
            n = rec.export_chrome_tracing(path)
        finally:
            cm.__exit__(None, None, None)
    assert n == 1
    evs = json.load(open(path))["traceEvents"]
    stuck = [e for e in evs if e.get("name") ==
             "collective.stuck_all_gather"]
    assert len(stuck) == 1
    assert stuck[0]["args"]["open"] is True and stuck[0]["dur"] > 0


def test_profiler_record_event_bridges_into_telemetry():
    """Satellite: legacy profiler RecordEvent spans land in the active
    TelemetryRecorder (one merged chrome trace), exactly once even when
    the profiler table is also enabled."""
    from paddle_tpu import profiler
    rec = telemetry.TelemetryRecorder(track_memory=False)
    with rec:
        with profiler.RecordEvent("legacy_region"):
            pass
        profiler.start_profiler()
        try:
            with telemetry.span("modern_region"):
                pass
            with profiler.RecordEvent("legacy_region2"):
                pass
        finally:
            table = profiler.stop_profiler(print_table=False)
    names = [s["name"] for s in rec.spans]
    assert names.count("legacy_region") == 1
    assert names.count("legacy_region2") == 1
    assert names.count("modern_region") == 1   # no double-record
    # and the reverse bridge still holds: telemetry.span landed in the
    # profiler table while it was enabled
    assert "modern_region" in table


def test_open_spans_registry_threads():
    """open_spans() names spans across threads (what the dump reads)."""
    seen = {}
    go = threading.Event()
    done = threading.Event()

    def worker():
        with telemetry.span("worker_io", cat="io"):
            go.set()
            done.wait(5)

    t = threading.Thread(target=worker, name="io-thread")
    t.start()
    go.wait(5)
    try:
        spans = telemetry.open_spans()
        mine = [s for s in spans if s["name"] == "worker_io"]
        assert len(mine) == 1 and mine[0]["thread"] == "io-thread"
    finally:
        done.set()
        t.join(5)
    assert not [s for s in telemetry.open_spans()
                if s["name"] == "worker_io"]


# ---------------------------------------------------------------------------
# hapi callback + pipeline hook
# ---------------------------------------------------------------------------

def test_telemetry_callback_health(tmp_path):
    """TelemetryCallback(health=...) runs record-level rules per batch
    inside Model.fit and leaves no armed watchdog behind."""
    import paddle_tpu.nn as nn
    from paddle_tpu.hapi.callbacks import TelemetryCallback
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    model = paddle.Model(net)
    model.prepare(optimizer.SGD(learning_rate=0.01,
                                parameters=net.parameters()),
                  nn.CrossEntropyLoss())
    rs = np.random.RandomState(0)
    x = rs.randn(12, 8).astype(np.float32)
    y = rs.randint(0, 4, (12, 1)).astype(np.int64)
    data = [(x[i:i + 4], y[i:i + 4]) for i in range(0, 12, 4)]
    cb = TelemetryCallback(
        str(tmp_path / "fit.jsonl"),
        health=HealthConfig(every_k=1, action="record",
                            hang_deadline_s=60.0,
                            dump_dir=str(tmp_path)))
    model.fit(data, epochs=2, verbose=0, callbacks=[cb])
    assert cb.health.detector._n >= 6       # every batch judged
    assert cb.health.anomalies == []
    wd = cb.health.watchdog
    assert wd is not None and not wd.armed
    assert len(cb.health.ring) >= 6


def test_pipeline_train_batch_health():
    """PipelineParallel.health taps the accumulation path: loss + raw
    grad stats fetched on the every_k cadence."""
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed.pipeline import (PipelineLayer,
                                                 PipelineParallel)
    layers = [nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 4)]
    pipe = PipelineLayer(layers=layers, num_stages=1,
                         loss_fn=nn.MSELoss())
    pp = PipelineParallel(pipe, None, None)
    pp.health = HealthConfig(every_k=2, action="record")
    opt = optimizer.SGD(learning_rate=0.01,
                        parameters=pipe.parameters())
    rs = np.random.RandomState(0)
    x = paddle.to_tensor(rs.randn(4, 8).astype(np.float32))
    y = paddle.to_tensor(rs.randn(4, 4).astype(np.float32))
    rec = telemetry.TelemetryRecorder(track_memory=False)
    with rec:
        for _ in range(4):
            pp.train_batch((x, y), opt)
    tapped = [r for r in rec.records if "grad_norm" in r]
    assert len(tapped) == 2
    assert all(r["grad_norm"] > 0 and r["nan_count"] == 0
               for r in tapped)
    assert pp._health_mon.anomalies == []


# ---------------------------------------------------------------------------
# tools/healthwatch.py
# ---------------------------------------------------------------------------

def _healthwatch_main(args, capsys):
    """Run tools/healthwatch.py in-process (a subprocess would pay a
    full fresh jax import per invocation); returns (rc, stdout)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "healthwatch", os.path.join(REPO, "tools", "healthwatch.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main(args)
    return rc, capsys.readouterr().out


def test_healthwatch_specimen_selfcheck(capsys):
    """Acceptance: the checked-in anomalous specimen trips every
    planted family (exactly the ci.sh stage-4 invocation, exercised as
    a real subprocess once); asking for a family that cannot fire
    exits 9; gate mode on the same file exits 5 naming each kind."""
    spec = os.path.join(REPO, "tools", "specimens",
                        "health_anomalous.jsonl")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "healthwatch.py"),
         spec, "--expect",
         "nan,loss_spike,grad_explosion,step_time_regression"],
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "selfcheck OK" in out.stdout
    # gate mode on the same file: findings -> exit 5
    rc, text = _healthwatch_main([spec], capsys)
    assert rc == 5
    for kind in ("nan", "loss_spike", "grad_explosion",
                 "step_time_regression"):
        assert f"[{kind}]" in text
    # a family the specimen can't produce fails the selfcheck
    rc, _ = _healthwatch_main([spec, "--expect", "comm_bw_degraded"],
                              capsys)
    assert rc == 9


def test_healthwatch_clean_run_and_empty_file(tmp_path, capsys):
    """A clean training JSONL exits 0; an empty file fails loudly."""
    step, x, y = _linear_step(
        health=HealthConfig(every_k=2, action="record"))
    path = str(tmp_path / "clean.jsonl")
    rec = telemetry.TelemetryRecorder(sink=path, track_memory=False)
    with rec:
        for _ in range(6):
            step(x, y)
    rc, text = _healthwatch_main(
        [path, "--json", str(tmp_path / "report.json")], capsys)
    assert rc == 0, text
    assert "clean" in text
    report = json.load(open(str(tmp_path / "report.json")))
    assert report["files"][path]["n_step_records"] == 6

    empty = str(tmp_path / "empty.jsonl")
    open(empty, "w").close()
    rc, text = _healthwatch_main([empty], capsys)
    assert rc == 5
    assert "no records" in text
