"""Training flight recorder (paddle_tpu.telemetry) on the CPU backend:
compile/execute split, MFU accounting, JSONL schema round-trip,
multi-rank Chrome trace export, monitor-counter integration, and the
tools/trace_check.py validator."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import monitor, optimizer, telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _gpt_step():
    """Tiny GPT + fused TrainStep (toy sizes)."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32, dropout=0.0,
                    use_flash_attention=False)
    model = GPTForPretraining(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters())
    step = paddle.jit.TrainStep(model, model.loss, opt)
    rs = np.random.RandomState(0)
    ids = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (2, 16)), "int32")
    lbl = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (2, 16)), "int32")
    return model, cfg, step, ids, lbl


def test_gpt_train_loop_flight_record(tmp_path):
    """Acceptance: a GPT train-step loop under TelemetryRecorder produces
    a JSONL log where step 0 shows nonzero compile_ms, steady-state steps
    show compile_ms == 0 with the cache-hit counter advancing, and every
    record carries tokens/sec and a finite MFU from model FLOPs."""
    model, cfg, step, ids, lbl = _gpt_step()
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    fpt = telemetry.model_flops_per_token(
        n_params, cfg.num_layers, cfg.hidden_size, seq_len=16)
    path = str(tmp_path / "run.jsonl")
    before = monitor.get("telemetry.compile_cache_hits")
    rec = telemetry.TelemetryRecorder(
        sink=path, tokens_per_step=2 * 16, flops_per_token=fpt,
        peak_flops=1e12)   # explicit peak: CPU has no device table entry
    with rec:   # active recorder: TrainStep auto-records, no wrapping
        for _ in range(4):
            step(ids, lbl)

    assert len(rec.records) == 4
    r0, tail = rec.records[0], rec.records[2:]
    assert r0["compile_ms"] > 0, r0
    assert r0["cache_misses"] >= 1
    for r in tail:                       # steady state
        assert r["compile_ms"] == 0.0, r
        assert r["execute_ms"] > 0
    # cache-hit counter advances across the steady-state records
    assert tail[-1]["cache_hits"] > tail[0]["cache_hits"] - 1
    assert tail[-1]["cache_hits"] >= 2
    assert monitor.get("telemetry.compile_cache_hits") >= before + 2
    for r in rec.records:
        assert r["tokens_per_sec"] > 0
        assert np.isfinite(r["mfu"]) and r["mfu"] > 0
        assert np.isfinite(r["loss"])
        assert r["step_ms"] >= r["execute_ms"]
    # JSONL round-trip matches the in-memory records and the schema
    loaded = telemetry.read_jsonl(path)
    assert loaded == rec.records
    for r in loaded:
        assert telemetry.validate_step_record(r) == []


def test_compile_split_detects_recompilation():
    """Shape change => new XLA program => nonzero compile_ms again."""
    rec = telemetry.TelemetryRecorder(track_memory=False)

    @jax.jit
    def f(x):
        return (x * 2 + 1).sum()

    step = rec.wrap(f)
    step(jnp.ones((4, 32)))
    step(jnp.ones((4, 32)))
    step(jnp.ones((8, 32)))   # recompile
    c = [r["compile_ms"] for r in rec.records]
    assert c[0] > 0 and c[1] == 0.0 and c[2] > 0, c
    assert rec.records[-1]["cache_misses"] == 2
    assert rec.records[-1]["cache_hits"] == 1


def test_step_timer_aot_split():
    """StepTimer: explicit jax.stages lower/compile cache keyed on input
    avals, deterministic hit/miss counters."""
    timer = telemetry.StepTimer(lambda x: x @ x.T)
    x = jnp.ones((16, 8))
    timer(x)
    assert timer.cache_misses == 1 and timer.last_compile_ms > 0
    timer(x)
    assert timer.cache_hits == 1 and timer.last_compile_ms == 0.0
    timer(jnp.ones((32, 8)))   # new aval -> miss
    assert timer.cache_misses == 2


def test_multi_rank_chrome_trace(tmp_path):
    """Acceptance: export_chrome_tracing output with spans from >=2
    simulated ranks loads as valid Chrome trace JSON with collective
    spans attributed to their rank."""
    from paddle_tpu.distributed import collective
    recs = []
    for rank in range(2):
        rec = telemetry.TelemetryRecorder(rank=rank, track_memory=False)
        with rec:
            with rec.step():
                collective.all_reduce(paddle.ones([4]))
                collective.barrier()
        recs.append(rec)
    # per-step comm attribution landed in the JSONL record too
    assert "collective.all_reduce" in recs[0].records[0]["collectives"]

    path = str(tmp_path / "trace.json")
    n = telemetry.export_chrome_tracing(path, recs)
    assert n >= 6   # 2 ranks x (step + all_reduce + barrier)
    trace = json.load(open(path))
    evs = trace["traceEvents"]
    coll = [e for e in evs if e.get("cat") == "collective"]
    assert {e["pid"] for e in coll} == {0, 1}
    for e in coll:
        assert e["ph"] == "X" and e["dur"] >= 0 and "ts" in e
    names = {e["name"] for e in coll}
    assert "collective.all_reduce" in names and \
        "collective.barrier" in names


def test_monitor_counters_through_recorder():
    """monitor.snapshot() still triages a run driven by the recorder."""
    base = {k: monitor.get(k) for k in
            ("telemetry.steps", "jit.train_steps", "comm.all_reduce")}
    from paddle_tpu.distributed import collective
    _, _, step, ids, lbl = _gpt_step()
    rec = telemetry.TelemetryRecorder(track_memory=False)
    with rec:
        for _ in range(2):
            step(ids, lbl)
        collective.all_reduce(paddle.ones([2]))
    snap = monitor.snapshot()
    assert snap["telemetry.steps"] >= base["telemetry.steps"] + 2
    assert snap["jit.train_steps"] >= base["jit.train_steps"] + 2
    assert snap["comm.all_reduce"] >= base["comm.all_reduce"] + 1


def test_trace_check_tool(tmp_path):
    """tools/trace_check.py passes a valid pair, fails a broken one."""
    _, _, step, ids, lbl = _gpt_step()
    jsonl = str(tmp_path / "run.jsonl")
    trace = str(tmp_path / "trace.json")
    rec = telemetry.TelemetryRecorder(sink=jsonl, track_memory=False)
    with rec:
        for _ in range(2):
            step(ids, lbl)
    rec.export_chrome_tracing(trace)

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_check.py"),
         jsonl, trace], capture_output=True, text=True, env=env,
        timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout

    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "w") as f:
        f.write(json.dumps({"kind": "step", "schema": 1}) + "\n")
        f.write("not json\n")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_check.py"),
         bad], capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 7
    assert "INVALID" in out.stdout


def test_telemetry_callback_model_fit(tmp_path):
    """hapi TelemetryCallback: Model.fit writes one record per batch."""
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
    path = str(tmp_path / "fit.jsonl")
    cb = TelemetryCallback(path, tokens_per_step=4)
    model.fit(data, epochs=2, verbose=0, callbacks=[cb])
    recs = telemetry.read_jsonl(path)
    assert len(recs) == 6   # 3 batches x 2 epochs
    assert recs[0]["compile_ms"] > 0
    assert all(telemetry.validate_step_record(r) == [] for r in recs)
    assert all(np.isfinite(r["loss"]) for r in recs)
    # the callback deactivates its recorder when fit ends, and while fit
    # ran it was context-active (so collective/h2d spans would have been
    # captured — step spans at minimum are present)
    assert telemetry.current_recorder() is None
    assert any(s["cat"] == "step" for s in cb.recorder.spans)
    # chrome export from the callback's recorder
    tpath = str(tmp_path / "fit_trace.json")
    assert cb.export(tpath) > 0
    json.load(open(tpath))


def test_unlisted_accelerator_kind_is_an_error(monkeypatch):
    """The live device's peak: None on the CPU, an error (not a silent
    MFU of 0) for an accelerator the table does not list."""
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v9 mega"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(LookupError, match="no row in the peak table"):
        telemetry.device_peak_flops()
    Dev.platform, Dev.device_kind = "cpu", "cpu"
    assert telemetry.device_peak_flops() is None


def test_mfu_accounting():
    assert telemetry.device_peak_flops("TPU v5 lite") == 197e12
    assert telemetry.device_peak_flops("TPU v5p") == 459e12
    assert telemetry.device_peak_flops("weird accelerator") is None
    # 6N + 12*L*H*S
    assert telemetry.model_flops_per_token(100, 2, 8, 4) == 600 + 12 * 64
    assert telemetry.mfu.mfu(1e12, 0.01, peak_flops=200e12) == \
        1e12 / 0.01 / 200e12
    # unknown peak (the CPU) is "no MFU", never a 0.0 measurement; a
    # degenerate window stays finite
    assert telemetry.mfu.mfu(1e12, 0.01, peak_flops=None) is None
    assert telemetry.mfu.mfu(1e12, 0.0, peak_flops=1e12) == 0.0
    # exact compiled per-step flops beat zero and include backward
    import paddle_tpu.nn as nn
    net = nn.Linear(16, 8, bias_attr=False)

    def loss_fn(t):
        return (net(t) ** 2).sum()

    got = telemetry.train_step_flops(
        loss_fn, [np.zeros((4, 16), np.float32)], model=net)
    assert got is None or got >= 2 * 4 * 16 * 8
