"""Compile observatory (paddle_tpu.telemetry.compile_obs) on the CPU
backend: signature cause-diffs, recompile-storm rule, compiled-HBM
accounting + SH206 cross-check, cost-model drift, StepTimer/JSONL
integration, /metrics exposure, and the tools/compile_report.py +
tools/trace_check.py offline halves."""
import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor, optimizer, telemetry
from paddle_tpu.telemetry import compile_obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECIMEN = os.path.join(REPO, "tools", "specimens", "compile_thrash.jsonl")


def _mlp_step():
    """Tiny 2-layer MLP TrainStep: same dispatch wiring as the GPT
    bench config but ~10x cheaper to compile, so the thrash loops below
    stay cheap inside tier-1."""
    from paddle_tpu import nn
    from paddle_tpu.nn import functional as F

    class MLP(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(16, 32)
            self.fc2 = nn.Linear(32, 16)

        def forward(self, x):
            return self.fc2(F.gelu(self.fc1(x)))

    model = MLP()
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters())
    step = paddle.jit.TrainStep(
        model, lambda x, y: F.mse_loss(model(x), y), opt)
    return model, step


def _batch(b, d=16, seed=0):
    rs = np.random.RandomState(seed)
    x = paddle.to_tensor(rs.rand(b, d).astype(np.float32))
    y = paddle.to_tensor(rs.rand(b, d).astype(np.float32))
    return x, y


# ---------------------------------------------------------------------------
# signatures + cause diffs (pure, no compilation)
# ---------------------------------------------------------------------------

def test_signature_diff_shape_names_arg_and_axis():
    a = compile_obs.signature_of((jnp.zeros((32, 128), jnp.int32),),
                                 arg_names=("input_ids",))
    b = compile_obs.signature_of((jnp.zeros((48, 128), jnp.int32),),
                                 arg_names=("input_ids",))
    causes = compile_obs.diff_signatures(a, b)
    assert len(causes) == 1
    assert "input_ids" in causes[0]
    assert "axis 0: 32→48" in causes[0]


def test_signature_diff_dtype_weaktype_static_donate():
    x32 = jnp.zeros((4,), jnp.float32)
    a = compile_obs.signature_of((x32, jnp.float32(0.1)),
                                 arg_names=("x", "lr"),
                                 static={"amp": False}, donate=(0,))
    # dtype flip on x
    b = compile_obs.signature_of((x32.astype(jnp.bfloat16),
                                  jnp.float32(0.1)),
                                 arg_names=("x", "lr"),
                                 static={"amp": False}, donate=(0,))
    causes = compile_obs.diff_signatures(a, b)
    assert any("dtype float32→bfloat16" in c and "`x`" in c
               for c in causes), causes
    # weak_type flip on lr (python float traces weak)
    c_ = compile_obs.signature_of((x32, 0.1), arg_names=("x", "lr"),
                                  static={"amp": False}, donate=(0,))
    causes = compile_obs.diff_signatures(a, c_)
    assert any("weak_type flip on `lr`" in c for c in causes), causes
    # static-arg change
    d = compile_obs.signature_of((x32, jnp.float32(0.1)),
                                 arg_names=("x", "lr"),
                                 static={"amp": True}, donate=(0,))
    causes = compile_obs.diff_signatures(a, d)
    assert any("static `amp` False→True" in c for c in causes), causes
    # donate-set change
    e = compile_obs.signature_of((x32, jnp.float32(0.1)),
                                 arg_names=("x", "lr"),
                                 static={"amp": False}, donate=())
    causes = compile_obs.diff_signatures(a, e)
    assert any("donate set (0,)→()" in c for c in causes), causes


def test_signature_equal_key_and_unexplained_miss():
    x = jnp.zeros((4,), jnp.float32)
    a = compile_obs.signature_of((x,))
    b = compile_obs.signature_of((jnp.ones((4,), jnp.float32),))
    assert a == b and a.key == b.key      # values don't recompile
    causes = compile_obs.diff_signatures(a, b)
    assert causes and "signature unchanged" in causes[0]


# ---------------------------------------------------------------------------
# in-flight observatory over a real TrainStep
# ---------------------------------------------------------------------------

def test_trainstep_recompile_causes_storm_and_memory():
    """Acceptance: a shape-thrashing loop produces recompile records
    whose causes name the changed argument and axis, trips the storm
    rule, carries the memory snapshot, and advances compile.* counters."""
    _, step = _mlp_step()
    before = monitor.get("compile.recompiles")
    obs = telemetry.CompileObservatory(action="record")
    with obs:
        for b in (2, 3, 4, 5, 6, 7):      # 5 recompiles
            step(*_batch(b))
    fam = [r for r in obs.records if r["fn"].startswith("TrainStep[")]
    assert len(fam) == 6
    assert "cause" not in fam[0]          # first compile: no cause
    for k, r in enumerate(fam[1:], start=2):
        assert r["n_compiles"] == k
        assert any("`batch[0]`" in c and "axis 0" in c
                   for c in r["cause"]), r["cause"]
    # storm rule fired once (5 recompiles well inside the window)
    assert "recompile_storm" in obs.detector.kinds()
    assert monitor.get("compile.storms") >= 1
    assert monitor.get("compile.recompiles") >= before + 5
    # memory observatory: snapshot fields present on every compile
    for r in fam:
        hbm = r["hbm"]
        for key in ("arg_bytes", "out_bytes", "temp_bytes", "code_bytes",
                    "total_bytes"):
            assert key in hbm and hbm[key] >= 0
        assert hbm["arg_bytes"] > 0
        assert r["cost"]["flops"] > 0
        assert r["hlo_ops"] and r["hlo_ops"][0]["count"] > 0
    assert monitor.get_gauge("compile.hbm_total_bytes") > 0


def test_clean_run_stays_silent_and_caches():
    """Fixed shapes: one attributed compile, AOT hits after, no storm."""
    _, step = _mlp_step()
    obs = telemetry.CompileObservatory(action="record")
    hits_before = monitor.get("compile.aot_hits")
    with obs:
        ids, lbl = _batch(2)
        for _ in range(4):
            step(ids, lbl)
    fam = [r for r in obs.records if r["fn"].startswith("TrainStep[")]
    assert len(fam) == 1
    assert obs.detector.kinds() == []
    assert monitor.get("compile.aot_hits") >= hits_before + 3


@pytest.mark.slow
def test_observatory_dispatch_matches_plain_dispatch():
    """The AOT path must train identically to plain jit dispatch."""
    paddle.seed(7)
    _, s1 = _mlp_step()
    paddle.seed(7)
    _, s2 = _mlp_step()
    ids, lbl = _batch(2)
    plain = [float(s1(ids, lbl)) for _ in range(3)]
    paddle.seed(7)   # reseed so rng splits line up
    with telemetry.CompileObservatory(action="record"):
        paddle.seed(7)
        observed = [float(s2(ids, lbl)) for _ in range(3)]
    np.testing.assert_allclose(plain, observed, rtol=1e-5)


def test_hbm_projection_drift_on_misbudgeted_config():
    """A deliberately wrong static projection (far below what the
    executable actually needs) fires the SH206 cross-check."""
    _, step = _mlp_step()
    obs = telemetry.CompileObservatory(action="record", hbm_projection=1024)
    with obs:
        step(*_batch(2))
    kinds = obs.detector.kinds()
    assert "hbm_projection_drift" in kinds
    rec = [r for r in obs.records if r["fn"].startswith("TrainStep[")][0]
    assert rec["hbm_projected_bytes"] == 1024
    assert rec["hbm"]["total_bytes"] > 1024
    # the accurate-projection silent case is pinned (synthetically) by
    # test_detector_drift_latch below — no second compile needed here


def test_project_train_step_hbm_feeds_observatory():
    from paddle_tpu.analysis.sharding_lint import project_train_step_hbm
    _, step = _mlp_step()
    report, findings = project_train_step_hbm(step)
    assert report["per_device"]["total_bytes"] > 0
    assert findings == []
    obs = telemetry.CompileObservatory(action="record",
                                       hbm_projection=report)
    assert obs.hbm_projection == report["per_device"]["total_bytes"]


def test_flops_drift_against_analytic_table():
    """An analytic FLOPs number wildly off the compiled cost analysis
    fires flops_drift; the true compiled number stays silent."""
    _, step = _mlp_step()
    obs = telemetry.CompileObservatory(action="record",
                                       analytic_flops=1e18)
    with obs:
        step(*_batch(2))
    assert "flops_drift" in obs.detector.kinds()
    rec = [r for r in obs.records if r["fn"].startswith("TrainStep[")][0]
    assert rec["analytic_flops"] == 1e18
    assert rec["cost"]["flops"] > 0
    # the matching-FLOPs silent case rides the synthetic detector tests


def test_flops_drift_helper():
    from paddle_tpu.telemetry.mfu import flops_drift
    assert flops_drift(150.0, 100.0) == pytest.approx(0.5)
    assert flops_drift(None, 100.0) is None
    assert flops_drift(100.0, 0.0) is None


@pytest.mark.slow
def test_sharded_step_records_compiles():
    """ShardedTrainStep dispatch rides the same observatory."""
    from paddle_tpu import distributed as dist
    from paddle_tpu import nn
    from paddle_tpu.distributed import env
    from paddle_tpu.nn import functional as F

    dist.build_mesh(dp=8)
    try:
        model = nn.Linear(16, 16)
        dist.shard_model(model)
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=model.parameters())
        step = dist.ShardedTrainStep(
            model, lambda a, b: F.mse_loss(model(a), b), opt)
        rs = np.random.RandomState(0)
        obs = telemetry.CompileObservatory(action="record")
        with obs:
            for b in (8, 16):
                x = paddle.to_tensor(
                    rs.rand(b, 16).astype(np.float32))
                y = paddle.to_tensor(
                    rs.rand(b, 16).astype(np.float32))
                step(x, y)
        fam = [r for r in obs.records
               if r["fn"].startswith("ShardedTrainStep[")]
        assert len(fam) == 2
        assert any("`batch[0]`" in c for c in fam[1]["cause"])
        assert fam[0]["hbm"]["arg_bytes"] > 0
    finally:
        env.clear_mesh()


@pytest.mark.slow
def test_pipeline_train_batch_records_compiles():
    """PipelineParallel.train_batch's 1F1B executor rides the
    observatory too (fused path, donated stacked params)."""
    from paddle_tpu import distributed as dist
    from paddle_tpu import nn
    from paddle_tpu.distributed import env as dist_env
    from paddle_tpu.distributed.pipeline import LayerDesc
    from paddle_tpu.nn import functional as F

    class Block(nn.Layer):
        def __init__(self, d):
            super().__init__()
            self.fc = nn.Linear(d, d)

        def forward(self, x):
            return x + F.gelu(self.fc(x))

    def loss_fn(out, y):
        return F.mse_loss(out, y)

    dist.build_mesh(pp=2, devices=jax.devices()[:2])
    try:
        paddle.seed(3)
        layer = dist.PipelineLayer([LayerDesc(Block, 8)
                                    for _ in range(4)],
                                   num_stages=2, loss_fn=loss_fn)
        strategy = dist.DistributedStrategy()
        strategy.pipeline_configs = {"accumulate_steps": 2}
        pp = dist.PipelineParallel(layer, strategy=strategy)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=layer.parameters())
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.rand(4, 8).astype(np.float32))
        y = paddle.to_tensor(rs.rand(4, 8).astype(np.float32))
        obs = telemetry.CompileObservatory(action="record")
        with obs:
            pp.train_batch((x, y), opt)
        fam = [r for r in obs.records
               if r["fn"] == "PipelineParallel.train_batch"]
        assert len(fam) == 1
        assert fam[0]["hbm"]["arg_bytes"] > 0
    finally:
        dist_env.clear_mesh()


def test_metrics_endpoint_exposes_compile_gauges():
    """Acceptance: /metrics exposes compile.hbm_total_bytes and
    compile.count after one compiled step."""
    _, step = _mlp_step()
    with telemetry.CompileObservatory(action="record"):
        step(*_batch(2))
    srv = telemetry.MetricsServer(port=0).start()
    try:
        with urllib.request.urlopen(f"{srv.url}/metrics") as r:
            text = r.read().decode()
        assert "paddle_tpu_compile_count" in text
        line = [ln for ln in text.splitlines()
                if ln.startswith("paddle_tpu_compile_hbm_total_bytes ")]
        assert line and float(line[0].split()[1]) > 0
        with urllib.request.urlopen(f"{srv.url}/healthz") as r:
            body = json.loads(r.read().decode())
        assert body["compiles"] >= 1
    finally:
        srv.stop()


def test_step_timer_records_cache_and_memory(tmp_path):
    """Satellite: StepTimer lands its AOT cache counters and the last
    memory_analysis() bytes in the step JSONL it already emits."""
    path = str(tmp_path / "timer.jsonl")
    rec = telemetry.TelemetryRecorder(sink=path, track_memory=False)

    def f(x):
        return (x * 2.0).sum()

    timer = telemetry.StepTimer(f, recorder=rec)
    timer(jnp.ones((8, 8)))
    timer(jnp.ones((8, 8)))
    loaded = telemetry.read_jsonl(path)
    assert [r["cache_misses"] for r in loaded] == [1, 1]
    assert [r["cache_hits"] for r in loaded] == [0, 1]
    hbm = loaded[0]["extra"]["hbm"]
    assert hbm["arg_bytes"] > 0 and "total_bytes" in hbm
    for r in loaded:
        assert telemetry.validate_step_record(r) == []


def test_step_timer_compiles_attributed_not_unattributed():
    """Under an observatory, StepTimer's own lower/compile must land as
    an attributed StepTimer family record, not in the (jax) stream."""
    def g(x):
        return x + 1

    obs = telemetry.CompileObservatory(action="record")
    with obs:
        timer = telemetry.StepTimer(g)
        timer(jnp.ones((4,)))
        timer(jnp.ones((6,)))
    fams = [r["fn"] for r in obs.records]
    assert sum(1 for f in fams if f.startswith("StepTimer:g")) == 2
    st = [r for r in obs.records if r["fn"].startswith("StepTimer:g")]
    assert any("axis 0: 4→6" in c for c in st[1]["cause"])


def test_unattributed_jax_compiles_are_recorded():
    """A stray jax.jit compiled while the observatory is active surfaces
    through the jax.monitoring bridge as an untracked record."""
    before = monitor.get("compile.unattributed")
    obs = telemetry.CompileObservatory(action="record")
    with obs:
        jax.jit(lambda x: x * 3.0)(jnp.ones((5, 5)))
    un = [r for r in obs.records if r.get("untracked")]
    assert un and un[0]["fn"] == "(jax)"
    assert monitor.get("compile.unattributed") >= before + 1


# ---------------------------------------------------------------------------
# detector rules offline (synthetic records; no compilation)
# ---------------------------------------------------------------------------

def _compile_rec(step, n, cause=None, fn="TrainStep[M]", **kw):
    from paddle_tpu.telemetry.sink import make_compile_record
    return make_compile_record(fn=fn, step=step, compile_ms=100.0,
                               n_compiles=n, cause=cause, **kw)


def test_detector_storm_rule_and_muzzle():
    from paddle_tpu.telemetry.health import AnomalyDetector, HealthConfig
    det = AnomalyDetector(HealthConfig(storm_compiles=3,
                                       storm_window_steps=10))
    found = []
    for i in range(6):
        found += det.observe(_compile_rec(i, i + 2, cause=["arg `b` x"]))
    storms = [a for a in found if a.kind == "recompile_storm"]
    assert len(storms) == 1        # muzzled within the window
    # first compiles (n_compiles == 1) never count toward a storm
    det2 = AnomalyDetector(HealthConfig(storm_compiles=3,
                                        storm_window_steps=10))
    for i in range(6):
        assert det2.observe(_compile_rec(i, 1, fn=f"F{i}")) == []


def test_detector_drift_latch():
    from paddle_tpu.telemetry.health import AnomalyDetector, HealthConfig
    det = AnomalyDetector(HealthConfig(hbm_drift_tol=0.15))
    hbm = {"total_bytes": 200}
    r = _compile_rec(0, 1, hbm=hbm, hbm_projected_bytes=100)
    assert [a.kind for a in det.observe(r)] == ["hbm_projection_drift"]
    # same drifting program again: latched, no re-fire
    assert det.observe(_compile_rec(1, 2, cause=["c"], hbm=hbm,
                                    hbm_projected_bytes=100)) == []
    # recovery re-arms
    ok = _compile_rec(2, 3, cause=["c"], hbm={"total_bytes": 100},
                      hbm_projected_bytes=100)
    assert det.observe(ok) == []
    again = _compile_rec(3, 4, cause=["c"], hbm=hbm,
                         hbm_projected_bytes=100)
    assert [a.kind for a in det.observe(again)] == ["hbm_projection_drift"]


# ---------------------------------------------------------------------------
# offline tools
# ---------------------------------------------------------------------------

def _report_main(argv):
    """Run tools/compile_report.py in-process (same module the CLI
    executes; subprocess spin-up is pinned once by the slow test)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import compile_report
    return compile_report.main(argv)


def test_compile_report_selfcheck_on_specimen(capsys):
    rc = _report_main(["--selfcheck", SPECIMEN, "--expect-arg", "batch"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "storm fired" in out


def test_compile_report_gate_flags_thrash_and_passes_clean(tmp_path,
                                                           capsys):
    # gate mode on the thrash specimen: exit 6 naming the storm
    rc = _report_main([SPECIMEN])
    out = capsys.readouterr().out
    assert rc == 6, out
    assert "recompile_storm" in out
    # a clean single-compile ledger passes
    clean = tmp_path / "clean.jsonl"
    with open(clean, "w") as f:
        f.write(json.dumps(_compile_rec(0, 1)) + "\n")
    assert _report_main([str(clean)]) == 0
    # a compile-FREE file fails the gate: a dead observatory must not
    # green-light the run it stopped describing (trace_check stance)
    dead = tmp_path / "dead.jsonl"
    with open(dead, "w") as f:
        f.write(json.dumps({"schema": 1, "kind": "step", "rank": 0,
                            "step": 0, "step_ms": 1.0, "compile_ms": 0.0,
                            "execute_ms": 1.0}) + "\n")
    capsys.readouterr()
    assert _report_main([str(dead)]) == 6
    assert "no compile records" in capsys.readouterr().out


def test_compile_report_selfcheck_fails_without_storm(tmp_path, capsys):
    quiet = tmp_path / "quiet.jsonl"
    with open(quiet, "w") as f:
        f.write(json.dumps(_compile_rec(0, 1)) + "\n")
    rc = _report_main(["--selfcheck", str(quiet)])
    assert rc == 9
    assert "SELFCHECK FAILED" in capsys.readouterr().err


@pytest.mark.slow
def test_compile_report_cli_subprocess():
    """The actual CI invocation (fresh interpreter, argv handling)."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "compile_report.py"),
         "--selfcheck", SPECIMEN, "--expect-arg", "batch"],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout + out.stderr
    assert "storm fired" in out.stdout


def test_trace_check_compile_record_rules(tmp_path):
    """Recompile-without-cause and non-monotonic steps fail validation;
    the specimen (causes present) passes."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from trace_check import check_pair
    problems, stats = check_pair(SPECIMEN)
    assert problems == []
    assert stats["n_compiles"] == 9
    bad = tmp_path / "bad.jsonl"
    with open(bad, "w") as f:
        f.write(json.dumps(_compile_rec(0, 1)) + "\n")
        f.write(json.dumps(_compile_rec(5, 2)) + "\n")      # no cause
        f.write(json.dumps(_compile_rec(3, 3,                # step goes back
                                        cause=["arg `b` x"])) + "\n")
    problems, _ = check_pair(str(bad))
    assert any("carries no cause" in p for p in problems)
    assert any("non-monotonic" in p for p in problems)


def test_specimen_validates_and_detector_sees_all_families():
    """The checked-in thrash specimen must stay schema-valid and trip
    storm + both drift cross-checks (healthwatch selfcheck pattern)."""
    from paddle_tpu.telemetry.health import AnomalyDetector, HealthConfig
    from paddle_tpu.telemetry.sink import read_jsonl, validate_step_record
    records = read_jsonl(SPECIMEN)
    for r in records:
        assert validate_step_record(r) == []
    det = AnomalyDetector(HealthConfig(action="record"))
    for r in records:
        det.observe(r)
    kinds = det.kinds()
    for want in ("recompile_storm", "hbm_projection_drift", "flops_drift"):
        assert want in kinds, kinds


def test_hapi_flops_compiled_works_and_does_not_swallow():
    """flops_compiled rides cost_model._cost_analysis: real numbers on
    CPU, {} for an executable that carries no analysis, and a backend
    error propagates (swallowed zeros read as "no FLOPs" downstream)."""
    from paddle_tpu import nn
    from paddle_tpu.hapi.flops import flops_compiled
    from paddle_tpu.cost_model import _cost_analysis

    class Refuses:
        def cost_analysis(self):
            raise RuntimeError("backend refuses")

    class Empty:
        def cost_analysis(self):
            return None

    with pytest.raises(RuntimeError, match="backend refuses"):
        _cost_analysis(Refuses())
    assert _cost_analysis(Empty()) == {}
    net = nn.Linear(8, 4)
    got = flops_compiled(net, [np.zeros((2, 8), np.float32)])
    assert got["flops"] > 0
