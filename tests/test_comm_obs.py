"""Mesh observatory (paddle_tpu/telemetry/comm_obs + tools/commlab.py):
measured collective latencies on the 8-virtual-device CPU mesh,
bandwidth attribution against the planner's peak tables, the persistent
comm DB contract, comm-cost calibration feedback into the planner, the
comm_bw_degraded / straggler anomaly rules (in-flight AND in the
healthwatch replay), kind=commbench schema + trace_check cross-rules
both ways, per-step comm_ms/comm_frac attribution, the reqtrace
collective/transfer span vocabulary, and the comm_audit wire-byte
honesty leg."""
import itertools
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from paddle_tpu import telemetry
from paddle_tpu.analysis import comm_audit
from paddle_tpu.distributed import env
from paddle_tpu.planner import plan
from paddle_tpu.cost_model import estimate_layout_cost
from paddle_tpu.models.gpt import gpt_tiny_config
from paddle_tpu.planner.planner import calibration_from_comm_records
from paddle_tpu.telemetry import comm_obs, sink
from paddle_tpu.telemetry.health import AnomalyDetector, HealthConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
import trace_check  # noqa: E402


@pytest.fixture(autouse=True)
def _clear_mesh():
    yield
    env.clear_mesh()


def _fake_clock(step_s=0.5):
    """Injectable deterministic clock: every call advances step_s, so a
    timed interval is exactly step_s seconds regardless of host load."""
    c = itertools.count()
    return lambda: next(c) * step_s


# ---------------------------------------------------------------------------
# sweep plumbing: payload ladder, DB key, sweep programs
# ---------------------------------------------------------------------------

def test_payload_sweep_ladder_and_db_key():
    rungs = comm_obs.payload_sweep(256 * 1024, 1024 * 1024)
    assert rungs == [256 * 1024, 512 * 1024, 1024 * 1024]
    assert comm_obs.db_key("psum", 4, 65536, "cpu") == "psum|ax4|65536|cpu"


def test_sweep_program_payloads_and_primitives():
    """Every sweep op builds a program whose per-device operand is the
    rounded payload, and whose jaxpr contains exactly the collective
    primitive the op names (the identity the comm_audit third leg
    leans on)."""
    mesh = env.build_mesh(dp=2, mp=4)
    axis_sizes = {a: int(mesh.shape[a]) for a in mesh.axis_names}
    for op in comm_obs.SWEEP_OPS:
        for axis in ("dp", "mp"):
            fn, sds, _spec, actual = comm_obs.sweep_program(
                op, axis, mesh, 16384)
            # the payload only ever rounds along the sharded dim
            assert actual % (128 * 4) == 0 and actual > 0
            acct = comm_audit.trace_collective_wire_bytes(
                fn, jax.ShapeDtypeStruct(sds.shape, sds.dtype),
                axis_sizes=axis_sizes)
            prims = set(acct) & set(comm_obs.SWEEP_OPS)
            assert prims == {op}, (op, axis, sorted(acct))
    with pytest.raises(ValueError):
        comm_obs.sweep_program("bcast", "dp", mesh, 16384)


# ---------------------------------------------------------------------------
# attribution: hand-computed fractions, clamp, CPU exemption
# ---------------------------------------------------------------------------

def test_attribution_hand_computed():
    """psum of a 1 MiB operand over n=4 at 0.05 ms against a 100 GB/s
    peak: every derived field recomputed by hand (the same numbers the
    checked-in degraded specimen carries)."""
    a = comm_obs.attribution("psum", 1 << 20, 4, 0.05, peak_bw=1e11)
    assert a["wire_bytes"] == 2 * 3 / 4 * (1 << 20)      # ring 2(n-1)/n
    assert a["achieved_bw"] == pytest.approx(1572864 / 5e-5)
    assert a["bw_frac"] == pytest.approx(0.3145728)
    assert a["predicted_ms"] == pytest.approx(0.01572864)
    assert a["medium"] == "ici"


def test_attribution_clamp_and_cpu_exemption():
    # impossibly fast measurement: the fraction clamps at 1.0
    fast = comm_obs.attribution("all_gather", 1 << 20, 4, 1e-6,
                                peak_bw=1e9)
    assert fast["bw_frac"] == 1.0
    # CPU: no entry in the peak tables -> no roofline, no prediction,
    # but the raw achieved bandwidth still computes from the record
    cpu = comm_obs.attribution("psum", 65536, 2, 0.5, device_kind="cpu")
    assert cpu["peak_bw"] is None and cpu["bw_frac"] is None
    assert cpu["predicted_ms"] is None and cpu["medium"] is None
    assert cpu["achieved_bw"] == pytest.approx(65536 / 5e-4)
    # wire-byte convention is comm_audit's, not a private copy
    assert comm_obs.wire_bytes("ppermute", 1000, 8) == 1000.0
    assert comm_obs.wire_bytes("all_gather", 1000, 8) == 875.0


# ---------------------------------------------------------------------------
# measurement: deterministic under an injected clock, schema-valid out
# ---------------------------------------------------------------------------

def test_measure_collective_fake_clock_deterministic():
    """With an injected counter clock every timed interval is exactly
    one tick: compile_ms and time_ms come out bit-deterministic, and
    the emitted record passes the sink validator and the trace_check
    cross-rules."""
    mesh = env.build_mesh(dp=2, mp=4)
    res = comm_obs.measure_collective(
        "psum", "mp", mesh=mesh, payload_bytes=16384,
        warmup=1, k=3, clock=_fake_clock(0.25))
    assert res.time_ms == 250.0          # one tick per timed sample
    assert res.compile_ms == 250.0       # one tick around lower/compile
    assert res.axis_size == 4 and res.backend == "cpu"
    assert res.db_ms is None             # no DB flag -> no reference
    rec = res.to_record()
    assert sink.validate_step_record(rec) == []
    assert trace_check.check_commbench_records([rec], "mem") == []
    # gauges mirrored for /metrics
    from paddle_tpu import monitor
    assert monitor.get_gauge("comm.psum.ms") == 250.0


def test_sweep_mesh_covers_every_op_and_axis():
    mesh = env.build_mesh(dp=2, mp=4)
    results = comm_obs.sweep_mesh(mesh=mesh, payloads=[8192],
                                  warmup=0, k=1, clock=_fake_clock(0.01))
    got = {(r.op, r.axis) for r in results}
    assert got == {(op, ax) for op in comm_obs.SWEEP_OPS
                   for ax in ("dp", "mp")}
    recs = [r.to_record() for r in results]
    assert all(sink.validate_step_record(r) == [] for r in recs)
    assert trace_check.check_commbench_records(recs, "mem") == []


# ---------------------------------------------------------------------------
# schema + cross-rules, both ways
# ---------------------------------------------------------------------------

def test_commbench_schema_rejects_bad_records():
    good = sink.make_commbench_record(
        op="psum", axis="dp", axis_size=2, payload_bytes=8192,
        backend="cpu", time_ms=0.5)
    assert sink.validate_step_record(good) == []
    bad_op = dict(good, op="bcast")
    assert any("unknown commbench op" in p
               for p in sink.validate_step_record(bad_op))
    bad_frac = dict(good, bw_frac=1.5)
    assert sink.validate_step_record(bad_frac) != []
    bad_time = dict(good, time_ms=-1.0)
    assert sink.validate_step_record(bad_time) != []
    # a NaN timing becomes null + an error note, never a silent NaN
    nan = sink.make_commbench_record(
        op="psum", axis="dp", axis_size=2, payload_bytes=8192,
        backend="cpu", time_ms=float("nan"))
    assert nan["time_ms"] is None and nan["error"] == "non-finite time_ms"
    assert sink.validate_step_record(nan) == []


def test_commbench_cross_rules_catch_doctored_claims(tmp_path):
    """The trace_check cross-rules must reject a record whose derived
    claims don't follow from its own inputs — and accept the honest
    version of the same row."""
    honest = sink.make_commbench_record(
        op="psum", axis="dp", axis_size=4, payload_bytes=1 << 20,
        backend="tpu", time_ms=0.05, wire_bytes=1572864.0,
        achieved_bw=31457280000.0, peak_bw=1e11, bw_frac=0.3145728,
        predicted_ms=0.01572864, db_key="psum|ax4|1048576|tpu",
        event="measure")
    assert trace_check.check_commbench_records([honest], "t") == []
    doctored = dict(honest, achieved_bw=honest["achieved_bw"] * 10)
    assert any("achieved_bw" in p for p in
               trace_check.check_commbench_records([doctored], "t"))
    inflated = dict(honest, wire_bytes=3.0 * (1 << 20))   # > 2x payload
    assert any("wire_bytes" in p for p in
               trace_check.check_commbench_records([inflated], "t"))
    wrong_frac = dict(honest, bw_frac=0.9)
    assert any("bw_frac" in p for p in
               trace_check.check_commbench_records([wrong_frac], "t"))
    # a db_update must reference a measured row in the same file
    upd = dict(honest, event="db_update")
    assert trace_check.check_commbench_records([honest, upd], "t") == []
    orphan = dict(upd, db_key="psum|ax8|1048576|tpu")
    assert any("db_update references" in p for p in
               trace_check.check_commbench_records([honest, orphan], "t"))
    # and the rules run from inside the file-level checker
    path = tmp_path / "comm.jsonl"
    path.write_text(json.dumps(doctored) + "\n")
    problems, stats = trace_check.check_pair(str(path))
    assert stats["n_commbench"] == 1
    assert any("achieved_bw" in p for p in problems)


# ---------------------------------------------------------------------------
# CommDB: round-trip, keep-best, refuse non-finite
# ---------------------------------------------------------------------------

def test_comm_db_roundtrip_keep_best_refuse(tmp_path):
    path = str(tmp_path / "db.json")
    db = comm_obs.CommDB(path)
    key = comm_obs.db_key("psum", 2, 8192, "cpu")
    updated, refused = db.update([(key, {"best_ms": 1.0})])
    assert updated == [key] and refused == []
    # the key-derived lookup axes were backfilled
    assert db.entries[key]["op"] == "psum"
    assert db.entries[key]["axis_size"] == 2
    assert db.best_ms("psum", 2, 8192, "cpu") == 1.0
    assert db.lookup("psum", axis_size=2)[0][0] == key
    # keep-best: a slower row is silently skipped, a faster one lands
    updated, _ = db.update([(key, {"best_ms": 2.0})])
    assert updated == [] and db.best_ms("psum", 2, 8192, "cpu") == 1.0
    updated, _ = db.update([(key, {"best_ms": 0.5})])
    assert updated == [key] and db.best_ms("psum", 2, 8192, "cpu") == 0.5
    # refuse non-finite: best_ms NaN/inf, or any non-finite float field
    _, refused = db.update([(key, {"best_ms": float("nan")})])
    assert refused and "REFUSED" in refused[0][1]
    _, refused = db.update(
        [(key, {"best_ms": 0.1, "wire_bytes": float("inf")})])
    assert refused and "wire_bytes" in refused[0][1]
    assert db.best_ms("psum", 2, 8192, "cpu") == 0.5   # poison never landed
    # atomic save round-trips losslessly
    db.save()
    reloaded = comm_obs.CommDB(path)
    assert reloaded.entries == db.entries


def test_measure_attaches_db_reference_when_db_passed(tmp_path):
    """An explicit db= makes the measurement carry
    db_ms — the reference the comm_bw_degraded rule judges against,
    riding ON the record so replay judges identically."""
    mesh = env.build_mesh(dp=2, mp=4)
    clock = _fake_clock(0.1)
    first = comm_obs.measure_collective(
        "all_gather", "dp", mesh=mesh, payload_bytes=8192,
        warmup=0, k=1, clock=clock)
    db = comm_obs.CommDB(str(tmp_path / "db.json"))
    db.update([first])
    again = comm_obs.measure_collective(
        "all_gather", "dp", mesh=mesh, payload_bytes=8192,
        warmup=0, k=1, clock=_fake_clock(0.1), db=db)
    assert again.db_ms == first.time_ms
    assert again.to_record()["db_ms"] == first.time_ms


# ---------------------------------------------------------------------------
# calibration feedback into the planner
# ---------------------------------------------------------------------------

def _cal_rec(op, time_ms, predicted_ms, event=None):
    return sink.make_commbench_record(
        op=op, axis="dp", axis_size=4, payload_bytes=1 << 20,
        backend="tpu", time_ms=time_ms, predicted_ms=predicted_ms,
        event=event)


def test_calibration_from_comm_records_ratios_and_clamp():
    recs = [
        _cal_rec("psum", 2.0, 1.0),          # 2x slower than analytic
        _cal_rec("psum", 4.0, 1.0),          # median of [2, 4] = 3
        _cal_rec("psum", 3.0, 1.0),
        _cal_rec("all_to_all", 100.0, 1.0),  # clamped to the band's 4.0
        _cal_rec("ppermute", 0.1, 1.0),      # clamped up to 0.5
        _cal_rec("all_gather", 1.0, 1.0, event="db_update"),  # excluded
        _cal_rec("reduce_scatter", -1.0, 1.0),                # excluded
    ]
    cal = calibration_from_comm_records(recs)
    assert cal == {"psum": 3.0, "all_to_all": 4.0, "ppermute": 0.5}
    assert calibration_from_comm_records([]) == {}
    assert calibration_from_comm_records(None) == {}


def test_calibration_reranks_hand_built_candidates():
    """Acceptance: a measured psum running 4x over analytic flips the
    ranking between a tp-heavy (psum-dominated) and an sp-heavy
    (ppermute-dominated) layout — the planner would now pick the other
    one. Pure host arithmetic, exact both ways."""
    base = dict(n_params=125_000_000, num_layers=12, hidden_size=768,
                seq_len=2048, vocab_size=50304, chip="v5p",
                micro_batch=1)
    tp_heavy = dict(base, dp=2, mp=4)
    sp_heavy = dict(base, dp=2, sp=4)
    analytic_tp = estimate_layout_cost(**tp_heavy)["step_time_s"]
    analytic_sp = estimate_layout_cost(**sp_heavy)["step_time_s"]
    assert analytic_tp < analytic_sp          # analytically tp wins
    cal = {"psum": 4.0}
    cal_tp = estimate_layout_cost(**tp_heavy,
                                  comm_calibration=cal)["step_time_s"]
    cal_sp = estimate_layout_cost(**sp_heavy,
                                  comm_calibration=cal)["step_time_s"]
    assert cal_sp < cal_tp                    # measured psum flips it
    # only psum-priced terms scaled; the sp ring stayed analytic
    assert estimate_layout_cost(**sp_heavy, comm_calibration=cal)["sp_s"] \
        == estimate_layout_cost(**sp_heavy)["sp_s"]


def test_plan_threads_comm_calibration_into_record():
    """plan(comm_calibration=...) resolves records into per-op factors,
    prices candidates with them, and ships the factors on the Plan and
    its kind=plan telemetry record (the ledger shows what the ranking
    believed)."""
    recs = [_cal_rec("psum", 2.0, 1.0)]
    p = plan(gpt_tiny_config(), {"dp": 2, "mp": 4}, chip="v5p",
             verify="sharding", comm_calibration=recs)
    assert p.comm_calibration == {"psum": 2.0}
    rec = p.to_record()
    assert rec["comm_calibration"] == {"psum": 2.0}
    assert sink.validate_step_record(rec) == []
    # an explicit dict rides through unchanged; None means analytic
    p2 = plan(gpt_tiny_config(), {"dp": 2, "mp": 4}, chip="v5p",
              verify="sharding", comm_calibration={"all_to_all": 1.5})
    assert p2.comm_calibration == {"all_to_all": 1.5}
    p3 = plan(gpt_tiny_config(), {"dp": 2, "mp": 4}, chip="v5p",
              verify="sharding")
    assert p3.comm_calibration == {}
    assert "comm_calibration" not in p3.to_record()


# ---------------------------------------------------------------------------
# the comm_bw_degraded rule: fire, latch, re-arm, exemption
# ---------------------------------------------------------------------------

def _bench_rec(op="psum", time_ms=0.05, db_ms=0.02, **kw):
    return sink.make_commbench_record(
        op=op, axis="dp", axis_size=4, payload_bytes=1 << 20,
        backend="tpu", time_ms=time_ms, db_ms=db_ms, **kw)


def test_comm_bw_degraded_fires_latches_rearms():
    det = AnomalyDetector(HealthConfig(comm_bw_tol=1.0))   # band 2.0x
    found = det.observe(_bench_rec(time_ms=0.05, db_ms=0.02))  # 2.5x
    assert [a.kind for a in found] == ["comm_bw_degraded"]
    assert found[0].z == pytest.approx(2.5)
    assert found[0].expected == 0.02
    # latched: the same op stays quiet while still out of band
    assert det.observe(_bench_rec(time_ms=0.06, db_ms=0.02)) == []
    # a different op has its own latch
    found = det.observe(_bench_rec(op="all_to_all",
                                   time_ms=0.05, db_ms=0.02))
    assert [a.kind for a in found] == ["comm_bw_degraded"]
    # back in band re-arms; the next excursion fires again
    assert det.observe(_bench_rec(time_ms=0.03, db_ms=0.02)) == []
    found = det.observe(_bench_rec(time_ms=0.05, db_ms=0.02))
    assert [a.kind for a in found] == ["comm_bw_degraded"]


def test_comm_bw_degraded_exempt_without_reference():
    """No db_ms (flag off / no row) or no timing -> no jurisdiction;
    faster-than-DB is good news, not an anomaly (one-sided rule)."""
    det = AnomalyDetector()
    assert det.observe(_bench_rec(db_ms=None)) == []
    assert det.observe(_bench_rec(time_ms=None, db_ms=0.02)) == []
    assert det.observe(_bench_rec(time_ms=0.001, db_ms=0.02)) == []


def test_comm_bw_degraded_specimen_through_healthwatch(capsys):
    """The checked-in degraded specimen replays through the offline
    analyzer to the same verdict the in-flight detector reaches: the
    out-of-band psum pages BY NAME, the in-band and reference-free
    rows stay silent (ci.sh runs the same file through commlab
    --selfcheck)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "healthwatch", os.path.join(REPO, "tools", "healthwatch.py"))
    hw = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(hw)
    specimen = os.path.join(REPO, "tools", "specimens",
                            "commbench_degraded.jsonl")
    rc = hw.main([specimen])
    out = capsys.readouterr().out
    assert rc == 5
    assert out.count("[comm_bw_degraded]") == 1
    assert "psum" in out


# ---------------------------------------------------------------------------
# the straggler rule: fire, latch, re-arm, exemptions
# ---------------------------------------------------------------------------

def _step_rec(step, rank, step_ms, compile_ms=0.0):
    return sink.make_step_record(step=step, step_ms=step_ms,
                                 compile_ms=compile_ms, rank=rank)


def test_straggler_fires_latches_rearms():
    cfg = HealthConfig(straggler_rel=0.5, straggler_abs_ms=10.0)
    det = AnomalyDetector(cfg)
    # one rank: no skew to judge
    assert not [a for a in det.observe(_step_rec(0, 0, 100.0))
                if a.kind == "straggler"]
    # rank 1 at 2x + 100ms over: fires, names the rank and the gap
    found = [a for a in det.observe(_step_rec(0, 1, 200.0))
             if a.kind == "straggler"]
    assert len(found) == 1
    assert "rank 1" in found[0].message
    assert found[0].expected == 100.0
    assert found[0].z == pytest.approx(2.0)
    # latched: the same rank straggling on the next step stays quiet
    det.observe(_step_rec(1, 0, 100.0))
    assert not [a for a in det.observe(_step_rec(1, 1, 190.0))
                if a.kind == "straggler"]
    # back in band re-arms, the next excursion fires again
    det.observe(_step_rec(2, 0, 100.0))
    assert not [a for a in det.observe(_step_rec(2, 1, 105.0))
                if a.kind == "straggler"]
    det.observe(_step_rec(3, 0, 100.0))
    found = [a for a in det.observe(_step_rec(3, 1, 200.0))
             if a.kind == "straggler"]
    assert len(found) == 1


def test_straggler_exemptions():
    cfg = HealthConfig(straggler_rel=0.5, straggler_abs_ms=10.0)
    det = AnomalyDetector(cfg)
    # both bands must bind: +60% of 10ms is only 6ms absolute -> silent
    det.observe(_step_rec(0, 0, 10.0))
    assert not [a for a in det.observe(_step_rec(0, 1, 16.0))
                if a.kind == "straggler"]
    # a recompiling rank is legitimately slow -> exempt
    det.observe(_step_rec(1, 0, 100.0))
    assert not [a for a in det.observe(
        _step_rec(1, 1, 300.0, compile_ms=250.0))
        if a.kind == "straggler"]


def test_rank_step_skew_offline():
    recs = [_step_rec(0, 0, 100.0), _step_rec(0, 1, 160.0),
            _step_rec(1, 0, 90.0),                       # single rank
            {"kind": "compile", "step": 0, "rank": 2, "fn": "x",
             "compile_ms": 500.0}]
    skew = comm_obs.rank_step_skew(recs)
    assert skew == {0: {0: 0.0, 1: 60.0}}


# ---------------------------------------------------------------------------
# per-step comm attribution (recorder) + step-record schema
# ---------------------------------------------------------------------------

def test_recorder_attributes_comm_ms_and_excludes_traced():
    """Wall-time collective spans aggregate into comm_ms/comm_frac on
    the step record; spans tagged traced=true (shard_map trace time)
    are excluded from BOTH the per-op breakdown and the total."""
    rec = telemetry.TelemetryRecorder(track_memory=False)
    win = rec.start_step()
    t0 = win.t0
    rec.add_span("collective.all_reduce", t0, 0.010, cat="collective",
                 args={"axis": "dp", "bytes": 4096})
    rec.add_span("collective.psum", t0, 0.020, cat="collective",
                 args={"traced": True, "axis": "mp"})
    rec.add_span("host.io", t0, 0.5, cat="host")
    out = rec.end_step()
    assert "collective.all_reduce" in out["collectives"]
    assert "collective.psum" not in out["collectives"]
    assert out["comm_ms"] == pytest.approx(10.0, rel=1e-3)
    assert 0.0 < out["comm_frac"] <= 1.0
    assert sink.validate_step_record(out) == []
    # a step with no wall-time collectives carries neither field
    rec.start_step()
    out2 = rec.end_step()
    assert "comm_ms" not in out2 and "comm_frac" not in out2


def test_sharded_step_carries_bounded_comm_fields(tmp_path):
    """Acceptance: a REAL sharded step (wall-time all_reduce inside a
    recorded step) emits comm_ms/comm_frac the validator bounds, and
    trace_check passes the ledger."""
    from paddle_tpu import distributed as dist
    env.build_mesh(dp=2, mp=4)
    path = str(tmp_path / "run.jsonl")
    rec = telemetry.TelemetryRecorder(sink=path, track_memory=False)
    with rec:
        with rec.step():
            dist.collective.all_reduce(np.ones((8, 8), np.float32))
    out = rec.records[0]
    assert out["comm_ms"] > 0
    assert 0.0 < out["comm_frac"] <= 1.0
    problems, stats = trace_check.check_pair(path)
    assert problems == [] and stats["n_steps"] == 1


def test_step_record_comm_field_bounds():
    good = sink.make_step_record(step=0, step_ms=100.0, compile_ms=0.0,
                                 comm_ms=12.5, comm_frac=0.125)
    assert good["comm_ms"] == 12.5 and good["comm_frac"] == 0.125
    assert sink.validate_step_record(good) == []
    assert sink.validate_step_record(dict(good, comm_frac=1.5)) != []
    assert sink.validate_step_record(dict(good, comm_ms=-1.0)) != []


def test_traced_collective_span_tagged():
    """distributed/collective.py's shard_map primitives tag their spans
    traced=true with uniform payload/axis attrs — the contract the
    recorder's exclusion and the hang watchdog's black-box dump share."""
    from paddle_tpu.distributed.collective import _comm_span
    mesh = env.build_mesh(dp=2, mp=4)
    rec = telemetry.TelemetryRecorder(track_memory=False)
    t = type("T", (), {"_value": np.ones((4, 4), np.float32)})()
    with rec:
        with _comm_span("psum", tensor=t, axis_name="mp", traced=True):
            pass
        with _comm_span("all_reduce", tensor=t, axis_name="dp"):
            pass
    traced, wall = rec.spans[0], rec.spans[1]
    assert traced["name"] == "collective.psum"
    assert traced["args"]["traced"] is True
    assert traced["args"]["axis"] == "mp"
    assert traced["args"]["axis_size"] == 4
    assert traced["args"]["bytes"] == 64
    assert "traced" not in (wall.get("args") or {})
    assert wall["args"]["axis_size"] == 2


# ---------------------------------------------------------------------------
# reqtrace span vocabulary: collective/transfer
# ---------------------------------------------------------------------------

def test_reqtrace_collective_transfer_spans_validate_and_decompose():
    """The span vocabulary admits collective/transfer kinds (multi-chip
    serving: a tp allreduce or a host<->device transfer inside a
    request's life) and the decomposition invariant still holds — each
    gets its own attribution column and the spans still sum to e2e."""
    from paddle_tpu.telemetry import reqtrace
    spans = [
        {"kind": "queued", "t0_ms": 0.0, "dur_ms": 1.0},
        {"kind": "admit", "t0_ms": 1.0, "dur_ms": 0.5},
        {"kind": "collective", "t0_ms": 1.5, "dur_ms": 2.0,
         "op": "psum", "axis": "mp"},
        {"kind": "prefill_chunk", "t0_ms": 3.5, "dur_ms": 4.0},
        {"kind": "transfer", "t0_ms": 7.5, "dur_ms": 1.0,
         "bytes": 4096},
        {"kind": "decode", "t0_ms": 8.5, "dur_ms": 1.5},
    ]
    rec = sink.make_reqtrace_record(rid=1, outcome="finished",
                                    spans=spans, e2e_ms=10.0)
    assert sink.validate_step_record(rec) == []
    causes = reqtrace.decompose(rec)
    assert causes["collective"] == pytest.approx(2.0)
    assert causes["transfer"] == pytest.approx(1.0)
    assert causes["other"] == pytest.approx(0.5)   # admit only
    assert sum(causes.values()) == pytest.approx(10.0)
    # an off-vocabulary kind is still rejected
    bad = sink.make_reqtrace_record(
        rid=2, outcome="finished", e2e_ms=1.0,
        spans=[{"kind": "dma", "t0_ms": 0.0, "dur_ms": 1.0}])
    assert any("vocabulary" in p for p in sink.validate_step_record(bad))


# ---------------------------------------------------------------------------
# comm_audit third honesty leg
# ---------------------------------------------------------------------------

def test_comm_audit_third_leg_catches_dishonest_claims():
    mesh = env.build_mesh(dp=2, mp=4)
    res = comm_obs.measure_collective(
        "all_gather", "mp", mesh=mesh, payload_bytes=16384,
        warmup=0, k=1, clock=_fake_clock(0.01))
    honest = res.to_record()
    assert comm_audit.check_commbench_wire_bytes([honest],
                                                 mesh=mesh) == []
    # a 10x-inflated claim no longer describes the measured program
    doctored = dict(honest, wire_bytes=honest["wire_bytes"] * 10)
    problems = comm_audit.check_commbench_wire_bytes([doctored],
                                                     mesh=mesh)
    assert any("claimed wire_bytes" in p for p in problems)
    # an axis the mesh lacks is named (every build_mesh axis exists at
    # size >= 1, so use a name outside the vocabulary entirely)
    wrong_axis = dict(honest, axis="xx")
    problems = comm_audit.check_commbench_wire_bytes([wrong_axis],
                                                     mesh=mesh)
    assert any("not on the live mesh" in p for p in problems)
    # db_update echoes and no-claim rows are skipped, no mesh is loud
    upd = dict(honest, event="db_update")
    assert comm_audit.check_commbench_wire_bytes([upd], mesh=mesh) == []
    env.clear_mesh()                      # mesh=None falls back to global
    assert comm_audit.check_commbench_wire_bytes([honest], mesh=None) \
        == ["check_commbench_wire_bytes: no mesh — pass mesh= or "
            "env.build_mesh(...) first"]


# ---------------------------------------------------------------------------
# the CLI (subprocess: the exact ci.sh legs)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_commlab_selfcheck_subprocess():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "commlab.py"),
         "--selfcheck"],
        capture_output=True, text=True, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "selfcheck OK" in out.stdout


@pytest.mark.slow
def test_commlab_smoke_subprocess(tmp_path):
    tele = str(tmp_path / "smoke.jsonl")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "commlab.py"),
         "--smoke", "--telemetry", tele],
        capture_output=True, text=True, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    recs = [json.loads(x) for x in open(tele)]
    assert {r.get("kind") for r in recs} == {"commbench"}
    # every (op, axis) measured
    assert {(r["op"], r["axis"]) for r in recs} \
        == {(op, ax) for op in comm_obs.SWEEP_OPS for ax in ("dp", "mp")}
    problems, _ = trace_check.check_pair(tele)
    assert problems == []
