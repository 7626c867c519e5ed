"""Kernel observatory (paddle_tpu/telemetry/kernel_obs.py + the
kernellab CLI): injectable-clock timing determinism, hand-computed
roofline fractions, the persistent timing DB (round-trip, non-finite
refusal, key stability), KN504 re-fuzz on tuned configs, the
kernel_time_drift rule in both directions, the kind=kernelbench record
schema + trace_check cross-rules, and the CLI gates."""
import itertools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from paddle_tpu import monitor
from paddle_tpu.telemetry import kernel_obs, sink
from paddle_tpu.telemetry.health import AnomalyDetector, HealthConfig
from paddle_tpu.telemetry.kernel_obs import (
    KernelDB, MeasureResult, db_key, measure_kernel, roofline,
    shape_signature)
from paddle_tpu.ops.kernel_registry import get_kernel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
import trace_check  # noqa: E402

# registration is import-driven: pull in every kernel-owning module
from paddle_tpu.moe import kernels as _moe_kernels        # noqa: F401,E402
from paddle_tpu.ops import pallas_attention               # noqa: F401,E402
from paddle_tpu.ops import pallas_decode                  # noqa: F401,E402
from paddle_tpu.ops import pallas_int8                    # noqa: F401,E402
from paddle_tpu.ops import pallas_layernorm               # noqa: F401,E402


def _fake_clock(step_s=1.0):
    """Monotone clock advancing exactly step_s per call: every timed
    interval comes out as step_s, so medians are exact."""
    c = itertools.count()
    return lambda: next(c) * step_s


def _kb_record(**kw):
    base = dict(kernel="k", sig="f32[8,8]", backend="tpu",
                kernel_ms=1.0)
    base.update(kw)
    return sink.make_kernelbench_record(**base)


# ---------------------------------------------------------------------------
# timing harness
# ---------------------------------------------------------------------------

def test_timed_call_deterministic_with_injected_clock():
    # clock ticks 1s per call: compile interval = 1s, each of the k
    # sample intervals = 1s -> median exactly 1000 ms, no wall time in
    # the numbers at all
    med, compile_ms, samples = kernel_obs._timed_call(
        lambda x: x + 1.0, (np.ones(8, np.float32),), {},
        warmup=2, k=3, clock=_fake_clock(1.0))
    assert med == 1000.0
    assert compile_ms == 1000.0
    assert samples == [1000.0, 1000.0, 1000.0]


def test_timed_call_compile_excluded_from_samples():
    # a slow first interval (the compile) must not leak into the
    # execute median: feed explicit timestamps where compile takes 50s
    # and every execute interval 1s
    times = iter([0.0, 50.0,            # compile
                  50.0, 51.0, 51.0, 52.0, 52.0, 53.0])  # 3 samples
    med, compile_ms, _ = kernel_obs._timed_call(
        lambda x: x * 2.0, (np.ones(4, np.float32),), {},
        warmup=0, k=3, clock=lambda: next(times))
    assert compile_ms == 50000.0
    assert med == 1000.0


def test_measure_kernel_deterministic_given_clock_and_seed():
    reg = get_kernel("moe_gather")
    a = measure_kernel(reg, seed=7, warmup=1, k=3,
                       clock=_fake_clock(0.5))
    b = measure_kernel(reg, seed=7, warmup=1, k=3,
                       clock=_fake_clock(0.5))
    assert a.kernel_ms == b.kernel_ms == 500.0
    assert a.sig == b.sig
    assert a.flops == b.flops
    assert a.bytes_accessed == b.bytes_accessed


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------

def test_shape_signature_arrays_only_positional_order():
    args = (np.zeros((4, 128), np.float32), 512,
            np.zeros(40, np.int32), True)
    assert shape_signature(args) == "f32[4,128],i32[40]"
    # kwargs fold in sorted by name, after positionals
    sig = shape_signature((np.zeros(8, np.float32),),
                          {"b": np.zeros(2, np.int8),
                           "a": np.zeros(3, np.int32)})
    assert sig == "f32[8],i32[3],i8[2]"


def test_db_key_stability():
    assert db_key("flash_fwd", "f32[4,128]", "f32", "tpu") == \
        "flash_fwd|f32[4,128]|f32|tpu"


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------

def test_roofline_hand_computed_fractions():
    # 1e12 flops, 1e9 bytes in 10 ms on a (2e14 FLOP/s, 4e11 B/s)
    # machine: achieved 1e14 FLOP/s (50%), 1e11 B/s (25%);
    # floor = max(5ms compute, 2.5ms memory) -> compute-bound, 5 ms
    r = roofline(int(1e12), int(1e9), 10.0,
                 peak_flops=2e14, peak_bw=4e11)
    assert r["achieved_flops"] == pytest.approx(1e14)
    assert r["achieved_bw"] == pytest.approx(1e11)
    assert r["flops_frac"] == pytest.approx(0.5)
    assert r["bw_frac"] == pytest.approx(0.25)
    assert r["predicted_ms"] == pytest.approx(5.0)
    assert r["bound"] == "compute"


def test_roofline_memory_bound_and_clamp():
    r = roofline(int(1e6), int(1e9), 0.001,
                 peak_flops=1e12, peak_bw=1e9)
    assert r["bound"] == "memory"
    # absurdly fast measurement vs a tiny peak: fracs clamp to 1.0 so
    # the record validator's [0, 1] bound always holds
    assert r["flops_frac"] == 1.0
    assert r["bw_frac"] == 1.0


def test_roofline_unknown_peaks_cpu_exempt():
    # CPU backends: the peak tables answer None -> no fractions, no
    # predicted_ms, and therefore no kernel_time_drift jurisdiction
    r = roofline(int(1e9), int(1e6), 1.0, device_kind="cpu-model-x")
    assert r["flops_frac"] is None
    assert r["bw_frac"] is None
    assert r["predicted_ms"] is None
    assert r["bound"] is None
    assert r["achieved_flops"] == pytest.approx(1e12)


def test_peak_hbm_bw_table_matches_flops_table_kinds():
    from paddle_tpu.telemetry import mfu
    assert mfu.PEAK_HBM_BW_BY_KIND.keys() == mfu.PEAK_FLOPS_BY_KIND.keys()
    for kind, bw in mfu.PEAK_HBM_BW_BY_KIND.items():
        assert bw > 0, kind


# ---------------------------------------------------------------------------
# measurement -> record -> gauges
# ---------------------------------------------------------------------------

def test_measure_kernel_record_validates_and_exports_gauges():
    monitor.reset()
    reg = get_kernel("moe_combine")
    res = measure_kernel(reg, warmup=1, k=2)
    rec = res.to_record()
    assert sink.validate_step_record(rec) == []
    assert rec["kind"] == "kernelbench"
    assert rec["db_key"] == db_key(res.kernel, res.sig, res.dtype,
                                   res.backend)
    assert rec["n_samples"] == 2 and rec["warmup"] == 1
    # fallback timed on the SAME inputs -> speedup is their ratio
    assert rec["speedup"] == pytest.approx(
        rec["fallback_ms"] / rec["kernel_ms"])
    snap = monitor.snapshot()
    assert snap.get("kernel.measured") == 1
    assert "kernel.moe_combine.ms" in snap


def test_make_kernelbench_record_nonfinite_to_none_plus_note():
    rec = _kb_record(kernel_ms=float("nan"), fallback_ms=float("inf"))
    # required kernel_ms stays as an explicit null; optional bad
    # fields are dropped; either way the error note survives so the
    # validator's null-needs-note rule holds
    assert rec["kernel_ms"] is None
    assert "fallback_ms" not in rec
    assert "error" in rec
    assert sink.validate_step_record(rec) == []


def test_validate_kernelbench_rejects_bad_records():
    bad_frac = _kb_record()
    bad_frac["flops_frac"] = 1.5
    assert sink.validate_step_record(bad_frac)
    neg = _kb_record()
    neg["kernel_ms"] = -1.0
    assert sink.validate_step_record(neg)
    null_no_note = _kb_record()
    null_no_note["kernel_ms"] = None
    assert sink.validate_step_record(null_no_note)
    bad_event = _kb_record(event="measure")
    bad_event["event"] = "yolo"
    assert sink.validate_step_record(bad_event)


def test_trace_check_cross_rules(tmp_path):
    # speedup must equal fallback_ms / kernel_ms; a db_update record
    # must reference a key some measured record in the file carries
    good = _kb_record(kernel_ms=2.0, fallback_ms=4.0, speedup=2.0,
                      db_key="k|f32[8,8]|f32|tpu", event="measure")
    lying = _kb_record(kernel_ms=2.0, fallback_ms=4.0, speedup=9.0)
    orphan = _kb_record(event="db_update",
                        db_key="other|f32[1]|f32|tpu")
    p = tmp_path / "m.jsonl"
    p.write_text("".join(json.dumps(r) + "\n"
                         for r in (good, lying, orphan)))
    problems, stats = trace_check.check_pair(str(p))
    assert stats["n_kernelbench"] == 3
    assert any("speedup" in pr for pr in problems)
    assert any("db_update" in pr for pr in problems)
    ok = tmp_path / "ok.jsonl"
    ok.write_text(json.dumps(good) + "\n" + json.dumps(
        _kb_record(event="db_update", db_key="k|f32[8,8]|f32|tpu")) + "\n")
    problems, _ = trace_check.check_pair(str(ok))
    assert problems == []


# ---------------------------------------------------------------------------
# the DB
# ---------------------------------------------------------------------------

def _result(kernel="k1", ms=2.0, **kw):
    base = dict(kernel=kernel, sig="f32[8,8]", dtype="f32",
                backend="cpu", kernel_ms=ms, fallback_ms=4.0,
                flops=100, bytes_accessed=200)
    base.update(kw)
    return MeasureResult(**base)


def test_db_roundtrip_and_keep_best(tmp_path):
    path = str(tmp_path / "db.json")
    db = KernelDB(path)
    updated, refused = db.update([_result(ms=2.0)])
    assert len(updated) == 1 and refused == []
    # slower row loses the race silently (not an error)
    updated, refused = db.update([_result(ms=3.0)])
    assert updated == [] and refused == []
    # faster row rolls forward
    updated, _ = db.update([_result(ms=1.0)])
    assert len(updated) == 1
    db.save()
    reloaded = KernelDB(path)
    assert reloaded.entries == db.entries
    key = db_key("k1", "f32[8,8]", "f32", "cpu")
    assert reloaded.entries[key]["best_ms"] == 1.0


def test_db_refuses_nonfinite(tmp_path):
    db = KernelDB(str(tmp_path / "db.json"))
    _, refused = db.update([_result(ms=float("nan"))])
    assert refused and "non-finite" in refused[0][1]
    _, refused = db.update([_result(ms=2.0, fallback_ms=float("inf"))])
    assert refused and "non-finite" in refused[0][1]
    assert db.entries == {}


def test_db_tuple_entry_backfills_axes_from_key(tmp_path):
    # a hand-built (key, entry) pair gets its lookup axes from the key
    # itself, so lookup() can always find what update() accepted
    db = KernelDB(str(tmp_path / "db.json"))
    key = db_key("flash_fwd", "f32[1,256,2,64]x3", "f32", "cpu")
    updated, _ = db.update([(key, {"best_ms": 1.5,
                                   "config": {"block_q": 256}})])
    assert updated == [key]
    hits = db.lookup("flash_fwd")
    assert len(hits) == 1
    assert hits[0][1]["backend"] == "cpu"


# ---------------------------------------------------------------------------
# config search
# ---------------------------------------------------------------------------

def test_tune_skips_infeasible_candidates_before_measuring():
    winner, results, skipped = kernel_obs.tune_flash_fwd(
        seq=256, candidates=[(512, 512), (1024, 256)])
    assert winner is None and results == []
    assert len(skipped) == 2
    assert all("exceed" in why for _, why in skipped)


def test_flash_fwd_vmem_feasibility_predicate():
    assert kernel_obs._flash_fwd_vmem_feasible(256, 512, 64)
    # a block pair that cannot fit the 10 MiB VMEM budget is rejected
    # by the SAME vmem_footprint model KN502 projects with
    assert not kernel_obs._flash_fwd_vmem_feasible(8192, 8192, 256)


@pytest.mark.slow
def test_tune_flash_fwd_measures_and_refuzzes_parity():
    winner, results, skipped = kernel_obs.tune_flash_fwd(
        seq=256, warmup=0, k=1, seeds=(0,),
        candidates=[(128, 128), (256, 256)])
    assert winner is not None
    assert len(results) == 2
    assert winner["best_ms"] == min(r.kernel_ms for r in results)
    # the winner carried KN502 feasibility and a clean KN504 re-fuzz
    assert winner["vmem_feasible"]
    assert winner["parity_findings"] == []
    assert winner["config"]["sq"] == 256
    assert winner["config"]["block_q"] in (128, 256)


# ---------------------------------------------------------------------------
# the drift rule
# ---------------------------------------------------------------------------

def test_kernel_time_drift_fires_both_directions_and_latches():
    det = AnomalyDetector(HealthConfig(kernel_drift_tol=1.0))
    slow = _kb_record(kernel="ka", kernel_ms=10.0, predicted_ms=1.0)
    fast = _kb_record(kernel="kb", kernel_ms=0.1, predicted_ms=1.0)
    inband = _kb_record(kernel="kc", kernel_ms=1.5, predicted_ms=1.0)
    assert [a.kind for a in det.observe(slow)] == ["kernel_time_drift"]
    assert [a.kind for a in det.observe(fast)] == ["kernel_time_drift"]
    assert det.observe(inband) == []
    # latched per kernel: the sweep measures ka at many shapes -> one
    # page, not N
    assert det.observe(slow) == []
    # back in band re-arms
    det.observe(_kb_record(kernel="ka", kernel_ms=1.0,
                           predicted_ms=1.0))
    assert [a.kind for a in det.observe(slow)] == ["kernel_time_drift"]


def test_kernel_time_drift_cpu_records_exempt():
    det = AnomalyDetector()
    # no predicted_ms (CPU: peaks unknown) -> no jurisdiction
    assert det.observe(_kb_record(kernel_ms=999.0)) == []


def test_drift_specimen_schema_valid_and_trips():
    spec_path = os.path.join(REPO, "tools", "specimens",
                             "kernelbench_drift.jsonl")
    with open(spec_path) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    det = AnomalyDetector()
    kinds = []
    for rec in recs:
        assert sink.validate_step_record(rec) == [], rec["kernel"]
        kinds += [a.kind for a in det.observe(rec)]
    assert kinds.count("kernel_time_drift") == 2


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_kernellab_selfcheck_cli():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "kernellab.py"),
         "--selfcheck"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "selfcheck OK" in proc.stdout


@pytest.mark.slow
def test_kernellab_smoke_cli(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = str(tmp_path / "smoke.jsonl")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "kernellab.py"),
         "--smoke", "--telemetry", out],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert proc.returncode == 0, proc.stderr + proc.stdout
    recs = [json.loads(line) for line in open(out)]
    from paddle_tpu.ops.kernel_registry import registered_kernels
    assert len(recs) == len(registered_kernels())
    assert {r["kind"] for r in recs} == {"kernelbench"}
