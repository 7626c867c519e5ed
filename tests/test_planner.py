"""Auto-sharding planner (paddle_tpu.planner): the layout search is
pure host arithmetic + static analysis, so everything here asserts on
exact numbers and exact findings — no step executes, no collective
runs, and the only trace is the planner's own cached proxy jaxpr.

Covers: abstract-param/rule parity against the live GPT model (the
pin that keeps placement-as-data and placement-in-code identical),
the 1.3B v5p-32 and 13B two-level 2x8 parity against the hand-written
plans of the pre-chip dry runs, search determinism, infeasibility with a named
binding constraint, kind=plan telemetry records through
tools/trace_check.py (incl. the >15% projection-drift gate),
observatory calibration, and the distributed-layer wiring
(shard_model rules=, ShardedTrainStep plan=, PipelineParallel
.apply_plan)."""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import planner
from paddle_tpu import optimizer as popt
from paddle_tpu.distributed import env
from paddle_tpu.models.gpt import (GPTConfig, GPTForPretraining,
                                   gpt_tiny_config)
from paddle_tpu.planner import (InfeasiblePlanError, Layout, MeshSpec,
                                evaluate_layout, gpt_abstract_params,
                                gpt_partition_rules,
                                match_partition_rules, plan)


# ---------------------------------------------------------------------------
# parity pins: abstract params and rules vs the live model
# ---------------------------------------------------------------------------

def test_abstract_params_match_live_model():
    """The planner never builds the model, so its (name, shape) view
    must be pinned to the real one — names, shapes AND order."""
    cfg = gpt_tiny_config()
    paddle.seed(0)
    model = GPTForPretraining(cfg)
    live = [(n, tuple(p._value.shape)) for n, p in
            model.named_parameters()]
    abstract = [(n, p.shape) for n, p in gpt_abstract_params(cfg)]
    assert live == abstract


def test_partition_rules_match_model_tags():
    """placement-as-data == placement-in-code: the regex rules resolve
    every parameter to exactly the mesh_axes tag models/gpt.py sets
    (untagged == explicit replicate)."""
    cfg = gpt_tiny_config()
    paddle.seed(0)
    model = GPTForPretraining(cfg)
    named = list(model.named_parameters())
    resolved = match_partition_rules(gpt_partition_rules(), named)
    for (name, p), (name2, axes, _rule) in zip(named, resolved):
        assert name == name2
        tag = tuple(getattr(p, "mesh_axes", None) or ())
        assert tuple(axes or ()) == tag, \
            f"{name}: rules say {axes}, model tags {tag}"


def test_meshspec_quacks_like_a_mesh():
    """MeshSpec feeds the same lint code paths a real Mesh does — a
    v5p-64 layout lints from a zero-device host."""
    from paddle_tpu.analysis import sharding_lint
    spec = MeshSpec(dp=4, mp=8, pp=2)
    assert spec.devices.size == 64 and spec.size == 64
    findings = sharding_lint.lint_spec("w", (6, 8), ("mp", None), spec)
    assert [f.rule_id for f in findings] == ["SH203"]
    report, _ = sharding_lint.project_hbm(
        [("w", planner.AbstractParam((64, 64)))], spec)
    assert report["n_devices"] == 64


# ---------------------------------------------------------------------------
# parity vs the hand-written plans of the pre-chip dry runs
# ---------------------------------------------------------------------------

def test_plan_1_3b_v5p32_beats_handwritten():
    """Acceptance pin: plan() on GPT-1.3B / v5p-32 is Graph-Doctor
    clean and beats the hand-written dp=4/mp=2/pp=2/zero-1/mb=2 spec
    on BOTH projected per-device HBM and
    modeled cost."""
    cfg = GPTConfig.gpt3_1_3b(max_seq_len=2048)
    chosen = plan(cfg, 32, chip="v5p", verify="full")
    lo = chosen.layout
    assert lo.dp * lo.pp * lo.mp * lo.sp * lo.ep == 32
    # zero findings across the full battery — nothing compiled/executed
    assert chosen.chosen.findings == []
    assert chosen.verify["findings_on_chosen"]["n"] == 0
    assert set(chosen.verify["families_checked"]) == \
        {"sharding", "jaxpr", "collective_order"}
    hand = evaluate_layout(
        cfg, Layout(dp=4, mp=2, pp=2, zero_stage=1, micro_batch=2),
        chip="v5p", global_batch=32)
    assert hand.feasible
    assert chosen.projected_hbm_bytes <= hand.projected_hbm_bytes
    assert chosen.chosen.s_per_token <= hand.s_per_token


def test_plan_13b_two_level_2x8_reproduces_handwritten():
    """The hand-written plan for 13B on 2 slices x 8 chips, dp
    over the slice (DCN) axis, mp=8 inner, ZeRO-3 — comes back out of
    the planner when given the fixed topology, at hand-written HBM and
    cost or better."""
    cfg = GPTConfig.gpt3_13b(max_seq_len=2048)
    p = plan(cfg, {"dp": 2, "mp": 8}, chip="v5p", dp_over_dcn=True,
             zero_stages=(3,), verify="sharding")
    assert (p.layout.dp, p.layout.mp, p.layout.zero_stage) == (2, 8, 3)
    hand = evaluate_layout(
        cfg, Layout(dp=2, mp=8, zero_stage=3), chip="v5p",
        dp_over_dcn=True, global_batch=16)
    assert hand.feasible
    assert p.projected_hbm_bytes <= hand.projected_hbm_bytes
    assert p.chosen.s_per_token <= hand.s_per_token
    # and with the stage free, the searched 2x8 plan may differ but
    # must still fit and verify clean
    free = plan(cfg, {"dp": 2, "mp": 8}, chip="v5p", dp_over_dcn=True,
                verify="sharding")
    assert free.chosen.findings == []
    assert free.projected_hbm_bytes <= free.hbm_budget


def test_plan_13b_v5p_pods_feasible():
    """BASELINE config 5 carried over from search_plan: full-size 13B
    must have verified plans on v5p-32 AND v5p-64."""
    cfg = GPTConfig.gpt3_13b(max_seq_len=2048)
    for n in (32, 64):
        p = plan(cfg, n, chip="v5p", verify="sharding")
        assert p.chosen.findings == []
        lo = p.layout
        assert lo.dp * lo.pp * lo.mp * lo.sp * lo.ep == n
        assert cfg.num_heads % lo.mp == 0
        assert cfg.num_layers % lo.pp == 0


def test_plan_deterministic():
    """Same config -> bit-identical plan report (no clocks, no
    randomness, total-ordered ranking)."""
    cfg = GPTConfig.gpt3_1_3b(max_seq_len=2048)
    a = plan(cfg, 32, chip="v5p", verify="sharding")
    b = plan(cfg, 32, chip="v5p", verify="sharding")
    assert a.to_dict() == b.to_dict()
    # and the report is strict JSON
    json.dumps(a.to_dict())


# ---------------------------------------------------------------------------
# infeasibility and rejection ledger
# ---------------------------------------------------------------------------

def test_infeasible_names_binding_constraint():
    cfg = GPTConfig.gpt3_1_3b(max_seq_len=2048)
    with pytest.raises(InfeasiblePlanError) as ei:
        plan(cfg, 4, chip="v5e", hbm_budget=1 << 30, verify="sharding")
    msg = str(ei.value)
    assert "SH206" in msg and "binding constraint" in msg
    cands = ei.value.candidates
    assert cands and all(not c.feasible for c in cands)
    # every rejection carries a reason naming its rule
    assert all(c.reason and c.reason.split(":")[0].startswith("SH")
               for c in cands)


def test_enumeration_skips_sh203_killable_factorizations():
    """Satellite pin: the candidate stream never proposes a
    factorization SH203 would reject — hidden_size % mp was the hole
    (mp | num_heads does NOT imply mp | hidden when hidden is not a
    multiple of the head count)."""
    cfg = GPTConfig(vocab_size=50304, hidden_size=100, num_heads=6,
                    ffn_hidden_size=396, num_layers=6, max_seq_len=64)
    p = plan(cfg, 6, chip="v5p", verify="sharding")
    assert all(c.layout.mp != 6 for c in p.candidates), \
        "mp=6 proposed although hidden 100 % 6 != 0 (SH203 bait)"
    # and every feasible candidate is actually lint-clean
    assert all(c.findings == [] for c in p.candidates if c.feasible)


# ---------------------------------------------------------------------------
# telemetry: kind=plan records + drift gate + calibration
# ---------------------------------------------------------------------------

def _trace_check(path):
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from trace_check import check_metrics_jsonl
    return check_metrics_jsonl(path)


def test_plan_record_roundtrip_and_drift_gate(tmp_path):
    from paddle_tpu.telemetry import sink
    p = plan(GPTConfig.gpt3_125m(), 8, chip="v5p", verify="sharding")
    rec = p.to_record(rank=0)
    assert sink.validate_step_record(rec) == []
    assert rec["kind"] == "plan"
    assert rec["candidates_considered"] > len(rec["candidates_rejected"])

    good = tmp_path / "plans.jsonl"
    good.write_text(json.dumps(rec) + "\n")
    *counts, problems = _trace_check(str(good))
    assert problems == [] and counts[4] == 1       # n_plan

    # measured-vs-projected drift >15% must fail (the PR-4 rule
    # mirrored onto the planner's own numbers)
    drifted = dict(rec)
    drifted["measured_hbm_bytes"] = int(rec["projected_hbm_bytes"] * 1.3)
    bad = tmp_path / "drift.jsonl"
    bad.write_text(json.dumps(drifted) + "\n")
    *_, bad_problems = _trace_check(str(bad))
    assert any("drift" in pr for pr in bad_problems)
    # within 15% passes
    close = dict(rec)
    close["measured_hbm_bytes"] = int(rec["projected_hbm_bytes"] * 1.1)
    ok = tmp_path / "close.jsonl"
    ok.write_text(json.dumps(close) + "\n")
    *_, ok_problems = _trace_check(str(ok))
    assert ok_problems == []


def test_plan_record_rejects_reasonless_and_bad_mesh(tmp_path):
    from paddle_tpu.telemetry import sink
    rec = sink.make_plan_record(
        model="m", chosen={"dp": 2, "pp": 1, "mp": 4}, n_chips=16,
        candidates_considered=3,
        candidates_rejected=[{"layout": "dp8", "reason": ""}])
    assert any("reason" in p for p in sink.validate_step_record(rec))
    path = tmp_path / "p.jsonl"
    path.write_text(json.dumps(dict(rec, candidates_rejected=[])) + "\n")
    *_, problems = _trace_check(str(path))
    assert any("multiplies to 8" in p for p in problems)


def test_calibration_from_records():
    from paddle_tpu.planner import calibration_from_records
    recs = [
        {"kind": "compile", "hbm": {"total_bytes": 150},
         "hbm_projected_bytes": 100},
        {"kind": "compile", "hbm": {"total_bytes": 130},
         "hbm_projected_bytes": 100},
        {"kind": "step"},            # ignored
    ]
    assert calibration_from_records(recs) == pytest.approx(1.4)
    assert calibration_from_records([]) == 1.0
    # clamped to the sanity band
    wild = [{"kind": "compile", "hbm": {"total_bytes": 10_000},
             "hbm_projected_bytes": 1}]
    assert calibration_from_records(wild) == 4.0
    # and the ratio scales the projection -> can flip feasibility
    cfg = GPTConfig.gpt3_1_3b(max_seq_len=2048)
    lo = Layout(dp=4, mp=2, pp=2, zero_stage=1)
    base = evaluate_layout(cfg, lo, chip="v5p")
    tight_budget = int(base.projected_hbm_bytes * 1.2)
    ok = evaluate_layout(cfg, lo, chip="v5p", hbm_budget=tight_budget)
    over = evaluate_layout(cfg, lo, chip="v5p", hbm_budget=tight_budget,
                           calibration=2.0)
    assert ok.feasible and not over.feasible
    assert "SH206" in over.reason


# ---------------------------------------------------------------------------
# wiring: shard_model(rules=), ShardedTrainStep(plan=), pipeline
# ---------------------------------------------------------------------------

def _tiny_plan(mesh_shape, **kw):
    kw.setdefault("verify", "sharding")
    kw.setdefault("zero_stages", (1,))
    return plan(gpt_tiny_config(), mesh_shape, chip="v5p", **kw)


def test_plan_apply_and_sharded_step_wiring():
    """End-to-end on the 8-virtual-device CPU mesh: planner tags +
    places a live tiny GPT, ShardedTrainStep(plan=...) picks up
    zero_stage, and one real step runs finite."""
    p = _tiny_plan({"dp": 2, "mp": 4})
    mesh = p.build_mesh()
    try:
        paddle.seed(0)
        model = GPTForPretraining(gpt_tiny_config())
        p.apply(model, mesh)
        qkv = model.gpt.blocks[0].attn.qkv_proj.weight
        assert tuple(qkv._value.sharding.spec) == (None, "mp")
        opt = popt.AdamW(learning_rate=1e-4,
                         parameters=model.parameters())
        from paddle_tpu import distributed as dist
        step = dist.ShardedTrainStep(model, model.loss, opt,
                                     mesh=mesh, plan=p)
        assert step.zero_stage == p.layout.zero_stage
        rs = np.random.RandomState(0)
        ids = paddle.to_tensor(rs.randint(0, 256, (4, 32)), "int32")
        lbl = paddle.to_tensor(rs.randint(0, 256, (4, 32)), "int32")
        loss = step(ids, lbl)
        assert np.isfinite(loss.item())
    finally:
        env.clear_mesh()


def test_sharded_step_rejects_mismatched_mesh():
    p = _tiny_plan({"dp": 2, "mp": 4})
    mesh = env.build_mesh(dp=4, mp=2)       # wrong factorization
    try:
        paddle.seed(0)
        model = GPTForPretraining(gpt_tiny_config())
        opt = popt.AdamW(learning_rate=1e-4,
                         parameters=model.parameters())
        from paddle_tpu import distributed as dist
        with pytest.raises(ValueError, match="does not match the plan"):
            dist.ShardedTrainStep(model, model.loss, opt, mesh=mesh,
                                  plan=p)
    finally:
        env.clear_mesh()


def test_shard_model_rules_kwarg():
    from paddle_tpu import distributed as dist
    mesh = env.build_mesh(dp=2, mp=4)
    try:
        net = paddle.nn.Linear(16, 32)
        assert getattr(net.weight, "mesh_axes", None) is None
        dist.shard_model(net, mesh,
                         rules=[(r"weight$", (None, "mp")), (r".*", ())])
        assert tuple(net.weight._value.sharding.spec) == (None, "mp")
    finally:
        env.clear_mesh()


def test_pipeline_apply_plan():
    from paddle_tpu import distributed as dist
    p = _tiny_plan({"pp": 2, "mp": 4})
    pp_mod = dist.PipelineParallel(paddle.nn.Linear(4, 4))
    # no mesh installed: schedule config applies, no validation target
    pp_mod.apply_plan(p)
    assert pp_mod._num_micro >= 4 and pp_mod.plan is p
    # mismatched process mesh must be rejected loudly
    mesh = env.build_mesh(dp=8)
    try:
        with pytest.raises(ValueError, match="wants pp=2"):
            dist.PipelineParallel(paddle.nn.Linear(4, 4)).apply_plan(p)
    finally:
        env.clear_mesh()


def test_trainer_kwargs_and_seq_shard():
    cfg = gpt_tiny_config()
    cfg.sequence_parallel = "ring"
    p = plan(cfg, {"dp": 2, "sp": 2, "mp": 2}, chip="v5p",
             verify="sharding", zero_stages=(1,))
    kw = p.trainer_kwargs()
    assert kw == {"zero_stage": 1, "seq_shard_batch": True}
