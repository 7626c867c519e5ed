"""Sparse + long-context subsystem tests (paddle_tpu/moe +
ops/ring_attention as production paths).

Covers, per the PR's acceptance criteria:
- fused Pallas dispatch/combine == gather fallback == legacy
  `distributed.MoELayer` forward AND backward (CPU interpret mode);
- expert-parallel shard_map path (ep=2) kernel-vs-fallback parity;
- GPTMoE `plan()` over an ep>=2 mesh comes back lint-clean and runs a
  finite ShardedTrainStep step through the planner's layout;
- planner parity: gpt_moe_abstract_params vs the live model,
  gpt_moe_partition_rules vs MoEFFN's tags;
- cost-model honesty: `estimate_layout_cost`'s ep all-to-all and sp
  ring-hop byte terms vs collectives counted in the REAL traced
  programs (analysis.comm_audit) on the 8-device CPU mesh;
- moe.* telemetry: first-class step-record fields, schema bounds,
  trace_check entropy cross-rule, /metrics gauges;
- graphdoctor gpt_moe config traces clean (JX + SH incl. SH208);
- the >=128k long-context preset: sp=8 layout passes the sharding
  battery, tiny-dims ring training step is finite.
"""
import functools
import json
import math
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import distributed as dist
from paddle_tpu import optimizer, planner as autoshard, telemetry
from paddle_tpu.distributed import env as dist_env
from paddle_tpu.moe import (GPTMoE, GPTMoEConfig, MoEFFN,
                            combine_fallback, gather_fallback,
                            gpt_moe_tiny_config, moe_combine,
                            moe_ffn_values, moe_gather, route_top_k)


@pytest.fixture(autouse=True)
def _clear_mesh():
    yield
    dist_env.clear_mesh()


def _rs(seed=0):
    return np.random.RandomState(seed)


# ---------------------------------------------------------------------------
# kernels: fused == fallback, forward and backward
# ---------------------------------------------------------------------------

def test_gather_kernel_matches_fallback():
    rs = _rs(1)
    src = jnp.asarray(rs.randn(20, 128), jnp.float32)
    idx = jnp.asarray(rs.randint(0, 21, (37,)), jnp.int32)  # 20 = empty
    k = moe_gather(src, idx, True)       # Pallas (interpret on CPU)
    f = gather_fallback(src, idx)
    assert np.allclose(np.asarray(k), np.asarray(f), atol=0)
    # sentinel rows really are zero
    assert np.all(np.asarray(k)[np.asarray(idx) == 20] == 0.0)
    g1 = jax.grad(lambda s: jnp.sum(moe_gather(s, idx, True) ** 2))(src)
    g2 = jax.grad(lambda s: jnp.sum(gather_fallback(s, idx) ** 2))(src)
    assert np.allclose(np.asarray(g1), np.asarray(g2), atol=1e-6)


def test_combine_kernel_matches_fallback():
    rs = _rs(2)
    src = jnp.asarray(rs.randn(24, 128), jnp.float32)
    idx = jnp.asarray(rs.randint(0, 25, (19, 2)), jnp.int32)
    w = jnp.asarray(rs.rand(19, 2), jnp.float32)
    k = moe_combine(src, idx, w, True)
    f = combine_fallback(src, idx, w)
    assert np.allclose(np.asarray(k), np.asarray(f), atol=1e-6)
    g1 = jax.grad(lambda s, ww: jnp.sum(moe_combine(s, idx, ww, True)
                                        ** 2), (0, 1))(src, w)
    g2 = jax.grad(lambda s, ww: jnp.sum(combine_fallback(s, idx, ww)
                                        ** 2), (0, 1))(src, w)
    for a, b in zip(g1, g2):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_router_capacity_and_stats_bounds():
    rs = _rs(3)
    n, E, k, C = 32, 4, 2, 3   # tight capacity forces drops
    logits = jnp.asarray(rs.randn(n, E) * 2.0, jnp.float32)
    comb_w, comb_slot, slot_token, aux, z, stats = route_top_k(
        logits, k, C)
    entropy, dropped, overflow = (float(stats[0]), float(stats[1]),
                                  float(stats[2]))
    assert 0.0 <= dropped <= 1.0
    assert 0.0 <= entropy <= math.log(E) + 1e-6
    assert overflow >= 1.0   # 32*2 assignments into 4*3 slots must spill
    assert dropped > 0.0
    # kept slots are a bijection: every non-sentinel slot_token entry is
    # a distinct token/slot pair, and comb_slot points back into it
    st = np.asarray(slot_token)
    kept = st[st < n]
    assert len(kept) == len(set(zip(range(len(kept)), kept))) and \
        len(kept) == int(round((1.0 - dropped) * n * k))
    cs, cw = np.asarray(comb_slot), np.asarray(comb_w)
    assert np.all(cw[cs == E * C] == 0.0)    # dropped choices weigh 0


# ---------------------------------------------------------------------------
# layer: kernel == fallback == legacy MoELayer
# ---------------------------------------------------------------------------

def _legacy_and_new(d=16, f=32, E=4, k=2, cf=2.0, use_kernel=False):
    paddle.seed(0)
    legacy = dist.MoELayer(d_model=d, d_ff=f, num_experts=E, k=k,
                           capacity_factor=cf)
    cfg = GPTMoEConfig(hidden_size=d, ffn_hidden_size=f, num_experts=E,
                       expert_top_k=k, capacity_factor=cf)
    new = MoEFFN(cfg, use_kernel=use_kernel)
    new.w_gate._value = legacy.w_gate._value
    new.w_in._value = legacy.w_in._value
    new.w_out._value = legacy.w_out._value
    return legacy, new


@pytest.mark.parametrize("use_kernel", [False, True])
def test_moe_ffn_matches_legacy_layer(use_kernel):
    """The production layer reproduces the reference einsum-mask layer
    exactly (same routing math, same gelu, same capacity formula) —
    forward, aux loss, and grads — with either dispatch/combine path.
    d=128 so the Pallas path is eligible."""
    legacy, new = _legacy_and_new(d=128, f=64, use_kernel=use_kernel)
    x = paddle.randn([24, 128]) * 0.5
    x.stop_gradient = False
    out_new = new(x)
    out_old = legacy(x)
    assert np.allclose(np.asarray(out_new._value),
                       np.asarray(out_old._value), atol=1e-5)
    assert np.allclose(float(new.aux_loss().item()),
                       float(legacy.aux_loss().item()), atol=1e-6)
    (out_new.sum() + new.aux_loss()).backward()
    x2 = paddle.to_tensor(np.asarray(x._value))
    x2.stop_gradient = False
    (legacy(x2).sum() + legacy.aux_loss()).backward()
    for a, b in ((new.w_in, legacy.w_in), (new.w_out, legacy.w_out),
                 (new.w_gate, legacy.w_gate)):
        assert np.allclose(np.asarray(a.grad._value),
                           np.asarray(b.grad._value), atol=2e-5)


def test_moe_ep2_kernel_vs_fallback_parity():
    """Under the expert-parallel shard_map (ep=2, explicit all_to_all)
    the fused kernels and the jnp fallback stay bit-comparable — the two
    paths share routing and differ only in dispatch/combine."""
    rs = _rs(5)
    mesh = dist.build_mesh(ep=2, devices=jax.devices()[:2])
    d, f, E = 128, 64, 4
    x = jnp.asarray(rs.randn(16, d) * 0.5, jnp.float32)
    wg = jnp.asarray(rs.randn(d, E) * 0.1, jnp.float32)
    wi = jnp.asarray(rs.randn(E, d, f) * 0.1, jnp.float32)
    wo = jnp.asarray(rs.randn(E, f, d) * 0.1, jnp.float32)

    # jitted like every production caller (TrainStep): an eager
    # shard_map re-lowers the whole region on each call
    def run(use_kernel):
        out, aux, z, stats = jax.jit(functools.partial(
            moe_ffn_values, num_experts=E, k=2, capacity_factor=2.0,
            use_kernel=use_kernel, mesh=mesh))(x, wg, wi, wo)
        return np.asarray(out), float(aux), np.asarray(stats)

    o1, a1, s1 = run(False)
    o2, a2, s2 = run(True)
    assert np.allclose(o1, o2, atol=1e-6)
    assert np.allclose(a1, a2, atol=1e-6)
    assert np.allclose(s1, s2, atol=1e-6)
    # grads through the ep path stay finite and kernel==fallback
    def loss(use_kernel, *args):
        out, aux, _z, _s = moe_ffn_values(
            *args, num_experts=E, k=2, capacity_factor=2.0,
            use_kernel=use_kernel, mesh=mesh)
        return jnp.sum(out ** 2) + aux
    g1 = jax.jit(jax.grad(functools.partial(loss, False),
                          (0, 1, 2, 3)))(x, wg, wi, wo)
    g2 = jax.jit(jax.grad(functools.partial(loss, True),
                          (0, 1, 2, 3)))(x, wg, wi, wo)
    for a, b in zip(g1, g2):
        assert np.all(np.isfinite(np.asarray(a)))
        assert np.allclose(np.asarray(a), np.asarray(b), atol=5e-5)


# ---------------------------------------------------------------------------
# planner integration
# ---------------------------------------------------------------------------

def test_gpt_moe_abstract_params_match_live_model():
    cfg = gpt_moe_tiny_config()
    paddle.seed(0)
    model = GPTMoE(cfg)
    live = [(n, tuple(p.shape)) for n, p in model.named_parameters()
            if p is not None]
    abstract = [(n, tuple(p.shape))
                for n, p in autoshard.gpt_moe_abstract_params(cfg)]
    assert live == abstract


def test_gpt_moe_rules_match_live_tags():
    """gpt_moe_partition_rules resolves every live parameter to exactly
    the mesh_axes the layers tag — placement has ONE owner."""
    from paddle_tpu.planner.rules import (gpt_moe_partition_rules,
                                          match_partition_rules)
    cfg = gpt_moe_tiny_config()
    paddle.seed(0)
    model = GPTMoE(cfg)
    named = [(n, p) for n, p in model.named_parameters() if p is not None]
    resolved = dict()
    for name, axes, _i in match_partition_rules(
            gpt_moe_partition_rules(), named):
        resolved[name] = tuple(axes or ())
    for name, p in named:
        tagged = tuple(getattr(p, "mesh_axes", None) or ())
        assert resolved[name] == tagged, (name, resolved[name], tagged)


def test_gpt_moe_params_accounting():
    cfg = gpt_moe_tiny_config()
    paddle.seed(0)
    model = GPTMoE(cfg)
    live = sum(int(np.prod(p.shape)) for _n, p in
               model.named_parameters() if p is not None)
    assert autoshard.gpt_params(cfg) == live


def test_gpt_moe_plan_and_sharded_step():
    """Acceptance: plan() over an ep>=2 mesh comes back lint-clean and
    the chosen layout runs a finite ShardedTrainStep step, with moe.*
    fields landing first-class in the telemetry step record."""
    cfg = gpt_moe_tiny_config(max_seq_len=32)
    plan = autoshard.plan(cfg, {"ep": 2, "dp": 4}, chip="v5p",
                          verify="sharding")
    assert plan.layout.ep == 2
    assert plan.chosen.findings == []
    mesh = plan.build_mesh()
    paddle.seed(0)
    model = GPTMoE(cfg)
    plan.apply(model)
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters())
    step = dist.ShardedTrainStep(model, lambda a, b: model.loss(a, b),
                                 opt, plan=plan)
    rs = _rs(0)
    ids = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (8, 32)),
                           "int32")
    lbl = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (8, 32)),
                           "int32")
    rec = telemetry.TelemetryRecorder()
    with rec:
        loss = step(ids, lbl)
    assert np.isfinite(float(loss.item()))
    r = rec.records[0]
    assert r["moe_num_experts"] == cfg.num_experts
    assert 0.0 <= r["moe_dropped_frac"] <= 1.0
    assert r["moe_entropy"] <= math.log(cfg.num_experts) + 1e-6
    assert "moe_overflow" in r and "moe_aux_loss" in r
    from paddle_tpu.telemetry.sink import validate_step_record
    assert validate_step_record(r) == []
    # gauges reached /metrics' registry
    from paddle_tpu import monitor
    snap = monitor.snapshot()
    assert "moe.entropy" in snap and "moe.aux_loss" in snap


def test_moe_loss_includes_aux_and_z():
    cfg = gpt_moe_tiny_config(max_seq_len=32)
    paddle.seed(0)
    model = GPTMoE(cfg)
    rs = _rs(0)
    ids = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (2, 32)),
                           "int32")
    lm_plus = float(model.loss(ids, ids).item())
    # zeroing the weights removes the aux/z contribution
    cfg2 = gpt_moe_tiny_config(max_seq_len=32, aux_loss_weight=0.0,
                               z_loss_weight=0.0)
    paddle.seed(0)
    model2 = GPTMoE(cfg2)
    lm_only = float(model2.loss(ids, ids).item())
    assert lm_plus > lm_only


# ---------------------------------------------------------------------------
# cost-model honesty: analytic comm terms vs the real traced programs
# ---------------------------------------------------------------------------

def test_cost_model_ep_all_to_all_matches_traced_program():
    """estimate_layout_cost's ep term models 4 dispatch/combine
    all-to-alls of the activation tile per layer. Trace the REAL MoE
    layer (fwd+bwd) on an ep=8 mesh and count what `lax.all_to_all`
    actually moves — the two must agree within 2x (k=1, cf=1.0 makes
    the routed volume equal one activation tile)."""
    from paddle_tpu.analysis.comm_audit import trace_collective_wire_bytes
    from paddle_tpu.cost_model import estimate_layout_cost, \
        ICI_BW_BY_CHIP

    ep, E, d, n = 8, 8, 32, 64
    mesh = dist.build_mesh(ep=ep)
    rs = _rs(7)
    x = jnp.asarray(rs.randn(n, d) * 0.5, jnp.float32)
    wg = jnp.asarray(rs.randn(d, E) * 0.1, jnp.float32)
    wi = jnp.asarray(rs.randn(E, d, 2 * d) * 0.1, jnp.float32)
    wo = jnp.asarray(rs.randn(E, 2 * d, d) * 0.1, jnp.float32)

    def loss(xx, g, i, o):
        out, aux, _z, _s = moe_ffn_values(
            xx, g, i, o, num_experts=E, k=1, capacity_factor=1.0,
            use_kernel=False, mesh=mesh)
        return jnp.sum(out ** 2) + aux

    audit = trace_collective_wire_bytes(
        jax.grad(loss, (0, 1, 2, 3)), x, wg, wi, wo,
        axis_sizes={"ep": ep})
    measured = audit["all_to_all"]["bytes"]
    assert audit["all_to_all"]["calls"] == 4   # 2 fwd + 2 bwd

    # the analytic term, in BYTES: ep_s * ici_bw with the dims mapped
    # so act_tile == the per-device routed volume (n/ep tokens of d
    # f32); 1 layer, 1 microbatch
    cost = estimate_layout_cost(
        n_params=1, num_layers=1, hidden_size=d, seq_len=n // ep,
        micro_batch=1, num_micro=1, ep=ep, compute_dtype_bytes=4,
        chip="v5p")
    model_bytes = cost["ep_s"] * ICI_BW_BY_CHIP["v5p"]
    ratio = measured / model_bytes
    assert 0.5 <= ratio <= 2.0, (measured, model_bytes, ratio)


def test_cost_model_sp_ring_hops_match_traced_program():
    """The sp term models (sp-1) K/V ring hops, doubled for backward.
    Trace the real ring-attention step on an sp=8 mesh and count the
    ppermute payloads — agreement within 2x (the scan runs sp hops vs
    the model's sp-1, and the transposed scan mirrors them)."""
    from paddle_tpu.analysis.comm_audit import trace_collective_wire_bytes
    from paddle_tpu.cost_model import estimate_layout_cost, \
        ICI_BW_BY_CHIP
    from paddle_tpu.ops.ring_attention import ring_attention_values

    sp, b, s, nh, h = 8, 1, 64, 2, 8
    mesh = dist.build_mesh(sp=sp)
    rs = _rs(8)
    mk = lambda: jnp.asarray(rs.randn(b, s, nh, h), jnp.float32) * 0.3

    def loss(q, k, v):
        return jnp.sum(ring_attention_values(q, k, v, causal=False,
                                             mesh=mesh) ** 2)

    audit = trace_collective_wire_bytes(
        jax.grad(loss, (0, 1, 2)), mk(), mk(), mk(),
        axis_sizes={"sp": sp})
    measured = audit["ppermute"]["bytes"]
    assert audit["ppermute"]["calls"] >= sp   # fwd hops at least

    cost = estimate_layout_cost(
        n_params=1, num_layers=1, hidden_size=nh * h, seq_len=s,
        micro_batch=b, num_micro=1, sp=sp, compute_dtype_bytes=4,
        chip="v5p")
    model_bytes = cost["sp_s"] * ICI_BW_BY_CHIP["v5p"]
    ratio = measured / model_bytes
    assert 0.5 <= ratio <= 2.0, (measured, model_bytes, ratio)


# ---------------------------------------------------------------------------
# telemetry schema + cross-rules
# ---------------------------------------------------------------------------

def test_sink_moe_field_bounds():
    from paddle_tpu.telemetry.sink import (make_step_record,
                                           validate_step_record)
    good = make_step_record(0, 10.0, 0.0, moe_entropy=1.2,
                            moe_dropped_frac=0.1, moe_overflow=1.5,
                            moe_aux_loss=1.01, moe_num_experts=8)
    assert validate_step_record(good) == []
    assert good["moe_entropy"] == 1.2 and good["moe_num_experts"] == 8
    bad = make_step_record(0, 10.0, 0.0, moe_dropped_frac=1.5,
                           moe_num_experts=8)
    assert any("moe_dropped_frac" in p for p in validate_step_record(bad))
    bad2 = make_step_record(0, 10.0, 0.0, moe_entropy=-0.5,
                            moe_num_experts=8)
    assert any("moe_entropy" in p for p in validate_step_record(bad2))


def test_trace_check_moe_entropy_cross_rule(tmp_path):
    """A step record whose entropy exceeds log(num_experts) — or that
    carries moe fields with no expert count — fails trace_check."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "trace_check", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "trace_check.py"))
    tc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tc)
    from paddle_tpu.telemetry.sink import make_step_record

    ok = make_step_record(0, 10.0, 0.0, moe_entropy=math.log(4) - 0.01,
                          moe_dropped_frac=0.0, moe_num_experts=4)
    doctored = make_step_record(1, 10.0, 0.0,
                                moe_entropy=math.log(4) + 0.5,
                                moe_dropped_frac=0.0, moe_num_experts=4)
    anonymous = make_step_record(2, 10.0, 0.0, moe_dropped_frac=0.0)
    path = str(tmp_path / "moe.jsonl")
    with open(path, "w") as f:
        for r in (ok, doctored, anonymous):
            f.write(json.dumps(r) + "\n")
    *_counts, problems = tc.check_metrics_jsonl(path)
    assert any("exceeds" in p for p in problems)
    assert any("moe_num_experts" in p for p in problems)
    # and the clean record alone passes
    path2 = str(tmp_path / "moe_ok.jsonl")
    with open(path2, "w") as f:
        f.write(json.dumps(ok) + "\n")
    *_c2, problems2 = tc.check_metrics_jsonl(path2)
    assert problems2 == []


# ---------------------------------------------------------------------------
# graph doctor + long-context config
# ---------------------------------------------------------------------------

def test_graphdoctor_gpt_moe_clean():
    """The gpt_moe config traces clean through the full static battery
    (JX101-106 over the routed step, SH201-208 incl. expert-rule
    coverage over the dp x mp x ep mesh)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "graphdoctor", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "graphdoctor.py"))
    gd = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gd)
    findings, extras = gd.run_config("gpt_moe")
    assert findings == [], [str(f) for f in findings]
    assert extras["mesh"].get("ep") == 2


def test_128k_preset_sp_layout_passes_battery():
    """The >=128k ring preset: an sp=8 layout on v5p passes the full
    sharding battery lint-clean (plan() with sp fixed), and the sp
    candidates are feasible at 131072 tokens of context."""
    from paddle_tpu.models.gpt import GPTConfig
    cfg = GPTConfig.gpt3_1_3b_128k()
    assert cfg.max_seq_len >= 131072 and cfg.sequence_parallel == "ring"
    plan = autoshard.plan(cfg, {"sp": 8}, chip="v5p", verify="sharding")
    assert plan.layout.sp == 8
    assert plan.chosen.findings == []
    # per-chip HBM stays inside the budget the battery checked
    assert plan.projected_hbm_bytes <= plan.hbm_budget


def test_128k_preset_tiny_dims_trains_on_sp_mesh():
    """The preset's ring+remat composition runs a finite sharded train
    step on a dp x sp mesh at test dims (the full size has not been run on the
    chip)."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    cfg = GPTConfig.gpt3_1_3b_128k(
        hidden_size=32, num_layers=2, num_heads=4, max_seq_len=64,
        vocab_size=128, use_flash_attention=False)
    mesh = dist.build_mesh(dp=2, sp=4)
    paddle.seed(0)
    model = GPTForPretraining(cfg)
    dist.shard_model(model)
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters())
    step = dist.ShardedTrainStep(model, lambda a, b: model.loss(a, b),
                                 opt, zero_stage=1, seq_shard_batch=True)
    rs = _rs(0)
    ids = paddle.to_tensor(rs.randint(0, 128, (4, 64)), "int32")
    loss = step(ids, ids)
    assert np.isfinite(float(loss.item()))


def test_legacy_moe_layer_still_works():
    """The deprecated reference layer stays functional (back-compat)."""
    mesh = dist.build_mesh(dp=2, ep=4)
    moe = dist.MoELayer(d_model=16, d_ff=32, num_experts=4, k=2,
                        capacity_factor=2.0)
    dist.shard_model(moe)
    x = paddle.randn([8, 16]) * 0.5
    x.stop_gradient = False
    (moe(x).sum() + moe.aux_loss()).backward()
    assert moe.w_in.grad is not None
