"""Graph doctor (paddle_tpu.analysis): one positive (rule fires on a
broken specimen) and one clean case per rule, plus the end-to-end
doctor run over the in-repo configs — the static-analysis analog of the
reference's ProgramDesc-validation tests. Everything here traces; no
step executes, no collective runs."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer as popt
from paddle_tpu.analysis import (Finding, GraphDoctorError, SEV_ERROR,
                                 astlint, collective_order, emit,
                                 jaxpr_lint, sharding_lint, summarize)
from paddle_tpu.distributed import env
from paddle_tpu.jit import TrainStep


def _rules(findings):
    return [f.rule_id for f in findings]


def _tiny_step(donate=True, lint=False):
    net = paddle.nn.Linear(8, 8)
    opt = popt.SGD(learning_rate=0.1, parameters=net.parameters())
    step = TrainStep(net, lambda x: (net(x) ** 2).mean(), opt,
                     donate=donate, lint=lint)
    x = paddle.to_tensor(np.ones((4, 8), np.float32))
    return step, x


# ---------------------------------------------------------------------------
# jaxpr lint (JX)
# ---------------------------------------------------------------------------

def test_jx101_undonated_state_fires_and_donated_is_clean():
    step, x = _tiny_step(donate=False)
    findings = jaxpr_lint.lint_train_step(step, x)
    assert "JX101" in _rules(findings)
    jx101 = [f for f in findings if f.rule_id == "JX101"][0]
    assert "donat" in jx101.message
    step2, x2 = _tiny_step(donate=True)
    assert "JX101" not in _rules(jaxpr_lint.lint_train_step(step2, x2))


def test_jx102_host_callback_in_step():
    def bad(v):
        jax.debug.print("v={v}", v=v)
        return v * 2

    sds = jax.ShapeDtypeStruct((4,), jnp.float32)
    findings = jaxpr_lint.lint_callable(bad, sds)
    assert "JX102" in _rules(findings)
    assert "JX102" not in _rules(
        jaxpr_lint.lint_callable(lambda v: v * 2, sds))


def test_jx103_silent_upcast_large_only():
    big = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16)
    small = jax.ShapeDtypeStruct((8, 8), jnp.bfloat16)

    def upcast(v):
        return v.astype(jnp.float32).sum()

    assert "JX103" in _rules(jaxpr_lint.lint_callable(upcast, big))
    # small tensors (biases, norms) are noise, not findings
    assert "JX103" not in _rules(jaxpr_lint.lint_callable(upcast, small))


def test_jx104_x64_hazard():
    i64 = jax.ShapeDtypeStruct((4,), jnp.dtype("int64"))
    i32 = jax.ShapeDtypeStruct((4,), jnp.int32)
    fn = lambda v: v + 1  # noqa: E731
    # int64 avals only survive tracing with x64 on — exactly the leak
    # JX104 exists to catch; scope it to this one trace
    jax.config.update("jax_enable_x64", True)
    try:
        assert "JX104" in _rules(jaxpr_lint.lint_callable(fn, i64))
    finally:
        jax.config.update("jax_enable_x64", False)
    assert "JX104" not in _rules(jaxpr_lint.lint_callable(fn, i32))


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def test_jx105_degenerate_collective_size1_axis():
    from jax.sharding import Mesh, PartitionSpec as P
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("dp",))
    f = _shard_map(lambda x: jax.lax.psum(x, "dp"), mesh1,
                   P("dp"), P())
    sds = jax.ShapeDtypeStruct((4,), jnp.float32)
    findings = jaxpr_lint.lint_callable(f, sds,
                                        mesh_axis_sizes={"dp": 1})
    assert "JX105" in _rules(findings)
    # same program on a real (size-2) axis is legitimate
    mesh2 = Mesh(np.array(jax.devices()[:2]), ("dp",))
    g = _shard_map(lambda x: jax.lax.psum(x, "dp"), mesh2,
                   P("dp"), P())
    assert "JX105" not in _rules(
        jaxpr_lint.lint_callable(g, sds, mesh_axis_sizes={"dp": 2}))


def test_jx106_reduce_then_broadcast():
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))

    def rs_then_ag(x):
        r = jax.lax.psum_scatter(x, "dp", scatter_dimension=0, tiled=True)
        return jax.lax.all_gather(r, "dp", axis=0, tiled=True)

    f = _shard_map(rs_then_ag, mesh, P("dp"), P("dp"))
    sds = jax.ShapeDtypeStruct((8,), jnp.float32)
    findings = jaxpr_lint.lint_callable(
        f, sds, mesh_axis_sizes={"dp": 2})
    assert "JX106" in _rules(findings)
    # a lone psum is the fused form — clean
    g = _shard_map(lambda x: jax.lax.psum(x, "dp"), mesh, P("dp"), P())
    assert "JX106" not in _rules(
        jaxpr_lint.lint_callable(g, sds, mesh_axis_sizes={"dp": 2}))


def test_trainstep_lint_true_warns_at_trace_time():
    step, x = _tiny_step(donate=False, lint=True)
    with pytest.warns(UserWarning, match="graph doctor"):
        step(x)
    assert step.lint_findings and "JX101" in _rules(step.lint_findings)
    # lint runs once per program build, not per step
    step(x)


def test_trainstep_lint_strict_raises():
    net = paddle.nn.Linear(4, 4)
    opt = popt.SGD(learning_rate=0.1, parameters=net.parameters())

    def bad_loss(x):
        y = net(x)
        from paddle_tpu.core.tensor import apply

        def dbg(v):
            jax.debug.print("loss={v}", v=v)
            return v
        return apply(dbg, (y ** 2).mean())

    step = TrainStep(net, bad_loss, opt, lint="strict")
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    with pytest.raises(GraphDoctorError, match="JX102"):
        step(x)


def test_pipeline_train_batch_lint_runs_clean():
    """The jaxpr lint also walks PipelineParallel.train_batch's fused
    1F1B program (traced once more, never executed twice): the in-repo
    schedule lints clean."""
    from paddle_tpu import distributed as dist
    from paddle_tpu import nn
    from paddle_tpu.distributed import env as dist_env
    from paddle_tpu.distributed.pipeline import LayerDesc
    from paddle_tpu.nn import functional as F

    pp_size = 2
    mesh = dist.build_mesh(pp=pp_size, devices=jax.devices()[:pp_size])
    try:
        paddle.seed(0)
        layer = dist.PipelineLayer(
            [LayerDesc(nn.Linear, 8, 8) for _ in range(4)],
            num_stages=pp_size,
            loss_fn=lambda out, y: ((out - y) ** 2).mean())
        pp = dist.PipelineParallel(layer)
        pp._num_micro = 2
        pp.lint = True
        opt = popt.SGD(learning_rate=0.1, parameters=layer.parameters())
        x = paddle.to_tensor(np.ones((4, 8), np.float32))
        y = paddle.to_tensor(np.ones((4, 8), np.float32))
        pp.train_batch((x, y), opt)
        assert pp.lint_findings == []
    finally:
        dist_env.clear_mesh()


# ---------------------------------------------------------------------------
# sharding lint (SH)
# ---------------------------------------------------------------------------

@pytest.fixture
def mesh24():
    mesh = env.build_mesh(dp=2, mp=4)
    yield mesh
    env.clear_mesh()


def test_sh201_rank_mismatch(mesh24):
    findings = sharding_lint.lint_spec("w", (8,), ("mp", None), mesh24)
    assert "SH201" in _rules(findings)
    assert not sharding_lint.lint_spec("w", (8, 8), ("mp", None), mesh24)


def test_sh202_unknown_axis(mesh24):
    findings = sharding_lint.lint_spec("w", (8, 8), ("tp", None), mesh24)
    assert "SH202" in _rules(findings)


def test_sh203_non_divisible(mesh24):
    findings = sharding_lint.lint_spec("w", (6, 8), ("mp", None), mesh24)
    assert _rules(findings) == ["SH203"]
    assert "silently dropped" in findings[0].message
    assert not sharding_lint.lint_spec("w", (8, 8), ("mp", None), mesh24)


def test_sh204_duplicate_axis(mesh24):
    findings = sharding_lint.lint_spec("w", (8, 8), ("mp", "mp"), mesh24)
    assert "SH204" in _rules(findings)


def test_sh207_tuple_entry_unsupported_by_apply_path(mesh24):
    """PartitionSpec tuple entries are legal GSPMD but the mesh_axes
    apply path drops them (silent replication) — the lint must say so
    instead of green-lighting the spec."""
    findings = sharding_lint.lint_spec(
        "w", (8, 8), (("dp", "mp"), None), mesh24)
    assert [f.rule_id for f in findings] == ["SH207"]
    assert "replicate" in findings[0].message


def test_sh205_replicated_under_zero3(mesh24):
    # 2 MB param with no dp-divisible dim stays replicated under ZeRO-3
    p = paddle.create_parameter([3, 174763], "float32")
    findings = sharding_lint.lint_model_sharding(
        [("big.w", p)], mesh24, zero_stage=3)
    assert "SH205" in _rules(findings)
    # a dp-divisible param shards: clean
    p2 = paddle.create_parameter([4, 174763], "float32")
    assert "SH205" not in _rules(sharding_lint.lint_model_sharding(
        [("ok.w", p2)], mesh24, zero_stage=3))


def test_project_hbm_accounts_sharding(mesh24):
    p = paddle.create_parameter([16, 32], "float32")
    p.mesh_axes = (None, "mp")
    rep, _ = sharding_lint.project_hbm([("w", p)], mesh24, zero_stage=0)
    # mp=4 shards the 2048-element param: 512 f32 per device
    assert rep["per_device"]["param_bytes"] == 16 * 32 * 4 // 4
    _, findings = sharding_lint.project_hbm(
        [("w", p)], mesh24, zero_stage=0, hbm_bytes=1024)
    assert "SH206" in _rules(findings)


def test_sh208_param_fallthrough_flagged(mesh24):
    """Direction 1: under a sharded layout, a parameter no rule
    matches silently replicates — error for large params, warning for
    small ones; a catch-all rule makes it clean."""
    rules = [(r"weight$", (None, "mp"))]
    big = paddle.create_parameter([512, 1024], "float32")   # 2 MB
    w = paddle.create_parameter([16, 32], "float32")   # keeps rule live
    findings = sharding_lint.lint_partition_rules(
        rules, [("blk.fc.weight", w), ("blk.untagged", big)], mesh24)
    assert [f.rule_id for f in findings] == ["SH208"]
    assert findings[0].severity == SEV_ERROR
    assert "falls through" in findings[0].message
    assert findings[0].location == "blk.untagged"
    small = paddle.create_parameter([8], "float32")
    findings = sharding_lint.lint_partition_rules(
        rules, [("blk.fc.weight", w), ("blk.tiny", small)], mesh24)
    assert [f.severity for f in findings] == ["warning"]
    # explicit catch-all: replication becomes a decision, not a finding
    covered = rules + [(r".*", ())]
    assert sharding_lint.lint_partition_rules(
        covered, [("blk.fc.weight", w), ("blk.untagged", big)],
        mesh24) == []


def test_sh208_dead_rule_flagged(mesh24):
    """Direction 2: a rule whose pattern matches no parameter is dead
    — whatever it was written to shard is NOT being sharded."""
    p = paddle.create_parameter([16, 32], "float32")
    rules = [(r"qkv_proj\.weight$", (None, "mp")), (r".*", ())]
    findings = sharding_lint.lint_partition_rules(
        rules, [("blk.fc.weight", p)], mesh24)
    assert [f.rule_id for f in findings] == ["SH208"]
    assert findings[0].severity == "warning"
    assert "matches no parameter" in findings[0].message
    assert "qkv_proj" in findings[0].location
    # a matching param set is clean
    assert sharding_lint.lint_partition_rules(
        rules, [("blk.attn.qkv_proj.weight", p)], mesh24) == []


def test_sh208_scalars_exempt_from_fallthrough(mesh24):
    """Scalar / size-1 leaves are never worth sharding: no finding
    even when no rule matches them."""
    scalar = paddle.create_parameter([1], "float32")
    findings = sharding_lint.lint_partition_rules(
        [(r"weight$", (None, "mp"))], [("step_count", scalar)], mesh24)
    # only the dead-rule warning may fire — never a fall-through error
    assert all("matches no parameter" in f.message for f in findings)


def test_apply_time_rank_validation_names_param(mesh24):
    """Satellite: ShardedTrainStep/shard_model raise a clear error
    naming the parameter instead of an opaque JAX trace error."""
    from paddle_tpu.distributed.sharded_train import shard_model
    net = paddle.nn.Linear(8, 8)
    net.bias.mesh_axes = ("mp", None)      # rank-2 spec on a rank-1 bias
    with pytest.raises(ValueError, match="'bias'.*rank"):
        shard_model(net, mesh24)


# ---------------------------------------------------------------------------
# collective order (CO)
# ---------------------------------------------------------------------------

def test_co301_injected_rank_order_mismatch_no_execution():
    """Acceptance: the checker catches an injected rank-order mismatch
    recorded through the real collective.py span hooks, without
    executing any collective (no mesh, pure host bookkeeping)."""
    from paddle_tpu.distributed import collective
    t = paddle.ones([4])
    with collective_order.capture(rank=0) as tr0:
        collective.all_reduce(t)
        collective.broadcast(t, src=0)
    with collective_order.capture(rank=1) as tr1:
        collective.broadcast(t, src=0)      # swapped order: deadlock
        collective.all_reduce(t)
    findings = collective_order.verify_ranks([tr0, tr1])
    assert _rules(findings) == ["CO301"]
    assert findings[0].severity == SEV_ERROR
    assert "rank" in findings[0].message


def test_co_matching_ranks_clean():
    from paddle_tpu.distributed import collective
    traces = []
    for rank in range(2):
        t = paddle.ones([4])
        with collective_order.capture(rank=rank) as tr:
            collective.all_reduce(t)
            collective.broadcast(t, src=0)
        traces.append(tr)
    assert collective_order.verify_ranks(traces) == []
    # signatures carry op/shape/dtype for the report
    sig = traces[0].sigs[0]
    assert sig.op == "all_reduce" and sig.shape == (4,)


def test_co302_extra_collective_on_one_rank():
    mk = lambda op: collective_order.CollectiveSig(  # noqa: E731
        op, None, (2,), "float32", "here")
    t0 = (0, [mk("psum")])
    t1 = (1, [mk("psum"), mk("all_gather")])
    findings = collective_order.verify_ranks([t0, t1])
    assert _rules(findings) == ["CO302"]
    assert "extra collective" in findings[0].message


def test_co_capture_records_shard_map_primitives_at_trace_time():
    """Traced-regime collectives (psum & co) also land in the capture —
    recorded while TRACING a shard_map region, nothing dispatched."""
    from paddle_tpu.distributed import collective
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))

    def body(v):
        return collective.psum(paddle.Tensor(v), "dp")._value

    f = _shard_map(body, mesh, P("dp"), P())
    with collective_order.capture(rank=0) as tr:
        jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4,), jnp.float32))
    assert [s.op for s in tr] == ["psum"]
    assert tr.sigs[0].axis == "dp"


# ---------------------------------------------------------------------------
# framework lint (FW)
# ---------------------------------------------------------------------------

_TRACER_LEAK = """
import jax
class M:
    def build(self):
        def step(x):
            self.cache = x
            return x
        return jax.jit(step)
"""

_IMPURE = """
import time, jax
def outer():
    def step(x):
        return x * time.time()
    return jax.jit(step)
"""

_DEVICE_GET = """
import jax
def fetch(x):
    return jax.device_get(x)
"""

_BARE_PALLAS = """
def build(pl, kernel):
    return pl.pallas_call(kernel, grid=(1,))
"""

# interpret= present (FW404-clean) but no @register_kernel decorator:
# the kernel dodges every Kernel Doctor check -> FW405
_UNREGISTERED_PALLAS = """
def build(pl, kernel, interp):
    return pl.pallas_call(kernel, grid=(1,), interpret=interp)
"""

_CLEAN = """
import time, jax
from paddle_tpu.ops.kernel_registry import register_kernel
def host_timer():
    return time.time()          # impurity OUTSIDE traced fns is fine
def outer():
    def step(x):
        return x + 1
    return jax.jit(step)
@register_kernel("k", example=None)
def build(pl, kernel, interp):
    return pl.pallas_call(kernel, grid=(1,), interpret=interp)
"""


@pytest.mark.parametrize("src,rule", [
    (_TRACER_LEAK, "FW401"), (_IMPURE, "FW402"),
    (_DEVICE_GET, "FW403"), (_BARE_PALLAS, "FW404"),
    (_UNREGISTERED_PALLAS, "FW405")])
def test_fw_rules_fire(src, rule):
    assert rule in _rules(astlint.lint_source(src, "spec.py"))


def test_fw405_registered_site_is_clean():
    """The registry decorator (any spelling reaching register_kernel)
    clears FW405; the bare-pallas specimen fires BOTH FW404 and FW405
    (no escape hatch AND unregistered)."""
    rules = _rules(astlint.lint_source(_BARE_PALLAS, "spec.py"))
    assert "FW404" in rules and "FW405" in rules
    qualified = _CLEAN.replace(
        "@register_kernel(", "@kernel_registry.register_kernel(")
    assert astlint.lint_source(qualified, "ok.py") == []


def test_fw_clean_module():
    assert astlint.lint_source(_CLEAN, "ok.py") == []


def test_fw_pragma_disables():
    src = _DEVICE_GET.replace(
        "jax.device_get(x)",
        "jax.device_get(x)  # astlint: disable=FW403")
    assert astlint.lint_source(src, "ok.py") == []


def test_fw_tree_is_clean():
    """Satellite: paddle_tpu/ itself lints clean (every violation the
    tool found in-tree was fixed in this PR) — the ci.sh gate."""
    import os
    import paddle_tpu
    root = os.path.dirname(paddle_tpu.__file__)
    findings = astlint.lint_tree(root)
    assert findings == [], "\n".join(map(repr, findings))


# ---------------------------------------------------------------------------
# Finding model + doctor CLI end-to-end
# ---------------------------------------------------------------------------

def test_finding_model_and_summary():
    f = Finding("SH203", SEV_ERROR, "w", "boom", suggestion="pad")
    d = f.to_dict()
    assert d["family"] == "sharding" and d["suggestion"] == "pad"
    s = summarize([f, Finding("JX101", "warning", "x", "m")])
    assert s["n"] == 2 and s["by_family"] == {"sharding": 1, "jaxpr": 1}
    with pytest.raises(GraphDoctorError):
        emit([f], mode="strict")


def test_graphdoctor_cli_gpt_clean(tmp_path):
    """Acceptance: the doctor runs the in-repo GPT config under
    JAX_PLATFORMS=cpu, reports zero findings, and its selfcheck shows
    all four rule families firing."""
    import importlib.util
    import json
    import os
    spec = importlib.util.spec_from_file_location(
        "graphdoctor", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "graphdoctor.py"))
    gd = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gd)
    report_path = str(tmp_path / "doctor.json")
    rc = gd.main(["--model", "gpt", "--report", report_path])
    assert rc == 0
    report = json.load(open(report_path))
    assert report["findings"] == []
    fired = {fam for fam, fs in report["selfcheck"].items() if fs}
    assert fired == {"jaxpr", "sharding", "collective_order", "framework"}
    assert report["hbm_projection"]["per_device"]["total_bytes"] > 0
