"""Kernel Doctor (paddle_tpu/analysis/kernel_lint.py + the kernel
registry): KN501 grid races on synthetic and real kernels, KN502 VMEM
boundaries, KN503 cost drift both directions, KN504 seeded fallback
fuzzing, KN505 grid-spec sanity, the single-sourced support
predicates, the typed kernel_lint records, and the kerneldoctor CLI
gate."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.analysis import kernel_lint
from paddle_tpu.analysis.kernel_lint import (
    capture_kernels, check_cost, check_fallback_parity, check_grid_races,
    check_gridspec, check_vmem, lint_kernel, trace_kernel_jaxprs)
from paddle_tpu.ops.kernel_registry import (
    KernelRegistry, PallasKernel, VMEM_BUDGET, block_bytes, fits_vmem,
    get_kernel, register_kernel, registered_kernels, vmem_footprint)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(findings):
    return [f.rule_id for f in findings]


def _capture(name, seed=0):
    reg = get_kernel(name)
    args, kwargs = reg.example(np.random.default_rng(seed))
    caps, _ = capture_kernels(reg.fn, args, kwargs, name=name)
    return caps, (args, kwargs), reg


# ---------------------------------------------------------------------------
# KN501: grid races
# ---------------------------------------------------------------------------

def _sum_kernel(x_ref, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += x_ref[...]


def _racy_entry(x, parallel):
    cp = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel")) if parallel else None
    kw = {"compiler_params": cp} if cp else {}
    return pl.pallas_call(
        _sum_kernel, grid=(2, 4),
        in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
        interpret=True, **kw)(x)


def test_kn501_synthetic_racy_kernel():
    """The flash accumulation pattern (inner axis revisits the output
    window) races iff the axis is marked parallel; sequential default
    is clean — the generalized sequential-flush invariant."""
    x = np.ones((16, 512), np.float32)
    caps, _ = capture_kernels(_racy_entry, (x, True), name="racy")
    findings = check_grid_races(caps[0])
    assert _rules(findings) == ["KN501"]
    assert "axis 1" in findings[0].message
    caps, _ = capture_kernels(_racy_entry, (x, False), name="seq")
    assert check_grid_races(caps[0]) == []


@pytest.mark.parametrize("name,revisits", [
    ("flash_fwd_tri", True), ("flash_bwd_merged_tri", True),
    ("paged_decode", False)])
def test_kn501_real_kernels_clean_and_parallelizable_copy_fails(
        name, revisits):
    """The real tri/paged kernels pass KN501 as shipped (all axes
    sequential). The tri kernels accumulate across a revisiting axis
    (the flat-T axis): force-parallelizing every axis of the SAME
    captured grid must fail — proof the rule sees the revisits, not the
    absence of the keyword. The paged kernel's grid is one step a slot
    and its tile loop runs inside the step, so no axis revisits an
    output block and a parallel copy raises nothing; its slot axis is
    sequential all the same, and says so, because the tile buffers and
    their in-flight copies pass from one slot to the next (state KN501
    does not model: it follows output blocks only)."""
    caps, _, _ = _capture(name)
    for cap in caps:
        assert check_grid_races(cap) == []
        bad = check_grid_races(
            cap, semantics=("parallel",) * len(cap.grid))
        if revisits:
            assert bad and all(f.rule_id == "KN501" for f in bad), \
                f"{name}: every-axis-parallel copy produced no race"
        else:
            assert bad == []
            assert len(cap.grid) == 1
            assert cap.semantics() == ("arbitrary",)


def test_kn501_decode_l_tile_axis_must_stay_sequential():
    """The fused decode kernel accumulates its online softmax across
    L-tiles; at a cache long enough to tile (nl > 1) the L axis
    revisits each row's output block, so a parallel marking races."""
    from paddle_tpu.ops.pallas_decode import decode_attention

    rng = np.random.default_rng(0)
    q = rng.standard_normal((1, 1, 128)).astype(np.float32)
    kb = rng.standard_normal((1, 4096, 128)).astype(np.float32)
    caps, _ = capture_kernels(
        decode_attention, (q, kb, kb, np.int32(100), 4), name="decode")
    (cap,) = caps
    assert cap.grid[1] >= 2, "cache did not tile; the test lost its bite"
    assert check_grid_races(cap) == []
    bad = check_grid_races(cap, semantics=("arbitrary", "parallel"))
    assert bad and all(f.rule_id == "KN501" for f in bad)


@pytest.mark.parametrize("name", ["moe_gather", "moe_combine"])
def test_kn501_moe_kernels_are_genuinely_parallelizable(name):
    """Counter-case: the MoE gather/combine grids write DISJOINT output
    blocks per step (no revisits), so KN501 stays silent even under a
    parallel marking — the rule flags races, not parallelism."""
    caps, _, _ = _capture(name)
    for cap in caps:
        assert check_grid_races(cap) == []
        assert check_grid_races(
            cap, semantics=("parallel",) * len(cap.grid)) == []


# ---------------------------------------------------------------------------
# KN502: VMEM projection boundaries
# ---------------------------------------------------------------------------

def test_kn502_exact_boundary():
    """Exactly-at-budget passes; one byte over fails."""
    blocks = [((64, 128), np.dtype(np.float32))]
    total = vmem_footprint(moving=blocks)
    assert total == 2 * 64 * 128 * 4
    assert fits_vmem(moving=blocks, budget=total)
    assert not fits_vmem(moving=blocks, budget=total - 1)
    # end-to-end through a real capture
    caps, _, _ = _capture("moe_gather")
    total = kernel_lint.project_vmem(caps[0])[0]
    assert check_vmem(caps[0], budget=total) == []
    over = check_vmem(caps[0], budget=total - 1)
    assert _rules(over) == ["KN502"]
    assert str(total) in over[0].message


def test_kn502_dtype_sensitivity():
    """The same block shape flips the verdict with its dtype — f32
    blows the budget where bf16 fits."""
    shape = (11000, 128)
    assert 2 * block_bytes(shape, jnp.bfloat16) <= VMEM_BUDGET
    assert 2 * block_bytes(shape, np.float32) > VMEM_BUDGET
    assert fits_vmem(moving=[(shape, jnp.bfloat16)])
    assert not fits_vmem(moving=[(shape, np.float32)])


def test_kn502_resident_vs_moving():
    """Constant-index blocks are charged once (held resident), moving
    blocks twice (double-buffered) — the distinction the MoE gather's
    VMEM-resident source depends on. A multi-step grid is forced so
    the output block actually moves."""
    from paddle_tpu.moe.kernels import _gather_pallas

    src = np.ones((48, 128), np.float32)
    idx = np.zeros((300,), np.int32)          # pads to 384 -> grid (3,)
    caps, _ = capture_kernels(_gather_pallas, (src, idx), name="g")
    total, moving, resident, _ = kernel_lint.project_vmem(caps[0])
    # src (constant index_map) resident, the output block moving
    assert len(resident) == 1 and len(moving) == 1
    assert resident[0][0] == (48, 128)
    assert total == 48 * 128 * 4 + 2 * moving[0][0][0] * 128 * 4


# ---------------------------------------------------------------------------
# KN503: cost honesty, both directions
# ---------------------------------------------------------------------------

def _dot_kernel(x_ref, w_ref, o_ref):
    o_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _dot_entry(x, w, flops_factor=1.0):
    M, K = x.shape
    N = w.shape[1]
    true_flops = 2 * M * N * K
    return pl.pallas_call(
        _dot_kernel, grid=(1,),
        in_specs=[pl.BlockSpec((M, K), lambda i: (0, 0)),
                  pl.BlockSpec((K, N), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((M, N), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=int(true_flops * flops_factor),
            bytes_accessed=(M * K + K * N + M * N) * 4,
            transcendentals=0),
        interpret=True)(x, w)


@pytest.mark.parametrize("factor,fires", [
    (1.0, False),      # honest
    (4.0, True),       # overdeclared 4x
    (0.25, True),      # underdeclared 4x
])
def test_kn503_drift_both_directions(factor, fires):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((256, 256)).astype(np.float32)
    w = rng.standard_normal((256, 256)).astype(np.float32)
    caps, _ = capture_kernels(_dot_entry, (x, w, factor), name="dot")
    bodies = trace_kernel_jaxprs(_dot_entry, (x, w, factor))
    findings, counted = check_cost(caps[0], bodies[0])
    assert counted["flops"] == 2 * 256 * 256 * 256
    assert (_rules(findings) == ["KN503"]) == fires, findings


def test_kn503_in_tree_estimates_honest():
    """Every in-tree kernel that declares a CostEstimate passes the
    drift rule — the declared flops ARE the traced kernel's work."""
    for name in ("flash_fwd_tri", "flash_fwd_rect",
                 "flash_bwd_merged_tri", "moe_gather", "moe_combine"):
        caps, (args, kwargs), reg = _capture(name)
        bodies = trace_kernel_jaxprs(reg.fn, args, kwargs)
        for cap, body in zip(caps, bodies):
            findings, _ = check_cost(cap, body)
            assert findings == [], f"{name}: {findings}"


# ---------------------------------------------------------------------------
# KN504: seeded fallback-parity fuzzing
# ---------------------------------------------------------------------------

def test_kn504_seeded_fuzz_reproducible():
    """The same seed derives the same shapes AND values, so a parity
    failure replays bit-for-bit."""
    reg = get_kernel("moe_gather")
    (a1, _), (a2, _) = (reg.example(np.random.default_rng(7))
                        for _ in range(2))
    assert a1[0].shape == a2[0].shape
    np.testing.assert_array_equal(a1[0], a2[0])
    np.testing.assert_array_equal(a1[1], a2[1])


def test_kn504_parity_passes_and_detects_divergence():
    assert check_fallback_parity(get_kernel("moe_gather"),
                                 seeds=(0, 1)) == []
    assert check_fallback_parity(get_kernel("moe_combine"),
                                 seeds=(0, 1)) == []
    # a deliberately-wrong fallback must be caught, naming the seed
    good = get_kernel("int8_matvec")
    bad = PallasKernel(
        "int8_matvec_bad", good.fn, good.example,
        fallback=lambda h, wq, scale: 2.0 * good.fallback(h, wq, scale),
        tol=good.tol)
    findings = check_fallback_parity(bad, seeds=(3,))
    assert _rules(findings) == ["KN504"]
    assert "seed 3" in findings[0].message


# ---------------------------------------------------------------------------
# KN505: scalar-prefetch / grid-spec sanity
# ---------------------------------------------------------------------------

def test_kn505_paged_kernel_prefetch_clean():
    """The scalar-prefetched paged decode kernel: 2 small int32
    prefetch operands (the block table and the contexts, which the
    kernel's own page copies and its loop bound read), pure in-bounds
    index_maps on the two blocked operands (q, out: one block a slot,
    full coverage), and the arenas left whole in HBM: no block shape,
    so no index_map to check and nothing of them in the VMEM
    projection but the kernel's two tile buffers each."""
    from paddle_tpu.ops.pallas_decode import paged_decode_tile_rows

    caps, (args, kwargs), _ = _capture("paged_decode")
    (cap,) = caps
    assert cap.num_scalar_prefetch == 2
    assert all(np.asarray(v).dtype.kind in "iu"
               for v in cap.prefetch_values)
    assert check_gridspec(cap) == []
    q, kp, vp, tables, ctx, n_heads = args
    S = q.shape[0]
    # q regrouped: one row a member of each K/V head, the arenas' width
    kv_heads, nh = kwargs["kv_heads"], kp.shape[2]
    group = n_heads // kv_heads
    assert cap.grid == (S,)
    blocked = [s.block_shape for s in cap.in_specs]
    assert blocked == [(1, group, nh), None, None]
    assert [s.array_shape for s in cap.in_specs[1:]] == \
        [kp.shape, vp.shape]
    rows = paged_decode_tile_rows(kp.shape[1], nh, kv_heads,
                                  kp.dtype.itemsize, tables.shape[1], group)
    bs = kp.shape[1]
    tile = ((2, rows // bs, bs, nh), np.dtype(kp.dtype))
    assert cap.scratch.count(tile) == 2
    assert check_vmem(cap) == []


def test_kn505_oversized_prefetch_and_coverage_hole():
    def entry(tab, x, cover):
        from jax.experimental.pallas import tpu as pltpu
        out_map = (lambda i, t: (i,)) if cover else (lambda i, t: (0,))
        gs = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(2,),
            in_specs=[pl.BlockSpec((8, 128), lambda i, t: (i, 0))],
            out_specs=pl.BlockSpec((8, 128),
                                   lambda i, t: (out_map(i, t)[0], 0)))
        return pl.pallas_call(
            lambda t_ref, x_ref, o_ref: o_ref.__setitem__(
                ..., x_ref[...]),
            grid_spec=gs,
            out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
            interpret=True)(tab, x)

    x = np.zeros((16, 128), np.float32)
    # tensor-sized float array smuggled onto the prefetch channel
    big = np.zeros((512, 256), np.float32)       # 512 KiB, 2-D
    caps, _ = capture_kernels(entry, (big, x, True), name="bigpf")
    findings = check_gridspec(caps[0])
    assert "KN505" in _rules(findings)
    assert "prefetch" in findings[0].message
    # grid covers only block 0 of a 2-block output
    tab = np.zeros((4,), np.int32)
    caps, _ = capture_kernels(entry, (tab, x, False), name="hole")
    findings = check_gridspec(caps[0])
    assert any("does not cover" in f.message for f in findings)


# ---------------------------------------------------------------------------
# single-sourced support predicates (delegation parity)
# ---------------------------------------------------------------------------

def test_moe_supported_parity_on_shipped_configs():
    """moe_kernel_supported now derives its n_src VMEM-residency bound
    from the KN502 projection; on the shipped configs it must agree
    with the pre-registry hand formula (n_src + block) * d * itemsize
    <= budget (the new model adds double-buffering of the output block
    — a 64 KiB refinement invisible away from the boundary)."""
    from paddle_tpu.moe.kernels import _BLOCK_ROWS, moe_kernel_supported

    def old(d, dtype, n_src):
        if d % 128:
            return False
        it = jnp.dtype(dtype).itemsize
        return (n_src + _BLOCK_ROWS) * d * it <= VMEM_BUDGET

    shipped = [
        (128, jnp.float32, 4096), (512, jnp.float32, 2048),
        (768, jnp.bfloat16, 8192), (1024, jnp.float32, 2048),
        (4096, jnp.bfloat16, 256), (1024, jnp.float32, 1_000_000),
        (128, jnp.bfloat16, 16384),
    ]
    for d, dtype, n_src in shipped:
        assert moe_kernel_supported(d, dtype, n_src) == \
            old(d, dtype, n_src), (d, dtype, n_src)


def test_paged_supported_parity_on_shipped_configs():
    """paged_decode_supported is the tile policy's answer: a page of
    whole packed sublane tiles, whole lanes, at most 128 heads, and a
    tile of K and V in two buffers each that fits VMEM_BUDGET by
    kernel_registry.vmem_footprint. On the shipped configs it admits
    what the one-page-a-step gate admitted."""
    from paddle_tpu.ops.pallas_decode import (_COLS, _paged_footprint,
                                              decode_attention_supported,
                                              paged_decode_supported,
                                              paged_decode_tile_rows)

    shipped = [(16, 768, 12, 2, True), (16, 5120, 40, 2, True),
               (32, 4096, 32, 2, True), (8, 128, 4, 4, True),
               (16, 768, 200, 2, False), (10, 768, 12, 2, False)]
    for bs, hidden, n_heads, it, admitted in shipped:
        rows = paged_decode_tile_rows(bs, hidden, n_heads, it, _COLS) \
            if n_heads <= _COLS and bs % 8 == 0 else 0
        assert paged_decode_supported(bs, hidden, n_heads, it) \
            == admitted == (rows > 0), (bs, hidden, n_heads)
        if rows:
            assert rows % bs == 0 and rows % _COLS == 0
            assert _paged_footprint(rows, hidden, n_heads, it) \
                <= VMEM_BUDGET
            # and the next tile up would not have fitted, or is past
            # the policy's most
            assert rows == 512 or _paged_footprint(
                rows + _COLS, hidden, n_heads, it) > VMEM_BUDGET
    # the dense gate keeps covering every real model layout
    assert decode_attention_supported(2048, 768, 12)
    assert decode_attention_supported(4096, 5120, 40)


# ---------------------------------------------------------------------------
# registry coverage + records + CLI
# ---------------------------------------------------------------------------

def test_registry_covers_every_pallas_site():
    """The acceptance grep, machine-checked BOTH ways: no pallas_call
    under paddle_tpu/ outside a @register_kernel function (FW405), and
    the registered functions are exactly the functions the AST sweep
    sees containing sites — a stale registration covering nothing is
    as much a hole as an unregistered site."""
    root = os.path.join(REPO, "paddle_tpu")
    assert kernel_lint.unregistered_pallas_sites(root) == []
    regs = registered_kernels()
    assert len(regs) >= 12
    assert {"flash_fwd_tri", "flash_bwd_merged_tri", "paged_decode",
            "decode_fused", "int8_matvec", "moe_gather", "moe_combine",
            "layernorm_fused"} <= set(regs.names())
    swept = kernel_lint.pallas_site_functions(root)
    registered_fns = {r.fn_name for r in regs}
    assert set(swept) == registered_fns, (
        f"stale registrations: {registered_fns - set(swept)}; "
        f"uncovered site functions: {set(swept) - registered_fns}")


def test_registry_rejects_duplicate_names():
    reg = KernelRegistry()

    @register_kernel("dup", example=None, registry=reg)
    def a():
        pass

    with pytest.raises(ValueError, match="registered twice"):
        @register_kernel("dup", example=None, registry=reg)
        def b():
            pass


def test_kernel_record_schema_and_cross_rules(tmp_path):
    from paddle_tpu.telemetry.sink import (make_kernel_record,
                                           validate_step_record)
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_check

    clean = make_kernel_record(
        "k1", findings=(), module="m", grid=(2, 4), vmem_bytes=1000,
        vmem_budget=VMEM_BUDGET, flops_declared=100, flops_counted=100)
    assert validate_step_record(clean) == []
    f = {"rule": "KN501", "message": "race"}
    dirty = make_kernel_record("k2", findings=[f])
    assert validate_step_record(dirty) == []
    # count/list disagreement and unknown rules fail per-record
    bad = dict(clean, n_findings=2)
    assert any("disagree" in p for p in validate_step_record(bad))
    bad2 = make_kernel_record("k3", findings=[{"rule": "XX999",
                                               "message": "?"}])
    assert any("vocabulary" in p for p in validate_step_record(bad2))

    def check(records):
        p = tmp_path / "kl.jsonl"
        p.write_text("".join(json.dumps(r) + "\n" for r in records))
        return trace_check.check_metrics_jsonl(str(p))[-1]

    assert check([clean, dirty]) == []
    # over-budget projection with a clean verdict: the cross-rule fires
    sneaky = make_kernel_record("k4", findings=(),
                                vmem_bytes=VMEM_BUDGET + 1,
                                vmem_budget=VMEM_BUDGET)
    assert any("KN502" in p for p in check([sneaky]))
    # silent flops drift
    lying = make_kernel_record("k5", findings=(),
                               flops_declared=100_000_000,
                               flops_counted=10_000_000)
    assert any("KN503" in p for p in check([lying]))
    # contradictory verdicts for one kernel
    assert any("stale" in p for p in check([clean,
                                            dict(dirty, kernel="k1")]))


def test_specimens_are_caught_by_name():
    """The checked-in broken specimens (the ci.sh stage-3 gate): the
    racy grid fires KN501 and the over-VMEM BlockSpec fires KN502,
    each naming its kernel."""
    import importlib.util

    for fname, rule, kname in (
            ("kernel_racy.py", "KN501", "specimen_racy_grid"),
            ("kernel_overvmem.py", "KN502", "specimen_overvmem_block")):
        path = os.path.join(REPO, "tools", "specimens", fname)
        spec = importlib.util.spec_from_file_location(
            fname[:-3], path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        (reg,) = list(mod.SPECIMENS)
        findings, _ = lint_kernel(reg)
        assert any(f.rule_id == rule and kname in f.location
                   for f in findings), (fname, findings)


@pytest.mark.slow
def test_full_registry_fuzz_sweep():
    """Every registered kernel, all five rules, three fuzz seeds —
    the exhaustive pass ci.sh runs via kerneldoctor."""
    findings, infos = kernel_lint.lint_registry(seeds=(0, 1, 2))
    assert findings == [], "\n".join(map(repr, findings))
    assert len(infos) >= 12
    assert all(i["n_calls"] >= 1 for i in infos)


@pytest.mark.slow
def test_kerneldoctor_cli_selfcheck():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "kerneldoctor.py"),
         "--selfcheck"], capture_output=True, text=True, env=env,
        cwd=REPO, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "selfcheck OK" in out.stdout


@pytest.mark.slow
def test_kerneldoctor_cli_telemetry(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    tele = tmp_path / "kl.jsonl"
    report = tmp_path / "report.json"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "kerneldoctor.py"),
         "--telemetry", str(tele), "--report", str(report)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_check
    problems, stats = trace_check.check_pair(str(tele))
    assert problems == []
    assert stats["n_kernel"] >= 12
    rep = json.loads(report.read_text())
    assert rep["summary"]["n"] == 0
