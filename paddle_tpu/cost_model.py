"""Cost model: per-op cost profiling of a compiled program.

Reference surface: `python/paddle/cost_model/cost_model.py` +
`framework/ir/cost_model.cc` — run a Program under the profiler and
report per-op time for pass/placement decisions.

TPU-native design: the "ops" of a compiled program are XLA's fused
computations, not framework ops, so the honest cost model reads the
compiled executable itself: static costs from XLA's cost analysis
(flops, bytes accessed — the roofline inputs) and measured wall time
from real dispatches.  `ProgramCostModel` adds a per-HLO-instruction
breakdown parsed from the optimized HLO text, giving the same
"which op dominates" feedback the reference's per-op profile gives.
"""
import time

import numpy as np

# aggregate per-chip ICI bandwidth (bytes/s, all links summed) by chip
# generation — the wire the collective estimates below divide by.
# Two-level (multi-slice) plans cross DCN on the outer axis; that is
# modeled as a bandwidth discount on the axis that rides it.
ICI_BW_BY_CHIP = {
    "v4": 300e9,       # 2.4 Tbps
    "v5e": 200e9,      # 1.6 Tbps
    "v5p": 600e9,      # 4.8 Tbps
    "v6e": 400e9,      # 3.2 Tbps
}
# DCN (data-center network) per-host bandwidth for the outer axis of a
# two-level plan — order-of-magnitude below ICI, which is exactly why
# the planner must put the low-volume axis (dp grads, once per step)
# there and keep TP's per-layer allreduces on ICI
DCN_BW_BYTES = 25e9


def _chip_peak_flops(chip):
    """bf16 peak FLOP/s for a chip name via the shared telemetry table
    ('v5p' -> 459e12); None when unknown (the caller substitutes a
    neutral constant — RELATIVE layout ranking survives, absolute step
    times do not)."""
    from .telemetry.mfu import device_peak_flops
    return device_peak_flops(chip)


def _allreduce_wire_bytes(nbytes, n):
    """Ring all-reduce wire traffic per participant: 2(n-1)/n * bytes
    (reduce-scatter + all-gather halves). n <= 1 is free."""
    if n <= 1:
        return 0.0
    return 2.0 * (n - 1) / n * float(nbytes)


def _allgather_wire_bytes(nbytes, n):
    """(n-1)/n * bytes per participant for an all-gather (or a
    reduce-scatter — same wire volume, opposite direction)."""
    if n <= 1:
        return 0.0
    return (n - 1) / n * float(nbytes)


def estimate_layout_cost(*, n_params, num_layers, hidden_size,
                         seq_len, ffn_hidden_size=None, vocab_size=None,
                         dp=1, pp=1, mp=1, sp=1, ep=1, zero_stage=1,
                         micro_batch=1, num_micro=None, chip="v5p",
                         param_dtype_bytes=4, compute_dtype_bytes=2,
                         dp_over_dcn=False, peak_flops=None, ici_bw=None,
                         comm_calibration=None):
    """Analytic per-step cost of one dp x pp x mp x sp x ep layout:
    compute seconds from the PaLM-style FLOPs count against the chip's
    bf16 peak (pipeline-bubble adjusted), plus per-collective ICI
    seconds for every communication the layout implies. No overlap is
    assumed — the estimate is an upper bound, and because every
    candidate is scored the same way it is a fair RANKING function,
    which is all the planner needs (the roofline-honest numbers come
    from the compile observatory after the winner compiles).

    Communication model (per chip, per step):
      - dp gradient all-reduce of the local param shard (ZeRO >= 2
        issues reduce-scatter + all-gather — same wire bytes); ZeRO-3
        additionally all-gathers the bf16 params in fwd AND bwd;
      - mp: 4 activation all-reduces per transformer layer (attn fwd,
        mlp fwd, and their backward mirrors — Megatron's count);
      - sp: ring attention circulates K and V around the sp ring,
        (sp-1) hops forward, doubled for backward;
      - pp: one boundary activation send per microbatch per direction;
      - ep: token dispatch/combine all-to-all, 2 forward + 2 backward.

    num_micro defaults to 2*pp (the 1F1B in-flight bound — also what
    the memory planner charges). dp_over_dcn marks the dp axis as the
    outer axis of a two-level (multi-slice) plan: its collectives then
    divide by DCN bandwidth, not ICI.

    comm_calibration: optional {op: factor} multiplicative corrections
    from MEASURED collective latencies (the mesh observatory —
    telemetry/comm_obs via planner.calibration_from_comm_records; op
    names are comm_obs.SWEEP_OPS). Each comm term is scaled by its
    collective's factor (dp grads + tp allreduces -> psum, the ZeRO-3
    gather -> all_gather, sp/pp ring hops -> ppermute, ep
    dispatch/combine -> all_to_all); a factor of 2.0 means this mesh
    measured that collective at half the analytic bandwidth, so its
    terms cost double. Missing ops default to 1.0 — analytic. This is
    the comm sibling of the planner's HBM `calibration` ratio.
    """
    n_chips = dp * pp * mp * sp * ep
    if num_micro is None:
        num_micro = max(1, 2 * pp)
    if peak_flops is None:
        peak_flops = _chip_peak_flops(chip) or 275e12
    if ici_bw is None:
        ici_bw = ICI_BW_BY_CHIP.get(chip, 300e9)
    dp_bw = DCN_BW_BYTES if (dp_over_dcn and dp > 1) else ici_bw

    from .telemetry.mfu import model_flops_per_token
    tokens = dp * micro_batch * num_micro * seq_len
    total_flops = model_flops_per_token(
        n_params, num_layers=num_layers, hidden_size=hidden_size,
        seq_len=seq_len) * tokens
    compute_s = total_flops / n_chips / peak_flops
    # pipeline bubble: of (num_micro + pp - 1) schedule slots only
    # num_micro do useful work per stage
    bubble_frac = (pp - 1) / (num_micro + pp - 1) if pp > 1 else 0.0
    compute_s /= max(1e-9, 1.0 - bubble_frac)

    # measured per-collective corrections (mesh observatory); missing
    # ops stay analytic (factor 1.0)
    cal = comm_calibration or {}
    _c = lambda op: float(cal.get(op, 1.0))  # noqa: E731

    local_layers = max(1, -(-num_layers // pp))
    # per-chip shard of the gradient (f32 master grads)
    grad_shard = n_params * param_dtype_bytes / (mp * pp)
    dp_grad_s = _allreduce_wire_bytes(grad_shard, dp) / dp_bw * _c("psum")
    if zero_stage >= 3:
        # bf16 param all-gather before use, fwd + bwd recompute
        gather = _allgather_wire_bytes(
            n_params * compute_dtype_bytes / (mp * pp), dp)
        dp_grad_s += 2 * gather / dp_bw * _c("all_gather")

    # activation tile entering/leaving each TP region
    act_tile = micro_batch * (seq_len // sp) * hidden_size \
        * compute_dtype_bytes
    tp_s = (4 * local_layers * num_micro *
            _allreduce_wire_bytes(act_tile, mp)) / ici_bw * _c("psum")

    # K and V blocks circulating the sp ring; act_tile is already the
    # per-device (seq/sp) local block, so each of the (sp-1) hops moves
    # the full kv_tile — no further /sp
    kv_tile = 2 * act_tile
    sp_s = (2 * local_layers * num_micro * (sp - 1) * kv_tile
            ) / ici_bw * _c("ppermute") if sp > 1 else 0.0

    pp_s = (2 * num_micro * act_tile / ici_bw) * _c("ppermute") \
        if pp > 1 else 0.0

    ep_s = (4 * local_layers * num_micro *
            _allgather_wire_bytes(act_tile, ep)) / ici_bw \
        * _c("all_to_all") if ep > 1 else 0.0

    comm_s = dp_grad_s + tp_s + sp_s + pp_s + ep_s
    step_s = compute_s + comm_s
    return {
        "step_time_s": step_s,
        "compute_s": compute_s,
        "comm_s": comm_s,
        "dp_grad_s": dp_grad_s,
        "tp_s": tp_s,
        "sp_s": sp_s,
        "pp_s": pp_s,
        "ep_s": ep_s,
        "bubble_frac": bubble_frac,
        "tokens_per_step": tokens,
        "flops_per_chip": total_flops / n_chips,
        "comm_frac": comm_s / step_s if step_s > 0 else 0.0,
        "n_chips": n_chips,
        "num_micro": num_micro,
    }


def layout_cost_from_config(cfg, *, chip="v5p", n_params=None, **layout):
    """`estimate_layout_cost` with the model dims pulled from a
    GPTConfig-shaped object (the planner's entry point)."""
    if n_params is None:
        from .planner.memory import gpt_params
        n_params = gpt_params(cfg)
    return estimate_layout_cost(
        n_params=n_params, num_layers=cfg.num_layers,
        hidden_size=cfg.hidden_size,
        ffn_hidden_size=cfg.ffn_hidden_size,
        vocab_size=cfg.vocab_size, seq_len=cfg.max_seq_len,
        chip=chip, **layout)


def _cost_analysis(compiled):
    """XLA's cost analysis of a compiled executable as one dict ({} when
    the executable carries none). Both the CPU backend and libtpu answer
    with a dict under jax 0.9.0; an error here is a real one and
    propagates — a profile built on swallowed zeros reads as "no
    FLOPs", and `flops_drift` cannot tell that from a measurement."""
    return compiled.cost_analysis() or {}


def profile_hlo_text(hlo, top_k=20):
    """Per-opcode breakdown of an optimized-HLO text dump: count
    instructions by opcode (fusions appear as 'fusion' — XLA's own unit
    of scheduling), skipping pure plumbing. The parsing half of
    `ProgramCostModel.instruction_profile`, split out so callers that
    already hold a compiled executable (telemetry.compile_obs) can
    profile `compiled.as_text()` without recompiling."""
    import collections
    import re

    counts = collections.Counter()
    for m in re.finditer(
            r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*[\w\[\]{}_,:\s/]*?"
            r"\b([a-z][\w\-]*)\(", hlo, re.M):
        op = m.group(1)
        if op in ("parameter", "constant", "tuple", "get-tuple-element",
                  "bitcast"):
            continue
        counts[op] += 1
    total = sum(counts.values())
    table = [{"op": op, "count": n, "share": round(n / total, 6)}
             for op, n in counts.most_common(top_k)]
    return {"n_instructions": total, "by_op": table}


class CostModel:
    """Profile a jittable function (or hapi Model-style Layer forward).

    `profile_measure(fn, example_args)` returns a dict with:
      - static flops / bytes_accessed (XLA cost analysis — exact, from
        the optimized executable)
      - measured mean wall time over `repeat` dispatches
      - achieved FLOP/s and arithmetic intensity (roofline position)
    """

    def __init__(self):
        self._last = None

    def profile_measure(self, fn, example_args, warmup=2, repeat=10):
        import jax

        jitted = jax.jit(fn)
        lowered = jitted.lower(*example_args)
        compiled = lowered.compile()
        ca = _cost_analysis(compiled)
        flops = float(ca.get("flops", 0.0))
        bytes_accessed = float(ca.get("bytes accessed", 0.0))

        out = None
        for _ in range(warmup):
            out = compiled(*example_args)
        jax.tree_util.tree_map(
            lambda x: np.asarray(x) if hasattr(x, "shape") else x, out)
        t0 = time.perf_counter()
        for _ in range(repeat):
            out = compiled(*example_args)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / repeat
        result = {
            "time_s": dt,
            "flops": flops,
            "bytes_accessed": bytes_accessed,
            "achieved_flops_per_s": flops / dt if dt > 0 else 0.0,
            "arithmetic_intensity": (flops / bytes_accessed
                                     if bytes_accessed else 0.0),
        }
        self._last = result
        return result

    def static_cost(self, fn, example_args):
        """Cost analysis only (no execution) — usable for placement
        decisions before any dispatch."""
        import jax
        compiled = jax.jit(fn).lower(*example_args).compile()
        ca = _cost_analysis(compiled)
        return {"flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0))}


class ProgramCostModel(CostModel):
    """Adds a per-instruction breakdown of the optimized HLO — the
    analog of the reference's per-op time table (`cost_model.cc`
    CostData::GetOpTimeMs), with static cost standing in for time on
    instructions XLA fused away."""

    def instruction_profile(self, fn, example_args, top_k=20):
        import jax

        compiled = jax.jit(fn).lower(*example_args).compile()
        return profile_hlo_text(compiled.as_text(), top_k=top_k)
