"""MFU / throughput accounting: model FLOPs x measured time / device peak.

One home for the three inputs every MFU number needs:

- model FLOPs per step — either analytic (PaLM-style 6N + attention term,
  `model_flops_per_token`), hook-counted (`hapi.flops.flops`), or exact
  from the compiled program (`hapi.flops.flops_compiled` /
  `cost_model.CostModel` — XLA's own cost analysis);
- measured step time — from the TelemetryRecorder;
- device peak FLOP/s — `device_peak_flops` below, keyed on the JAX
  device_kind string (bf16 peaks; the table bench.py's MFU numbers have
  always used, now shared).
"""
import jax


# bf16 peak FLOP/s per chip by device kind substring
PEAK_FLOPS_BY_KIND = {
    "v2": 45e12, "v3": 123e12, "v4": 275e12,
    "v5 lite": 197e12, "v5e": 197e12, "v5p": 459e12,
    "v6 lite": 918e12, "v6e": 918e12,
}

# peak HBM bandwidth (bytes/s) per chip by device kind substring — the
# second axis of the roofline the kernel observatory
# (telemetry/kernel_obs.py) places measured kernels on; same
# longest-substring keying as the FLOPs table so the two can never
# disagree about which chip they describe
PEAK_HBM_BW_BY_KIND = {
    "v2": 700e9, "v3": 900e9, "v4": 1228e9,
    "v5 lite": 819e9, "v5e": 819e9, "v5p": 2765e9,
    "v6 lite": 1638e9, "v6e": 1638e9,
}


def _match_kind(table, kind):
    """Longest-substring lookup of a device-kind string. kind=None
    reads the live default device, and there an accelerator with no
    row is an error, not a default: a utilization computed against a
    missing peak is silently 0. The CPU (and an explicit name nobody
    listed, e.g. a planner what-if chip) answers None."""
    live = None
    if kind is None:
        live = jax.devices()[0]
        kind = live.device_kind
    low = str(kind).lower()
    for key, val in sorted(table.items(), key=lambda kv: -len(kv[0])):
        if key in low:
            return val
    if live is not None and live.platform != "cpu":
        raise LookupError(
            f"device_kind {kind!r} (platform {live.platform!r}) has no "
            "row in the peak table; add one to telemetry/mfu.py with "
            "its source")
    return None


def device_peak_flops(kind=None):
    """Peak bf16 FLOP/s for a device-kind string (longest-substring match,
    e.g. 'TPU v5 lite' -> 197e12). kind=None reads the default jax
    device and raises for an accelerator the table does not list.
    Returns None on CPU backends — 'MFU not computable'."""
    return _match_kind(PEAK_FLOPS_BY_KIND, kind)


def device_peak_hbm_bw(kind=None):
    """Peak HBM bandwidth (bytes/s) for a device-kind string, same
    matching rules as device_peak_flops. None when unknown (CPU) —
    the roofline's bandwidth fraction is then not computable."""
    return _match_kind(PEAK_HBM_BW_BY_KIND, kind)


def model_flops_per_token(n_params, num_layers=0, hidden_size=0, seq_len=0):
    """PaLM-style train FLOPs per token: 6N for the parameter matmuls
    (fwd 2N + bwd 4N) plus 12*L*H*S for self-attention score/value work."""
    return 6 * int(n_params) + 12 * int(num_layers) * int(hidden_size) \
        * int(seq_len)


def mfu(flops_per_step, step_time_s, peak_flops=None, n_devices=1):
    """Model FLOPs utilization in [0, ~1]: achieved model FLOP/s over the
    aggregate peak. None when the peak is unknown (the CPU: there is no
    utilization to report, and 0.0 would read as a measurement); 0.0
    for a degenerate window, never NaN/inf."""
    if peak_flops is None:
        peak_flops = device_peak_flops()
    if not peak_flops:
        return None
    if not step_time_s or step_time_s <= 0:
        return 0.0
    return float(flops_per_step) / float(step_time_s) \
        / (float(peak_flops) * max(1, int(n_devices)))


def flops_drift(compiled_flops, analytic_flops):
    """Relative drift of the compiled program's cost-analysis FLOPs from
    the analytic number the MFU accounting multiplies by: (compiled -
    analytic) / analytic. MFU reports analytic_flops / (time * peak), so
    positive drift = the analytic table UNDERCOUNTS and the reported MFU
    UNDERSTATES real utilization; negative drift = the table overcounts
    and the reported MFU is inflated. None when either side is
    missing/zero (no cross-check possible)."""
    try:
        c, a = float(compiled_flops), float(analytic_flops)
    except (TypeError, ValueError):
        return None
    if c <= 0 or a <= 0:
        return None
    return (c - a) / a


def train_step_flops(loss_fn, example_batch, model=None):
    """EXACT per-step FLOPs: lower loss_fn through XLA with backprop (the
    `hapi.flops.flops_compiled` feedback loop — fusion and the dL/dW
    contractions included) and read the compiler's own cost analysis.
    Returns None when the backend refuses cost analysis; callers fall back
    to the analytic `model_flops_per_token` formula."""
    try:
        from ..hapi.flops import flops_compiled
        got = flops_compiled(loss_fn, list(example_batch),
                             backprop=True, net=model)
        flops = float(got.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception:
        return None
