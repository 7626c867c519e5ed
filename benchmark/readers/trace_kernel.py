"""Kernel time from the device trace, by name pattern.

args: pattern (regex on the op name); mode "time_share" (kernel time
over device busy time) or "roofline" (least time the chip could take
for the kernel's work over the time it took). For the roofline, `work`
names the driver's record of what the traced steps needed: `bytes` and
`flops` summed over the traced interval, or `flops_per_unit` with
`count_pattern`/`per_unit` to count the units (steps) from the trace.
Finds nothing, returns None: never 0.
"""
import re

from benchmark import trace_reduce, work


def read(args, run):
    tr = run["trace"]
    if tr is None:
        return None
    t = trace_reduce.kernel_seconds(tr, args["pattern"])
    if not t:
        return None
    if args["mode"] == "time_share":
        busy = trace_reduce.busy_seconds(tr)
        return 100.0 * t / busy if busy else None
    w = run["records"]["work"].get(args["work"])
    if not w:
        return None
    peaks = run["spec"].peaks["devices"][run["device_kind"]]
    flops, bytes_ = w.get("flops", 0.0), w.get("bytes", 0.0)
    if "flops_per_unit" in w:
        # the units (steps) inside the traced interval, counted from the
        # trace itself: `events_per_unit` events of `count_pattern` each
        rx = re.compile(args["count_pattern"])
        ops = tr.devices[sorted(tr.devices)[0]]
        n = sum(1 for name, _, _ in ops if rx.search(name))
        flops = w["flops_per_unit"] * n / w["events_per_unit"]
    share, _ = work.roofline_share(flops, bytes_, t,
                                   peaks["bf16_flops_per_s"],
                                   peaks["hbm_bytes_per_s"])
    return share
