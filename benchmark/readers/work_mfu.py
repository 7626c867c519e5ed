"""The whole step's share of the chip's peak: operations the traffic
needed per second (the driver's work record, from shapes and from the
traffic) over chips x peak, in %. args: work."""


def read(args, run):
    w = run["records"]["work"].get(args["work"])
    if not w or not w.get("flops_per_s"):
        return None
    peak = run["spec"].peaks["devices"][run["device_kind"]][
        "bf16_flops_per_s"]
    return 100.0 * w["flops_per_s"] / (peak * run["spec"].chips)
