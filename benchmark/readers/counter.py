"""A count or ratio the program keeps (engine counters, prefix
statistics, what a dispatch worked on) or that JAX's monitoring events
give (compiles, cache misses). args: group ("counters" | "compile"),
key."""


def read(args, run):
    if args.get("group", "counters") == "compile":
        return run["compile"].get(args["key"])
    return run["records"].get("counters", {}).get(args["key"])
