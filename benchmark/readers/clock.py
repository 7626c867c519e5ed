"""A time the harness took on its own clock. args: key."""


def read(args, run):
    return run["records"].get("clock", {}).get(args["key"])
