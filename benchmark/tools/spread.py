"""Spread of each metric over the result lines of some runs, as the
contract measures it: the distance between the first and the third
quartile by `statistics.quantiles(values, n=4)`, as a share of the
median.

    python3 benchmark/tools/spread.py chiprun_out/chat_A*.out [...]
"""
import json
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else 0.0


def main(paths):
    rows = {}
    for p in paths:
        with open(p) as f:
            lines = [l for l in f.read().splitlines() if l.startswith("{")]
        if not lines:
            print(f"{p}: no result line")
            continue
        res = json.loads(lines[-1])
        if not res["correct"] or res["failed"]:
            print(f"{p}: correct={res['correct']} failed={res['failed']}")
        for k, v in res["metrics"].items():
            rows.setdefault(k, []).append(v["value"])
    for k, vals in rows.items():
        if len(vals) < 2:
            print(f"{k}: {vals}")
            continue
        print(f"{k}: n={len(vals)} median={statistics.median(vals):.6g} "
              f"min={min(vals):.6g} max={max(vals):.6g} "
              f"spread={100 * spread(vals):.3f}%")


if __name__ == "__main__":
    main(sys.argv[1:])
