"""Latency arithmetic of the serving cells, on the harness's own clock.

Every request is timed from the moment it was DUE on the schedule, not
from when the generator got round to submitting it, so a stall charges
the requests queued behind it and a late generator shows in the tail.
"""
from benchmark.harness import percentile


def request_latencies(rows, t_end):
    """rows: per request a dict of `due` (absolute time it was due),
    `submitted` (or None), `times` (delivery time of each token) and
    `finished`. Returns (ttft_ms, tpot_ms, late_ms) lists. A request
    that failed or had not finished by `t_end` (the end of the drain)
    counts as the worst: it is charged its whole wait until `t_end` in
    both lists. A row with `cut` set was stopped by the harness itself
    when a backlog's window closed: it gives what it has (a first token,
    a gap between tokens) and is charged nothing for the rest."""
    ttft, tpot, late = [], [], []
    for r in rows:
        if r["submitted"] is not None:
            late.append((r["submitted"] - r["due"]) * 1e3)
        times = r["times"]
        if r["finished"] and times:
            ttft.append((times[0] - r["due"]) * 1e3)
            if len(times) > 1:
                tpot.append((times[-1] - times[0]) * 1e3 / (len(times) - 1))
        elif r.get("cut"):
            if times:
                ttft.append((times[0] - r["due"]) * 1e3)
            if len(times) > 1:
                tpot.append((times[-1] - times[0]) * 1e3 / (len(times) - 1))
        else:
            worst = (t_end - r["due"]) * 1e3
            ttft.append(worst)
            tpot.append(worst)
    return ttft, tpot, late


def delivered_in_window(rows, t_close):
    """Output tokens delivered to clients up to the close of the window
    by requests that did not fail, counted when delivered."""
    return sum(sum(1 for t in r["times"] if t <= t_close)
               for r in rows if not r.get("failed"))


def tails(ttft, tpot):
    return {"ttft_p95_ms": percentile(ttft, 95),
            "tpot_p95_ms": percentile(tpot, 95) if tpot else None,
            "ttft_p50_ms": percentile(ttft, 50),
            "tpot_p50_ms": percentile(tpot, 50) if tpot else None,
            "beyond_p95": len(ttft) - int(0.95 * len(ttft))}
