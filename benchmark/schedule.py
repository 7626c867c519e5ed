"""The one traffic generator: a traffic file's parameters -> a request
schedule -> the token arrays of one run.

The SCHEDULE (when each request is due, how long its prompt and its
answer are, which shared prefix it opens with) is drawn from the traffic
file's `schedule_seed` alone, so every run of a cell replays the same
requests at the same due times, whatever `--seed` is. `--seed` draws
only the token ids that fill that fixed structure (and the weights).

Arrivals are generated as cumulative gaps, so a shorter `--seconds`
replays a prefix of the same schedule.
"""
import json
import math

import numpy as np


def _lengths(rng, spec, n):
    kind = spec["dist"]
    if kind == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    elif kind == "uniform":
        x = rng.uniform(spec["min"], spec["max"] + 1, n)
    elif kind == "fixed":
        x = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo = spec.get("min", 1)
    hi = spec.get("max", spec.get("value"))
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def _arrivals(rng, spec, seconds):
    kind = spec["kind"]
    if kind == "backlog":           # everything due when the window opens
        return np.zeros(int(spec["count"]))
    if kind == "poisson":
        rate = float(spec["rate_per_s"])
        # gaps are drawn in fixed-size batches so that a longer window
        # extends, never re-draws, a shorter one
        due, t = [], 0.0
        while t < seconds:
            for gap in rng.exponential(1.0 / rate, 256):
                t += gap
                if t >= seconds:
                    break
                due.append(t)
        return np.asarray(due)
    raise ValueError(f"unknown arrival kind {kind!r}")


def build_schedule(traffic, seconds):
    """List of requests, in due order: dicts of due_s, prompt_len,
    output_len and prefix_id (-1: no shared prefix). A function of the
    traffic file and `seconds` only."""
    seed = int(traffic["schedule_seed"])
    due = _arrivals(np.random.default_rng([seed, 1]), traffic["arrivals"],
                    float(seconds))
    n = len(due)
    # lengths and prefixes are drawn for a fixed large count and cut, so
    # request i has the same sizes at any window length
    cap = max(n, 4096)
    plen = _lengths(np.random.default_rng([seed, 2]),
                    traffic["prompt_len"], cap)[:n]
    olen = _lengths(np.random.default_rng([seed, 3]),
                    traffic["output_len"], cap)[:n]
    shared = traffic.get("shared_prefix")
    if shared:
        ranks = np.arange(1, shared["count"] + 1, dtype=float)
        p = (1.0 / ranks) / np.sum(1.0 / ranks)     # popularity 1/rank
        pid = np.random.default_rng([seed, 4]).choice(
            shared["count"], size=cap, p=p)[:n]
    else:
        pid = np.full(n, -1)
    return [{"due_s": float(due[i]), "prompt_len": int(plen[i]),
             "output_len": int(olen[i]), "prefix_id": int(pid[i])}
            for i in range(n)]


def schedule_bytes(schedule):
    """Canonical serialisation (what 'byte-identical' is judged on)."""
    return json.dumps(schedule, sort_keys=True).encode()


def materialize(schedule, traffic, seed, vocab_size):
    """The prompt token arrays of one run, from `--seed`: one int32
    array per request, the shared prefixes first where the traffic has
    them. Ids are drawn from [1, vocab)."""
    rng = np.random.default_rng([int(seed), 7])
    shared = traffic.get("shared_prefix")
    prefixes = []
    if shared:
        prefixes = [rng.integers(1, vocab_size, shared["len"],
                                 dtype=np.int64).astype(np.int32)
                    for _ in range(shared["count"])]
    prompts = []
    for r in schedule:
        head = prefixes[r["prefix_id"]] if r["prefix_id"] >= 0 \
            else np.zeros(0, np.int32)
        n_tail = r["prompt_len"] - len(head)
        if n_tail < 1:
            raise ValueError("prompt shorter than its shared prefix + 1")
        tail = rng.integers(1, vocab_size, n_tail,
                            dtype=np.int64).astype(np.int32)
        prompts.append(np.concatenate([head, tail]))
    return prompts


def token_batches(seed, vocab_size, batch, seq_len, pool):
    """Training traffic: a pool of `pool` host batches of (ids, labels),
    int32 [batch, seq_len], every row different, from `--seed`."""
    rng = np.random.default_rng([int(seed), 11])
    return [(rng.integers(0, vocab_size, (batch, seq_len),
                          dtype=np.int64).astype(np.int32),
             rng.integers(0, vocab_size, (batch, seq_len),
                          dtype=np.int64).astype(np.int32))
            for _ in range(pool)]
