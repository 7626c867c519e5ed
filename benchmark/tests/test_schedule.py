import os

import numpy as np

from benchmark import harness, schedule

TRAFFIC = os.path.join(harness.HERE, "traffic")


def _traffic(name):
    return harness.load_json(TRAFFIC, name + ".json")


def test_schedule_is_the_cells_not_the_runs():
    """One schedule_seed gives byte-identical schedules whatever --seed
    is: --seed never reaches build_schedule, and two builds agree."""
    for name in ("serve-chat", "serve-long"):
        t = _traffic(name)
        a = schedule.schedule_bytes(schedule.build_schedule(t, 45))
        b = schedule.schedule_bytes(schedule.build_schedule(t, 45))
        assert a == b and len(a) > 100
        # --seed changes the tokens and nothing of the structure
        s = schedule.build_schedule(t, 45)
        p1 = schedule.materialize(s, t, 1, 50304)
        p2 = schedule.materialize(s, t, 2 ** 31 + 99, 50304)
        assert [len(x) for x in p1] == [len(x) for x in p2] \
            == [r["prompt_len"] for r in s]
        assert any((x != y).any() for x, y in zip(p1, p2))


def test_another_schedule_seed_is_another_schedule():
    t = _traffic("serve-chat")
    other = dict(t, schedule_seed=t["schedule_seed"] + 1)
    assert schedule.schedule_bytes(schedule.build_schedule(t, 45)) != \
        schedule.schedule_bytes(schedule.build_schedule(other, 45))


def test_shorter_window_replays_a_prefix():
    t = _traffic("serve-chat")
    long_, short = (schedule.build_schedule(t, s) for s in (45, 10))
    assert 0 < len(short) < len(long_)
    assert long_[:len(short)] == short


def test_lengths_and_shared_prefixes_follow_the_file():
    t = _traffic("serve-chat")
    s = schedule.build_schedule(t, 45)
    assert all(t["prompt_len"]["min"] <= r["prompt_len"]
               <= t["prompt_len"]["max"] for r in s)
    assert all(t["output_len"]["min"] <= r["output_len"]
               <= t["output_len"]["max"] for r in s)
    prompts = schedule.materialize(s, t, 5, 50304)
    n = t["shared_prefix"]["len"]
    heads = {r["prefix_id"]: prompts[i][:n].tobytes()
             for i, r in enumerate(s)}
    for i, r in enumerate(s):       # same id, same opening tokens
        assert prompts[i][:n].tobytes() == heads[r["prefix_id"]]
    assert len(set(heads.values())) == len(heads) > 1
    # popularity 1/rank: the first prompt is the most used
    counts = np.bincount([r["prefix_id"] for r in s])
    assert counts[0] == counts.max()


def test_backlog_is_all_due_at_once():
    t = _traffic("serve-long")
    s = schedule.build_schedule(t, 45)
    assert len(s) == t["arrivals"]["count"]
    assert all(r["due_s"] == 0.0 and r["prefix_id"] == -1 for r in s)
    assert max(r["prompt_len"] + r["output_len"] for r in s) <= 2048


def test_training_rows_all_differ():
    pool = schedule.token_batches(3, 512, 4, 16, 3)
    rows = [tuple(r) for ids, _ in pool for r in ids]
    assert len(set(rows)) == len(rows)
    again = schedule.token_batches(3, 512, 4, 16, 3)
    assert all((a[0] == b[0]).all() and (a[1] == b[1]).all()
               for a, b in zip(pool, again))
