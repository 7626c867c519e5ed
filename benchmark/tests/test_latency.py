import pytest

from benchmark import harness, latency


def test_percentiles_by_hand():
    xs = list(range(1, 101))                # 1..100
    assert harness.percentile(xs, 50) == pytest.approx(50.5)
    assert harness.percentile(xs, 95) == pytest.approx(95.05)
    assert harness.percentile([7.0], 95) == 7.0
    assert harness.percentile([1.0, 3.0], 50) == pytest.approx(2.0)


def _row(due, submitted, times, finished=True, **kw):
    return dict(due=due, submitted=submitted, times=times,
                finished=finished, **kw)


def test_timed_from_due_with_a_stall():
    """A synthetic log: ten requests due 0.1 s apart, 20 ms to a first
    token, 10 ms a token. The system stalls from 0.35 s to 0.85 s, so
    requests 4..8 are submitted late and answered after the stall.
    Timed from `submit` the stall would hide; timed from due it is
    charged to every request queued behind it."""
    rows = []
    for i in range(10):
        due = 0.1 * i
        start = due if not 0.35 <= due < 0.85 else 0.85   # held by the stall
        times = [start + 0.02 + 0.01 * k for k in range(5)]
        rows.append(_row(due, start, times))
    ttft, tpot, late = latency.request_latencies(rows, t_end=2.0)
    assert ttft[0] == pytest.approx(20.0)
    assert ttft[4] == pytest.approx((0.85 - 0.4) * 1e3 + 20.0)   # 470 ms
    assert ttft[8] == pytest.approx((0.85 - 0.8) * 1e3 + 20.0)   # 70 ms
    assert ttft[9] == pytest.approx(20.0)
    assert all(x == pytest.approx(10.0) for x in tpot)
    assert late[4] == pytest.approx(450.0) and late[0] == 0.0
    # p95 of ten values: between the 9th and 10th order statistics
    top = sorted(ttft)
    assert latency.tails(ttft, tpot)["ttft_p95_ms"] == pytest.approx(
        top[8] + (top[9] - top[8]) * 0.55)


def test_failed_and_unfinished_count_as_the_worst():
    rows = [_row(0.0, 0.0, [0.05, 0.06, 0.07]),
            _row(0.1, 0.1, [0.2], finished=False),        # cut off by drain
            _row(0.2, None, [], finished=False, failed=True)]
    ttft, tpot, late = latency.request_latencies(rows, t_end=10.0)
    assert ttft == pytest.approx([50.0, 9900.0, 9800.0])
    assert tpot == pytest.approx([10.0, 9900.0, 9800.0])
    assert late == pytest.approx([0.0, 0.0])


def test_a_backlog_cut_at_the_close_is_charged_nothing():
    rows = [_row(0.0, 0.0, [1.0, 1.1, 1.2], finished=False, cut=True),
            _row(0.0, 0.0, [], finished=False, cut=True)]
    ttft, tpot, _ = latency.request_latencies(rows, t_end=10.0)
    assert ttft == pytest.approx([1000.0]) and tpot == pytest.approx([100.0])


def test_tokens_are_counted_when_delivered():
    rows = [_row(0, 0, [1.0, 2.0, 3.0, 4.0]),            # 3 inside
            _row(0, 0, [2.5, 3.5], finished=False),      # 1 inside
            _row(0, 0, [0.5], failed=True)]              # failed: none
    assert latency.delivered_in_window(rows, t_close=3.0) == 4
