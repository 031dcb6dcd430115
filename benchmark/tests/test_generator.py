"""The generator's arithmetic, driven against a fake ``send``: what a seed
fixes, what a window counts, and both arrival kinds."""

import time

import numpy as np

from benchmark import generator as g
from benchmark import reduce

CLOSED = {"arrival": "closed", "callers": 4, "sizes": [64, 256, 1024],
          "size_block": 2, "pool_per_size": 3, "warm_calls_per_caller": 2, "warm_seconds": 0.0}
OPEN = dict(CLOSED, arrival="open", rate_per_s=400.0)
BIG_SEED = 2**31 + 12345


def test_pools_come_from_the_seed_and_differ_between_callers():
    a = g.make_pool(CLOSED, BIG_SEED, 0)
    assert a == g.make_pool(CLOSED, BIG_SEED, 0)
    assert a != g.make_pool(CLOSED, BIG_SEED + 1, 0)
    b = g.make_pool(CLOSED, BIG_SEED, 1)
    assert not set(a[256]) & set(b[256])
    assert [len(p) for p in a[1024]] == [1024] * 3


def test_every_seed_walks_the_same_mix_in_another_order():
    def first(seed, n=60):
        walk = g.size_walk(CLOSED, seed, 0)
        return [next(walk) for _ in range(n)]

    assert first(1) == first(1) and first(1) != first(2)
    for seed in (1, 2, BIG_SEED):
        assert sorted(first(seed)) == sorted(CLOSED["sizes"] * 20)


def test_open_schedule_is_seeded_and_at_the_rate():
    due = g.due_offsets_ns(OPEN, 7, 5.0)
    assert (due == g.due_offsets_ns(OPEN, 7, 5.0)).all()
    assert (np.diff(due) > 0).all() and due[-1] < 5e9
    assert abs(len(due) - 2000) < 200


def _fake_send(latency_s, wrong_below=0):
    """Answers after ``latency_s``; wrongly where the payload's first byte
    is under ``wrong_below`` (the seed fixes which payloads those are)."""
    seen = []

    def send(data):
        seen.append(data)
        time.sleep(latency_s)
        return time.monotonic_ns(), g.MISMATCH if data[0] < wrong_below else g.OK

    return send, seen


def test_closed_loop_counts_what_completed_inside_the_window():
    send, seen = _fake_send(0.002)
    opened = []
    table, t_open = g.run_load(send, CLOSED, 5, 0.5, on_open=opened.append)
    assert opened == [t_open]
    assert len(seen) == len(table) + 4 * 2  # warm calls are not recorded
    assert (table[:, g.SEND_NS] >= t_open).all()
    done = reduce.in_window(table, t_open, 0.5)
    assert 0 < len(table) - len(done) <= 4  # at most one per caller runs over
    out = reduce.end_to_end(table, t_open, 0.5)
    assert out["call_rate"] == len(done) / 0.5
    assert out["goodput"] == done[:, g.SIZE].sum() / 0.5 / 1e9
    assert 2000 <= out["latency_p50_us"] < 20000
    assert "latency_p99_us" not in out  # under 1,000 calls
    assert sum(reduce.per_second(table, t_open, 1.0)) == len(
        reduce.in_window(table, t_open, 1.0))


def test_a_wrong_answer_is_not_counted_and_is_reported():
    send, _ = _fake_send(0.001, wrong_below=64)
    table, t_open = g.run_load(send, CLOSED, 5, 0.3)
    bad = int((table[:, g.STATUS] == g.MISMATCH).sum())
    assert 0 < bad < len(table)
    assert len(reduce.in_window(table, t_open, 0.3)) <= len(table) - bad


def test_open_loop_times_each_call_from_when_it_was_due():
    send, _ = _fake_send(0.001)
    table, t_open = g.run_load(send, OPEN, 9, 0.5)
    due = g.due_offsets_ns(OPEN, 9, 0.5)
    assert len(table) == len(due)
    assert sorted(table[:, g.DUE_NS] - t_open) == sorted(due)
    assert (table[:, g.SEND_NS] >= table[:, g.DUE_NS]).all()
    lat = reduce.latencies_us(reduce.in_window(table, t_open, 1.0))
    assert lat.min() >= 1000
    assert 0 <= reduce.lateness_us(table) < 50000


def test_percentile_is_an_observed_value():
    values = np.arange(1, 1001, dtype=float)
    assert reduce.percentile(values, 50) == 500
    assert reduce.percentile(values, 99) == 990
