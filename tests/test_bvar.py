"""bvar tests — per-primitive suites like the reference's
bvar_{variable,reducer,recorder,...}_unittest.cpp (SURVEY.md §4)."""

import threading

from incubator_brpc_tpu import bvar


def test_adder_multi_thread():
    a = bvar.Adder()
    n_threads, per_thread = 8, 10000

    def work():
        for _ in range(per_thread):
            a << 1

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert a.get_value() == n_threads * per_thread


def test_maxer_miner():
    m = bvar.Maxer()
    for v in (3, 9, 1):
        m << v
    assert m.get_value() == 9
    mn = bvar.Miner()
    for v in (3, 9, 1):
        mn << v
    assert mn.get_value() == 1


def test_int_recorder_average():
    r = bvar.IntRecorder()
    for v in range(1, 101):
        r << v
    assert abs(r.average() - 50.5) < 1e-9


def test_latency_recorder():
    lr = bvar.LatencyRecorder(window_size=2)
    for v in range(1000):
        lr << v
    assert lr.count() == 1000
    assert 0 <= lr.latency_percentile(0.5) <= 999
    assert lr.max_latency() == 999
    assert lr.latency() == sum(range(1000)) / 1000


def test_expose_registry_and_normalize():
    from incubator_brpc_tpu.bvar.variable import normalize_name

    assert normalize_name("FooBar::BazQps") == "foo_bar_baz_qps"
    a = bvar.Adder(name="test_expose_adder_xyz")
    a << 5
    dump = bvar.dump_exposed("test_expose_adder")
    assert dump.get("test_expose_adder_xyz") == "5"
    # duplicate exposure refused (reference variable.cpp behavior)
    b = bvar.Adder()
    assert not b.expose("test_expose_adder_xyz")
    assert a.hide()


def test_re_expose_drops_old_registry_entry():
    a = bvar.Adder(name="test_reexpose_old")
    assert a.expose("test_reexpose_new")
    assert bvar.dump_exposed("test_reexpose_old") == {}
    assert "test_reexpose_new" in bvar.dump_exposed("test_reexpose_new")
    assert a.hide()
    assert bvar.dump_exposed("test_reexpose") == {}


def test_passive_status():
    x = {"v": 1}
    p = bvar.PassiveStatus(lambda: x["v"] * 2)
    assert p.get_value() == 2
    x["v"] = 21
    assert p.get_value() == 42


def test_adder_reset_rebase():
    a = bvar.Adder()
    for _ in range(10):
        a << 1
    assert a.reset() == 10
    assert a.get_value() == 0
    a << 5
    assert a.get_value() == 5
    assert a.reset() == 5


def test_per_second_returns_float_fraction():
    from incubator_brpc_tpu.bvar.window import PerSecond

    a = bvar.Adder()
    ps = PerSecond(a, window_size=10)
    a << 9
    ps._take_sample()  # seed one sample so the span is tiny but nonzero
    import time

    time.sleep(0.05)
    v = ps.get_value()
    assert isinstance(v, float)


class TestRecorderFeed:
    """Rows appended on a hot path, fed to a row of LatencyRecorders in bulk."""

    def test_flush_feeds_each_recorder_its_column(self):
        import pytest

        from incubator_brpc_tpu.bvar import LatencyRecorder, RecorderFeed

        a, b, c = LatencyRecorder(), LatencyRecorder(), LatencyRecorder()
        feed = RecorderFeed(((a, 1e-3), (b, 1e-3), (c, 1)))
        for i in range(40):
            feed.rows.append((1000 * (i + 1), feed.MISSING if i % 2 else 5000, 3))
        assert a.count() == 0  # nothing until a flush
        feed.flush()
        assert (a.count(), b.count(), c.count()) == (40, 20, 40)  # MISSING skipped
        assert a.latency_sum() == pytest.approx(sum(range(1, 41)))  # ns -> us
        assert a.max_latency() == pytest.approx(40.0)
        assert b.latency() == pytest.approx(5.0)
        assert c.latency_sum() == 120 and c.max_latency() == 3  # unscaled
        # the reservoir saw one row of 16, real values all
        assert sorted(a._percentile.merged_samples()) == [1.0, 17.0, 33.0]
        feed.flush()  # nothing waits: nothing changes
        assert a.count() == 40

    def test_the_sampler_thread_feeds_within_the_second(self):
        import time

        from incubator_brpc_tpu.bvar import LatencyRecorder, RecorderFeed

        rec = LatencyRecorder()
        feed = RecorderFeed(((rec, 1),))
        feed.rows.append((7,))
        deadline = time.monotonic() + 5
        while rec.count() == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert rec.count() == 1 and rec.latency_sum() == 7

    def test_rows_appended_from_many_threads_are_each_fed_once(self):
        import sys
        import threading

        from incubator_brpc_tpu.bvar import LatencyRecorder, RecorderFeed

        rec = LatencyRecorder()
        feed = RecorderFeed(((rec, 1),))
        stop = threading.Event()

        def flusher():
            while not stop.is_set():
                feed.flush()

        def writer():
            for _ in range(2000):
                feed.rows.append((1,))

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            flushers = [threading.Thread(target=flusher) for _ in range(2)]
            writers = [threading.Thread(target=writer) for _ in range(12)]
            for t in flushers + writers:
                t.start()
            for t in writers:
                t.join(30)
            stop.set()
            for t in flushers:
                t.join(30)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in flushers + writers)
        feed.flush()
        assert rec.count() == 24000 and rec.latency_sum() == 24000
