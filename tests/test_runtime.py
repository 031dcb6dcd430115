"""runtime (L2) tests — one scenario per primitive, mirroring the
reference's per-primitive suites (test/bthread_butex_unittest.cpp,
bthread_id_unittest.cpp, execution_queue_unittest.cpp, ...)."""

import threading
import time

import pytest

from incubator_brpc_tpu.bvar import LatencyRecorder
from incubator_brpc_tpu.runtime import device_butex
from incubator_brpc_tpu.runtime.device_butex import CQ_THREADS, _WatcherPool
from incubator_brpc_tpu.runtime import (
    Butex,
    CallIdSpace,
    DeviceCompletionButex,
    ETIMEDOUT,
    EWOULDBLOCK,
    ExecutionQueue,
    TimerThread,
    WAIT_OK,
    WorkerPool,
    WorkStealingQueue,
    spawn,
)


def wait_until(cond, timeout=5.0):
    """Poll until ``cond()`` — deadline-bounded, never a bare sleep whose
    margin a loaded host can blow through."""
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            return False
        time.sleep(0.002)
    return True


# ---------------------------------------------------------------- butex ----

def test_butex_wake_before_wait_returns_ewouldblock():
    b = Butex(0)
    b.add(1)
    assert b.wait(0) == EWOULDBLOCK  # value moved: never parks, never loses a wake


def test_butex_timed_wait():
    b = Butex(0)
    t0 = time.monotonic()
    assert b.wait(0, timeout=0.05) == ETIMEDOUT
    assert 0.04 <= time.monotonic() - t0 < 1.0


def test_butex_wake_n_and_wake_all():
    b = Butex(0)
    results = []

    def waiter():
        results.append(b.wait(0))

    threads = [threading.Thread(target=waiter) for _ in range(4)]
    for t in threads:
        t.start()
    while not b.has_waiters() or len(b._waiters) < 4:
        time.sleep(0.001)
    assert b.wake(1) == 1
    assert b.wake_all() == 3
    for t in threads:
        t.join()
    assert results == [WAIT_OK] * 4


def test_butex_wake_except_skips_token():
    b = Butex(0)
    woken = []

    def waiter(tok):
        b.wait(0, token=tok)
        woken.append(tok)

    t1 = threading.Thread(target=waiter, args=("me",))
    t2 = threading.Thread(target=waiter, args=("other",))
    t1.start(), t2.start()
    while len(b._waiters) < 2:
        time.sleep(0.001)
    assert b.wake_except("me") == 1
    t2.join(timeout=2)
    assert woken == ["other"]
    b.wake_all()
    t1.join(timeout=2)


def test_butex_timeout_then_normal_wake_race():
    # A wake arriving after the timer fired must not double-release.
    b = Butex(0)
    assert b.wait(0, timeout=0.01) == ETIMEDOUT
    assert b.wake(1) == 0


# ---------------------------------------------------------------- timer ----

def test_timer_schedule_and_order():
    tt = TimerThread("test-timer")
    try:
        fired = []
        tt.schedule(lambda: fired.append("b"), delay=0.04)
        tt.schedule(lambda: fired.append("a"), delay=0.01)
        assert wait_until(lambda: len(fired) == 2)
        assert fired == ["a", "b"]
    finally:
        tt.stop_and_join()


def test_timer_unschedule_prevents_run():
    tt = TimerThread("test-timer-2")
    try:
        fired = []
        tid = tt.schedule(lambda: fired.append(1), delay=0.05)
        assert tt.unschedule(tid) is True
        assert tt.unschedule(tid) is False  # already cancelled
        time.sleep(0.12)
        assert fired == []
        assert tt.stats()["pending"] == 0
    finally:
        tt.stop_and_join()


def test_timer_earlier_schedule_preempts():
    tt = TimerThread("test-timer-3")
    try:
        fired = []
        tt.schedule(lambda: fired.append("late"), delay=5.0)
        tt.schedule(lambda: fired.append("early"), delay=0.02)
        assert wait_until(lambda: fired == ["early"])
        assert fired == ["early"]  # did not wait behind the 5s head
    finally:
        tt.stop_and_join()


# ----------------------------------------------------------- worker pool ----

def test_fiber_spawn_join_result():
    f = spawn(lambda a, b: a + b, 2, 3)
    assert f.join(timeout=5)
    assert f.get() == 5


def test_fiber_exception_propagates_via_get():
    def boom():
        raise ValueError("boom")

    f = spawn(boom)
    assert f.join(timeout=5)
    with pytest.raises(ValueError):
        f.get()


def test_fiber_join_timeout():
    gate = threading.Event()
    f = spawn(gate.wait)
    assert f.join(timeout=0.05) is False
    gate.set()
    assert f.join(timeout=5)


def test_pool_runs_many_fibers_and_nested_spawn():
    pool = WorkerPool(concurrency=4, name="test_pool_many")
    try:
        total = 64
        done = []
        lock = threading.Lock()

        def leaf(i):
            with lock:
                done.append(i)

        def parent(i):
            # spawn from inside a worker: exercises the local-queue path
            return pool.spawn(leaf, i)

        parents = [pool.spawn(parent, i) for i in range(total)]
        leaves = [p.get(timeout=10) for p in parents]
        for leaf_fiber in leaves:
            assert leaf_fiber.join(timeout=10)
        assert sorted(done) == list(range(total))
        assert int(pool.nfibers_run.get_value()) == 2 * total
    finally:
        pool.stop_and_join()


def test_work_stealing_queue_order():
    q = WorkStealingQueue()
    for i in range(5):
        q.push(i)
    assert q.pop() == 4  # owner pops LIFO
    assert q.steal() == 0  # thief steals FIFO
    assert len(q) == 3


# ------------------------------------------------------- execution queue ----

def test_execution_queue_n_producers_per_producer_order():
    seen = []

    def consumer(it):
        for item in it:
            seen.append(item)

    q = ExecutionQueue(consumer)
    nproducers, nitems = 8, 200

    def producer(p):
        for i in range(nitems):
            assert q.execute((p, i)) == 0

    threads = [threading.Thread(target=producer, args=(p,)) for p in range(nproducers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    q.stop()
    assert q.join(timeout=10)
    assert len(seen) == nproducers * nitems
    # single-consumer actor: each producer's items arrive in order
    for p in range(nproducers):
        mine = [i for (pp, i) in seen if pp == p]
        assert mine == list(range(nitems))


def test_execution_queue_single_consumer_at_a_time():
    active = [0]
    max_active = [0]
    lock = threading.Lock()

    def consumer(it):
        with lock:
            active[0] += 1
            max_active[0] = max(max_active[0], active[0])
        for _ in it:
            time.sleep(0.0005)
        with lock:
            active[0] -= 1

    q = ExecutionQueue(consumer, max_batch=4)
    threads = [
        threading.Thread(target=lambda: [q.execute(i) for i in range(50)])
        for _ in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    q.stop()
    assert q.join(timeout=10)
    assert max_active[0] == 1


def test_execution_queue_high_priority_lane():
    seen = []
    gate = threading.Event()

    def consumer(it):
        gate.wait()
        for item in it:
            seen.append(item)

    q = ExecutionQueue(consumer)
    q.execute("n1")
    q.execute("n2")
    q.execute("hi", high_priority=True)
    gate.set()
    q.stop()
    assert q.join(timeout=10)
    # the batch drained after the gate put the high-priority item first
    assert seen.index("hi") < seen.index("n1") or seen[0] == "n1"
    assert set(seen) == {"n1", "n2", "hi"}


def test_execution_queue_stop_rejects_and_reports():
    stopped_seen = []

    def consumer(it):
        list(it)
        stopped_seen.append(it.is_queue_stopped())

    q = ExecutionQueue(consumer)
    q.execute(1)
    q.stop()
    assert q.execute(2) != 0  # EINVAL after stop
    assert q.join(timeout=10)
    assert stopped_seen[-1] is True


# -------------------------------------------------------- correlation id ----

def test_call_id_lock_unlock_and_destroy():
    space = CallIdSpace()
    cid = space.create(data={"x": 1})
    code, data = space.lock(cid)
    assert code == 0 and data == {"x": 1}
    assert space.unlock(cid) == 0
    code, _ = space.lock(cid)
    assert code == 0
    assert space.unlock_and_destroy(cid) == 0
    assert not space.valid(cid)
    code, _ = space.lock(cid)
    assert code != 0  # stale id: EINVAL, no fault (never-freed slot)


def test_call_id_error_when_unlocked_runs_handler_inline():
    space = CallIdSpace()
    handled = []

    def on_error(call_id, data, code, text):
        handled.append((data, code, text))
        space.unlock_and_destroy(call_id)

    cid = space.create(data="D", on_error=on_error)
    assert space.error(cid, 1008, "timeout") == 0
    assert handled == [("D", 1008, "timeout")]
    assert not space.valid(cid)


def test_call_id_error_while_locked_is_queued_until_unlock():
    space = CallIdSpace()
    handled = []

    def on_error(call_id, data, code, text):
        handled.append(code)
        space.unlock_and_destroy(call_id)

    cid = space.create(data="D", on_error=on_error)
    code, _ = space.lock(cid)
    assert code == 0
    assert space.error(cid, 1009) == 0  # queued, not delivered
    assert handled == []
    assert space.unlock(cid) == 0  # delivery point
    assert handled == [1009]
    assert not space.valid(cid)


def test_call_id_join_wakes_on_destroy():
    space = CallIdSpace()
    cid = space.create()
    joined = []

    def joiner():
        joined.append(space.join(cid, timeout=10))

    t = threading.Thread(target=joiner)
    t.start()
    time.sleep(0.05)
    assert joined == []  # still parked
    code, _ = space.lock(cid)
    assert code == 0
    space.unlock_and_destroy(cid)
    t.join(timeout=5)
    assert joined == [True]
    assert space.join(cid) is True  # joining a destroyed id returns at once


def test_call_id_ranged_versions_shared_across_retries():
    # One RPC + retries share a slot via a version range (channel.cpp:307).
    space = CallIdSpace()
    cid = space.create(data="rpc", version_range=3)
    assert space.valid(cid)
    assert space.valid(cid + 1)
    assert space.valid(cid + 2)
    assert not space.valid(cid + 3)
    code, data = space.lock(cid + 2)  # a retry's version resolves to the slot
    assert code == 0 and data == "rpc"
    space.unlock_and_destroy(cid + 2)
    for d in range(3):
        assert not space.valid(cid + d)


def test_call_id_lock_contention():
    space = CallIdSpace()
    cid = space.create(data=[])
    order = []

    def contender(i):
        code, data = space.lock(cid)
        assert code == 0
        order.append(i)
        time.sleep(0.005)
        space.unlock(cid)

    threads = [threading.Thread(target=contender, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sorted(order) == list(range(6))  # all got the lock exactly once


def test_call_id_unlock_with_pending_error_and_no_handler_destroys():
    # Default on_error is destroy (reference default_bthread_id_on_error):
    # a queued error delivered by unlock() must not leave the id locked.
    space = CallIdSpace()
    cid = space.create(data="d")
    code, _ = space.lock(cid)
    assert code == 0
    assert space.error(cid, 1008) == 0  # queued (locked, no handler)
    assert space.unlock(cid) == 0
    assert not space.valid(cid)  # destroyed, not stuck locked


def test_butex_requeue_preserves_timeout():
    b1, b2 = Butex(0), Butex(0)
    results = []

    def timed_waiter():
        results.append(b1.wait(0, timeout=0.15))

    def plain_waiter():
        results.append(b1.wait(0))

    t0 = threading.Thread(target=plain_waiter)
    t0.start()
    while len(b1._waiters) < 1:
        time.sleep(0.001)
    t1 = threading.Thread(target=timed_waiter)
    t1.start()
    while len(b1._waiters) < 2:
        time.sleep(0.001)
    # requeue wakes the first (plain) waiter, moves the timed one to b2
    assert b1.requeue(b2) == 1
    t0.join(timeout=2)
    # the moved timed waiter must still honor its timeout on b2
    t1.join(timeout=2)
    assert not t1.is_alive()
    assert results[-1] == ETIMEDOUT


def test_execution_queue_consumer_exception_does_not_drop_batch_remainder():
    seen = []

    def consumer(it):
        for item in it:
            if item == 2:
                raise RuntimeError("bad item")
            seen.append(item)

    q = ExecutionQueue(consumer)
    for i in range(6):
        q.execute(i)
    q.stop()
    assert q.join(timeout=10)
    # item 2 was consumed by the raising call (at-most-once); 3..5 survive
    assert seen == [0, 1, 3, 4, 5]


# ------------------------------------------------------ device completion ----

def test_device_completion_butex_wakes_on_ready():
    import jax
    import jax.numpy as jnp

    cq = DeviceCompletionButex()
    out = jax.jit(lambda x: x * 2 + 1)(jnp.arange(1024.0))
    cq.watch(out)
    assert cq.wait_for(1, timeout=30)
    assert cq.load() == 1
    assert float(out[1]) == 3.0


def test_device_completion_callback_and_multiple_ops():
    import jax
    import jax.numpy as jnp

    cq = DeviceCompletionButex()
    done = []
    outs = [jax.jit(lambda x: x + i)(jnp.ones(8)) for i in range(3)]
    for o in outs:
        cq.watch(o, on_complete=lambda arr, err: done.append(err))
    assert cq.wait_for(3, timeout=30)
    assert done == [None, None, None]
    assert cq.inflight == 0
    assert cq.errors == []


def test_device_completion_failure_counts_and_records():
    # A failing readiness wait must still settle the butex (no hung
    # waiters) and surface the error.
    class _Boom:
        def block_until_ready(self):
            raise RuntimeError("device melted")

    cq = DeviceCompletionButex()
    cb = []
    cq.watch(_Boom(), on_complete=lambda arr, err: cb.append(type(err).__name__))
    assert cq.wait_for(1, timeout=10)
    assert len(cq.errors) == 1
    assert cb == ["RuntimeError"]


def test_device_completion_raising_callback_does_not_strand_waiters():
    import jax
    import jax.numpy as jnp

    cq = DeviceCompletionButex()

    def bad_cb(arr, err):
        raise ValueError("callback bug")

    cq.watch(jax.jit(lambda x: x * 2)(jnp.ones(4)), on_complete=bad_cb)
    assert cq.wait_for(1, timeout=10)  # bump/wake happened before the callback


# ------------------------------------------------ the completion watchers ----
# The pool's contract (PR 42): a watch is handed over without a lock a
# watcher holds, and a job's end wakes nobody. Every wait below has its own
# timeout, the blocking jobs' included, so none can hang the suite.

HOLD_S = 20.0  # a held job lets go by itself after this, whatever the test did


class _Pool:
    """A pool of its own, fed into recorders of its own: the process's pool
    and its rows belong to whatever else the worker runs."""

    def __init__(self, nthreads=CQ_THREADS):
        self.submit_us, self.backlog = LatencyRecorder(), LatencyRecorder()
        self.feed = device_butex._submit_feed(self.submit_us, self.backlog)
        self.pool = _WatcherPool(nthreads, self.feed)
        self.started = []

    def hold(self, n):
        """``n`` jobs that block, each on an event of its own; returns the
        events once every one of them is inside its job."""
        gates = [threading.Event() for _ in range(n)]
        before = len(self.started)
        for gate in gates:
            def held(gate=gate):
                self.started.append(gate)
                gate.wait(HOLD_S)
            self.pool.submit(held)
        assert wait_until(lambda: len(self.started) == before + n)
        return gates


def test_watcher_pool_runs_jobs_in_the_order_submitted():
    p = _Pool(nthreads=1)
    ran = []
    for i in range(500):
        p.pool.submit(lambda i=i: ran.append(i))
    assert p.pool.quiesce(timeout=10.0)
    assert ran == list(range(500))


def test_watcher_pool_takes_held_jobs_in_the_order_submitted():
    # all watchers held: what queued behind them runs in its order on the
    # first watcher that comes free
    p = _Pool()
    gates = p.hold(CQ_THREADS)
    ran = []
    for i in range(4):
        p.pool.submit(lambda i=i: ran.append(i))
    gates[5].set()
    assert wait_until(lambda: len(ran) == 4)
    assert ran == [0, 1, 2, 3]  # the one free watcher took all four, in order
    for gate in gates:
        gate.set()
    assert p.pool.quiesce(timeout=10.0)


def test_watch_stamps_of_successive_watches_are_monotonic():
    class _Ready:
        def block_until_ready(self):
            return self

    cq = DeviceCompletionButex()
    stamps = [[0, 0] for _ in range(64)]
    for i, s in enumerate(stamps):
        cq.watch(_Ready(), stamps=s)
        assert cq.wait_for(i + 1, timeout=10)
    took = [s[0] for s in stamps]
    assert all(took) and took == sorted(took)
    assert all(s[1] >= s[0] for s in stamps)


def test_watcher_pool_submit_returns_while_every_watcher_is_held():
    p = _Pool()
    gates = p.hold(CQ_THREADS)
    ninth = threading.Event()
    t0 = time.monotonic()
    p.pool.submit(ninth.set)
    assert time.monotonic() - t0 < 1.0  # nobody to stand behind
    assert not ninth.wait(0.2)  # no watcher is free to run it
    gates[3].set()
    assert ninth.wait(10.0)  # the one released took it
    for gate in gates:
        gate.set()
    assert p.pool.quiesce(timeout=10.0)


def test_watcher_pool_runs_every_job_of_every_submitter_exactly_once():
    import sys

    p = _Pool()
    submitters, jobs_each = 12, 400
    ran = [[0] * jobs_each for _ in range(submitters)]

    def job(s, j):
        ran[s][j] += 1  # its own cell: only a second run could make it 2

    def submitter(s):
        for j in range(jobs_each):
            p.pool.submit(lambda s=s, j=j: job(s, j))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [
            threading.Thread(target=submitter, args=(s,)) for s in range(submitters)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
        assert not any(t.is_alive() for t in threads)
        assert p.pool.quiesce(timeout=30.0)
    finally:
        sys.setswitchinterval(interval)
    assert all(count == 1 for row in ran for count in row)
    assert not p.pool._open and p.pool._jobs.empty()
    p.feed.flush()
    assert p.submit_us.count() == p.backlog.count() == submitters * jobs_each


def test_watcher_pool_job_that_raises_leaves_its_watcher_alive():
    p = _Pool(nthreads=1)

    def boom():
        raise RuntimeError("job bug")

    after = threading.Event()
    p.pool.submit(boom)
    p.pool.submit(after.set)
    assert after.wait(10.0)  # the one watcher went on to the next job
    assert p.pool.quiesce(timeout=10.0)  # the raising job counts as ended
    assert all(t.is_alive() for t in p.pool._threads)


def test_watcher_pool_quiesce_is_true_once_nothing_is_pending_or_executing():
    p = _Pool()
    assert p.pool.quiesce(timeout=0.0)  # a pool that never ran a job
    gates = p.hold(CQ_THREADS)
    queued = threading.Event()
    p.pool.submit(queued.set)  # pending behind the held ones
    answer = []
    waiter = threading.Thread(target=lambda: answer.append(p.pool.quiesce(10.0)))
    waiter.start()
    for gate in gates[:-1]:
        gate.set()
    assert queued.wait(10.0)
    waiter.join(0.3)
    assert waiter.is_alive()  # one job still executes: not yet
    gates[-1].set()
    waiter.join(10.0)
    assert not waiter.is_alive() and answer == [True]


def test_watcher_pool_quiesce_is_false_at_its_timeout_while_a_job_blocks():
    p = _Pool()
    (gate,) = p.hold(1)
    t0 = time.monotonic()
    assert p.pool.quiesce(timeout=0.3) is False
    assert 0.3 <= time.monotonic() - t0 < 5.0
    gate.set()
    assert p.pool.quiesce(timeout=10.0)


def test_watcher_pool_quiesce_never_blocks_a_concurrent_submit():
    p = _Pool()
    (gate,) = p.hold(1)
    answer = []
    waiter = threading.Thread(target=lambda: answer.append(p.pool.quiesce(10.0)))
    waiter.start()
    time.sleep(0.05)  # the waiter is inside quiesce
    ran = threading.Event()
    t0 = time.monotonic()
    p.pool.submit(ran.set)
    assert time.monotonic() - t0 < 1.0
    assert ran.wait(10.0)  # and a free watcher ran it meanwhile
    assert waiter.is_alive()
    gate.set()
    waiter.join(10.0)
    assert not waiter.is_alive() and answer == [True]


def test_watcher_pool_feeds_one_row_a_submit_with_backlog_0_while_idle():
    p = _Pool()
    for _ in range(5):
        done = threading.Event()
        p.pool.submit(done.set)
        assert done.wait(10.0)
        assert p.pool.quiesce(timeout=10.0)
    p.feed.flush()
    assert p.submit_us.count() == p.backlog.count() == 5
    assert p.backlog.latency_sum() == 0 and p.backlog.max_latency() == 0
    assert 0 < p.submit_us.latency_sum() < 5 * 1e6  # us, each under a second


def test_watcher_pool_backlog_counts_the_jobs_no_watcher_is_free_to_take():
    p = _Pool()
    gates = p.hold(CQ_THREADS)
    p.feed.flush()
    assert p.backlog.count() == CQ_THREADS and p.backlog.latency_sum() == 0
    for _ in range(3):
        p.pool.submit(lambda: None)
    p.feed.flush()
    assert p.backlog.count() == CQ_THREADS + 3
    assert p.backlog.latency_sum() == 1 + 2 + 3 and p.backlog.max_latency() == 3
    for gate in gates:
        gate.set()
    assert p.pool.quiesce(timeout=10.0)


def test_the_process_pool_feeds_the_exposed_recorders():
    from incubator_brpc_tpu.bvar import expose_registry

    exposed = {name for name, _ in expose_registry.snapshot("device_transport_cq_")}
    assert {"device_transport_cq_submit_us", "device_transport_cq_backlog"} <= exposed
    device_butex._cq_feed.flush()
    before = device_butex.m_cq_submit.count(), device_butex.m_cq_backlog.count()

    class _Ready:
        def block_until_ready(self):
            return self

    cq = DeviceCompletionButex()
    for i in range(3):
        cq.watch(_Ready())
    assert cq.wait_for(3, timeout=10)
    device_butex._cq_feed.flush()
    assert device_butex.m_cq_submit.count() >= before[0] + 3
    assert device_butex.m_cq_backlog.count() >= before[1] + 3
    assert device_butex.CQ_THREADS == 8
