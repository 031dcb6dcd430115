"""The interpreter lock's probe and the processors by thread (PR 51;
``bvar/lock_probe.py``, ``bvar/processors.py``, ``/threads``).

Orderings and counts only, no time held against a constant (ROADMAP.md
D11 (4)): a row's three stamps are in order on ``time.monotonic_ns()``'s
clock; beside a thread that spins in pure Python the probe waits several
times as long as in an idle process and is made to wait the switch
interval out, beside a thread asleep in native code neither; a thread that
keeps the lock for 150 ms makes exactly one stall, named with its frame;
the feed declares no span, so the timeline's labels are the ones the other
feeds give; the by-thread walk on canned ``schedstat``, ``stat`` and
``comm`` text, and live.
"""

import ctypes
import logging
import os
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import timeline  # noqa: E402
from incubator_brpc_tpu import bvar, native  # noqa: E402
from incubator_brpc_tpu.bvar import lock_probe, processors  # noqa: E402

pytestmark = pytest.mark.skipif(
    not native.NATIVE_AVAILABLE, reason="no native library: no probe")


@pytest.fixture(scope="module")
def feed():
    """The process's probe, started with the sampler thread as any
    process's is (a feed of any layer registers with it)."""
    bvar.RecorderFeed([(bvar.LatencyRecorder(), 1)])
    assert lock_probe._probe is not None
    assert lock_probe._probe.feed is bvar.feeds()["interpreter_lock"]
    return lock_probe._probe.feed


def ticks_during(feed, seconds: float, beside=None, name="beside"):
    """The probe's rows whose tick was due while ``beside`` ran on a thread
    of its own for ``seconds`` (nothing: while this thread slept)."""
    stop = threading.Event()
    thread = threading.Thread(target=beside, args=(stop,), name=name) if beside else None
    if thread:
        thread.start()
    begin = time.monotonic_ns()
    time.sleep(seconds)
    end = time.monotonic_ns()
    stop.set()
    if thread:
        thread.join(10)
        assert not thread.is_alive()
    stamps, rows = feed.timeline()
    assert stamps == ("due", "woken", "running")
    return rows[(rows[:, 0] > begin) & (rows[:, 2] < end)]


def spin(stop):
    while not stop.is_set():
        pass


def asleep_in_native_code(stop):
    while not stop.is_set():
        native.LIB.tb_sleep_until_ns(time.monotonic_ns() + 20_000_000)


def test_a_rows_stamps_are_in_order_on_the_monotonic_clock(feed):
    before = time.monotonic_ns()
    rows = ticks_during(feed, 0.6)
    after = time.monotonic_ns()
    assert len(rows) >= 10  # a tick every 10 ms, jitter and all
    due, woken, running = rows.T
    assert (due <= woken).all() and (woken <= running).all()
    assert (np.diff(due) > 0).all()
    assert before < due.min() and running.max() < after


def test_an_idle_process_finds_the_lock_free_and_a_spinner_takes_it(feed):
    counted = ("device_transport_lock_probes", "device_transport_lock_busy",
               "device_transport_lock_forced")

    def counts():
        feed.flush()
        return np.array([bvar.expose_registry.describe(n) for n in counted], np.int64)

    idle = ticks_during(feed, 1.0)
    idle_wait = idle[:, 2] - idle[:, 1]
    assert (idle_wait <= lock_probe.BUSY_NS).sum() > len(idle) / 2

    before = counts()
    parked = ticks_during(feed, 1.0, asleep_in_native_code)
    parked_gain = counts() - before
    parked_wait = parked[:, 2] - parked[:, 1]
    assert (parked_wait <= lock_probe.BUSY_NS).sum() > len(parked) / 2
    assert parked_gain[0] >= len(parked) and parked_gain[2] <= parked_gain[0] / 10

    before = counts()
    spun = ticks_during(feed, 1.0, spin)
    probes, busy, forced = counts() - before
    spun_wait = spun[:, 2] - spun[:, 1]
    # a spinner gives the lock up when the switch interval makes it
    assert spun_wait.mean() > 5 * max(idle_wait.mean(), parked_wait.mean())
    assert (spun_wait >= sys.getswitchinterval() * 1e9).sum() > len(spun) / 2
    assert probes >= len(spun) and busy > len(spun) / 2 and forced > len(spun) / 2
    assert forced > 5 * max(parked_gain[2], 1)


def keep_the_lock(how: str, done: threading.Event):
    """Keep the interpreter lock for 150 ms, then stay alive, parked."""
    if how == "asleep":
        # a library call that keeps the lock and sleeps with it
        ctypes.PyDLL(None).usleep(150_000)
    else:
        # bytecode nobody interrupts: the test lengthened the switch interval
        end = time.monotonic_ns() + 150_000_000
        while time.monotonic_ns() < end:
            pass
    done.wait(10)


@pytest.mark.parametrize("how", ["computing", "asleep"])
def test_a_thread_that_keeps_the_lock_makes_one_stall_with_its_name(feed, how, caplog):
    probe = lock_probe._probe
    done = threading.Event()
    holder = threading.Thread(
        target=keep_the_lock, args=(how, done), name=f"keeps-the-lock-{how}-7")
    time.sleep(0.3)  # the process quiet, whatever ran before
    known = len(lock_probe.stalls())
    interval = sys.getswitchinterval()
    # an earlier test's line is old, and it stood for no other
    probe._logged_ns, probe._unlogged = -lock_probe.LOG_EVERY_NS, 0
    with caplog.at_level(logging.WARNING, logger=lock_probe.__name__):
        try:
            sys.setswitchinterval(10.0)
            probe.remember()  # the reading the stall is measured from
            holder.start()
            time.sleep(0.5)
        finally:
            sys.setswitchinterval(interval)
        stalls = lock_probe.stalls()[known:]
        done.set()
        holder.join(10)
    assert not holder.is_alive()
    assert len(stalls) == 1
    (stall,) = stalls
    assert 100_000_000 < stall["wait_ns"] < 400_000_000
    assert stall["late_ns"] >= 0 and stall["woken_ns"] < time.monotonic_ns()
    lines = [r.getMessage() for r in caplog.records if r.name == lock_probe.__name__]
    assert lines == [stall["text"]]
    assert stall["text"].startswith("interpreter lock stall: waited 1")
    feed.flush()
    assert bvar.expose_registry.describe("device_transport_lock_stall_us") != "0"
    if how == "computing":
        # on a processor all through it: named, with where it is now
        assert stall["holder"] == "keeps-the-lock-computing"
        assert stall["holder_cpu_ns"] > stall["wait_ns"] / 2
        assert ("keep_the_lock", True) in [
            (function, os.path.samefile(file, __file__))
            for file, function, _line in stall["frames"]]
        assert "thread 'keeps-the-lock-computing' (tid " in stall["text"]
        assert f"{__file__}:" in stall["text"] and "in keep_the_lock" in stall["text"]
    else:
        # it slept with the lock: no thread of the interpreter ran, and the
        # line says that is all the processors can tell
        assert stall["holder_cpu_ns"] < stall["wait_ns"] / 10
        assert "the holder slept with the lock, or the process was off" in stall["text"]


def test_the_probe_declares_no_span_and_the_timelines_labels_stay(feed):
    assert feed.worker == () and feed.call == ()
    assert feed.ring is not None and len(feed.ring._table) >= 4 * 60 * 100
    stage = bvar.RecorderFeed(
        [(bvar.LatencyRecorder(), 1e-3, ("begin", "end"))],
        stamps=("begin", "end"), name="test_lock_probe_stage", ring_rows=8,
        worker=(("begin", "end"),),
    )
    now = time.monotonic_ns()
    stage.rows.append((now - 2_000_000, now - 1_000_000))
    time.sleep(0.05)  # ticks beside the stage's row
    found = timeline.spans(now - 1_000_000_000, time.monotonic_ns())
    labels = [label for kind in found.values() for label, _s, _e in kind]
    assert "test_lock_probe_stage:begin->end" in labels
    assert not any("interpreter_lock" in label for label in labels)
    without = {
        name: one for name, one in bvar.feeds().items() if name != "interpreter_lock"}
    declared = {
        f"{name}:{b}->{e}" for name, one in without.items()
        for b, e in one.worker + one.call}
    assert set(labels) <= declared


# -- the processors by thread ------------------------------------------------


def canned(tmp_path, tasks: dict):
    """``<tid>/{schedstat,stat,comm}`` under ``tmp_path`` from
    ``{tid: {file: text}}``; a tid with no file is an empty directory."""
    for tid, files in tasks.items():
        os.mkdir(tmp_path / str(tid))
        for name, text in files.items():
            (tmp_path / str(tid) / name).write_text(text)
    return str(tmp_path)


def test_the_walk_on_canned_text_joins_names_and_skips_what_ended(tmp_path, monkeypatch):
    task_dir = canned(tmp_path, {
        101: {"schedstat": "5000000 700000 12\n", "comm": "python3\n"},
        102: {"schedstat": "3000000 100000 4\n", "comm": "python3\n"},
        103: {"schedstat": "1000000 0 1\n", "comm": "python3\n"},
        # two of the runtime's own, an index on each
        201: {"schedstat": "40000000 2000000 9\n", "comm": "tpu_worker_0\n"},
        202: {"schedstat": "60000000 3000000 9\n", "comm": "tpu_worker_17\n"},
        # ended between the listing and the read: a directory and nothing in it
        401: {},
        "self": {"schedstat": "1 1 1\n"},
    })

    class Known:
        def __init__(self, native_id, name):
            self.native_id, self.name = native_id, name

    monkeypatch.setattr(processors.threading, "enumerate", lambda: [
        Known(101, "MainThread"), Known(102, "tbrpc-worker-3"),
        Known(103, "tbrpc-worker-11"), Known(999, "not-a-task-yet"),
    ])
    table = processors.TaskTable(task_dir)
    reading = table.read()
    assert sorted(reading.tasks) == [101, 102, 103, 201, 202]
    assert reading.tasks[102] == processors.Task("tbrpc-worker", True, 3000000, 100000)
    assert reading.by_name() == {
        "MainThread": (5000000, 700000, 1),
        "tbrpc-worker": (4000000, 100000, 2),
        "tpu_worker": (100000000, 5000000, 2),
    }
    assert reading.totals() == (9000000, 100000000, 5800000)
    # comm is read once a task: a name that changes under it is not seen,
    # a task that is gone is forgotten
    (tmp_path / "201" / "comm").write_text("renamed\n")
    os.remove(tmp_path / "202" / "schedstat")
    again = table.read()
    assert again.tasks[201].name == "tpu_worker" and 202 not in again.tasks
    # a task that ended keeps what it read when last seen: the totals only grow
    assert again.ended == (0, 60000000, 3000000)
    assert again.totals() == reading.totals()
    assert again is table.read(fresh=False) and again is not table.read()
    assert set(table._comm) == {201}
    # and so does one whose tid another task took: its clock starts again
    (tmp_path / "103" / "schedstat").write_text("200000 50000 1\n")
    reused = table.read()
    assert reused.ended == (1000000, 60000000, 3000000)
    assert reused.totals() == (9200000, 100000000, 5850000)
    assert processors.TaskTable(str(tmp_path / "nowhere")).read() is None


def test_a_kernel_without_schedstat_gives_stats_ticks_and_no_run_queue(tmp_path, monkeypatch):
    """The chip's host: ``utime + stime`` of ``stat``, in the clock's ticks,
    counted from the name's closing bracket; no run-queue number at all."""
    tick_ns = 1_000_000_000 // os.sysconf("SC_CLK_TCK")
    stat = "{} ({}) S 1 1 1 0 -1 4194560 10 0 0 0 {} {} 0 0 20 0 9 0 100 0 0\n"
    task_dir = canned(tmp_path, {
        301: {"stat": stat.format(301, "odd) name", 7, 5), "comm": "odd) name\n"},
        302: {"stat": stat.format(302, "python3", 30, 10), "comm": "python3\n"},
        303: {},
    })
    monkeypatch.setattr(processors.threading, "enumerate", lambda: [])
    reading = processors.TaskTable(task_dir).read()
    assert reading.tasks == {
        301: processors.Task("odd) name", False, 12 * tick_ns, None),
        302: processors.Task("python3", False, 40 * tick_ns, None),
    }
    assert reading.by_name()["python3"] == (40 * tick_ns, 0, 1)
    # the CPU split stands, the wait is unknown: the gauge reads None
    assert reading.totals() == (0, 52 * tick_ns, None)


@pytest.mark.parametrize("name,row", [
    ("tbrpc-worker-3", "tbrpc-worker"), ("tbrpc-cq-0", "tbrpc-cq"),
    ("Thread-5 (run)", "Thread (run)"), ("ThreadPoolExecutor-0_1", "ThreadPoolExecutor"),
    ("tpu_worker_17", "tpu_worker"), ("python3", "python3"), ("MainThread", "MainThread"),
    ("7", "7"),
])
def test_a_threads_row_is_its_name_without_its_indices(name, row):
    assert processors.family(name) == row


def test_without_the_library_there_is_no_reading(monkeypatch):
    monkeypatch.setattr(processors.native, "LIB", None)
    assert processors.TaskTable().read() is None
    assert processors.cpu_python_threads_us.get_value() is None
    assert processors.runq_wait_us.get_value() is None


def test_a_named_spinners_time_lands_under_its_name_and_in_the_python_total():
    from benchmark import spans

    def gains():
        reading = processors.TABLE.read()
        counters = spans.counters()
        return reading.by_name(), [
            counters[f"device_transport_{n}_us"]
            for n in ("cpu_python_threads", "cpu_other_threads", "runq_wait")]

    stop = threading.Event()
    thread = threading.Thread(target=spin, args=(stop,), name="named-spinner-4")
    names_0, totals_0 = gains()
    thread.start()
    spin_until = time.thread_time() + 0.05
    cpu_0 = time.process_time()
    while time.thread_time() < spin_until:
        pass
    time.sleep(0.2)
    names_1, totals_1 = gains()
    cpu_1 = time.process_time()
    stop.set()
    thread.join(10)
    assert not thread.is_alive()
    time.sleep(0.05)
    names_2, totals_2 = gains()
    # ended, its name leaves the table and its time stays in the total
    assert totals_2[0] + totals_2[1] >= totals_1[0] + totals_1[1]
    assert "named-spinner" not in names_2
    assert "named-spinner" not in names_0
    cpu_ns, _runq_ns, tasks = names_1["named-spinner"]
    assert tasks == 1 and cpu_ns > 20_000_000
    python_gain = totals_1[0] - totals_0[0]
    assert python_gain >= cpu_ns / 1e3  # us; the spinner's and this thread's
    assert python_gain <= (cpu_1 - cpu_0 + 0.1) * 1e6
    assert totals_1[2] >= totals_0[2]  # schedstat: a wait that only grows


def test_the_threads_page_serves_every_stack_and_the_table():
    from incubator_brpc_tpu.builtin import pages

    stop = threading.Event()
    thread = threading.Thread(target=spin, args=(stop,), name="page-spinner-2")
    thread.start()
    try:
        before = set(sys._current_frames())
        status, kind, body = pages._threads(None, None)
        stayed = before & set(sys._current_frames())
    finally:
        stop.set()
        thread.join(10)
    text = body.decode()
    assert (status, kind) == (200, "text/plain")
    head, _, stacks = text.partition("\n\n")
    rows = head.splitlines()
    assert rows[0].split() == ["thread", "tasks", "cpu_s", "runq_s"]
    by_name = {r.split()[0]: r.split()[1:] for r in rows[1:]}
    assert "page-spinner" in by_name and "MainThread" in by_name
    assert by_name["bvar_lock_probe"][0] == "1"
    busy = [float(r.split()[-2]) for r in rows[1:]]
    assert busy == sorted(busy, reverse=True)
    # a stack a thread the interpreter has, each under its times
    assert stayed >= {thread.ident, threading.main_thread().ident}
    assert all(f"(tid={ident})" in stacks for ident in stayed)
    assert f"-- thread page-spinner-2 (tid={thread.ident}) cpu=" in stacks
    assert "in spin" in stacks and "test_the_threads_page_serves" in stacks
