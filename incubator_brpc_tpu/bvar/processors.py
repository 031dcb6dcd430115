"""The processors, by thread: where the process's CPU time goes.

``device_transport_process_cpu_us`` (runtime/device_butex.py) says how many
processors the process kept busy; this says which threads did, and whether
a runnable thread waited for a processor. One reading walks
``/proc/self/task``: for every task its on-processor time and its
run-queue wait (``schedstat``; ``stat``'s ``utime + stime`` where the
kernel keeps no ``schedstat``, and then no run-queue number). A task the
interpreter knows (``threading.enumerate()`` by ``native_id``) takes its
thread's name, any other its ``comm``, read once a task; a trailing index
is stripped (``tbrpc-worker-3`` -> ``tbrpc-worker``), so the rows are the
names that exist and not a list someone keeps.

The walk is ``tb_task_times`` of the native library, which lets the
interpreter lock go for the whole of it. The chip's host keeps 183 tasks
in a process that has only started JAX, 200 in a server, and a file of its
``/proc`` costs ~40 us: the walk takes 8.4 ms idle and 15 ms beside sixteen
busy threads, all of it with the lock free, where a walk in Python (one
system call a task at the least, each a release of the lock and a place
in its queue) took 706-713 ms beside the same threads (PERF.md, PR 51).
Without the library there is no reading (``None``).

Read only when asked: by a snapshot of the three ``PassiveStatus`` below
(the benchmark's, at a window's edges; ``/vars``), by ``/threads``, and by
the lock probe (bvar/lock_probe.py) once a second and on a stall. No
thread of a hot path reads it.
"""

from __future__ import annotations

import ctypes
import re
import threading
from time import monotonic_ns
from typing import NamedTuple, Optional

import numpy as np

from incubator_brpc_tpu import native
from incubator_brpc_tpu.bvar.reducer import PassiveStatus

# "-3", "_12", " 5": an index that tells threads of one name apart
_INDEX = re.compile(r"[-_:/ ]\d+(?![0-9A-Za-z])")


def family(name: str) -> str:
    """A thread's name without its indices: the row it counts under."""
    return _INDEX.sub("", name) or name


class Task(NamedTuple):
    name: str  # the thread's name, or the task's comm, indices stripped
    known: bool  # the interpreter knows it: it can hold the interpreter lock
    cpu_ns: int  # on a processor, since the task began
    runq_ns: Optional[int]  # runnable and waiting for one; None: no schedstat


class Reading(NamedTuple):
    at: int  # time.monotonic_ns() when the walk returned
    tasks: dict  # {tid: Task}, the tasks that live
    # what the tasks that have ended read when a reading last saw them:
    # (cpu of those the interpreter knew, cpu of the rest, run-queue wait)
    ended: tuple = (0, 0, 0)

    def totals(self) -> tuple:
        """``(cpu of the tasks the interpreter knows, cpu of the rest,
        every task's run-queue wait)``, ns, the living and the ended: they
        only grow, as the process's own clock does, and miss of a task's
        time what it ran after the last reading that saw it. The last is
        ``None`` without ``schedstat``."""
        tasks = self.tasks.values()
        known = self.ended[0] + sum(t.cpu_ns for t in tasks if t.known)
        other = self.ended[1] + sum(t.cpu_ns for t in tasks if not t.known)
        if any(t.runq_ns is None for t in tasks):
            return known, other, None
        return known, other, self.ended[2] + sum(t.runq_ns for t in tasks)

    def by_name(self) -> dict:
        """``{name: (cpu_ns, runq_ns, tasks)}``, a task counted under its
        name; ``runq_ns`` 0 without ``schedstat``."""
        out = {}
        for task in self.tasks.values():
            cpu, runq, n = out.get(task.name, (0, 0, 0))
            out[task.name] = (cpu + task.cpu_ns, runq + (task.runq_ns or 0), n + 1)
        return out


class TaskTable:
    """The tasks under ``task_dir`` as ``Reading``s. Keeps each unknown
    task's ``comm`` while the task lives, and the last reading, which a
    caller who asks for nothing fresher than ``REUSE_NS`` gets again and
    from which the next takes what the tasks that ended since had run."""

    REUSE_NS = 50_000_000

    def __init__(self, task_dir: str = "/proc/self/task"):
        self._dir = task_dir
        self._comm: dict = {}
        self._rows = np.zeros((1024, 3), dtype=np.int64)
        self._last: Optional[Reading] = None
        self._lock = threading.Lock()  # one walk at a time

    def _walk(self):
        """The native walk's rows, or ``None`` with no library or no
        ``task_dir``."""
        lib = native.LIB
        if lib is None:
            return None
        while True:
            n = lib.tb_task_times(
                self._dir.encode(),
                self._rows.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                len(self._rows),
            )
            if n <= len(self._rows):
                return None if n < 0 else self._rows[:n].tolist()
            self._rows = np.zeros((2 * n, 3), dtype=np.int64)

    def _read_comm(self, tid: int) -> str:
        try:
            with open(f"{self._dir}/{tid}/comm", encoding="utf-8", errors="replace") as f:
                return family(f.read().strip())
        except OSError:  # it ended since the walk; its times are still its own
            return "?"

    def read(self, fresh: bool = True) -> Optional[Reading]:
        """A reading; with ``fresh=False`` the last one where it is under
        ``REUSE_NS`` old (a snapshot asks for three numbers one after
        another: one walk serves them)."""
        with self._lock:
            last = self._last
            if not fresh and last is not None and monotonic_ns() - last.at < self.REUSE_NS:
                return last
            rows = self._walk()
            if rows is None:
                return None
            at = monotonic_ns()
            known = {t.native_id: t.name for t in threading.enumerate()}
            tasks, comm = {}, {}
            for tid, cpu_ns, runq_ns in rows:
                name = known.get(tid)
                if name is not None:
                    name = family(name)
                else:
                    name = comm[tid] = self._comm.get(tid) or self._read_comm(tid)
                tasks[tid] = Task(name, tid in known, cpu_ns, None if runq_ns < 0 else runq_ns)
            self._comm = comm  # of the tasks that live: a tid may come again
            ended = list(last.ended) if last is not None else [0, 0, 0]
            for tid, task in last.tasks.items() if last is not None else ():
                now = tasks.get(tid)
                if now is None or now.cpu_ns < task.cpu_ns:  # gone, or its tid another's
                    ended[0 if task.known else 1] += task.cpu_ns
                    ended[2] += task.runq_ns or 0
            self._last = Reading(at, tasks, tuple(ended))
            return self._last


TABLE = TaskTable()


def _total(which: int):
    def read():
        reading = TABLE.read(fresh=False)
        value = None if reading is None else reading.totals()[which]
        return None if value is None else value / 1e3

    return read


# us since the process began, as far as the readings saw its tasks; a
# snapshot's gain over a window, over the window's length, is processors
# (the first two add up to device_transport_process_cpu_us's but for what
# a task ran after the last reading that saw it: the probe reads once a
# second, so a thread that lives for one dispatch is never seen) and a
# share of the runnable time
cpu_python_threads_us = PassiveStatus(
    _total(0), name="device_transport_cpu_python_threads_us"
)
cpu_other_threads_us = PassiveStatus(
    _total(1), name="device_transport_cpu_other_threads_us"
)
runq_wait_us = PassiveStatus(_total(2), name="device_transport_runq_wait_us")
