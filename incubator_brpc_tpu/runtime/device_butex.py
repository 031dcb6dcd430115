"""DeviceCompletionButex — park fibers on device completions (SURVEY.md §7
step 2's new primitive; the reference analog is RdmaCompletionQueue
delivering CQ events into the event dispatcher,
src/brpc/rdma/rdma_completion_queue.{h,cpp}).

XLA dispatch is async: a jitted call returns device arrays whose buffers
materialize later. A DeviceCompletionButex turns that readiness into a
butex signal, so RPC fibers block on device work exactly the way they block
on network reads — without the *caller* spinning in block_until_ready.

Implementation: a small pool of completion-watcher threads (the analog of
the reference's CQ poller threads, rdma_completion_queue.cpp:39-55) parks
inside PJRT's ready-event wait (jax.block_until_ready) and then
bumps/wakes the butex. Callbacks registered via ``on_complete`` run on the
watcher thread and must be cheap — same contract as the reference's
HandleCompletion.

The hand-over of a watch to the pool is on every dispatching thread's
path (an endpoint's drain or ``-tx`` thread, a link's drive, a stream's
writer), so it takes no lock a watcher holds: ``_WatcherPool.submit`` puts
the job on a ``queue.SimpleQueue``, whose ``put`` is one C call that wakes
at most one parked watcher, and a job's end wakes nobody. One row a
``submit`` feeds ``device_transport_cq_submit_us`` (the dispatching
thread's time inside it) and ``device_transport_cq_backlog`` (jobs handed
over that no watcher is free to take, this one included: over 0, every
watcher was inside a job and this one waits for one to end).
"""

from __future__ import annotations

import atexit
import logging
import queue
import threading
import time
from collections import deque
from typing import Any, Callable, List, Optional

from incubator_brpc_tpu.bvar import LatencyRecorder, PassiveStatus, RecorderFeed
from incubator_brpc_tpu.runtime.butex import Butex, ETIMEDOUT

# CPU time of the whole process, us: every thread of it, the runtime's own
# included. Read only when a snapshot asks; its gain over a window, over
# the window's length, is the processors the process kept busy. Here
# because every device path of the process (endpoint, link, combo) comes
# through this module.
process_cpu_us = PassiveStatus(
    lambda: time.process_time_ns() / 1e3, name="device_transport_process_cpu_us"
)

# one row a submit waits here for the sampler thread: (entered, handed,
# backlog), two stamps of the submitting thread and a count of jobs
m_cq_submit = LatencyRecorder(name="device_transport_cq_submit_us")
m_cq_backlog = LatencyRecorder(name="device_transport_cq_backlog")


def _submit_feed(submit_us: LatencyRecorder, backlog: LatencyRecorder) -> RecorderFeed:
    return RecorderFeed(
        [(submit_us, 1e-3, ("entered", "handed")), (backlog, 1, "backlog")],
        stamps=("entered", "handed", "backlog"),
    )


_cq_feed = _submit_feed(m_cq_submit, m_cq_backlog)


class _WatcherPool:
    """Dedicated completion threads (NOT the worker pool: a watcher blocks in
    the PJRT event wait, which would starve RPC fibers). Jobs run in the
    order they were handed over, each exactly once, and a raising job
    leaves its watcher alive."""

    def __init__(self, nthreads: int, feed: RecorderFeed = _cq_feed):
        # put() takes no Python-level lock and wakes at most one watcher;
        # get() parks with the interpreter released
        self._jobs: queue.SimpleQueue = queue.SimpleQueue()
        # A token a job, from before its hand-over until its end: the
        # length counts the jobs pending and executing. A deque and not an
        # int because append and popleft are each one C call, atomic under
        # the interpreter lock, where ``+= 1`` from two threads is not; and
        # kept from the submitting side on because counts the watchers kept
        # alone would miss the job a watcher has taken off the queue and
        # not yet counted.
        self._open: deque = deque()
        self._submits = feed.rows
        self._threads = [
            threading.Thread(target=self._run, name=f"tbrpc-cq-{i}", daemon=True)
            for i in range(nthreads)
        ]
        for t in self._threads:
            t.start()
        # Interpreter-exit quiesce: a watcher still inside the PJRT wait
        # when CPython finalizes races XLA's own static teardown — the
        # blocked thread observes destructed runtime state and the process
        # aborts ("terminate called ... FATAL: exception not rethrown").
        # Draining pending/active jobs first (bounded) removes the race;
        # device work completes on its own, we only need to outwait it.
        atexit.register(self.quiesce)

    def submit(self, job: Callable[[], None]) -> None:
        entered = time.monotonic_ns()
        self._open.append(None)
        # jobs no watcher is free to take, this one included
        backlog = max(0, len(self._open) - len(self._threads))
        self._jobs.put(job)
        self._submits.append((entered, time.monotonic_ns(), backlog))

    def quiesce(self, timeout: float = 10.0) -> bool:
        """No job pending and none executing, within ``timeout``. Nobody
        tells it: it looks again every 10 ms."""
        deadline = time.monotonic() + timeout
        while self._open:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return False
            time.sleep(min(remaining, 0.01))
        return True

    def _run(self) -> None:
        while True:
            job = self._jobs.get()
            try:
                job()
            except Exception:  # noqa: BLE001
                logging.getLogger(__name__).exception("completion watcher raised")
            finally:
                self._open.popleft()


# Completion-watcher threads of the process (the reference's rdma_cq_num,
# CQ poller count rdma_completion_queue.cpp:39-55): completion handlers do
# the host readback, so this bounds how many device→host fetches overlap.
CQ_THREADS = 8

_watchers: Optional[_WatcherPool] = None
_watchers_lock = threading.Lock()


def _watcher_pool() -> _WatcherPool:
    global _watchers
    if _watchers is None:
        with _watchers_lock:
            if _watchers is None:
                _watchers = _WatcherPool(CQ_THREADS)
    return _watchers


class DeviceCompletionButex(Butex):
    """Butex whose value counts settled (completed OR failed) device ops.

    Failures are counted so waiters never hang; they are recorded in
    ``errors`` and the callback receives the exception (or None) — the
    reference likewise surfaces failed work requests as flushed-error CQ
    entries rather than silence (rdma_endpoint CQ error handling).
    """

    def __init__(self) -> None:
        super().__init__(0)
        self._cb_lock = threading.Lock()
        self._inflight = 0
        self._errors: List[BaseException] = []

    def watch(
        self,
        arrays: Any,
        on_complete: Optional[Callable[[Any, Optional[BaseException]], None]] = None,
        stamps: Optional[List[int]] = None,
    ):
        """Watch a pytree of device arrays; when settled, value += 1 and
        waiters wake; on_complete(arrays, error_or_None) then runs on the
        watcher thread (guarded — a raising callback cannot strand waiters,
        because the bump/wake already happened).

        ``stamps``: a list the watcher fills with ``time.monotonic_ns()``
        before on_complete runs — [0] when a watcher thread took the job
        (how long it queued behind the pool), [1] when
        ``block_until_ready`` returned — and, where it has a third slot,
        [2] with the watcher's own ``time.thread_time_ns()`` at that
        return: on_complete runs on this thread, so a stage it ends has
        both clocks to begin from."""
        import jax

        with self._cb_lock:
            self._inflight += 1

        def job() -> None:
            error: Optional[BaseException] = None
            if stamps is not None:
                stamps[0] = time.monotonic_ns()
            try:
                jax.block_until_ready(arrays)
            except BaseException as e:  # noqa: BLE001 — device failure is data here
                error = e
            if stamps is not None:
                stamps[1] = time.monotonic_ns()
                if len(stamps) > 2:
                    stamps[2] = time.thread_time_ns()
            with self._cb_lock:
                self._inflight -= 1
                if error is not None:
                    self._errors.append(error)
            self.add(1)
            self.wake_all()
            if on_complete is not None:
                try:
                    on_complete(arrays, error)
                except Exception:  # noqa: BLE001
                    import logging

                    logging.getLogger(__name__).exception(
                        "device completion callback raised"
                    )

        _watcher_pool().submit(job)
        return self

    def wait_for(self, completions: int, timeout: Optional[float] = None) -> bool:
        """Park until at least ``completions`` watched ops completed."""
        while True:
            seen = self.load()
            if seen >= completions:
                return True
            if self.wait(seen, timeout=timeout) == ETIMEDOUT:
                return self.load() >= completions

    @property
    def inflight(self) -> int:
        with self._cb_lock:
            return self._inflight

    @property
    def errors(self) -> List[BaseException]:
        with self._cb_lock:
            return list(self._errors)
